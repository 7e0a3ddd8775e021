"""Operations DistilBERT's forward and backward passes require, from shapes.

Counted as the algorithm needs them, not as any compiler reports them: a
multiply-add is 2, the backward pass costs twice the forward (one product
for the input gradient, one for the weight gradient), recomputation counts
nothing, and embeddings, layer norms, softmax and GELU are left out (under
1% at these widths). Every position of the padded sequence counts: the
reference pads every batch to ``seq_len`` and the model computes them all.
"""

from __future__ import annotations

from typing import Dict


def matmul_params(cfg: Dict) -> int:
    """Weights that take part in a matrix product for every token."""
    d, ff = cfg["dim"], cfg["hidden_dim"]
    return cfg["n_layers"] * (4 * d * d + 2 * d * ff)


def forward_flops_per_sample(cfg: Dict) -> float:
    d, t = cfg["dim"], cfg["seq_len"]
    dense = 2.0 * matmul_params(cfg) * t
    attention = 4.0 * t * t * d * cfg["n_layers"]  # QK^T and PV, all heads
    head = 2.0 * (d * d + d * cfg["num_labels"])  # first token only
    return dense + attention + head


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one sequence."""
    return 3.0 * forward_flops_per_sample(cfg)
