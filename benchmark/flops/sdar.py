"""Operations SDAR's (``sdar_moe``'s) block-diffusion forward and backward
passes require, from shapes.

Counted as ``flops/mellum.py`` counts: a multiply-add is 2, the backward pass
costs twice the forward, recomputation counts nothing, the routed experts
count the assignments expected on the experts held here (an eighth of them at
16 of 128: one a row), and embedding lookups, norms, the rotary turn,
activations and the softmax are left out. A sample is one sequence of
``text_len`` = L tokens, run as 2L rows (the noised copy beside the clean one):
the projections, the router and the experts see 2L rows, the head L (the
noised rows), and attention the pairs the block-wise rule shows. With blocks
of B, a noised query of block b sees its B noised keys and the ``b B`` clean
keys before its block, a clean one the ``(b + 1) B`` clean keys of its block
and before: ``B^2 (2b + 2)`` pairs a block, ``L^2 + L B`` a sequence — twice a
causal sequence's ``L (L + 1) / 2`` and a diagonal of blocks.

``blockwise_attention_cost`` is what the two flash kernels are asked for in
one layer of one step, for ``attn_blockwise_roofline``; ``experts_cost`` what
one expert layer's routed part requires, for ``moe_gated_experts_roofline``
(``flops/afmoe.py``'s, by the configuration's keys).
"""

from __future__ import annotations

from typing import Dict, Tuple

from .afmoe import expected_assignments_per_token, experts_cost, gated_forward_flops_per_row  # noqa: F401
from .nemotron_h import _bytes_per_element


def visible_pairs(text_len: int, block: int) -> int:
    """(query, key) pairs one head of one sample sees over its 2 ``text_len``
    rows under the block-wise rule."""
    return text_len * text_len + text_len * block


def forward_flops_per_sample(cfg: Dict) -> float:
    d, length = cfg["hidden_size"], cfg["text_len"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    projections = 2.0 * d * (hq + 2 * hkv) * hd + 2.0 * hq * hd * d  # q k v, o
    experts = (
        2.0 * d * cfg["router_width"]
        + expected_assignments_per_token(cfg) * gated_forward_flops_per_row(cfg, cfg["moe_intermediate_size"])
    )
    a_layer = 4.0 * hd * hq * visible_pairs(length, cfg["block_length"]) + (projections + experts) * 2 * length
    return 2.0 * d * cfg["vocab_size"] * length + cfg["num_hidden_layers"] * a_layer  # the head on the noised rows


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one sequence."""
    return 3.0 * forward_flops_per_sample(cfg)


def blockwise_attention_cost(cfg: Dict, tokens: int) -> Tuple[float, float]:
    """(operations, bytes) the flash kernels are asked for in ONE layer of one
    step over ``tokens`` text tokens (whole samples of ``text_len``, 2 rows a
    token): only the visible pairs, a head: the forward's two products (4 D a
    pair), run twice under ``remat``, and the backward's five (10 D: S again,
    dV, dP, dK, dQ); q, k, v, o, dO, dq, dk, dv of the 2 ``tokens`` rows moved
    once each in the compute dtype. Whole-tile work beyond the rule is time
    spent, not work required, so no walk of the tiles reads over 100."""
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    samples = tokens / cfg["text_len"]
    pairs = visible_pairs(cfg["text_len"], cfg["block_length"]) * hq * samples
    per_pair = (2 if cfg["remat"] else 1) * 4.0 * hd + 10.0 * hd
    moved = (4 * hq + 4 * hkv) * hd * 2 * tokens * _bytes_per_element(cfg)
    return per_pair * pairs, float(moved)
