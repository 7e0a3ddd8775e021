"""Operations LFM2's (``lfm2_moe``'s) forward and backward passes require,
from shapes.

Counted as ``flops/nemotron_h.py`` and ``flops/afmoe.py`` count: a
multiply-add is 2, the backward pass costs twice the forward, recomputation
counts nothing, causal attention counts the triangle of (query, key) pairs,
the routed experts count the assignments expected on the experts held here,
and embedding lookups, norms, the rotary turn, activations and the softmax
are left out. A gated expert (and the dense layer) is three products; the
model has no shared expert. The short convolution's mix is counted whole, 7
operations a channel and token (two gates, three taps, two adds): it is the
mixer, small as it is. A sample is one sequence of ``seq_len`` tokens.

``experts_cost`` is ``flops/afmoe.py``'s (three products an expert), which
``moe_gated_experts_roofline`` reads. The mix has no cost function and no
roofline share: on the chip XLA fuses its gates and taps into the two
projections' fusions, so no separate pass exists whose bytes could be
counted (PERF.md section 6, PR 41).
"""

from __future__ import annotations

from typing import Dict

from .afmoe import expected_assignments_per_token, experts_cost, gated_forward_flops_per_row, visible_pairs  # noqa: F401

MIX_OPS = 7.0  # a channel and token, forward: B * z, three taps and their two adds, C * .


def forward_flops_per_sample(cfg: Dict) -> float:
    d, t = cfg["hidden_size"], cfg["seq_len"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    conv = 2.0 * d * 3 * d + 2.0 * d * d + MIX_OPS * d  # in_proj, out_proj, the mix
    full = 2.0 * d * (hq + 2 * hkv) * hd + 2.0 * hq * hd * d  # q k v, o
    dense = gated_forward_flops_per_row(cfg, cfg["intermediate_size"])
    experts = (
        2.0 * d * cfg["router_width"]
        + expected_assignments_per_token(cfg) * gated_forward_flops_per_row(cfg, cfg["moe_intermediate_size"])
    )
    total = 2.0 * d * cfg["vocab_size"] * t  # the tied head
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            total += conv * t
        else:
            total += full * t + 4.0 * hd * hq * visible_pairs(t)  # QK^T and PV over the triangle
        total += (dense if i < cfg["num_dense_layers"] else experts) * t
    return total


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one sequence."""
    return 3.0 * forward_flops_per_sample(cfg)
