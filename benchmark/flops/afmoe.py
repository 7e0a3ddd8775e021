"""Operations afmoe's (Trinity's) forward and backward passes require, from
shapes.

Counted as ``flops/nemotron_h.py`` counts: a multiply-add is 2, the backward
pass costs twice the forward, recomputation counts nothing, the routed
experts count the assignments expected on the experts held here, and
embedding lookups, norms, the rotary turn, activations, gates and the softmax
are left out. Attention counts the (query, key) pairs a layer may look at: the
band ``W*T - W*(W-1)/2`` in a sliding layer, the triangle ``T*(T+1)/2`` in a
full one. A gated expert (and the dense layer, and the shared expert) is
three products. A sample is one sequence of ``seq_len`` tokens.

``window_attention_cost`` is what the two flash kernels are asked for in one
sliding layer of one step, for ``attn_window_roofline``; ``experts_cost``
what one expert layer's routed part requires, for
``moe_gated_experts_roofline``.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .nemotron_h import _bytes_per_element


def visible_pairs(seq_len: int, window: int = None) -> int:
    """(query, key) pairs one head of one sequence sees: key j by query i iff
    ``0 <= i - j < window`` (no window: every earlier key and itself)."""
    w = min(window or seq_len, seq_len)
    return w * seq_len - w * (w - 1) // 2


def gated_forward_flops_per_row(cfg: Dict, width: int) -> float:
    return 6.0 * cfg["hidden_size"] * width  # gate, up, down


def expected_assignments_per_token(cfg: Dict) -> float:
    return cfg["num_experts_per_tok"] * len(cfg["held_experts"]) / cfg["router_width"]


def forward_flops_per_sample(cfg: Dict) -> float:
    d, t = cfg["hidden_size"], cfg["seq_len"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    projections = 2.0 * d * (hq + 2 * hkv) * hd + 2.0 * d * hq * hd + 2.0 * hq * hd * d  # q k v, gate, o
    dense = gated_forward_flops_per_row(cfg, cfg["intermediate_size"])
    experts = (
        2.0 * d * cfg["router_width"]
        + gated_forward_flops_per_row(cfg, cfg["num_shared_experts"] * cfg["moe_intermediate_size"])
        + expected_assignments_per_token(cfg) * gated_forward_flops_per_row(cfg, cfg["moe_intermediate_size"])
    )
    total = 2.0 * d * cfg["vocab_size"] * t  # the head
    for i, kind in enumerate(cfg["layer_types"]):
        window = cfg["sliding_window"] if kind == "sliding_attention" else None
        total += 4.0 * hd * hq * visible_pairs(t, window)  # QK^T and PV
        total += (projections + (dense if i < cfg["num_dense_layers"] else experts)) * t
    return total


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one sequence."""
    return 3.0 * forward_flops_per_sample(cfg)


def experts_cost(cfg: Dict, assignments: float) -> Tuple[float, float]:
    """(operations, bytes) one expert layer's routed part requires for
    ``assignments`` (token, expert) pairs on the held experts, forward and
    backward, as ``flops/nemotron_h.py::experts_cost`` counts them with a third
    product an expert: the held experts' three stacked weights read in each
    pass and their gradients written once; per assignment a row of the model's
    width read and written forward, two read and one written backward."""
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], _bytes_per_element(cfg)
    weights = 3 * len(cfg["held_experts"]) * d * f * e
    return (
        3.0 * gated_forward_flops_per_row(cfg, f) * assignments,
        3.0 * weights + 5.0 * assignments * d * e,
    )


def window_attention_cost(cfg: Dict, tokens: int) -> Tuple[float, float]:
    """(operations, bytes) the flash kernels are asked for in ONE sliding
    layer of one step over ``tokens`` tokens (whole sequences of ``seq_len``):
    only the visible pairs of the band, a head: the forward's two products (4 D
    a pair), run twice under ``remat``, and the backward's five (10 D: S again,
    dV, dP, dK, dQ); q, k, v, o, dO, dq, dk, dv moved once each in the compute
    dtype. Whole-tile work beyond the band is time spent, not work required."""
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sequences = tokens / cfg["seq_len"]
    pairs = visible_pairs(cfg["seq_len"], cfg["sliding_window"]) * hq * sequences
    per_pair = (2 if cfg["remat"] else 1) * 4.0 * hd + 10.0 * hd
    moved = (4 * hq + 4 * hkv) * hd * tokens * _bytes_per_element(cfg)
    return per_pair * pairs, float(moved)
