"""Operations Qwen3-Next's forward and backward passes require, from shapes.

Counted as ``flops/nemotron_h.py`` and ``flops/afmoe.py`` count: a
multiply-add is 2, the backward pass costs twice the forward, recomputation
counts nothing, causal attention counts the triangle of (query, key) pairs,
the routed experts count the assignments expected on the experts held here,
and embedding lookups, norms, the rotary turn, activations, gates, the
softmaxes and the depthwise conv's neighbours (its four taps are counted)
are left out. A gated expert (and the shared expert) is three products. A
sample is one sequence of ``seq_len`` tokens.

The gated delta rule is counted in the chunked form the system (and every
published kernel) computes, at the configuration's chunk ``C``: per chunk
and value head the triangular solve by substitution (``C^3 / 3``
multiply-adds), ``T (beta V)`` and ``T (beta K)``, the three products with
the carried (d_k, d_v) state (``W S``, ``Q S``, ``K^T V'``) and ``(Q K^T)
V'``; per chunk and KEY head ``K K^T`` and ``Q K^T``, which its value heads
share. ``gated_delta_cost`` is that, forward and backward, with the bytes the
rule must move once, for ``gdn_roofline``; ``experts_cost`` is
``flops/afmoe.py``'s (three products an expert), which
``moe_gated_experts_roofline`` reads.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .afmoe import expected_assignments_per_token, experts_cost, gated_forward_flops_per_row, visible_pairs  # noqa: F401
from .nemotron_h import _bytes_per_element


def gated_delta_forward_flops(cfg: Dict, tokens: int) -> float:
    """The chunked rule's forward products of ONE linear layer over
    ``tokens`` tokens (whole chunks: a ragged tail is padded to one)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv, c = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["gdn_chunk_size"]
    chunks = -(-tokens // c)
    per_key_head = 2 * 2.0 * c * c * dk  # K K^T, Q K^T
    per_value_head = (
        2.0 * c * c * c / 3  # the solve by substitution
        + 2.0 * c * c * dv + 2.0 * c * c * dk  # U = T (beta V), W = T (beta K)
        + 3 * 2.0 * c * dk * dv  # W S, Q S, K^T V'
        + 2.0 * c * c * dv  # (Q K^T) V'
    )
    return chunks * (hk * per_key_head + hv * per_value_head)


def forward_flops_per_sample(cfg: Dict) -> float:
    d, t = cfg["hidden_size"], cfg["seq_len"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    key_dim, value_dim = hk * cfg["linear_key_head_dim"], hv * cfg["linear_value_head_dim"]
    linear = (
        2.0 * d * (2 * key_dim + 2 * value_dim) + 2.0 * d * 2 * hv + 2.0 * value_dim * d  # qkvz, ba, out
        + 2.0 * cfg["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
    )
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    full = 2.0 * d * (2 * hq + 2 * hkv) * hd + 2.0 * hq * hd * d  # q with its gate, k, v; o
    experts = (
        2.0 * d * cfg["router_width"] + 2.0 * d  # the router, the shared expert's scalar gate
        + gated_forward_flops_per_row(cfg, cfg["shared_expert_intermediate_size"])
        + expected_assignments_per_token(cfg) * gated_forward_flops_per_row(cfg, cfg["moe_intermediate_size"])
    )
    total = 2.0 * d * cfg["vocab_size"] * t  # the head
    for kind in cfg["layer_types"]:
        if kind == "linear_attention":
            total += linear * t + gated_delta_forward_flops(cfg, t)
        else:
            total += full * t + 4.0 * hd * hq * visible_pairs(t)  # QK^T and PV over the triangle
        total += experts * t
    return total


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one sequence."""
    return 3.0 * forward_flops_per_sample(cfg)


def gated_delta_cost(cfg: Dict, tokens: int) -> Tuple[float, float]:
    """(operations, bytes) the rule of ONE linear layer requires for
    ``tokens`` tokens, forward and backward: q, k (at the key heads), v, g and
    beta in and o out forward; q, k, v, g, beta and do in, their five
    cotangents out backward; g, beta and their cotangents in fp32, the rest
    in the compute dtype. What a chunked implementation keeps between its
    products (T, U, W, the states) is time spent, not work required."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    key_dim, value_dim = hk * cfg["linear_key_head_dim"], hv * cfg["linear_value_head_dim"]
    e = _bytes_per_element(cfg)
    forward = (2 * key_dim + 2 * value_dim) * e + 2 * hv * 4
    backward = (4 * key_dim + 3 * value_dim) * e + 4 * hv * 4
    return 3.0 * gated_delta_forward_flops(cfg, tokens), float(forward + backward) * tokens
