"""Operations Phi-4-mini-flash-reasoning's (``phi4flash``'s) forward and
backward passes require, from shapes.

Counted as the other language models' files count: a multiply-add is 2, the
backward pass costs twice the forward, recomputation counts nothing, and
embedding lookups, norms, activations, gates, the depthwise conv, lambda and
the softmax are left out. Which layer is what follows from ``layer_indices``
and the published depth, as the model's own rule has it (:func:`kind_of`).

Differential attention counts its visible (query, key) pairs **at 1.5 times a
plain layer's**: a pair of query heads owes, for each of its two softmaxes,
``2 hd`` for q . k and ``2 * 2 hd`` for the weights times the doubled value
``[v1 | v2]`` — ``12 hd`` a pair of heads and visible pair where two plain
heads owe ``8 hd`` — whatever implements it (the program makes four calls'
worth of flash attention a layer, each softmax twice; that is time spent, not
work required). The selective scan counts 7 operations a (token, channel,
state index): the product ``delta a``, its ``exp``, the decay's multiply, the
feed's two multiplies, its add, and the multiply-add of ``y`` (as one).

``s6_cost`` is what ONE Mamba layer's scan requires of a step, for
``s6_roofline``; ``diff_attention_cost`` what the flash kernels are asked
for in ONE attention layer of a kind, for ``diff_attn_roofline``.
"""

from __future__ import annotations

from typing import Dict, Tuple

from .afmoe import visible_pairs
from .nemotron_h import _bytes_per_element

S6_OPS = 7.0  # a (token, channel, state index), forward
MAMBA, SLIDING, FULL, GMU, CROSS = "mamba", "sliding_attention", "full_attention", "gmu", "cross_attention"


def kind_of(index: int, n_layers: int) -> str:
    """The kind of the published layer ``index`` of ``n_layers``."""
    half = n_layers // 2
    if index % 2 == 0:
        return MAMBA if index <= half else GMU
    return SLIDING if index < half else FULL if index == half + 1 else CROSS


def kinds(cfg: Dict):
    return [kind_of(i, cfg["published"]["num_hidden_layers"]) for i in cfg["layer_indices"]]


def d_inner(cfg: Dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def attention_pairs_flops(cfg: Dict, kind: str) -> float:
    """Forward operations of one layer's four attentions over one sequence."""
    hq = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // hq
    window = cfg["sliding_window"] if kind == SLIDING else None
    return 6.0 * hd * hq * visible_pairs(cfg["seq_len"], window)  # 12 hd a pair of heads


def forward_flops_per_sample(cfg: Dict) -> float:
    d, t, f = cfg["hidden_size"], cfg["seq_len"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, c, n, r = d // hq, d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    out = 2.0 * hq * hd * d
    per_token = {
        MAMBA: 2.0 * d * 2 * c + 2.0 * c * (r + 2 * n) + 2.0 * r * c + 2.0 * c * d + S6_OPS * c * n,
        SLIDING: 2.0 * d * (hq + 2 * hkv) * hd + out,
        FULL: 2.0 * d * (hq + 2 * hkv) * hd + out,
        GMU: 2.0 * d * c + 2.0 * c * d,
        CROSS: 2.0 * d * hq * hd + out,
    }
    total = 2.0 * d * cfg["vocab_size"] * t  # the tied head
    for kind in kinds(cfg):
        total += (per_token[kind] + 6.0 * d * f) * t  # the mixer's products and the MLP's three
        if kind in (SLIDING, FULL, CROSS):
            total += attention_pairs_flops(cfg, kind)
    return total


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one sequence."""
    return 3.0 * forward_flops_per_sample(cfg)


def s6_cost(cfg: Dict, tokens: int) -> Tuple[float, float]:
    """(operations, bytes) ONE Mamba layer's scan requires for ``tokens``
    tokens, forward and backward: x and delta in and y out forward (x, y in
    the compute dtype, delta in fp32), B and C in; backward those and dy in,
    dx, d delta, dB, dC out. No (token, channel, index) value need ever leave
    the chip, so the bytes are the (T, C) and (T, N) arrays alone."""
    c, n, e = d_inner(cfg), cfg["mamba_d_state"], _bytes_per_element(cfg)
    forward = c * (2 * e + 4) + 2 * n * e
    backward = c * (3 * e + 8) + 4 * n * e
    return 3.0 * S6_OPS * c * n * tokens, float(forward + backward) * tokens


def diff_attention_cost(cfg: Dict, tokens: int, kind: str) -> Tuple[float, float]:
    """(operations, bytes) the flash kernels are asked for in ONE attention
    layer of ``kind`` (sliding, full or cross) of one step over ``tokens``
    tokens (whole sequences of ``seq_len``): only the visible pairs, at 1.5
    times a plain layer's: forward ``6 hd`` a query head and pair, run twice
    under ``remat``, and the backward's five products where the forward has
    two (``15 hd``); q, k, v, the doubled output, its cotangent, dq, dk, dv
    moved once each in the compute dtype (a cross layer reads the cache's k
    and v and writes their cotangents just so)."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // hq
    sequences = tokens / cfg["seq_len"]
    forward = attention_pairs_flops(cfg, kind) * sequences
    passes = (2 if cfg["remat"] else 1) + 2.5
    moved = (6 * hq + 4 * hkv) * hd * tokens * _bytes_per_element(cfg)
    return passes * forward, float(moved)
