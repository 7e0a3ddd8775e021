"""Operations Mellum 2's (``mellum``'s) forward and backward passes require,
from shapes.

Counted as ``flops/nemotron_h.py`` and ``flops/afmoe.py`` count: a
multiply-add is 2, the backward pass costs twice the forward, recomputation
counts nothing, the routed experts count the assignments expected on the
experts held here (a quarter of them at 16 of 64: two a token), and embedding
lookups, norms, the rotary turn, activations and the softmax are left out.
Attention counts the (query, key) pairs a layer may look at: the band ``W*T
- W*(W-1)/2`` in a sliding layer, the triangle ``T*(T+1)/2`` in a full one. A
gated expert is three products; the model has no dense layer, no shared
expert and no gate on attention. A sample is one sequence of ``seq_len``
tokens.

The model runs no kernel of its own: ``attn_window_roofline`` and
``moe_gated_experts_roofline`` read ``flops/afmoe.py``'s
``window_attention_cost`` and ``experts_cost`` by the configuration's keys.
"""

from __future__ import annotations

from typing import Dict

from .afmoe import expected_assignments_per_token, gated_forward_flops_per_row, visible_pairs


def forward_flops_per_sample(cfg: Dict) -> float:
    d, t = cfg["hidden_size"], cfg["seq_len"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    projections = 2.0 * d * (hq + 2 * hkv) * hd + 2.0 * hq * hd * d  # q k v, o
    experts = (
        2.0 * d * cfg["router_width"]
        + expected_assignments_per_token(cfg) * gated_forward_flops_per_row(cfg, cfg["moe_intermediate_size"])
    )
    total = 2.0 * d * cfg["vocab_size"] * t  # the head
    for kind in cfg["layer_types"]:
        window = cfg["sliding_window"] if kind == "sliding_attention" else None
        total += 4.0 * hd * hq * visible_pairs(t, window)  # QK^T and PV
        total += (projections + experts) * t
    return total


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one sequence."""
    return 3.0 * forward_flops_per_sample(cfg)
