"""Operations Nemotron-H's forward and backward passes require, from shapes.

Counted as the algorithm needs them, not as any compiler reports them: a
multiply-add is 2, the backward pass costs twice the forward, recomputation
(``jax.checkpoint``) counts nothing, causal attention counts the half of the
score matrix it may look at, the routed experts count the assignments that
land on the experts held here (their expectation where no count is given),
and embedding lookups, norms, activations and the softmax are left out
(under 1% at these widths). A sample is one sequence of ``seq_len`` tokens.

``ssd_cost`` and ``experts_cost`` are the two new layers' required work for
their roofline shares: operations as above and the bytes each must move once
(operands in, results out, in the compute dtype), forward plus backward.
"""

from __future__ import annotations

from typing import Dict, Tuple


def _bytes_per_element(cfg: Dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def ssd_forward_flops_per_token(cfg: Dict) -> float:
    """The recurrence as written, per token: decay the (P, N) state, add the
    outer product, read it out with C; over H heads; plus ``dt*x`` and ``D*x``."""
    h, p, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    return 5.0 * h * p * n + 3.0 * h * p


def expert_forward_flops_per_assignment(cfg: Dict) -> float:
    return 4.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]  # two products


def expected_assignments_per_token(cfg: Dict) -> float:
    return cfg["num_experts_per_tok"] * len(cfg["held_experts"]) / cfg["router_width"]


def forward_flops_per_token(cfg: Dict) -> float:
    d, t = cfg["hidden_size"], cfg["seq_len"]
    h, p, g, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    d_inner, conv_dim = h * p, h * p + 2 * g * n
    mamba = (
        2.0 * d * (d_inner + conv_dim + h) + 2.0 * d_inner * d  # in_proj, out_proj
        + 2.0 * cfg["conv_kernel"] * conv_dim + ssd_forward_flops_per_token(cfg)
    )
    experts = (
        2.0 * d * cfg["router_width"]
        + 4.0 * d * cfg["moe_shared_expert_intermediate_size"]
        + expected_assignments_per_token(cfg) * expert_forward_flops_per_assignment(cfg)
    )
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attention = (
        2.0 * d * (hq + 2 * hkv) * hd + 2.0 * hq * hd * d  # q, k, v, o
        + 2.0 * t * hq * hd  # QK^T and PV over the causal half: 4 * (t / 2) * hq * hd
    )
    per_kind = {"M": mamba, "E": experts, "*": attention}
    layers = sum(per_kind[kind] for kind in cfg["hybrid_override_pattern"])
    return layers + 2.0 * d * cfg["vocab_size"]  # the head


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one sequence."""
    return 3.0 * forward_flops_per_token(cfg) * cfg["seq_len"]


def ssd_cost(cfg: Dict, tokens: int) -> Tuple[float, float]:
    """(operations, bytes) one Mamba layer's scan requires for ``tokens``
    tokens, forward and backward: x, B, C in and y out forward; those and dy
    in, dx, dB, dC out backward; dt and its cotangent in fp32."""
    h, p, g, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    e = _bytes_per_element(cfg)
    forward = (2 * h * p + 2 * g * n) * e + 4 * h
    backward = (4 * h * p + 4 * g * n) * e + 8 * h
    return 3.0 * ssd_forward_flops_per_token(cfg) * tokens, float(forward + backward) * tokens


def experts_cost(cfg: Dict, assignments: float) -> Tuple[float, float]:
    """(operations, bytes) one expert layer's routed part requires for
    ``assignments`` (token, expert) pairs on the held experts, forward and
    backward: the held experts' weights read in each pass and their gradients
    written once; per assignment a row of the model's width read and written
    forward, two read and one written backward."""
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], _bytes_per_element(cfg)
    weights = 2 * len(cfg["held_experts"]) * d * f * e
    return (
        3.0 * expert_forward_flops_per_assignment(cfg) * assignments,
        3.0 * weights + 5.0 * assignments * d * e,
    )
