"""afmoe — Arcee's Trinity family (``model_type: afmoe``): sliding-window
attention layers that carry rotary positions beside full-attention layers
that carry none, gated attention, and gated experts; first-party flax.

Follows HuggingFace's ``modeling_afmoe.py``. ``h = hidden_size``; every norm
is an RMSNorm with a learned scale:

- embedding: ``x = E[ids] * sqrt(h)`` (``mup_enabled``); after the last block
  RMSNorm, then the untied head.
- block, four norms: ``x <- x + N2(attn(N1(x)))``, then ``x <- x +
  N4(ffn(N3(x)))`` (``input_layernorm``, ``post_attention_layernorm``,
  ``pre_mlp_layernorm``, ``post_mlp_layernorm``).
- attention (``ops.flash_attention``), ``n_heads`` query heads over
  ``n_kv_heads`` key/value heads, no bias: ``q = RMSNorm_head(W_q u)``, ``k =
  RMSNorm_head(W_k u)``, ``v = W_v u``, ``g = W_g u``; ``o = softmax(q k^T /
  sqrt(head_dim)) v``; ``out = W_o (o * sigmoid(g))``. The layer's kind comes
  from ``layer_types``: a ``sliding_attention`` layer turns q and k by the
  rotary embedding (theta ``rope_theta``, the whole head, halves rotated as
  HuggingFace's ``rotate_half``, angles in fp32) and lets query i see key j
  iff ``0 <= i - j < sliding_window``; a ``full_attention`` layer applies NO
  rotary embedding and is causal.
- feed-forward: the first ``num_dense_layers`` layers a gated MLP of
  ``dense_width``, ``W_d (silu(W_g u) * W_u u)``; the others routed experts
  (``parallel.moe.held_experts_moe``): ``s = sigmoid(u W_r)`` in fp32 over
  all ``n_routed_experts``, the ``experts_per_token`` largest of ``s +
  expert_bias``, weights ``route_scale * s_i / sum_topk s``; every expert the
  gated form at ``expert_width``; this rank computes the experts in
  ``held_experts`` only and every rank the shared expert (``n_shared_experts
  * expert_width`` wide). Nothing is dropped.
- ``expert_bias`` is a buffer no gradient reaches (the ``buffers`` collection;
  zeros where the caller brings none). A run in training keeps it where every
  expert is chosen equally often; ``balanced_expert_bias`` puts it there for
  weights that come from a seed and not from such a run.

Parameters are fp32; ``dtype`` is what the products run in, and the residual
stream is carried in it. The router, every norm, the rotary angles and the
output gate's sigmoid compute in fp32. ``remat`` recomputes each block in the
backward pass. ``RMSNorm``, the projections, the loss and the counters' tree
are ``models/nemotron_h.py``'s: ``__call__`` returns ``(logits, counters)``
as that model does, so ``next_token_lm_loss`` and ``zero_counters`` serve both.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Mapping, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .nemotron_h import RMSNorm, _dense, _kernel, einsum_attention

SLIDING, FULL = "sliding_attention", "full_attention"
BUFFERS = "buffers"  # the variable collection of what no gradient reaches: every expert_bias


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    num_dense_layers: int = 2
    norm_eps: float = 1e-5
    mup_enabled: bool = True
    # attention
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    attn_impl: str = "auto"  # "auto" (flash on TPU, einsum elsewhere) | "flash" | "einsum"
    # feed-forward
    dense_width: int = 6144
    expert_width: int = 1024
    n_shared_experts: int = 1
    n_routed_experts: int = 128  # the router's width
    held_experts: Tuple[int, ...] = tuple(range(128))  # the expert ids this rank computes
    experts_per_token: int = 8
    route_scale: float = 2.826
    dtype: Any = jnp.float32
    remat: bool = False
    init_std: float = 0.02

    def __post_init__(self):
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types {self.layer_types!r}: {SLIDING} or {FULL} per layer")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("heads must divide into their groups, and a head into two halves")

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.num_dense_layers, len(self.layer_types)))


class Rope(NamedTuple):
    """One rotary embedding's numbers, hashable, so a config field: a theta
    alone is ``rope_type: default``; with ``factor`` it is YaRN's."""

    theta: float
    factor: Optional[float] = None  # YaRN's scaling factor; None: the default embedding
    original_positions: int = 0  # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None  # None: 0.1 ln(factor) + 1

    @classmethod
    def of(cls, parameters: Mapping) -> "Rope":
        """From HuggingFace's ``rope_parameters`` of one layer kind."""
        kind = parameters.get("rope_type", "default")
        if kind == "default":
            return cls(float(parameters["rope_theta"]))
        if kind != "yarn":
            raise ValueError(f"rope_type {kind!r}: default or yarn")
        return cls(
            float(parameters["rope_theta"]), float(parameters["factor"]),
            parameters["original_max_position_embeddings"], float(parameters.get("beta_fast", 32)),
            float(parameters.get("beta_slow", 1)), parameters.get("attention_factor"),
        )


def rope_frequencies(rope: Rope, dim: int) -> Tuple[jax.Array, float]:
    """``(inv_freq (dim/2,) in fp32, factor)`` of one rotary embedding over
    ``dim`` dims: position t turns pair i by ``t * inv_freq[i]``, and cos and
    sin are both multiplied by ``factor``.

    - the default embedding: ``theta^(-2i/dim)``, factor 1.
    - YaRN, as HuggingFace's ``_compute_yarn_parameters``: the default
      frequencies (``extrap``) blended with the same divided by ``factor``
      (``interp``), ``interp * ramp + extrap * (1 - ramp)`` with ``ramp_i =
      clip((i - low) / (high - low), 0, 1)`` and ``low`` / ``high`` the pairs
      that make ``beta_fast`` / ``beta_slow`` turns over the original
      positions, ``c(n) = dim * ln(L / (2 pi n)) / (2 ln theta)``, floored
      and ceiled (``truncate``, the default); the factor is
      ``attention_factor``, ``0.1 ln(factor) + 1`` where none is given."""
    inv_freq = rope.theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rope.factor is None:
        return inv_freq, 1.0
    turns_at = lambda n: dim * math.log(rope.original_positions / (2 * math.pi * n)) / (2 * math.log(rope.theta))
    low = max(math.floor(turns_at(rope.beta_fast)), 0)
    high = min(math.ceil(turns_at(rope.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    factor = rope.attention_factor or 0.1 * math.log(rope.factor) + 1.0
    return inv_freq / rope.factor * ramp + inv_freq * (1.0 - ramp), float(factor)


def rotary(x: jax.Array, rope: Rope, rotary_dim: Optional[int] = None) -> jax.Array:
    """``x`` (B, T, H, D) in fp32, position t turned by the angles ``t *
    inv_freq`` of ``rope_frequencies(rope, D)`` (the default embedding: ``t
    * theta^(-2i/D)``): ``x cos + rotate_half(x) sin``, the halves paired as
    HuggingFace pairs them (i with i + D/2), cos and sin times the
    embedding's factor where it has one (YaRN). With ``rotary_dim`` only the
    head's first ``rotary_dim`` dims turn, as a head of that size would
    (``partial_rotary_factor``); the others pass as they came."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate([rotary(x[..., :rotary_dim], rope), x[..., rotary_dim:]], axis=-1)
    angles, factor = _angles(rope, x.shape[1], x.shape[-1])
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _angles(rope: Rope, t: int, dim: int) -> Tuple[jax.Array, float]:
    """``(t * inv_freq`` (T, dim/2) in fp32``, factor)`` of ``rope_frequencies(rope, dim)``."""
    inv_freq, factor = rope_frequencies(rope, dim)
    return jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :], factor


def rope_tables(rope: Rope, t: int, dim: int) -> Tuple[jax.Array, jax.Array]:
    """What :func:`rotary` turns ``dim`` dims by, as tables: cos and sin of
    its angles times its factor, (T, dim/2) in fp32 each."""
    angles, factor = _angles(rope, t, dim)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos * factor, sin * factor) if factor != 1.0 else (cos, sin)


def normed_and_turned(
    q_norm: RMSNorm, k_norm: RMSNorm, q, k, rope: Optional[Rope], dtype, rotary_dim: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """What an attention layer does to q (B, T, H, D) and k (B, T, H_kv, D)
    between their projections and the attention itself, under its scope
    ``attn.rope``: each head through its norm, then turned by ``rope`` as
    :func:`rotary` turns it (``None``: the layer carries no positions), then
    cast to ``dtype``.

    ``interpret=None`` lets the backend decide: on TPU one Pallas pass forward
    and one backward (``ops/qk_rope.py``) where its tiles serve the shape,
    elsewhere and otherwise these XLA lines; ``True`` runs the kernels in the
    Pallas interpreter, ``False`` the kernels whatever traces them."""
    from ..ops import pallas_interpret, qk_rope

    t, d = q.shape[1], q.shape[-1]
    turning = 0 if rope is None else d if rotary_dim is None else min(rotary_dim, d)
    served = qk_rope.serves(t, q.shape[2], k.shape[2], d, turning)
    if not served or (interpret is None and pallas_interpret()):
        turned = lambda x: x if rope is None else rotary(x, rope, rotary_dim)
        return turned(q_norm(q)).astype(dtype), turned(k_norm(k)).astype(dtype)
    cos, sin = (None, None) if rope is None else rope_tables(rope, t, turning)
    scales = q_norm(q, scale_alone=True), k_norm(k, scale_alone=True)
    return qk_rope.normed_and_turned(q, k, *scales, cos, sin, q_norm.eps, dtype, bool(interpret))


class GatedMLP(nn.Module):
    """``W_d (silu(W_g u) * W_u u)``: the leading dense layers and the shared expert."""

    config: AfmoeConfig
    width: int
    out_std: float

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        u = u.astype(cfg.dtype)
        gate = _dense(cfg, self.width, cfg.init_std, "gate_proj")(u)
        up = _dense(cfg, self.width, cfg.init_std, "up_proj")(u)
        return _dense(cfg, cfg.hidden_size, self.out_std, "down_proj")(jax.nn.silu(gate) * up)


def _balancing_bias(tokens32, router, top_k):
    """The ``expert_bias`` under which every expert clears zero on ``top_k /
    experts`` of these tokens: minus the score its (tokens * top_k /
    experts)-th best token gives it. The ``top_k`` largest of ``s +
    expert_bias`` then take each expert about equally often."""
    scores = jax.nn.sigmoid(jnp.dot(tokens32, router, precision=jax.lax.Precision.HIGHEST))
    n_tokens, n_experts = scores.shape
    share = max(n_tokens * top_k // n_experts, 1)
    return -jnp.sort(scores, axis=0)[n_tokens - share]


def expert_bias_of(layer: nn.Module, tokens32, router, top_k: int):
    """An expert layer's ``expert_bias``, the (experts,) selection bias in
    its ``buffers`` collection: zeros (and no variable) where the caller
    brings none; under ``balanced_expert_bias``'s pass, where the collection
    is writable, found from this layer's own scores and written back."""
    expert_bias = jnp.zeros((router.shape[1],), jnp.float32)
    writable = layer.is_mutable_collection(BUFFERS)  # init, or balanced_expert_bias's pass
    if writable or layer.has_variable(BUFFERS, "expert_bias"):
        buffer = layer.variable(BUFFERS, "expert_bias", lambda: expert_bias)
        if writable and not layer.is_initializing():
            buffer.value = _balancing_bias(tokens32, router, top_k)
        expert_bias = buffer.value
    return expert_bias


class AfmoeExperts(nn.Module):
    config: AfmoeConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        from ..parallel.moe import held_experts_moe

        cfg = self.config
        d, f, held = cfg.hidden_size, cfg.expert_width, cfg.held_experts
        router = self.param("router", _kernel(cfg.init_std), (d, cfg.n_routed_experts))
        gate = self.param("experts_gate", _kernel(cfg.init_std), (len(held), d, f))
        up = self.param("experts_up", _kernel(cfg.init_std), (len(held), d, f))
        down = self.param("experts_down", _kernel(self.out_std), (len(held), f, d))
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        tokens32 = u32.reshape(bsz * t, d)
        expert_bias = expert_bias_of(self, tokens32, router, cfg.experts_per_token)
        routed, counters = held_experts_moe(
            u.reshape(bsz * t, d), tokens32, router, expert_bias,
            up, down, held, cfg.experts_per_token, cfg.route_scale, w_gate=gate,
        )
        with jax.named_scope("moe.shared"):
            shared = GatedMLP(cfg, cfg.n_shared_experts * f, self.out_std, name="shared")(u)
        return routed.reshape(bsz, t, d) + shared, counters


class AfmoeAttention(nn.Module):
    config: AfmoeConfig
    sliding: bool
    out_std: float

    @nn.compact
    def __call__(self, u32):
        from ..ops.flash_attention import resolve_attn_impl

        cfg = self.config
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        window = cfg.sliding_window if self.sliding else None
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        q = _dense(cfg, hq * hd, cfg.init_std, "q_proj")(u).reshape(bsz, t, hq, hd)
        k = _dense(cfg, hkv * hd, cfg.init_std, "k_proj")(u).reshape(bsz, t, hkv, hd)
        v = _dense(cfg, hkv * hd, cfg.init_std, "v_proj")(u).reshape(bsz, t, hkv, hd)
        gate = _dense(cfg, hq * hd, cfg.init_std, "gate_proj")(u)
        with jax.named_scope("attn.rope"):
            rope = Rope(cfg.rope_theta) if self.sliding else None  # the full layers carry no positions
            norms = RMSNorm(cfg.norm_eps, name="q_norm"), RMSNorm(cfg.norm_eps, name="k_norm")
            q, k = normed_and_turned(*norms, q, k, rope, cfg.dtype)
        with jax.named_scope("attn.window" if self.sliding else "attn.full"):
            if resolve_attn_impl(cfg.attn_impl) == "flash":
                from ..ops import flash_attention, pallas_interpret

                ctx = flash_attention(
                    q, k, v, causal=True, window=window, interpret=pallas_interpret()
                )
            else:
                ctx = einsum_attention(q, k, v, window)
        gated = ctx.reshape(bsz, t, hq * hd) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return _dense(cfg, cfg.hidden_size, self.out_std, "o_proj")(gated.astype(cfg.dtype))


class AfmoeBlock(nn.Module):
    config: AfmoeConfig
    sliding: bool
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.norm_eps, name=name)
        # as nemotron_h's blocks: each output projection starts 1/sqrt(layers) smaller
        out_std = cfg.init_std / np.sqrt(len(cfg.layer_types))
        attended = AfmoeAttention(cfg, self.sliding, out_std, name="self_attn")(norm("input_layernorm")(x))
        x = x + norm("post_attention_layernorm")(attended).astype(x.dtype)
        normed, counters = norm("pre_mlp_layernorm")(x), {}
        if self.dense:
            out = GatedMLP(cfg, cfg.dense_width, out_std, name="mlp")(normed)
        else:
            out, counters = AfmoeExperts(cfg, out_std, name="mlp")(normed)
        return x + norm("post_mlp_layernorm")(out).astype(x.dtype), counters


class AfmoeLM(nn.Module):
    config: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]]]:
        """``input_ids`` (B, T) -> fp32 logits (B, T, vocab) and the expert
        layers' counters of this call."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=_kernel(cfg.init_std),
            dtype=cfg.dtype, name="embed",
        )(input_ids)
        if cfg.mup_enabled:
            x = x * jnp.asarray(np.sqrt(cfg.hidden_size), x.dtype)
        block = nn.remat(AfmoeBlock) if cfg.remat else AfmoeBlock
        counters = {}
        for i, kind in enumerate(cfg.layer_types):
            x, layer_counters = block(
                cfg, kind == SLIDING, i < cfg.num_dense_layers, name=f"layer_{i}"
            )(x)
            if layer_counters:
                counters[f"layer_{i}"] = layer_counters
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x).astype(cfg.dtype)
        head = self.param("head", _kernel(cfg.init_std), (cfg.hidden_size, cfg.vocab_size))
        logits = jnp.dot(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)
        return logits, counters


def balanced_expert_bias(model, params, input_ids) -> Dict:
    """The ``buffers`` collection that balances ``params``' routing on
    ``input_ids`` (B, T): one forward pass in which every expert layer takes
    its ``expert_bias`` from its own scores (``_balancing_bias``) and routes
    by it, so the layers behind it see what they will see in training.
    ``model`` is this module's or any whose expert layers keep the buffer so
    (``models/lfm2.py``)."""
    model = type(model)(dataclasses.replace(model.config, remat=False))  # nothing to recompute
    # the buffers alone leave the program: the head and its logits are never computed
    return jax.jit(lambda p, ids: model.apply({"params": p}, ids, mutable=[BUFFERS])[1][BUFFERS])(
        params, input_ids
    )


def afmoe_tiny(**overrides) -> AfmoeLM:
    """The test tier's size: a leading dense layer, then sliding, full,
    sliding, sliding with experts; 16 experts with 4 held; a window a quarter
    of the sequences the tests use."""
    base = dict(
        vocab_size=256, hidden_size=64, layer_types=(SLIDING, SLIDING, FULL, SLIDING, SLIDING),
        num_dense_layers=1, n_heads=4, n_kv_heads=2, head_dim=16, sliding_window=16,
        dense_width=96, expert_width=32, n_routed_experts=16, held_experts=(0, 1, 2, 3),
        experts_per_token=2,
    )
    base.update(overrides)
    return AfmoeLM(AfmoeConfig(**base))
