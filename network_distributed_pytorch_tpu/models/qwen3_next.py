"""qwen3_next — Qwen3-Next's hybrid stack (``model_type: qwen3_next``): three
Gated-DeltaNet linear-attention layers to one gated full-attention layer,
every layer followed by softmax-routed experts with a gated shared expert;
first-party flax.

Follows HuggingFace's ``modeling_qwen3_next.py``. Every norm but the rule's
own is a zero-centred RMSNorm, ``x rsqrt(mean x^2 + eps) (1 + w)`` with ``w``
from zero:

- block ``i``: ``x <- x + mixer_i(N(x))``, then ``x <- x + moe(N(x))``; the
  mixer by ``layer_types``; after the last block ``N``, then the untied head.
- ``linear_attention`` (``ops.gated_delta``; ``H_k`` key heads, ``H_v`` value
  heads, ``r = H_v / H_k``): ``[q | k | v | z] = u W_qkvz`` and ``[b | a] = u
  W_ba``, one leaf each in HuggingFace's grouped column order (per key head
  its q, its k, then the v and the z of its ``r`` value heads; its ``r`` b's
  and a's); ``[q | k | v] <- silu(conv([q | k | v]))``, causal and depthwise
  without a bias (``ops.ssd.causal_conv1d``); ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; ``q <- l2norm(q) / sqrt(d_k)``, ``k <-
  l2norm(k)``; the gated delta rule, value head ``h`` reading key head ``h //
  r``; per head ``o <- w_n rmsnorm(o) silu(z)`` (the norm BEFORE the gate,
  ``w_n`` from one); ``out = o W_out``.
- ``full_attention`` (``ops.flash_attention``): ``[q | gate] = u W_q`` (one
  leaf; per head its q then its gate), ``k``, ``v``; ``q`` and ``k`` normed per
  head, then the first ``partial_rotary_factor`` of each head turned by the
  rotary embedding (``models/layers.rotary``); causal, ``n_heads`` query heads
  over ``n_kv_heads`` key/value heads; ``out = W_o (o * sigmoid(gate))``.
- experts (``parallel.moe.held_experts_moe``): ``p = softmax(u W_r)`` in fp32
  over all ``n_routed_experts``, the ``experts_per_token`` largest, weights
  ``p_i / sum_topk p``; every expert ``W_d (silu(W_g u) * W_u u)``; this rank
  computes the experts in ``held_experts`` only, and every rank the shared
  expert times ``sigmoid(u . w_sg)``, a scalar a token. Nothing is dropped.

Left out: the multi-token-prediction head (HuggingFace's
``Qwen3NextForCausalLM`` drops its weights) and the router's auxiliary loss.

Parameters are fp32; ``dtype`` is what the products run in, and the residual
stream is carried in it. The router, every norm, ``beta``, ``g``, the l2
norms, the rotary angles and both sigmoid gates compute in fp32. ``remat``
recomputes each block in the backward pass. The projections, the norm, the
gated MLP, the rotary turn, the loss and the counters' tree are
``models/layers.py``'s: ``__call__`` returns ``(logits, counters)``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import (
    FULL, GatedMLP, RMSNorm, Rope, causal_attention, dense, kernel, normed_and_turned, routed_experts, run_layers,
)

LINEAR = "linear_attention"
A_FLOOR = 1e-4  # A = max(U(0, 16), A_FLOOR): HuggingFace takes log U(0, 16), -inf at a draw of 0


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 12
    norm_eps: float = 1e-6
    # Gated DeltaNet
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64
    # full attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    attn_impl: str = "auto"  # "auto" (flash on TPU, einsum elsewhere) | "flash" | "einsum"
    # experts
    expert_width: int = 512
    shared_expert_width: int = 512
    n_routed_experts: int = 512  # the router's width
    held_experts: Tuple[int, ...] = tuple(range(512))  # the expert ids this rank computes
    experts_per_token: int = 10
    dtype: Any = jnp.float32
    remat: bool = False
    init_std: float = 0.02

    def __post_init__(self):
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types {self.layer_types!r}: {LINEAR} or {FULL} per layer")
        if self.n_heads % self.n_kv_heads or self.linear_value_heads % self.linear_key_heads:
            raise ValueError("heads must divide into their groups")
        if self.rotary_dim % 2:
            raise ValueError("the rotary part of a head must divide into two halves")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(len(self.layer_types)))  # decoder_sparse_step 1, mlp_only_layers []


def _norm(cfg, name: str) -> RMSNorm:
    return RMSNorm(cfg.norm_eps, zero_centred=True, name=name)


class GatedDeltaNet(nn.Module):
    config: Qwen3NextConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        from ..ops.gated_delta import gated_delta_rule
        from ..ops.gated_delta_frame import framed_rule

        cfg, f32 = self.config, jnp.float32
        hk, hv, dk, dv = cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
        r, key_dim, value_dim = hv // hk, hk * dk, hv * dv
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        # HuggingFace's grouped column order: a key head's q, k, then its r value heads' v and z
        qkvz = dense(cfg, 2 * key_dim + 2 * value_dim, cfg.init_std, "in_proj_qkvz")(u)
        ba = dense(cfg, 2 * hv, cfg.init_std, "in_proj_ba")(u)
        b, a = jnp.split(ba.reshape(bsz, t, hk, 2 * r), 2, axis=-1)
        flat = lambda x: x.reshape(bsz, t, -1)

        bound = 1.0 / np.sqrt(cfg.conv_kernel)  # torch's Conv1d default for a depthwise kernel
        conv_kernel = self.param(
            "conv_kernel",
            lambda key, shape: jax.random.uniform(key, shape, minval=-bound, maxval=bound),
            (cfg.conv_kernel, 2 * key_dim + value_dim),
        )
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
        a_log = self.param(
            "a_log", lambda key, shape: jnp.log(jnp.maximum(jax.random.uniform(key, shape, maxval=16.0), A_FLOOR)), (hv,)
        )
        norm_scale = self.param("norm_scale", nn.initializers.ones, (dv,))

        with jax.named_scope("gdn.frame"):
            beta = jax.nn.sigmoid(flat(b).astype(f32))
            g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(flat(a).astype(f32) + dt_bias)
        # conv, silu and the l2 norms, the rule, the gated norm: on TPU a Pallas pass each side of the rule
        rule = functools.partial(gated_delta_rule, chunk=cfg.chunk_size)
        o = framed_rule(rule, qkvz, conv_kernel, norm_scale, g, beta, cfg.norm_eps, hk, r, dk, dv)
        return dense(cfg, cfg.hidden_size, self.out_std, "out_proj")(o)


class GatedAttention(nn.Module):
    config: Qwen3NextConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        # the output gate lives inside q_proj: per head its q, then its gate
        q, gate = jnp.split(dense(cfg, 2 * hq * hd, cfg.init_std, "q_proj")(u).reshape(bsz, t, hq, 2 * hd), 2, axis=-1)
        k = dense(cfg, hkv * hd, cfg.init_std, "k_proj")(u).reshape(bsz, t, hkv, hd)
        v = dense(cfg, hkv * hd, cfg.init_std, "v_proj")(u).reshape(bsz, t, hkv, hd)
        with jax.named_scope("attn.rope"):
            norms = _norm(cfg, "q_norm"), _norm(cfg, "k_norm")
            q, k = normed_and_turned(*norms, q, k, Rope(cfg.rope_theta), cfg.dtype, cfg.rotary_dim)
        with jax.named_scope("attn.full"):
            ctx = causal_attention(cfg, q, k, v)
        gated = ctx.reshape(bsz, t, hq * hd) * jax.nn.sigmoid(gate.reshape(bsz, t, hq * hd).astype(jnp.float32))
        return dense(cfg, cfg.hidden_size, self.out_std, "o_proj")(gated.astype(cfg.dtype))


class Qwen3NextExperts(nn.Module):
    config: Qwen3NextConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        u = u32.astype(cfg.dtype)
        # softmax scores, top k renormalised, no scaling factor; the model has no selection bias
        routed, counters = routed_experts(
            self, cfg, u, u32, self.out_std, gated=True, score="softmax", route_scale=1.0, biased=False,
        )
        shared_gate = self.param("shared_gate", kernel(cfg.init_std), (cfg.hidden_size,))
        with jax.named_scope("moe.shared"):
            shared = GatedMLP(cfg, cfg.shared_expert_width, self.out_std, name="shared")(u)
            opened = jax.nn.sigmoid(jnp.dot(u32.astype(jnp.float32), shared_gate, precision=jax.lax.Precision.HIGHEST))
            shared = (shared * opened[..., None]).astype(cfg.dtype)
        return routed + shared, counters


class Qwen3NextBlock(nn.Module):
    config: Qwen3NextConfig
    kind: str  # LINEAR or FULL

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # rescale_prenorm_residual: each block's output projection starts 1/sqrt(layers) smaller
        out_std = cfg.init_std / np.sqrt(len(cfg.layer_types))
        normed = _norm(cfg, "input_layernorm")(x)
        if self.kind == LINEAR:
            mixed = GatedDeltaNet(cfg, out_std, name="linear_attn")(normed)
        else:
            mixed = GatedAttention(cfg, out_std, name="self_attn")(normed)
        x = x + mixed.astype(x.dtype)
        out, counters = Qwen3NextExperts(cfg, out_std, name="mlp")(_norm(cfg, "post_attention_layernorm")(x))
        return x + out.astype(x.dtype), counters


class Qwen3NextLM(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]]]:
        """``input_ids`` (B, T) -> fp32 logits (B, T, vocab) and the expert
        layers' counters of this call."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=kernel(cfg.init_std),
            dtype=cfg.dtype, name="embed",
        )(input_ids)
        x, counters = run_layers(Qwen3NextBlock, cfg, [(kind,) for kind in cfg.layer_types], x)
        x = _norm(cfg, "final_norm")(x).astype(cfg.dtype)
        head = self.param("head", kernel(cfg.init_std), (cfg.hidden_size, cfg.vocab_size))
        logits = jnp.dot(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)
        return logits, counters


def qwen3_next_tiny(**overrides) -> Qwen3NextLM:
    """The test tier's size: one period (three linear layers, one full), two
    value heads a key head, a quarter of each attention head rotary, 16
    experts with 4 held."""
    base = dict(
        vocab_size=256, hidden_size=64, layer_types=(LINEAR, LINEAR, LINEAR, FULL),
        linear_key_heads=2, linear_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16, chunk_size=8,
        n_heads=4, n_kv_heads=2, head_dim=16, expert_width=32, shared_expert_width=32,
        n_routed_experts=16, held_experts=(0, 1, 2, 3), experts_per_token=2,
    )
    base.update(overrides)
    return Qwen3NextLM(Qwen3NextConfig(**base))
