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
backward pass. ``RMSNorm``, the projections, the gated MLP, the rotary turn,
the buffers, the loss and the counters' tree are ``models/layers.py``'s:
``__call__`` returns ``(logits, counters)``, so its ``next_token_lm_loss`` and
``zero_counters`` serve this model as the other four.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import (
    FULL, SLIDING, GatedMLP, RMSNorm, Rope, causal_attention, dense, kernel, normed_and_turned, routed_experts,
    run_layers,
)
from .layers import BUFFERS, balanced_expert_bias  # noqa: F401  benchmark/builders and references read them here


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


class AfmoeExperts(nn.Module):
    config: AfmoeConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        u = u32.astype(cfg.dtype)
        routed, counters = routed_experts(
            self, cfg, u, u32, self.out_std, gated=True, score="sigmoid", route_scale=cfg.route_scale, biased=True,
        )
        with jax.named_scope("moe.shared"):
            shared = GatedMLP(cfg, cfg.n_shared_experts * cfg.expert_width, self.out_std, name="shared")(u)
        return routed + shared, counters


class AfmoeAttention(nn.Module):
    config: AfmoeConfig
    sliding: bool
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        window = cfg.sliding_window if self.sliding else None
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        q = dense(cfg, hq * hd, cfg.init_std, "q_proj")(u).reshape(bsz, t, hq, hd)
        k = dense(cfg, hkv * hd, cfg.init_std, "k_proj")(u).reshape(bsz, t, hkv, hd)
        v = dense(cfg, hkv * hd, cfg.init_std, "v_proj")(u).reshape(bsz, t, hkv, hd)
        gate = dense(cfg, hq * hd, cfg.init_std, "gate_proj")(u)
        with jax.named_scope("attn.rope"):
            rope = Rope(cfg.rope_theta) if self.sliding else None  # the full layers carry no positions
            norms = RMSNorm(cfg.norm_eps, name="q_norm"), RMSNorm(cfg.norm_eps, name="k_norm")
            q, k = normed_and_turned(*norms, q, k, rope, cfg.dtype)
        with jax.named_scope("attn.window" if self.sliding else "attn.full"):
            ctx = causal_attention(cfg, q, k, v, window)
        gated = ctx.reshape(bsz, t, hq * hd) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return dense(cfg, cfg.hidden_size, self.out_std, "o_proj")(gated.astype(cfg.dtype))


class AfmoeBlock(nn.Module):
    config: AfmoeConfig
    sliding: bool
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.norm_eps, name=name)
        # rescale_prenorm_residual: each block's output projection starts 1/sqrt(layers) smaller
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
            cfg.vocab_size, cfg.hidden_size, embedding_init=kernel(cfg.init_std),
            dtype=cfg.dtype, name="embed",
        )(input_ids)
        if cfg.mup_enabled:
            x = x * jnp.asarray(np.sqrt(cfg.hidden_size), x.dtype)
        kinds = [(kind == SLIDING, i < cfg.num_dense_layers) for i, kind in enumerate(cfg.layer_types)]
        x, counters = run_layers(AfmoeBlock, cfg, kinds, x)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x).astype(cfg.dtype)
        head = self.param("head", kernel(cfg.init_std), (cfg.hidden_size, cfg.vocab_size))
        logits = jnp.dot(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)
        return logits, counters


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
