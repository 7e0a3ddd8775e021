"""lfm2 — Liquid's LFM2 mixture-of-experts family (``model_type: lfm2_moe``):
gated short-convolution mixers, three to one grouped-query attention layer,
and sigmoid-routed gated experts; first-party flax.

Follows HuggingFace's ``modeling_lfm2_moe.py``. ``h = hidden_size``; every
norm is an RMSNorm with a learned scale; no bias anywhere:

- embedding: ``x = E[ids]`` (no scale); after the last block RMSNorm
  (``embedding_norm``), then the head TIED to the embedding, ``logits = x
  E^T``: one (V, h) leaf that takes the gradient of both uses.
- block, two norms: ``x <- x + mixer(N1(x))``, then ``x <- x + ffn(N2(x))``
  (``operator_norm``, ``ffn_norm``). The mixer's kind comes from
  ``layer_types``: ``conv`` or ``full_attention``.
- ``conv``, the gated short convolution: ``[B | C | z] = u W_in`` (h -> 3h, in
  that order); ``y = C * conv(B * z)``; ``out = y W_out``. ``conv`` is
  depthwise and causal over ``conv_kernel`` taps (``conv_L_cache`` 3),
  ``conv(s)_t = sum_j w[j] * s_{t-K+1+j}`` with zeros before the sequence
  (``ops.ssd.causal_conv1d``). No activation, no state past K - 1 positions.
- ``full_attention`` (``ops.flash_attention``), ``n_heads`` query heads over
  ``n_kv_heads`` key/value heads: ``q = RMSNorm_head(W_q u)``, ``k =
  RMSNorm_head(W_k u)``, ``v = W_v u``; q and k turned by the rotary embedding
  over the whole head in EVERY attention layer (``models/layers.rotary``,
  theta ``rope_theta``, angles in fp32); causal ``softmax(q k^T /
  sqrt(head_dim)) v``; ``out = W_o o``. No gate, no window.
- feed-forward: the first ``num_dense_layers`` layers a gated MLP of
  ``dense_width``, ``W_2 (silu(W_1 u) * W_3 u)`` (``models/layers.GatedMLP``);
  the others routed experts (``parallel.moe.held_experts_moe``): ``s =
  sigmoid(u W_r)`` in fp32 over all ``n_routed_experts``, the
  ``experts_per_token`` largest of ``s + expert_bias``, weights ``route_scale
  * s_i / sum_topk s``; every expert the gated form at ``expert_width``; no
  shared expert. This rank computes the experts in ``held_experts`` only and
  leaves out what the others would add. Nothing is dropped.
- ``expert_bias`` is a buffer no gradient reaches (the ``buffers`` collection,
  as ``models/afmoe.py``'s; zeros, the published initial value, where the
  caller brings none); ``layers.balanced_expert_bias`` balances it for weights
  that come from a seed.

Left out: any auxiliary loss, and the ``+ 1e-6`` HuggingFace adds to the
renormalising sum of the top scores (5e-7 of a weight; the plain reference
leaves it out too).

Parameters are fp32; ``dtype`` is what the products run in, and the residual
stream is carried in it. The router, every norm (q's and k's too) and the
rotary angles compute in fp32; the two gates and the taps' sum of the short
convolution compute in ``dtype``, as the depthwise convs of the other mixers
do. ``remat`` recomputes each block in the backward pass. ``RMSNorm``, the
projections, the gated MLP, the rotary turn, the buffers, the loss and the
counters' tree are ``models/layers.py``'s: ``__call__`` returns ``(logits,
counters)``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import (
    FULL, GatedMLP, RMSNorm, Rope, causal_attention, dense, kernel, normed_and_turned, routed_experts, run_layers,
)

CONV = "conv"


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (CONV, CONV) + (FULL, CONV, CONV, CONV) * 9 + (FULL, CONV)
    num_dense_layers: int = 2
    norm_eps: float = 1e-5
    conv_kernel: int = 3  # conv_L_cache
    # attention
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    attn_impl: str = "auto"  # "auto" (flash on TPU, einsum elsewhere) | "flash" | "einsum"
    # feed-forward
    dense_width: int = 11776
    expert_width: int = 1536
    n_routed_experts: int = 64  # the router's width
    held_experts: Tuple[int, ...] = tuple(range(64))  # the expert ids this rank computes
    experts_per_token: int = 4
    route_scale: float = 1.0  # routed_scaling_factor
    dtype: Any = jnp.float32
    remat: bool = False
    init_std: float = 0.02

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, FULL}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types {self.layer_types!r}: {CONV} or {FULL} per layer")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("heads must divide into their groups, and a head into two halves")

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.num_dense_layers, len(self.layer_types)))


class ShortConv(nn.Module):
    """``W_out (C * conv(B * z))``: the input gates the conv's input, a second
    gate its output."""

    config: Lfm2Config
    out_std: float

    @nn.compact
    def __call__(self, u32):
        from ..ops.ssd import causal_conv1d

        cfg = self.config
        u = u32.astype(cfg.dtype)
        bcz = dense(cfg, 3 * cfg.hidden_size, cfg.init_std, "in_proj")(u)
        bound = 1.0 / np.sqrt(cfg.conv_kernel)  # torch's Conv1d default for a depthwise kernel
        conv_kernel = self.param(
            "conv_kernel",
            lambda key, shape: jax.random.uniform(key, shape, minval=-bound, maxval=bound),
            (cfg.conv_kernel, cfg.hidden_size),
        )
        with jax.named_scope("shortconv.mix"):
            b, c, z = jnp.split(bcz, 3, axis=-1)
            y = c * causal_conv1d(b * z, conv_kernel, None)
        return dense(cfg, cfg.hidden_size, self.out_std, "out_proj")(y)


class Lfm2Attention(nn.Module):
    config: Lfm2Config
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        q = dense(cfg, hq * hd, cfg.init_std, "q_proj")(u).reshape(bsz, t, hq, hd)
        k = dense(cfg, hkv * hd, cfg.init_std, "k_proj")(u).reshape(bsz, t, hkv, hd)
        v = dense(cfg, hkv * hd, cfg.init_std, "v_proj")(u).reshape(bsz, t, hkv, hd)
        with jax.named_scope("attn.rope"):
            norms = RMSNorm(cfg.norm_eps, name="q_norm"), RMSNorm(cfg.norm_eps, name="k_norm")
            q, k = normed_and_turned(*norms, q, k, Rope(cfg.rope_theta), cfg.dtype)
        with jax.named_scope("attn.full"):
            ctx = causal_attention(cfg, q, k, v)
        return dense(cfg, cfg.hidden_size, self.out_std, "o_proj")(ctx.reshape(bsz, t, hq * hd))


class Lfm2Experts(nn.Module):
    config: Lfm2Config
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        return routed_experts(
            self, cfg, u32.astype(cfg.dtype), u32, self.out_std,
            gated=True, score="sigmoid", route_scale=cfg.route_scale, biased=True,
        )


class Lfm2Block(nn.Module):
    config: Lfm2Config
    kind: str  # CONV or FULL
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # rescale_prenorm_residual: each block's output projection starts 1/sqrt(layers) smaller
        out_std = cfg.init_std / np.sqrt(len(cfg.layer_types))
        normed = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
        if self.kind == CONV:
            mixed = ShortConv(cfg, out_std, name="conv")(normed)
        else:
            mixed = Lfm2Attention(cfg, out_std, name="self_attn")(normed)
        x = x + mixed.astype(x.dtype)
        normed, counters = RMSNorm(cfg.norm_eps, name="ffn_norm")(x), {}
        if self.dense:
            out = GatedMLP(cfg, cfg.dense_width, out_std, name="feed_forward")(normed)
        else:
            out, counters = Lfm2Experts(cfg, out_std, name="feed_forward")(normed)
        return x + out.astype(x.dtype), counters


class Lfm2LM(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]]]:
        """``input_ids`` (B, T) -> fp32 logits (B, T, vocab) and the expert
        layers' counters of this call."""
        cfg = self.config
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=kernel(cfg.init_std),
            dtype=cfg.dtype, name="embed",
        )
        x = embed(input_ids)
        kinds = [(kind, i < cfg.num_dense_layers) for i, kind in enumerate(cfg.layer_types)]
        x, counters = run_layers(Lfm2Block, cfg, kinds, x)
        x = RMSNorm(cfg.norm_eps, name="embedding_norm")(x).astype(cfg.dtype)
        # the tied head: x E^T, the (V, h) leaf contracted over h as it lies
        logits = jax.lax.dot_general(
            x, embed.embedding.astype(cfg.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return logits, counters


def lfm2_tiny(**overrides) -> Lfm2LM:
    """The test tier's size: a leading dense conv layer, then full, conv,
    conv, conv with experts, as the benchmark's cut; 16 experts with 4 held."""
    base = dict(
        vocab_size=256, hidden_size=64, layer_types=(CONV, FULL, CONV, CONV, CONV),
        num_dense_layers=1, n_heads=4, n_kv_heads=2, head_dim=16,
        dense_width=96, expert_width=32, n_routed_experts=16, held_experts=(0, 1, 2, 3),
        experts_per_token=2,
    )
    base.update(overrides)
    return Lfm2LM(Lfm2Config(**base))
