"""phi4flash — Microsoft's Phi-4-mini-flash-reasoning (``model_type:
phi4flash``), SambaY's decoder-hybrid-decoder (Ren et al. 2025): a
self-decoder of Mamba-1 and sliding-window layers, then a cross-decoder whose
layers read ONE layer's scan output (the memory) and ONE layer's keys and
values (the cache); dense, no positional encoding anywhere; first-party flax.

``D = hidden_size``; every norm of the residual stream is a LayerNorm with a
scale and a bias. Which layer is what follows from its PUBLISHED index ``l``
and the published depth ``n`` (:func:`layer_kind`): a Mamba module iff ``l``
is even and ``l <= n/2``; sliding-window attention iff ``l`` is odd and ``l <
n/2``; ``l = n/2`` is the Mamba layer whose scan output is kept (the memory
source), ``l = n/2 + 1`` the model's one full-attention layer, whose keys and
values are kept (the cache source); from ``n/2 + 2`` on, even ``l`` is a Gated
Memory Unit and odd ``l`` a cross-attention.

- block, every kind: ``h <- h + Mixer(LN1(h))``, ``h <- h + MLP(LN2(h))``;
  ``MLP(u) = (silu(g) * y) W2`` with ``[g | y] = u W1``, one (D, 2 * width)
  leaf, the gate the first half; no bias.
- Mamba-1 mixer: ``[x | z] = u W_in``; ``x <- silu(conv(x))`` (depthwise,
  causal, K taps, a bias; ``ops.ssd.causal_conv1d``); ``[dt | B | C] = x W_x``;
  ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; ``y`` by the
  recurrence of ``ops/selective_scan.py``; **the memory is ``y``**, before the
  gate; out ``(y * silu(z)) W_out``. No bias but the conv's and ``b_dt``.
- differential attention (sliding or full): ``[q | k | v] = u W_qkv + b``;
  adjacent heads pair, ``q1 = q[0::2]``, ``q2 = q[1::2]`` and so k and v;
  ``O1 = [Att(q1, k1, v1) | Att(q1, k1, v2)]``, ``O2`` the same of ``q2, k2``;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init =
  0.8 - 0.6 exp(-0.3 l)`` by the PUBLISHED index; ``O = RMSNorm_2hd(O1 -
  lambda O2) * (1 - lambda_init)``; out ``O W_o + b_o``. ``Att`` is causal
  ``softmax(q k^T / sqrt(hd)) v``, within ``sliding_window`` in a sliding
  layer. **The cache is ``(k1, k2, v1, v2)``** of the full layer.
- Gated Memory Unit: ``(memory * silu(u W_in)) W_out``, no bias.
- cross-attention: ``q = u W_q + b`` alone; keys and values are the cache;
  causal, no window; its own lambda vectors, subln and ``W_o, b_o``.
- head: final LayerNorm, then the head TIED to the embedding, ``logits = h
  E^T`` in fp32; no scaling of the embedding, no head bias.

A block takes and returns the carry ``(h, memory, cache)``, ``None`` where
the source has not run: ``layers.run_layers`` hands any pytree from block to
block, so under ``remat`` the memory's cotangent is the sum over the GMU
layers' and the cache's over the full and the cross layers', across
``jax.checkpoint`` boundaries. Reading either before its source raises while
tracing.

Parameters are fp32; ``dtype`` is what the products run in, and the residual
stream, the memory and the cache are carried in it. ``delta``, ``A``, the
scan's state, every LayerNorm's and the subln's statistics, lambda, the
difference ``O1 - lambda O2`` and the logits are fp32. The four attentions of
a layer are ONE call of ``layers.causal_attention`` and two softmaxes a pair:
query heads stacked ``(q1, q2)`` over key heads ``(k1, k2)``, and both read the
value heads ``[v1 | v2]``, twice as wide, so the call returns ``(O1, O2)``:
flash on TPU (heads of 64 over value heads of 128, grouped two to one: the
kernels' fold), einsum elsewhere.
``__call__`` returns ``(logits, {})``: the model has no expert layer, so
``layers.zero_counters`` of its config is empty.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import causal_attention, dense, kernel, run_layers

MAMBA, SLIDING, FULL, GMU, CROSS = "mamba", "sliding_attention", "full_attention", "gmu", "cross_attention"
TIME_STEP_MIN, TIME_STEP_MAX = 1e-3, 1e-1  # what b_dt's initial step sizes lie between (Mamba's dt_min, dt_max)


def layer_kind(index: int, n_layers: int) -> str:
    """The kind of the layer with published ``index`` of a model ``n_layers`` deep."""
    half = n_layers // 2
    if index % 2 == 0:
        return MAMBA if index <= half else GMU
    return SLIDING if index < half else FULL if index == half + 1 else CROSS


def lambda_init(index: int) -> float:
    """Differential attention's ``lambda_init`` at the published layer ``index``."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    layer_indices: Tuple[int, ...] = tuple(range(32))  # the PUBLISHED indices of the layers held here, in order
    n_published_layers: int = 32  # num_hidden_layers as published: what layer_kind reads
    norm_eps: float = 1e-5
    mlp_width: int = 10240
    # attention
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    sliding_window: int = 512
    attn_impl: str = "auto"  # "auto" (flash on TPU, einsum elsewhere) | "flash" | "einsum"
    # Mamba-1
    d_inner: int = 5120
    state_size: int = 16
    conv_kernel: int = 4
    dt_rank: int = 160
    dtype: Any = jnp.float32
    remat: bool = False
    init_std: float = 0.02
    # no expert layer: the counters' tree (layers.zero_counters) is empty
    expert_layers: Tuple[int, ...] = ()
    held_experts: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.n_heads % 2 or self.n_kv_heads % 2 or (self.n_heads // 2) % (self.n_kv_heads // 2):
            raise ValueError("heads pair, and the query pairs divide into the key/value pairs")
        if not self.layer_indices or list(self.layer_indices) != sorted(set(self.layer_indices)):
            raise ValueError(f"layer_indices {self.layer_indices!r}: published indices, ascending")
        if self.layer_indices[0] < 0 or self.layer_indices[-1] >= self.n_published_layers:
            raise ValueError(f"layer_indices {self.layer_indices!r} of {self.n_published_layers} layers")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(layer_kind(i, self.n_published_layers) for i in self.layer_indices)

    @property
    def out_std(self) -> float:
        # rescale_prenorm_residual: each block's output projections start 1/sqrt(layers) smaller
        return self.init_std / math.sqrt(len(self.layer_indices))


def biased(cfg, width: int, std: float, name: str) -> nn.Dense:
    """The attention layers' projections: as ``layers.dense`` with a bias from zero."""
    return nn.Dense(width, use_bias=True, dtype=cfg.dtype, kernel_init=kernel(std), name=name)


def layer_norm(cfg, name: str) -> nn.LayerNorm:
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)


class Mamba1Mixer(nn.Module):
    """-> ``(out (B, T, D), y (B, T, d_inner))``: ``y`` is the scan's output
    before the gate, what the memory source keeps."""

    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u32):
        from ..ops.selective_scan import selective_scan
        from ..ops.ssd import causal_conv1d

        cfg = self.config
        c, n, r = cfg.d_inner, cfg.state_size, cfg.dt_rank
        u = u32.astype(cfg.dtype)
        x, z = jnp.split(dense(cfg, 2 * c, cfg.init_std, "in_proj")(u), 2, axis=-1)

        def uniform(bound):
            return lambda key, shape: jax.random.uniform(key, shape, minval=-bound, maxval=bound)

        def dt_bias_init(key, shape):
            # softplus^-1 of step sizes log-uniform in [TIME_STEP_MIN, TIME_STEP_MAX]
            low, high = np.log(TIME_STEP_MIN), np.log(TIME_STEP_MAX)
            step = jnp.exp(jax.random.uniform(key, shape) * (high - low) + low)
            return step + jnp.log(-jnp.expm1(-step))

        # torch's Conv1d default for a depthwise kernel
        conv_kernel = self.param("conv_kernel", uniform(1.0 / np.sqrt(cfg.conv_kernel)), (cfg.conv_kernel, c))
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (c,))
        dt_kernel = self.param("dt_proj", uniform(r ** -0.5), (r, c))
        dt_bias = self.param("dt_bias", dt_bias_init, (c,))
        a_log = self.param(
            "a_log", lambda key, shape: jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), shape), (c, n)
        )
        d_skip = self.param("d", nn.initializers.ones, (c,))

        with jax.named_scope("mamba.conv"):
            x = jax.nn.silu(causal_conv1d(x, conv_kernel, conv_bias))
        with jax.named_scope("mamba.frame"):
            dt, b, c_out = jnp.split(dense(cfg, r + 2 * n, cfg.init_std, "x_proj")(x), [r, r + n], axis=-1)
            delta = jax.nn.softplus(
                jnp.dot(dt, dt_kernel.astype(cfg.dtype), preferred_element_type=jnp.float32) + dt_bias
            )
        with jax.named_scope("mamba.scan"):
            y = selective_scan(x, delta, -jnp.exp(a_log.astype(jnp.float32)), b, c_out, d_skip)
        with jax.named_scope("mamba.frame"):
            gated = y * jax.nn.silu(z)
        return dense(cfg, cfg.hidden_size, cfg.out_std, "out_proj")(gated), y


def paired(x):
    """Heads (B, T, H, hd) -> the even ones and the odd ones, (B, T, H/2, hd) each."""
    return x[:, :, 0::2], x[:, :, 1::2]


def difference(o1, o2, lam, scale, eps: float, rest: float, dtype):
    """``RMSNorm(o1 - lam o2) * scale * rest`` over the last axis, in fp32, cast
    to ``dtype``: what follows the four attentions (the subln)."""
    o = o1.astype(jnp.float32) - lam * o2.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)
    return (o * scale * rest).astype(dtype)


class DiffAttention(nn.Module):
    """Differential attention of the published layer ``index``. With a
    ``cache`` the layer is a cross-attention: it projects q alone and reads
    the cache's ``(k1, k2, v1, v2)``. -> ``(out, (k1, k2, v1, v2))``."""

    config: Phi4FlashConfig
    index: int
    window: Optional[int] = None

    @nn.compact
    def __call__(self, u32, cache=None):
        cfg = self.config
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        if cache is None:
            qkv = biased(cfg, (hq + 2 * hkv) * hd, cfg.init_std, "Wqkv")(u)
            q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
            scope = "attn.full" if self.window is None else "attn.window"
        else:
            q, scope = biased(cfg, hq * hd, cfg.init_std, "Wq")(u), "attn.cross"
        vector = lambda name: self.param(name, nn.initializers.normal(stddev=0.1), (hd,))
        lq1, lk1, lq2, lk2 = (vector(f"lambda_{name}") for name in ("q1", "k1", "q2", "k2"))
        init = lambda_init(self.index)
        with jax.named_scope("attn.diff"):
            q1, q2 = paired(q.reshape(bsz, t, hq, hd))
            if cache is None:
                cache = paired(k.reshape(bsz, t, hkv, hd)) + paired(v.reshape(bsz, t, hkv, hd))
            k1, k2, v1, v2 = cache
            heads = lambda *parts: jnp.concatenate(parts, axis=2)
            values = jnp.concatenate([v1, v2], axis=-1)  # (B, T, hkv/2, 2 hd): what q1's softmax and q2's both multiply
            stacked = heads(q1, q2), heads(k1, k2), heads(values, values)
        with jax.named_scope(scope):
            o1, o2 = jnp.split(causal_attention(cfg, *stacked, self.window), 2, axis=2)  # (B, T, hq/2, 2 hd) each
        with jax.named_scope("attn.diff"):
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
            scale = self.param("subln", nn.initializers.ones, (2 * hd,))
            o = difference(o1, o2, lam, scale, cfg.norm_eps, 1.0 - init, cfg.dtype)
        return biased(cfg, cfg.hidden_size, cfg.out_std, "out_proj")(o.reshape(bsz, t, hq * hd)), cache


class GatedMemoryUnit(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u32, memory):
        cfg = self.config
        with jax.named_scope("gmu.mix"):
            gate = dense(cfg, cfg.d_inner, cfg.init_std, "in_proj")(u32.astype(cfg.dtype))
            return dense(cfg, cfg.hidden_size, cfg.out_std, "out_proj")(memory * jax.nn.silu(gate))


class GatedUpMLP(nn.Module):
    """``(silu(g) * y) W2`` with ``[g | y] = u W1``: gate and up one leaf."""

    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        gate, up = jnp.split(dense(cfg, 2 * cfg.mlp_width, cfg.init_std, "gate_up_proj")(u32.astype(cfg.dtype)), 2, axis=-1)
        return dense(cfg, cfg.hidden_size, cfg.out_std, "down_proj")(jax.nn.silu(gate) * up)


class Phi4FlashBlock(nn.Module):
    config: Phi4FlashConfig
    index: int  # the layer's PUBLISHED index

    @nn.compact
    def __call__(self, carry):
        cfg = self.config
        h, memory, cache = carry
        kind = layer_kind(self.index, cfg.n_published_layers)
        half = cfg.n_published_layers // 2
        normed = layer_norm(cfg, "norm_1")(h)
        if kind == MAMBA:
            mixed, y = Mamba1Mixer(cfg, name="mixer")(normed)
            memory = y if self.index == half else memory
        elif kind == GMU:
            if memory is None:
                raise ValueError(f"layer {self.index} reads the memory, and no layer {half} ran before it")
            mixed = GatedMemoryUnit(cfg, name="mixer")(normed, memory)
        elif kind == CROSS:
            if cache is None:
                raise ValueError(f"layer {self.index} reads the cache, and no layer {half + 1} ran before it")
            mixed, _ = DiffAttention(cfg, self.index, name="mixer")(normed, cache)
        else:
            window = cfg.sliding_window if kind == SLIDING else None
            mixed, kv = DiffAttention(cfg, self.index, window, name="mixer")(normed)
            cache = kv if kind == FULL else cache
        h = h + mixed.astype(h.dtype)
        h = h + GatedUpMLP(cfg, name="mlp")(layer_norm(cfg, "norm_2")(h)).astype(h.dtype)
        return (h, memory, cache), {}


class Phi4FlashLM(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jax.Array, Dict]:
        """``input_ids`` (B, T) -> fp32 logits (B, T, vocab) and no counters."""
        cfg = self.config
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=kernel(cfg.init_std), dtype=cfg.dtype, name="embed",
        )
        carry = (embed(input_ids), None, None)
        (h, _, _), counters = run_layers(Phi4FlashBlock, cfg, [(i,) for i in cfg.layer_indices], carry)
        h = layer_norm(cfg, "final_norm")(h).astype(cfg.dtype)
        # the tied head: h E^T, the (V, D) leaf contracted over D as it lies
        logits = jax.lax.dot_general(
            h, embed.embedding.astype(cfg.dtype), (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        return logits, counters


def phi4flash_tiny(**overrides) -> Phi4FlashLM:
    """The test tier's size: the benchmark's cut, the model's own layers
    15-19 (sliding, the memory source, the cache source, a GMU, a cross)."""
    base = dict(
        vocab_size=256, hidden_size=64, layer_indices=(15, 16, 17, 18, 19), mlp_width=96,
        n_heads=4, n_kv_heads=2, head_dim=16, sliding_window=16, d_inner=128, state_size=4, dt_rank=4,
    )
    base.update(overrides)
    return Phi4FlashLM(Phi4FlashConfig(**base))
