"""mellum — JetBrains' Mellum 2 mixture-of-experts family (``model_type:
mellum``): sliding-window and full attention layers three to one, BOTH turned
by a rotary embedding (plain in the sliding layers, YaRN-scaled in the full
ones), and softmax-routed gated experts in every layer; first-party flax.

Written from the published ``config.json`` (its keys are the Qwen3-MoE
lineage's). ``h = hidden_size``; every norm is an RMSNorm with a learned
scale; no bias anywhere:

- embedding: ``x = E[ids]`` (no scale; initialised at unit scale,
  ``EMBED_STD``, so that weights from a seed route a token by its own id);
  after the last block RMSNorm, then the untied head.
- block, two norms: ``x <- x + attn(N1(x))``, then ``x <- x + experts(N2(x))``
  (``input_layernorm``, ``post_attention_layernorm``). Every layer is
  ``sparse``: no dense feed-forward, no leading dense layer.
- attention (``ops.flash_attention``), ``n_heads`` query heads over
  ``n_kv_heads`` key/value heads: ``q = RMSNorm_head(W_q u)``, ``k =
  RMSNorm_head(W_k u)``, ``v = W_v u``; q and k turned by the rotary embedding
  over the whole head (``models/layers.rotary``, angles, cos and sin in fp32)
  with the layer kind's own ``rope_parameters``: a ``sliding_attention`` layer
  the default frequencies ``theta^(-2i/D)`` and query i sees key j iff ``0 <=
  i - j < sliding_window``; a ``full_attention`` layer YaRN's blended
  frequencies with cos and sin both times ``attention_factor`` (so its logits
  carry the square), causal. ``o = softmax(q k^T / sqrt(head_dim)) v``; ``out
  = W_o o``. No gate, no sink.
- experts (``parallel.moe.held_experts_moe``): ``s = softmax(u W_r)`` in fp32
  over all ``n_routed_experts``, the ``experts_per_token`` largest, weights
  ``s_i / sum_topk s`` (``norm_topk_prob``; no scaling factor, no selection
  bias, no shared expert); every expert the gated form ``W_d (silu(W_g u) *
  W_u u)`` at ``expert_width``. This rank computes the experts in
  ``held_experts`` only and leaves out what the others would add. Nothing is
  dropped. One token in eight is a given expert's (8 of 64): a rank's share
  of the load is the heaviest of the models here, which
  ``parallel.moe.chunk_rows`` sizes the layer's first chunk by.

Assumed, the row carrying no key for it: the per-head RMSNorm of q and k
(the lineage norms them unconditionally). Left out: the multi-token-prediction
head the family's description mentions (no key in the config) and any
auxiliary loss (no ``router_aux_loss_coef``).

Parameters are fp32; ``dtype`` is what the products run in, and the residual
stream is carried in it. The router, every norm (q's and k's too) and the
rotary frequencies, angles, cos and sin compute in fp32. ``remat`` recomputes
each block in the backward pass. ``RMSNorm``, the projections, the rotary
turn, the loss and the counters' tree are ``models/layers.py``'s:
``__call__`` returns ``(logits, counters)``. The model
has no buffers: ``TrainState.model_state`` carries the counters only.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import (
    FULL, SLIDING, RMSNorm, Rope, causal_attention, dense, kernel, normed_and_turned, routed_experts, run_layers,
)


# The embedding starts at unit scale where every kernel starts at ``init_std``. With both at 0.02 a
# block's attention output at weights from a seed (the mean of the context's values: the same
# vector at every position, ~0.08 an element through W_o) is four times the token's own embedding
# in the residual stream, every router then scores about the same vector for every token, and a
# layer's load on this rank is (how many of the eight experts all tokens pick are held here) x T:
# 0.14 T to 2.97 T by seed and layer on the chip, past the 3 T chunk inside a window, ``step_ms``
# in levels 20 ms apart (PERF.md section 6, PR 44). At unit scale a token routes by its own id, as
# in a trained model, and the load is the expected 2 T within what Zipf ids do to it. A run that
# loads weights brings its own embedding.
EMBED_STD = 1.0


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 7
    norm_eps: float = 1e-6
    # attention
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    # rope_parameters by layer kind: the default embedding, and YaRN (factor 16 over 8192 positions,
    # beta 32 / 1, attention_factor 0.1 ln(16) + 1)
    rope_sliding: Rope = Rope(500000.0)
    rope_full: Rope = Rope(500000.0, 16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    attn_impl: str = "auto"  # "auto" (flash on TPU, einsum elsewhere) | "flash" | "einsum"
    # experts
    expert_width: int = 896
    n_routed_experts: int = 64  # the router's width
    held_experts: Tuple[int, ...] = tuple(range(64))  # the expert ids this rank computes
    experts_per_token: int = 8
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
        return tuple(range(len(self.layer_types)))


class MellumAttention(nn.Module):
    config: MellumConfig
    kind: str  # SLIDING or FULL
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        sliding = self.kind == SLIDING
        window = cfg.sliding_window if sliding else None
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        q = dense(cfg, hq * hd, cfg.init_std, "q_proj")(u).reshape(bsz, t, hq, hd)
        k = dense(cfg, hkv * hd, cfg.init_std, "k_proj")(u).reshape(bsz, t, hkv, hd)
        v = dense(cfg, hkv * hd, cfg.init_std, "v_proj")(u).reshape(bsz, t, hkv, hd)
        with jax.named_scope("attn.rope"):
            rope = cfg.rope_sliding if sliding else cfg.rope_full  # both kinds turn, each by its own frequencies
            norms = RMSNorm(cfg.norm_eps, name="q_norm"), RMSNorm(cfg.norm_eps, name="k_norm")
            q, k = normed_and_turned(*norms, q, k, rope, cfg.dtype)
        with jax.named_scope("attn.window" if sliding else "attn.full"):
            ctx = causal_attention(cfg, q, k, v, window)
        return dense(cfg, cfg.hidden_size, self.out_std, "o_proj")(ctx.reshape(bsz, t, hq * hd))


class MellumExperts(nn.Module):
    config: MellumConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        # softmax scores, top k renormalised, no scaling factor; the model publishes no selection bias
        return routed_experts(
            self, cfg, u32.astype(cfg.dtype), u32, self.out_std, gated=True, score="softmax", route_scale=1.0, biased=False,
        )


class MellumBlock(nn.Module):
    config: MellumConfig
    kind: str  # SLIDING or FULL

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # rescale_prenorm_residual: each block's output projection starts 1/sqrt(layers) smaller
        out_std = cfg.init_std / np.sqrt(len(cfg.layer_types))
        normed = RMSNorm(cfg.norm_eps, name="input_layernorm")(x)
        x = x + MellumAttention(cfg, self.kind, out_std, name="self_attn")(normed).astype(x.dtype)
        normed = RMSNorm(cfg.norm_eps, name="post_attention_layernorm")(x)
        out, counters = MellumExperts(cfg, out_std, name="mlp")(normed)
        return x + out.astype(x.dtype), counters


class MellumLM(nn.Module):
    config: MellumConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]]]:
        """``input_ids`` (B, T) -> fp32 logits (B, T, vocab) and the expert
        layers' counters of this call."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=kernel(EMBED_STD),
            dtype=cfg.dtype, name="embed",
        )(input_ids)
        x, counters = run_layers(MellumBlock, cfg, [(kind,) for kind in cfg.layer_types], x)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x).astype(cfg.dtype)
        head = self.param("head", kernel(cfg.init_std), (cfg.hidden_size, cfg.vocab_size))
        logits = jnp.dot(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)
        return logits, counters


def mellum_tiny(**overrides) -> MellumLM:
    """The test tier's size: one period sliding, sliding, sliding, full; 16
    experts with 4 held, top 2 (one assignment in eight an expert's, as the
    model's 8 of 64; a quarter held, as the benchmark's cut); a window a
    quarter of the sequences the tests use; YaRN over an original length the
    tests' sequences pass."""
    base = dict(
        vocab_size=256, hidden_size=64, layer_types=(SLIDING, SLIDING, SLIDING, FULL),
        n_heads=4, n_kv_heads=2, head_dim=16, sliding_window=16,
        rope_sliding=Rope(10000.0), rope_full=Rope(10000.0, 4.0, 32),
        expert_width=32, n_routed_experts=16, held_experts=(0, 1, 2, 3), experts_per_token=2,
    )
    base.update(overrides)
    return MellumLM(MellumConfig(**base))
