"""sdar — JetLM's SDAR mixture-of-experts family (``model_type: sdar_moe``):
a Qwen3-MoE decoder trained to denoise BLOCKS of tokens, so that it
generates a block at a time by diffusion and block after block
autoregressively; first-party flax, the training side.

Written from the published ``config.json`` (the Qwen3-MoE lineage's keys) and
the block-diffusion objective; every norm is an RMSNorm with a learned scale,
no bias anywhere.

The objective. A sample is L token ids ``x0`` in L / B blocks of B
(``block_length``). Block b draws a noise level ``t_b ~ U(eps, 1)`` and each of
its tokens is replaced by the ``[MASK]`` id independently with probability
``t_b``, which gives ``xt`` (``data.noising.block_noised``). The loss is ``(1 /
L) sum_b (1 / t_b) sum_{i in b, replaced} -log p(x0_i | xt_b, x0_{<b})``: a
replaced position's OWN output row predicts its token (no shift), from its own
noised block and the clean blocks before it
(``models.layers.masked_token_loss``).

One forward for all blocks. ``p`` for block b is the model on ``x0_{<b} + xt_b``
at positions ``0..(b+1)B-1`` under block-causal attention (a query sees every
key whose block is not later than its own: bidirectional inside a block). All
L / B of those runs are one run over 2L rows ``[xt ; x0]`` at positions
``[0..L) + [0..L)`` in which query i sees key j iff

- i and j are both noised and in the same block, or
- i is noised, j is clean and ``block(j) < block(i)``, or
- i and j are both clean and ``block(j) <= block(i)``;

a clean query sees no noised key (``models.layers.blockwise_seen``; the flash
kernels walk it by loop bounds, ``ops.flash_attention(blockwise=(L, B))``).
Every other operation is row-wise. The final norm and the head run on the L
noised rows only.

The block (Qwen3-MoE's):

- ``h <- h + W_o attn(rope(RMSNorm_head(W_q u)), rope(RMSNorm_head(W_k u)),
  W_v u)`` with ``u = RMSNorm(h)``: ``n_heads`` query heads over
  ``n_kv_heads`` key/value heads, the rotary embedding over the whole head
  (``theta^(-2i/D)``, row r at position ``r mod L``), ``softmax(q k^T /
  sqrt(head_dim))`` over the keys the rule shows.
- ``h <- h + experts(RMSNorm(h))`` (``parallel.moe.held_experts_moe``): ``s =
  softmax(u W_r)`` in fp32 over all ``n_routed_experts``, the
  ``experts_per_token`` largest, weights ``s_i / sum_topk s``
  (``norm_topk_prob``), every expert ``W_d (silu(W_g u) * W_u u)`` at
  ``expert_width``; no shared expert, no dense layer (``decoder_sparse_step``
  1, ``mlp_only_layers`` empty). This rank computes the experts in
  ``held_experts`` only and leaves out what the others would add. A layer sees
  2L rows, of which the replaced ones carry one id.
- embedding ``x = E[ids]`` at unit scale (``EMBED_STD``: weights from a seed
  then route a token by its own id); after the last block RMSNorm, then the
  untied head.

Assumed, the row carrying no key for them: ``block_length`` 4 (the release's
default) and the schedule above; the per-head RMSNorm of q and k (the lineage
norms them). Left out: any auxiliary loss (no key). Generation (a block
denoised over several steps against a cache of finished blocks) is not here.

Parameters are fp32 and ``dtype`` is what the products run in. THE RESIDUAL
STREAM IS CARRIED IN fp32, where the six other models carry it in ``dtype``:
the replaced rows of a step, a quarter of all it runs, are ONE token, so their
top-k in a layer is one decision for thousands of rows; rounding the stream
(the unit-scale embedding above all) to bf16 moved that decision against an
fp32 run on one seed in eight on the chip, and with it a held expert's whole
load and gradient (PERF.md section 6, PR 51). Every product still takes bf16
operands. The router, every norm and the rotary tables compute in fp32.
``remat`` recomputes each block in the backward pass. ``__call__`` returns
``(logits, counters)`` as every model of ``models/layers.py`` does.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import RMSNorm, Rope, causal_attention, dense, kernel, normed_and_turned, routed_experts, run_layers

# The embedding at unit scale beside kernels at ``init_std``: a token then routes by its own id, as in
# a trained model, and this rank's load is the expected one (PERF.md section 6, PR 44's finding).
EMBED_STD = 1.0


@dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    n_layers: int = 48
    norm_eps: float = 1e-6
    block_length: int = 4  # B: the tokens denoised together
    # attention
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope: Rope = Rope(1000000.0)
    attn_impl: str = "auto"  # "auto" (flash on TPU, einsum elsewhere) | "flash" | "einsum"
    # experts
    expert_width: int = 768
    n_routed_experts: int = 128  # the router's width
    held_experts: Tuple[int, ...] = tuple(range(128))  # the expert ids this rank computes
    experts_per_token: int = 8
    dtype: Any = jnp.float32
    remat: bool = False
    init_std: float = 0.02

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("heads must divide into their groups, and a head into two halves")
        if self.block_length < 1 or self.n_layers < 1:
            raise ValueError("at least one layer, and a block of at least one token")

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.n_layers))


class SdarAttention(nn.Module):
    config: SdarConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        """``u32`` (B, 2L, d): the noised copy's rows, then the clean copy's."""
        cfg = self.config
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        half = t // 2
        q = dense(cfg, hq * hd, cfg.init_std, "q_proj")(u).reshape(bsz, t, hq, hd)
        k = dense(cfg, hkv * hd, cfg.init_std, "k_proj")(u).reshape(bsz, t, hkv, hd)
        v = dense(cfg, hkv * hd, cfg.init_std, "v_proj")(u).reshape(bsz, t, hkv, hd)
        with jax.named_scope("attn.rope"):
            norms = RMSNorm(cfg.norm_eps, name="q_norm"), RMSNorm(cfg.norm_eps, name="k_norm")
            # both copies stand at positions 0..L-1
            q, k = normed_and_turned(*norms, q, k, cfg.rope, cfg.dtype, positions=jnp.arange(t) % half)
        with jax.named_scope("attn.blockwise"):
            ctx = causal_attention(cfg, q, k, v, blockwise=(half, cfg.block_length))
        return dense(cfg, cfg.hidden_size, self.out_std, "o_proj")(ctx.reshape(bsz, t, hq * hd))


class SdarExperts(nn.Module):
    config: SdarConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        cfg = self.config
        # softmax scores, top k renormalised, no scaling factor; the model publishes no selection bias
        return routed_experts(
            self, cfg, u32.astype(cfg.dtype), u32, self.out_std, gated=True, score="softmax", route_scale=1.0, biased=False,
        )


class SdarBlock(nn.Module):
    config: SdarConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # rescale_prenorm_residual: each block's output projection starts 1/sqrt(layers) smaller
        out_std = cfg.init_std / np.sqrt(cfg.n_layers)
        normed = RMSNorm(cfg.norm_eps, name="input_layernorm")(x)
        x = x + SdarAttention(cfg, out_std, name="self_attn")(normed).astype(x.dtype)
        normed = RMSNorm(cfg.norm_eps, name="post_attention_layernorm")(x)
        out, counters = SdarExperts(cfg, out_std, name="mlp")(normed)
        return x + out.astype(x.dtype), counters


class SdarLM(nn.Module):
    config: SdarConfig

    @nn.compact
    def __call__(self, ids) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]]]:
        """``ids`` (B, 2L): a sample's noised copy, then its clean copy -> fp32
        logits (B, L, vocab) of the NOISED rows and the expert layers'
        counters of this call (every layer saw 2L rows)."""
        cfg = self.config
        half = ids.shape[1] // 2
        if ids.shape[1] % 2 or half % cfg.block_length:
            raise ValueError(f"{ids.shape[1]} rows: two copies of whole blocks of {cfg.block_length}")
        # fp32, and with it the whole residual stream (a block adds its output in the stream's dtype): see the module's text
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=kernel(EMBED_STD),
            dtype=jnp.float32, name="embed",
        )(ids)
        x, counters = run_layers(SdarBlock, cfg, [()] * cfg.n_layers, x)
        with jax.named_scope("denoise.loss"):
            x = RMSNorm(cfg.norm_eps, name="final_norm")(x[:, :half]).astype(cfg.dtype)
            head = self.param("head", kernel(cfg.init_std), (cfg.hidden_size, cfg.vocab_size))
            logits = jnp.dot(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)
        return logits, counters


def sdar_tiny(**overrides) -> SdarLM:
    """The test tier's size: four layers; 16 experts with 4 held, top 2 (one
    assignment in eight an expert's, a quarter of them held); blocks of 4."""
    base = dict(
        vocab_size=256, hidden_size=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, rope=Rope(10000.0),
        expert_width=32, n_routed_experts=16, held_experts=(0, 1, 2, 3), experts_per_token=2,
    )
    base.update(overrides)
    return SdarLM(SdarConfig(**base))
