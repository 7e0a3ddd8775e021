"""Nemotron-H — a hybrid stack of Mamba-2, mixture-of-experts and attention
layers (NVIDIA-Nemotron-3-Nano's ``nemotron_h``), first-party flax.

One mixer a layer, chosen by ``pattern`` (HuggingFace's
``hybrid_override_pattern``): ``M`` a Mamba-2 mixer, ``E`` a routed expert
layer with one shared expert, ``*`` grouped-query attention. Every block is
``x <- x + mixer(RMSNorm(x))``; after the last, RMSNorm and an untied head.
No positional embedding anywhere: ``NemotronHAttention`` applies no rotary
embedding (the config's ``rope_theta`` is unused) and the Mamba layers carry
the order.

- **M** (``ops.ssd``): ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC))``
  (causal, depthwise, kernel ``conv_kernel``); ``x, B, C`` split from it;
  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the SSD scan in
  chunks of ``chunk_size``; ``y <- RMSNorm_grouped(y * silu(z))`` (the gate
  before the norm); ``out = y W_out``. ``d_inner = mamba_heads *
  mamba_head_dim``, not ``expand * hidden``.
- **E** (``parallel.moe.held_experts_moe``): sigmoid scores over all
  ``n_routed_experts``, top ``experts_per_token``, renormalised and scaled;
  relu² experts without a gate; this rank computes the experts in
  ``held_experts`` only and every rank the shared expert. Nothing is dropped.
- **\\*** (``ops.flash_attention``): ``n_heads`` query heads over
  ``n_kv_heads`` key/value heads, causal, scale ``1/sqrt(head_dim)``, no bias.
  The kernels take q, k, v as the projections emit them, ``(B, T, heads *
  head_dim)``, a head of 128 lanes a block, and read the key/value head a
  group shares in place: nothing is repeated or transposed on the way in or
  out (the einsum path, off the TPU, repeats K and V).

Parameters are fp32; ``dtype`` is what the products run in. The residual
stream is carried in ``dtype`` (the published ``residual_in_fp32: false``).
The router, the SSD's decay and every norm compute in fp32. ``remat``
recomputes each block in the backward pass (``jax.checkpoint`` per layer).

``__call__`` returns ``(logits, counters)``: ``counters["layer_<i>"]`` holds
an expert layer's int32 counts of the call (assignments per held expert,
assignments on absent experts, dropped assignments), which the loss function
hands to the trainer as model state (``parallel.trainer.STEP_COUNTERS``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import RMSNorm, causal_attention, dense, kernel, routed_experts, run_layers
from .layers import next_token_lm_loss, zero_counters  # noqa: F401  benchmark/builders and references read them here


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    norm_eps: float = 1e-5
    # Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts
    n_routed_experts: int = 128  # the router's width
    held_experts: Tuple[int, ...] = tuple(range(128))  # the expert ids this rank computes
    experts_per_token: int = 6
    routed_scaling: float = 2.5
    expert_width: int = 1856
    shared_expert_width: int = 3712
    # attention
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    attn_impl: str = "auto"  # "auto" (flash on TPU, einsum elsewhere) | "flash" | "einsum"
    dtype: Any = jnp.float32
    remat: bool = False
    init_std: float = 0.02

    def __post_init__(self):
        unknown = set(self.pattern) - set("ME*")
        if unknown or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: one of M, E, * per layer")
        if self.mamba_heads % self.mamba_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide into their groups")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.pattern) if kind == "E")


class Mamba2Mixer(nn.Module):
    config: NemotronHConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        from ..ops.ssd import causal_conv1d, gated_group_rms_norm, ssd_scan

        cfg = self.config
        h, p, g, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups, cfg.state_size
        d_inner, conv_dim = cfg.d_inner, cfg.d_inner + 2 * g * n
        u = u32.astype(cfg.dtype)
        zxbcdt = dense(cfg, d_inner + conv_dim + h, cfg.init_std, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)

        def dt_bias_init(key, shape):
            # softplus^-1 of step sizes log-uniform in [time_step_min, time_step_max]
            low, high = np.log(cfg.time_step_min), np.log(cfg.time_step_max)
            step = jnp.exp(jax.random.uniform(key, shape) * (high - low) + low)
            step = jnp.maximum(step, cfg.time_step_floor)
            return step + jnp.log(-jnp.expm1(-step))

        bound = 1.0 / np.sqrt(cfg.conv_kernel)  # torch's Conv1d default for a depthwise kernel
        conv_kernel = self.param(
            "conv_kernel",
            lambda key, shape: jax.random.uniform(key, shape, minval=-bound, maxval=bound),
            (cfg.conv_kernel, conv_dim),
        )
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
        dt_bias = self.param("dt_bias", dt_bias_init, (h,))
        a_log = self.param(
            "a_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0)), (h,)
        )
        d = self.param("d", nn.initializers.ones, (h,))
        norm_scale = self.param("norm_scale", nn.initializers.ones, (d_inner,))

        with jax.named_scope("mamba.conv"):
            xbc = jax.nn.silu(causal_conv1d(xbc, conv_kernel, conv_bias))
        x, b, c = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
        bsz, t = x.shape[:2]
        with jax.named_scope("mamba.ssd"):
            y = ssd_scan(
                x.reshape(bsz, t, h, p),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log.astype(jnp.float32)),
                b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n), d, cfg.chunk_size,
            )
        y = gated_group_rms_norm(y.reshape(bsz, t, d_inner), z, norm_scale, g, cfg.norm_eps)
        return dense(cfg, cfg.hidden_size, self.out_std, "out_proj")(y)


class ExpertsMixer(nn.Module):
    config: NemotronHConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        from ..parallel.moe import relu_squared

        cfg = self.config
        u = u32.astype(cfg.dtype)
        # e_score_correction_bias is a buffer the optimizer never touches, zeros here: not biased
        routed, counters = routed_experts(
            self, cfg, u, u32, self.out_std, gated=False, score="sigmoid", route_scale=cfg.routed_scaling, biased=False,
        )
        with jax.named_scope("moe.shared"):
            hidden = relu_squared(dense(cfg, cfg.shared_expert_width, cfg.init_std, "shared_in")(u))
            shared = dense(cfg, cfg.hidden_size, self.out_std, "shared_out")(hidden)
        return routed + shared, counters


class GroupedQueryAttention(nn.Module):
    config: NemotronHConfig
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
        with jax.named_scope("attn.core"):
            ctx = causal_attention(cfg, q, k, v)
        return dense(cfg, cfg.hidden_size, self.out_std, "o_proj")(ctx.reshape(bsz, t, hq * hd))


class NemotronHBlock(nn.Module):
    config: NemotronHConfig
    kind: str  # "M", "E" or "*"

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # rescale_prenorm_residual: each block's output projection starts 1/sqrt(layers) smaller
        out_std = cfg.init_std / np.sqrt(len(cfg.pattern))
        normed = RMSNorm(cfg.norm_eps, name="norm")(x)
        counters = {}
        if self.kind == "M":
            out = Mamba2Mixer(cfg, out_std, name="mixer")(normed)
        elif self.kind == "E":
            out, counters = ExpertsMixer(cfg, out_std, name="mixer")(normed)
        else:
            out = GroupedQueryAttention(cfg, out_std, name="mixer")(normed)
        return x + out.astype(x.dtype), counters


class NemotronHLM(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]]]:
        """``input_ids`` (B, T) -> fp32 logits (B, T, vocab) and the expert
        layers' counters of this call."""
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=kernel(cfg.init_std),
            dtype=cfg.dtype, name="embed",
        )(input_ids)
        x, counters = run_layers(NemotronHBlock, cfg, [(kind,) for kind in cfg.pattern], x)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x).astype(cfg.dtype)
        head = self.param("head", kernel(cfg.init_std), (cfg.hidden_size, cfg.vocab_size))
        logits = jnp.dot(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)
        return logits, counters


def nemotron_h_tiny(**overrides) -> NemotronHLM:
    """The test tier's size: every kind of layer, 16 experts with 4 held."""
    base = dict(
        vocab_size=256, hidden_size=64, pattern="MEMEM*EME",
        mamba_heads=4, mamba_head_dim=16, mamba_groups=2, state_size=16, chunk_size=8,
        n_routed_experts=16, held_experts=(0, 1, 2, 3), experts_per_token=2,
        expert_width=48, shared_expert_width=96, n_heads=4, n_kv_heads=2, head_dim=16,
    )
    base.update(overrides)
    return NemotronHLM(NemotronHConfig(**base))
