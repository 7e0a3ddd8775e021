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


def _kernel(std: float):
    return nn.initializers.normal(stddev=std)


def _dense(cfg, width: int, std: float, name: str) -> nn.Dense:
    """Every projection of the model (and of ``models/afmoe.py``): no bias,
    products in ``cfg.dtype``."""
    return nn.Dense(width, use_bias=False, dtype=cfg.dtype, kernel_init=_kernel(std), name=name)


class RMSNorm(nn.Module):
    eps: float
    zero_centred: bool = False  # the learned scale is 1 + w, w from zero (``models/qwen3_next.py``)

    @nn.compact
    def __call__(self, x, scale_alone: bool = False):
        """fp32 in, fp32 out: callers cast to what their products take. With
        ``scale_alone`` what multiplies the normed ``x`` and no arithmetic on
        it, for a caller whose kernel norms (``models/afmoe.normed_and_turned``)."""
        init = nn.initializers.zeros if self.zero_centred else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],))
        if self.zero_centred:
            scale = 1.0 + scale
        if scale_alone:
            return scale
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def einsum_attention(q, k, v, window: int = None):
    """Causal grouped-query attention with the weights materialised, the
    engine off the TPU: q (B, T, H, D), k and v (B, T, Hkv, D) repeated to H
    heads; with ``window``, query i sees key j iff ``0 <= i - j < window``."""
    t, hd = q.shape[1], q.shape[-1]
    k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]  # query - key
    seen = (behind >= 0) & (behind < (window or t))
    weights = jax.nn.softmax(jnp.where(seen, scores / np.sqrt(hd), -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(q.dtype), v)


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
        zxbcdt = _dense(cfg, d_inner + conv_dim + h, cfg.init_std, "in_proj")(u)
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
        return _dense(cfg, cfg.hidden_size, self.out_std, "out_proj")(y)


class ExpertsMixer(nn.Module):
    config: NemotronHConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        from ..parallel.moe import held_experts_moe, relu_squared

        cfg = self.config
        d, f, held = cfg.hidden_size, cfg.expert_width, cfg.held_experts
        router = self.param("router", _kernel(cfg.init_std), (d, cfg.n_routed_experts))
        experts_in = self.param("experts_in", _kernel(cfg.init_std), (len(held), d, f))
        experts_out = self.param("experts_out", _kernel(self.out_std), (len(held), f, d))
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        routed, counters = held_experts_moe(
            u.reshape(bsz * t, d), u32.reshape(bsz * t, d), router,
            # e_score_correction_bias: a buffer the optimizer never touches, zeros here
            jnp.zeros((cfg.n_routed_experts,), jnp.float32),
            experts_in, experts_out, held, cfg.experts_per_token, cfg.routed_scaling,
        )
        with jax.named_scope("moe.shared"):
            hidden = relu_squared(_dense(cfg, cfg.shared_expert_width, cfg.init_std, "shared_in")(u))
            shared = _dense(cfg, d, self.out_std, "shared_out")(hidden)
        return routed.reshape(bsz, t, d) + shared, counters


class GroupedQueryAttention(nn.Module):
    config: NemotronHConfig
    out_std: float

    @nn.compact
    def __call__(self, u32):
        from ..ops.flash_attention import resolve_attn_impl

        cfg = self.config
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        u = u32.astype(cfg.dtype)
        bsz, t, _ = u.shape
        q = _dense(cfg, hq * hd, cfg.init_std, "q_proj")(u).reshape(bsz, t, hq, hd)
        k = _dense(cfg, hkv * hd, cfg.init_std, "k_proj")(u).reshape(bsz, t, hkv, hd)
        v = _dense(cfg, hkv * hd, cfg.init_std, "v_proj")(u).reshape(bsz, t, hkv, hd)
        with jax.named_scope("attn.core"):
            # each key/value head serves n_heads // n_kv_heads query heads
            if resolve_attn_impl(cfg.attn_impl) == "flash":
                from ..ops import flash_attention, pallas_interpret

                # the kernels read the shared head in place
                ctx = flash_attention(q, k, v, causal=True, interpret=pallas_interpret())
            else:
                ctx = einsum_attention(q, k, v)
        return _dense(cfg, cfg.hidden_size, self.out_std, "o_proj")(ctx.reshape(bsz, t, hq * hd))


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
            cfg.vocab_size, cfg.hidden_size, embedding_init=_kernel(cfg.init_std),
            dtype=cfg.dtype, name="embed",
        )(input_ids)
        block = nn.remat(NemotronHBlock) if cfg.remat else NemotronHBlock
        counters = {}
        for i, kind in enumerate(cfg.pattern):
            x, layer_counters = block(cfg, kind, name=f"layer_{i}")(x)
            if layer_counters:
                counters[f"layer_{i}"] = layer_counters
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x).astype(cfg.dtype)
        head = self.param("head", _kernel(cfg.init_std), (cfg.hidden_size, cfg.vocab_size))
        logits = jnp.dot(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)
        return logits, counters


def zero_counters(config) -> Dict[str, Dict[str, jax.Array]]:
    """The counters' tree before the first step: what ``init_state`` takes.
    ``config`` names its ``expert_layers`` and ``held_experts`` (this model's
    or any that calls ``held_experts_moe``, whose counters these are)."""
    zero = lambda *shape: jnp.zeros(shape, jnp.int32)
    return {
        f"layer_{i}": {
            "held": zero(len(config.held_experts)), "absent": zero(), "dropped": zero(), "row_tiles": zero(),
        }
        for i in config.expert_layers
    }


def next_token_lm_loss(model):
    """The trainer's loss function: mean next-token cross-entropy of fp32
    logits (``labels`` already shifted by the data), the expert layers'
    counters handed on as model state under ``STEP_COUNTERS``; whatever
    else the model state holds is the model's other variable collections
    (``models/afmoe.py``'s ``buffers``) and goes to it unchanged."""
    from ..parallel.trainer import STEP_COUNTERS

    def loss_fn(params, model_state, batch):
        others = {k: v for k, v in model_state.items() if k != STEP_COUNTERS}
        logits, counters = model.apply({"params": params, **others}, batch["input_ids"])
        # logsumexp minus the label's logit: no (B, T, vocab) array of log-probabilities
        picked = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
        loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
        return loss, {**model_state, STEP_COUNTERS: counters}

    return loss_fn


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
