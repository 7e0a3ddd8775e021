"""What more than one of the seven language models uses (``nemotron_h``, ``afmoe``,
``qwen3_next``, ``lfm2``, ``mellum``, ``phi4flash``, ``sdar``), between ``parallel/moe.py``
and ``ops/`` below and one file per model above: projections and norms, the
rotary embedding, the one choice of attention engine, the one caller of
``held_experts_moe``, the layer stack, the counters' tree and the losses (every
model's ``__call__`` returns ``(logits, counters)``: next-token cross-entropy
serves six, the masked-token loss the one trained by block diffusion).

A model file imports from here and from no other model: a piece comes here
when a second model needs it, never by an import from a sibling. What a model
has alone stays in its file (its config, its mixers, its attention module's
projections and gating, its block, its ``*LM``, its ``*_tiny``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Iterable, Mapping, NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"
BUFFERS = "buffers"  # the variable collection of what no gradient reaches: every expert_bias


def kernel(std: float):
    return nn.initializers.normal(stddev=std)


def dense(cfg, width: int, std: float, name: str) -> nn.Dense:
    """Every projection of the five models: no bias, products in ``cfg.dtype``."""
    return nn.Dense(width, use_bias=False, dtype=cfg.dtype, kernel_init=kernel(std), name=name)


class RMSNorm(nn.Module):
    eps: float
    zero_centred: bool = False  # the learned scale is 1 + w, w from zero (``models/qwen3_next.py``)

    @nn.compact
    def __call__(self, x, scale_alone: bool = False):
        """fp32 in, fp32 out: callers cast to what their products take. With
        ``scale_alone`` what multiplies the normed ``x`` and no arithmetic on
        it, for a caller whose kernel norms (:func:`normed_and_turned`)."""
        init = nn.initializers.zeros if self.zero_centred else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],))
        if self.zero_centred:
            scale = 1.0 + scale
        if scale_alone:
            return scale
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


class GatedMLP(nn.Module):
    """``W_d (silu(W_g u) * W_u u)``: the leading dense layers and the shared expert."""

    config: Any
    width: int
    out_std: float

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        u = u.astype(cfg.dtype)
        gate = dense(cfg, self.width, cfg.init_std, "gate_proj")(u)
        up = dense(cfg, self.width, cfg.init_std, "up_proj")(u)
        return dense(cfg, cfg.hidden_size, self.out_std, "down_proj")(jax.nn.silu(gate) * up)


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


def rotary(x: jax.Array, rope: Rope, rotary_dim: Optional[int] = None, positions: Optional[jax.Array] = None) -> jax.Array:
    """``x`` (B, T, H, D) in fp32, position t turned by the angles ``t *
    inv_freq`` of ``rope_frequencies(rope, D)`` (the default embedding: ``t
    * theta^(-2i/D)``): ``x cos + rotate_half(x) sin``, the halves paired as
    HuggingFace pairs them (i with i + D/2), cos and sin times the
    embedding's factor where it has one (YaRN). With ``rotary_dim`` only the
    head's first ``rotary_dim`` dims turn, as a head of that size would
    (``partial_rotary_factor``); the others pass as they came. Row t stands
    at ``positions[t]`` ((T,) integers; ``None``: at t)."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate([rotary(x[..., :rotary_dim], rope, None, positions), x[..., rotary_dim:]], axis=-1)
    angles, factor = _angles(rope, x.shape[1], x.shape[-1], positions)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _angles(rope: Rope, t: int, dim: int, positions: Optional[jax.Array] = None) -> Tuple[jax.Array, float]:
    """``(position * inv_freq`` (T, dim/2) in fp32``, factor)`` of ``rope_frequencies(rope, dim)``; the positions
    ``0..t-1`` where none are given."""
    inv_freq, factor = rope_frequencies(rope, dim)
    at = jnp.arange(t, dtype=jnp.float32) if positions is None else positions.astype(jnp.float32)
    return at[:, None] * inv_freq[None, :], factor


def rope_tables(rope: Rope, t: int, dim: int, positions: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """What :func:`rotary` turns ``dim`` dims by, as tables: cos and sin of
    its angles times its factor, (T, dim/2) in fp32 each."""
    angles, factor = _angles(rope, t, dim, positions)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos * factor, sin * factor) if factor != 1.0 else (cos, sin)


def normed_and_turned(
    q_norm: RMSNorm, k_norm: RMSNorm, q, k, rope: Optional[Rope], dtype, rotary_dim: Optional[int] = None,
    interpret: Optional[bool] = None, positions: Optional[jax.Array] = None,
):
    """What an attention layer does to q (B, T, H, D) and k (B, T, H_kv, D)
    between their projections and the attention itself, under its scope
    ``attn.rope``: each head through its norm, then turned by ``rope`` as
    :func:`rotary` turns it (``None``: the layer carries no positions), row t
    at ``positions[t]`` (``None``: at t), then cast to ``dtype``.

    ``interpret=None`` lets the backend decide: on TPU one Pallas pass forward
    and one backward (``ops/qk_rope.py``) where its tiles serve the shape,
    elsewhere and otherwise these XLA lines; ``True`` runs the kernels in the
    Pallas interpreter, ``False`` the kernels whatever traces them."""
    from ..ops import pallas_interpret, qk_rope

    t, d = q.shape[1], q.shape[-1]
    turning = 0 if rope is None else d if rotary_dim is None else min(rotary_dim, d)
    served = qk_rope.serves(t, q.shape[2], k.shape[2], d, turning)
    at = {} if positions is None else {"positions": positions}  # without positions: the calls as they were
    if not served or (interpret is None and pallas_interpret()):
        turned = lambda x: x if rope is None else rotary(x, rope, rotary_dim, **at)
        return turned(q_norm(q)).astype(dtype), turned(k_norm(k)).astype(dtype)
    cos, sin = (None, None) if rope is None else rope_tables(rope, t, turning, **at)
    scales = q_norm(q, scale_alone=True), k_norm(k, scale_alone=True)
    return qk_rope.normed_and_turned(q, k, *scales, cos, sin, q_norm.eps, dtype, bool(interpret))


def blockwise_seen(half: int, block: int) -> jax.Array:
    """The block-diffusion mask over ``[noised copy ; clean copy]`` rows, (2 half, 2 half) booleans, query by key: both
    copies hold positions ``0..half-1`` in blocks of ``block``. A noised query sees the noised keys of its own block and
    the clean keys of earlier blocks; a clean query the clean keys of its own block and earlier, and no noised key."""
    row = jnp.arange(2 * half)
    clean, blk = row >= half, (row % half) // block
    (q_clean, q_blk), (k_clean, k_blk) = (clean[:, None], blk[:, None]), (clean[None, :], blk[None, :])
    return jnp.where(k_clean, jnp.where(q_clean, k_blk <= q_blk, k_blk < q_blk), ~q_clean & (k_blk == q_blk))


def einsum_attention(q, k, v, window: int = None, blockwise: Tuple[int, int] = None):
    """Causal grouped-query attention with the weights materialised, the
    engine off the TPU: q (B, T, H, D), k (B, T, Hkv, D) and v (B, T, Hkv, Dv)
    repeated to H heads, -> (B, T, H, Dv); with ``window``, query i sees key j
    iff ``0 <= i - j < window``; with ``blockwise=(half, block)`` iff
    :func:`blockwise_seen` says so, and not causally."""
    t, hd = q.shape[1], q.shape[-1]
    k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]  # query - key
    seen = (behind >= 0) & (behind < (window or t)) if blockwise is None else blockwise_seen(*blockwise)
    weights = jax.nn.softmax(jnp.where(seen, scores / np.sqrt(hd), -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(q.dtype), v)


def causal_attention(cfg, q, k, v, window: int = None, blockwise: Tuple[int, int] = None):
    """:func:`einsum_attention`'s result by the engine ``cfg.attn_impl`` names
    ("auto": flash on TPU, einsum elsewhere); the flash kernels read the key/value
    head a group shares in place, and skip by their loop bounds the tiles a
    window or the block-wise rule hides."""
    from ..ops.flash_attention import resolve_attn_impl

    if resolve_attn_impl(cfg.attn_impl) == "flash":
        from ..ops import flash_attention, pallas_interpret

        return flash_attention(
            q, k, v, causal=blockwise is None, window=window, interpret=pallas_interpret(), blockwise=blockwise
        )
    return einsum_attention(q, k, v, window, blockwise)


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


def routed_experts(
    layer: nn.Module, cfg, u, u32, out_std: float, *, gated: bool, score: str, route_scale: float, biased: bool,
):
    """This rank's routed experts of ``layer``, an expert layer's module in
    its ``__call__``: the tokens (B, T, d) as ``u`` in ``cfg.dtype`` (the
    caller's cast, which its shared expert reads too) and as ``u32`` in fp32
    (what the router scores) -> ``(routed (B, T, d), counters)``. The
    parameters are declared in ``layer``'s scope, ``router`` first, then
    ``experts_in``, ``experts_out`` or with ``gated`` ``experts_gate``,
    ``experts_up``, ``experts_down``: the PowerSGD reducer walks the leaves
    by name, so the names are part of a checkpoint and of the bytes on the
    wire. ``biased``: the top-k selects by ``score + expert_bias`` of the
    layer's buffer; otherwise by the scores alone (no published selection
    bias, or one the optimizer never touches: zeros)."""
    from ..parallel.moe import held_experts_moe

    d, f, held = cfg.hidden_size, cfg.expert_width, cfg.held_experts
    router = layer.param("router", kernel(cfg.init_std), (d, cfg.n_routed_experts))
    gate = layer.param("experts_gate", kernel(cfg.init_std), (len(held), d, f)) if gated else None
    up = layer.param("experts_up" if gated else "experts_in", kernel(cfg.init_std), (len(held), d, f))
    down = layer.param("experts_down" if gated else "experts_out", kernel(out_std), (len(held), f, d))
    bsz, t, _ = u.shape
    tokens32 = u32.reshape(bsz * t, d)
    if biased:
        expert_bias = expert_bias_of(layer, tokens32, router, cfg.experts_per_token)
    else:
        expert_bias = jnp.zeros((cfg.n_routed_experts,), jnp.float32)
    routed, counters = held_experts_moe(
        u.reshape(bsz * t, d), tokens32, router, expert_bias,
        up, down, held, cfg.experts_per_token, route_scale, w_gate=gate, score=score,
    )
    return routed.reshape(bsz, t, d), counters


def balanced_expert_bias(model, params, input_ids) -> Dict:
    """The ``buffers`` collection that balances ``params``' routing on
    ``input_ids`` (B, T): one forward pass in which every expert layer takes
    its ``expert_bias`` from its own scores (``_balancing_bias``) and routes
    by it, so the layers behind it see what they will see in training.
    ``model`` is any whose expert layers keep the buffer so
    (``models/afmoe.py``, ``models/lfm2.py``)."""
    model = type(model)(dataclasses.replace(model.config, remat=False))  # nothing to recompute
    # the buffers alone leave the program: the head and its logits are never computed
    return jax.jit(lambda p, ids: model.apply({"params": p}, ids, mutable=[BUFFERS])[1][BUFFERS])(
        params, input_ids
    )


def run_layers(block_cls, cfg, per_layer_args: Iterable[Sequence], x):
    """The layer stack inside a model's ``__call__``: layer ``i`` is ``block_cls(cfg, *per_layer_args[i],
    name="layer_<i>")``, recomputed in the backward pass where ``cfg.remat`` (``jax.checkpoint`` per layer). ``x`` is
    what a block takes and returns first: an array, or any pytree (``models/phi4flash.py``: ``(h, memory, cache)``,
    ``None`` before a source ran). -> ``(x, {"layer_<i>": its counters})``, a layer without experts left out."""
    block = nn.remat(block_cls) if cfg.remat else block_cls
    counters = {}
    for i, args in enumerate(per_layer_args):
        x, layer_counters = block(cfg, *args, name=f"layer_{i}")(x)
        if layer_counters:
            counters[f"layer_{i}"] = layer_counters
    return x, counters


def zero_counters(config, also: Sequence[str] = ()) -> Dict[str, Dict[str, jax.Array]]:
    """The counters' tree before the first step: what ``init_state`` takes.
    ``config`` names its ``expert_layers`` and ``held_experts`` (any model's
    that calls ``held_experts_moe``, whose counters these are); ``also`` the
    scalar counters the loss writes beside them (``masked_token_loss.counters``)."""
    zero = lambda *shape: jnp.zeros(shape, jnp.int32)
    return {
        f"layer_{i}": {
            "held": zero(len(config.held_experts)), "absent": zero(), "dropped": zero(), "row_tiles": zero(),
            **{name: zero() for name in also},
        }
        for i in config.expert_layers
    }


def next_token_lm_loss(model):
    """The trainer's loss function: mean next-token cross-entropy of fp32
    logits (``labels`` already shifted by the data), the expert layers'
    counters handed on as model state under ``STEP_COUNTERS``; whatever
    else the model state holds is the model's other variable collections
    (``BUFFERS``) and goes to it unchanged."""
    from ..parallel.trainer import STEP_COUNTERS

    def loss_fn(params, model_state, batch):
        others = {k: v for k, v in model_state.items() if k != STEP_COUNTERS}
        logits, counters = model.apply({"params": params, **others}, batch["input_ids"])
        # logsumexp minus the label's logit: no (B, T, vocab) array of log-probabilities
        picked = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
        loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
        return loss, {**model_state, STEP_COUNTERS: counters}

    return loss_fn


def masked_token_loss(model):
    """The trainer's loss function under block diffusion: the batch brings a sample's ``input_ids`` (B, L), its
    ``noisy_ids`` (some replaced by the ``[MASK]`` id, block by block) and ``loss_weight`` (``1 / t_b`` on a replaced
    position, t_b its block's noise level; 0 elsewhere: ``data.noising.block_noised``). The model runs on ``[noisy ;
    clean]`` rows (B, 2L) and returns logits for the L noised rows; a replaced position's OWN row predicts its token (no
    shift), and the loss is ``sum(weight * -log p) / (B L)``, under the device scope ``denoise.loss``. The counters and
    the other collections ride as :func:`next_token_lm_loss` hands them on, every expert layer's counters joined by
    ``masked``, how many positions carried loss in the step (they enter the layer as one token id: a layer's load
    moves with it)."""
    from ..parallel.trainer import STEP_COUNTERS

    def loss_fn(params, model_state, batch):
        others = {k: v for k, v in model_state.items() if k != STEP_COUNTERS}
        rows = jnp.concatenate([batch["noisy_ids"], batch["input_ids"]], axis=1)
        logits, counters = model.apply({"params": params, **others}, rows)
        with jax.named_scope("denoise.loss"):
            picked = jnp.take_along_axis(logits, batch["input_ids"][..., None], axis=-1)[..., 0]
            weight = batch["loss_weight"]
            loss = jnp.mean(weight * (jax.nn.logsumexp(logits, axis=-1) - picked))
        masked = jnp.sum(weight > 0, dtype=jnp.int32)
        counters = {layer: {**counted, "masked": masked} for layer, counted in counters.items()}
        return loss, {**model_state, STEP_COUNTERS: counters}

    return loss_fn


masked_token_loss.counters = ("masked",)  # what it adds to every expert layer's counters: ``zero_counters(config, also)``
