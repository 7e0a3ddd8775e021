"""GPT-style decoder LM — first-party flax implementation, TPU-first.

Beyond-parity model family: the reference's only transformer is an encoder
classifier consumed from HuggingFace (DistilBERT,
``ddp_powersgd_distillBERT_IMDb/ddp_init.py:150``); it has no generative /
decoder model and handles long sequences by truncation
(``ddp_init.py:74-77``). This adds the canonical decoder (GPT-2 layout:
pre-LN blocks, learned positions, weight-tied LM head — Radford et al. 2019)
with the framework's long-context machinery built in:

- ``seq_axis``: shard the sequence dimension over a mesh axis; causal
  attention runs as ring attention (K/V ``ppermute`` rotation) or
  DeepSpeed-Ulysses (head↔sequence ``all_to_all``) from
  ``parallel.sequence`` — both EXACT, so a sequence-sharded forward matches
  the single-device forward.
- ``dtype``: bfloat16 matmuls on the MXU with fp32 params.
- fully static shapes, attention as plain einsum for XLA fusion.

For training, shift host-side (``inputs = tokens[:, :-1]``,
``labels = tokens[:, 1:]``) so the model stays shift-agnostic and the same
next-token CE works sharded and unsharded.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    max_position_embeddings: int = 1024
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    hidden_dim: int = 3072
    dropout: float = 0.1
    dtype: Any = jnp.float32
    # sequence/context parallelism (see DistilBertConfig.seq_axis): mesh axis
    # the sequence is sharded over, and which exact schedule to run on it.
    # NOTE: like flash attention, the sequence-parallel schedules never
    # materialize the attention-weight matrix, so attention-weight dropout is
    # not applied on this path (residual/FFN dropout still is) — sharded and
    # unsharded training regularize slightly differently when dropout > 0.
    seq_axis: Any = None
    seq_impl: str = "ring"
    # single-device attention engine: "auto" (flash on TPU, einsum
    # elsewhere — ops.flash_attention.resolve_attn_impl), "einsum" (XLA),
    # or "flash" (the Pallas VMEM-tiled kernel, ops.flash_attention;
    # interpret mode off-TPU). Like the sequence-parallel schedules flash
    # never materializes the score matrix, so attention-weight dropout does
    # not apply on that path.
    attn_impl: str = "auto"
    # rematerialization: recompute each block's activations in the backward
    # pass instead of storing them (jax.checkpoint via nn.remat) — activation
    # memory drops from O(n_layers · seq · dim) to O(seq · dim) at ~1/3 more
    # FLOPs; the standard long-context/large-model memory trade. Parameter
    # tree and gradients are unchanged (pinned by test).
    remat: bool = False
    # scan-over-layers: run the n_layers identical pre-LN blocks as ONE
    # ``nn.scan`` (= ``lax.scan``) tick with a stacked leading layer axis on
    # every block parameter, instead of a Python-unrolled loop. The lowered
    # HLO shrinks with depth (measured ≈5.6× for the 12-layer 124M forward;
    # embed/head are shared either way), and with it XLA compile time — the lever that
    # matters when compiles travel a slow link or models grow deep (the
    # standard TPU LLM idiom). Same math: outputs match the unrolled form
    # bit-for-bit under identical params (pinned by test via
    # stack_gpt_layer_params). Parameter tree DIFFERS: blocks live under
    # ``h_scan/block`` with shape (n_layers, ...) instead of ``h_0..h_{n-1}``
    # — convert with stack_gpt_layer_params / unstack_gpt_layer_params.
    # Composes with remat (remat applies per scan tick).
    scan_layers: bool = False


def _resolve_attn_impl(attn_impl: str) -> str:
    if attn_impl != "auto":
        return attn_impl
    # lazy import for the same reason flash_attention itself is imported at
    # dispatch time: keep pallas off the plain-einsum module-import path
    from ..ops.flash_attention import resolve_attn_impl

    return resolve_attn_impl(attn_impl)


class CausalSelfAttention(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool):
        cfg = self.config
        head_dim = cfg.dim // cfg.n_heads
        dense = lambda feats, name: nn.Dense(feats, dtype=cfg.dtype, name=name)
        q = dense(cfg.dim, "q_proj")(x)
        k = dense(cfg.dim, "k_proj")(x)
        v = dense(cfg.dim, "v_proj")(x)

        def split(t):
            return t.reshape(t.shape[0], t.shape[1], cfg.n_heads, head_dim)

        q, k, v = split(q), split(k), split(v)
        attn_impl = _resolve_attn_impl(cfg.attn_impl)
        if (
            cfg.attn_impl == "auto"
            and attn_impl == "flash"
            and not deterministic
            and cfg.dropout > 0.0
        ):
            # "auto" must never change the math across backends: flash
            # cannot dropout-mask the attention weights, so a training step
            # with dropout stays on einsum. Explicit attn_impl="flash"
            # keeps flash (the documented no-weight-dropout trade).
            attn_impl = "einsum"
        if cfg.seq_axis is not None:
            from ..parallel.sequence import ring_attention, ulysses_attention

            impls = {"ring": ring_attention, "ulysses": ulysses_attention}
            if cfg.seq_impl not in impls:
                raise ValueError(
                    f"GPTConfig.seq_impl={cfg.seq_impl!r}: valid values are"
                    f" {sorted(impls)}"
                )
            ctx = impls[cfg.seq_impl](q, k, v, cfg.seq_axis, causal=True)
        elif attn_impl == "flash":
            from ..ops import flash_attention, pallas_interpret

            ctx = flash_attention(
                q, k, v, causal=True, interpret=pallas_interpret(),
            )
        else:
            t = x.shape[1]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                head_dim
            ).astype(cfg.dtype)
            causal = jnp.tril(jnp.ones((t, t), bool))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
                cfg.dtype
            )
            weights = nn.Dropout(cfg.dropout)(weights, deterministic=deterministic)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
        ctx = ctx.reshape(ctx.shape[0], ctx.shape[1], cfg.dim)
        return dense(cfg.dim, "out_proj")(ctx)


class GPTBlock(nn.Module):
    """Pre-LN block (GPT-2): x + attn(LN(x)); x + mlp(LN(x))."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool):
        cfg = self.config
        a = CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, name="ln_1")(x),
            deterministic,
        )
        x = x + a
        h = nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, name="ln_2")(x)
        h = nn.Dense(cfg.hidden_dim, dtype=cfg.dtype, name="mlp_fc")(h)
        h = nn.gelu(h, approximate=True)
        h = nn.Dense(cfg.dim, dtype=cfg.dtype, name="mlp_proj")(h)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return x + h


class _ScanBody(nn.Module):
    """One ``nn.scan`` tick for GPTConfig.scan_layers: applies the (possibly
    remat-wrapped) block to the carried activations; parameters carry a
    leading layer axis added by ``nn.scan(variable_axes={"params": 0})``."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool):
        cls = (
            nn.remat(GPTBlock, static_argnums=(2,))
            if self.config.remat
            else GPTBlock
        )
        return cls(self.config, name="block")(x, deterministic), None


def stack_gpt_layer_params(params, n_layers: int):
    """Unrolled block params (``h_0..h_{n-1}``) -> the scan_layers layout
    (``h_scan/block`` with a stacked leading layer axis). The inverse of
    :func:`unstack_gpt_layer_params`; use it to run checkpoints imported by
    ``models.import_weights`` (which emits the unrolled names) under
    ``scan_layers=True``."""
    present = sorted(k for k in params if _is_block_key(k))
    expected = sorted(f"h_{i}" for i in range(n_layers))
    if present != expected:
        # understating n_layers must fail loudly — silently dropping the
        # tail blocks would run a truncated model with no error
        raise ValueError(
            f"stack_gpt_layer_params(n_layers={n_layers}): params carry"
            f" block keys {present}, expected exactly {expected}"
        )
    layers = [params[f"h_{i}"] for i in range(n_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *layers)
    out = {k: v for k, v in params.items() if not _is_block_key(k)}
    out["h_scan"] = {"block": stacked}
    return out


def unstack_gpt_layer_params(params):
    """scan_layers layout -> unrolled ``h_0..h_{n-1}`` names (e.g. to export
    toward the torch converters, or to feed the pipeline-parallel splitter,
    which addresses blocks by name)."""
    stacked = params["h_scan"]["block"]
    n_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    out = {k: v for k, v in params.items() if k != "h_scan"}
    for i in range(n_layers):
        out[f"h_{i}"] = jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
    return out


def _is_block_key(k: str) -> bool:
    return k.startswith("h_") and k != "h_scan" and k[2:].isdigit()


class GPTLM(nn.Module):
    """Decoder LM: tokens -> next-token logits, LM head weight-tied to the
    token embedding (GPT-2)."""

    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True):
        cfg = self.config
        wte = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype, name="wte")
        positions = jnp.arange(input_ids.shape[1])[None, :]
        if cfg.seq_axis is not None:
            positions = (
                positions + jax.lax.axis_index(cfg.seq_axis) * input_ids.shape[1]
            )
        x = wte(input_ids)
        x = x + nn.Embed(
            cfg.max_position_embeddings, cfg.dim, dtype=cfg.dtype, name="wpe"
        )(positions)
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
        if cfg.scan_layers:
            x, _ = nn.scan(
                _ScanBody,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layers,
                in_axes=(nn.broadcast,),
            )(cfg, name="h_scan")(x, deterministic)
        else:
            block_cls = (
                nn.remat(GPTBlock, static_argnums=(2,)) if cfg.remat else GPTBlock
            )
            for i in range(cfg.n_layers):
                x = block_cls(cfg, name=f"h_{i}")(x, deterministic)
        x = nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, name="ln_f")(x)
        logits = wte.attend(x)  # weight-tied LM head
        return logits.astype(jnp.float32)


def gpt_small(dtype=jnp.float32, **overrides) -> GPTLM:
    """GPT-2 small shape (124M)."""
    return GPTLM(GPTConfig(dtype=dtype, **overrides))


def gpt_tiny(dtype=jnp.float32, **overrides) -> GPTLM:
    """Test-tier decoder: 2 layers, 4 heads, dim 32."""
    cfg = dict(
        vocab_size=128, max_position_embeddings=128, dim=32, n_layers=2,
        n_heads=4, hidden_dim=64, dropout=0.0,
    )
    cfg.update(overrides)
    return GPTLM(GPTConfig(dtype=dtype, **cfg))


def next_token_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy; ``labels`` already shifted host-side."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# ---- pipeline-parallel decomposition ------------------------------------
#
# The homogeneous-stage pipeline (parallel.pipeline) wants stage_fn(params,
# activation) with a shape-preserving activation. A GPT decomposes naturally:
# embedding (cheap, replicated on every pipe rank) -> n_stages stages of
# n_layers/n_stages pre-LN blocks (pipelined over the 'pipe' axis) -> final
# LN + weight-tied head (replicated). Only the blocks carry the FLOPs, so
# this pipelines >95% of the model while keeping stages homogeneous.
#
# Training scope: make_pipeline_train_fn with a hand-closed-over head
# differentiates the STAGE (block) params only — embed/wpe/ln_f and the tied
# head would enter the loss as constants and stay FROZEN. For full-model
# pipeline training use make_gpt_pipeline_train_fn below, which routes head
# gradients through the schedule's loss-params path and embedding gradients
# through the pipeline's input cotangent; GPipe pipeline_apply under plain
# jax.grad also differentiates everything.


def split_gpt_params(params, n_stages: int):
    """Split a GPTLM param tree into (embed, per-stage, final) pieces.

    ``per_stage[i]['layers']`` stacks that stage's blocks on a leading axis;
    feed the list to ``parallel.pipeline.stacked_stage_params`` and shard the
    result over the 'pipe' mesh axis. The weight-tied LM head lives in
    ``embed['wte']`` (as in GPTLM itself).
    """
    layer_names = sorted(
        (k for k in params if k.startswith("h_")), key=lambda k: int(k[2:])
    )
    n_layers = len(layer_names)
    assert n_layers % n_stages == 0, (
        f"{n_layers} layers do not split into {n_stages} equal stages"
    )
    from ..parallel.pipeline import stacked_stage_params

    per = n_layers // n_stages
    embed = {"wte": params["wte"], "wpe": params["wpe"]}
    stages = []
    for s in range(n_stages):
        blocks = [params[layer_names[s * per + j]] for j in range(per)]
        # same stacking as the stage-level helper, here over a stage's layers
        stages.append({"layers": stacked_stage_params(blocks)})
    final = {"ln_f": params["ln_f"]}
    return embed, stages, final


def make_gpt_stage_fn(config: GPTConfig, layers_per_stage: int):
    """stage_fn(stage_params, x) applying this stage's blocks sequentially
    (static unroll — layers_per_stage is small).

    Deterministic-only: the pipeline schedules have no per-microbatch rng
    plumbing, so block dropout cannot run here — configs with dropout > 0
    are rejected rather than silently regularizing differently.
    """
    if config.dropout > 0:
        raise ValueError(
            "pipeline stages run deterministically (no dropout rng plumbing);"
            " use a config with dropout=0.0"
        )
    block = GPTBlock(config)

    def stage_fn(p, x):
        for j in range(layers_per_stage):
            bp = jax.tree_util.tree_map(lambda t: t[j], p["layers"])
            x = block.apply({"params": bp}, x, True)
        return x

    return stage_fn


def gpt_position_ids(config: GPTConfig, input_ids):
    """Position ids for a (possibly sequence-sharded) token block: offset by
    this device's ring position when ``seq_axis`` is set (matching
    ``GPTLM.__call__``)."""
    positions = jnp.arange(input_ids.shape[1])[None, :]
    if config.seq_axis is not None:
        positions = (
            positions + jax.lax.axis_index(config.seq_axis) * input_ids.shape[1]
        )
    return positions


def gpt_position_embed(config: GPTConfig, wpe, input_ids):
    """Positional-embedding lookup (``seq_axis``-aware) shared by the
    replicated and vocab-parallel embedding fronts."""
    return nn.Embed(
        config.max_position_embeddings, config.dim, dtype=config.dtype
    ).apply({"params": wpe}, gpt_position_ids(config, input_ids))


def gpt_embed_apply(config: GPTConfig, embed, input_ids):
    """The (replicated) embedding front: tokens -> block-input activations.
    Deterministic (no dropout) — the pipeline path is an inference/training
    building block; compose dropout outside if needed. Honors ``seq_axis``
    (ring-offset positions), matching ``GPTLM.__call__``."""
    x = nn.Embed(config.vocab_size, config.dim, dtype=config.dtype).apply(
        {"params": embed["wte"]}, input_ids
    )
    return x + gpt_position_embed(config, embed["wpe"], input_ids)


def gpt_head_matmul(config: GPTConfig, ln_f, wte_matrix, x):
    """Final LN + weight-tied head matmul, the single source of truth for
    both the replicated head and the vocab-parallel head (which passes its
    vocab-row SHARD of the tied table and gets sharded logits back)."""
    x = nn.LayerNorm(epsilon=1e-5, dtype=config.dtype).apply(
        {"params": ln_f}, x
    )
    return (x @ wte_matrix.T.astype(config.dtype)).astype(jnp.float32)


def gpt_head_apply(config: GPTConfig, final, embed, x):
    """The (replicated) head: final LN + weight-tied logits."""
    return gpt_head_matmul(
        config, final["ln_f"], embed["wte"]["embedding"], x
    )


def tp_gpt_block_apply(config: GPTConfig, p, x, axis_name: str = "model"):
    """One GPT block, Megatron tensor-parallel over ``axis_name`` — a pure
    function on this device's parameter SHARDS (run under ``shard_map`` with
    :func:`gpt_tp_param_specs`).

    Head-sharded attention: q/k/v kernels hold this device's
    ``n_heads/N`` head columns (column-parallel, no comm — heads are
    contiguous ``head_dim`` column blocks, so a contiguous output-dim shard
    IS a head group), attention runs on the local heads, and the out
    projection is row-parallel — ONE ``psum`` restores the replicated
    residual stream. The MLP is the canonical column→row pair (one more
    psum). LayerNorms/residuals are computed redundantly on the replicated
    stream. Backward needs no hand-written collectives: the replicated
    activations/params are model-axis-invariant at differentiation time, so
    jax's replication-tracking transpose inserts the Megatron-standard psum
    that assembles their complete gradients across head/feature shards
    automatically. Numerics match ``GPTBlock`` exactly, forward AND backward
    (pinned by the single-device-equivalence test). Deterministic-only,
    like the pipeline stage fns.
    """
    cfg = config
    n_shards = jax.lax.axis_size(axis_name)
    local_heads = cfg.n_heads // n_shards
    head_dim = cfg.dim // cfg.n_heads
    ln = lambda name, t: nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype).apply(
        {"params": p[name]}, t
    )

    from ..parallel.tensor import column_parallel_dense, row_parallel_dense, tp_mlp

    h = ln("ln_1", x)
    attn_p = p["attn"]
    proj = lambda name, t: column_parallel_dense(
        t, attn_p[name]["kernel"], attn_p[name]["bias"]
    )
    q, k, v = proj("q_proj", h), proj("k_proj", h), proj("v_proj", h)
    split = lambda t: t.reshape(t.shape[0], t.shape[1], local_heads, head_dim)
    q, k, v = split(q), split(k), split(v)
    t_len = x.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(head_dim).astype(
        cfg.dtype
    )
    causal = jnp.tril(jnp.ones((t_len, t_len), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    ctx = ctx.reshape(ctx.shape[0], ctx.shape[1], local_heads * head_dim)
    x = x + row_parallel_dense(
        ctx, attn_p["out_proj"]["kernel"], attn_p["out_proj"]["bias"], axis_name
    )

    h = ln("ln_2", x)
    return x + tp_mlp(
        h, p["mlp_fc"]["kernel"], p["mlp_fc"]["bias"],
        p["mlp_proj"]["kernel"], p["mlp_proj"]["bias"], axis_name,
        activation=lambda t: nn.gelu(t, approximate=True),
    )


def vocab_parallel_embed(config: GPTConfig, wte_shard, input_ids, axis_name: str):
    """Megatron VocabParallelEmbedding: the token table is sharded over
    vocab ROWS; each rank looks up the ids that land in its row range
    (others contribute zero) and ONE psum assembles the replicated
    embedding."""
    local_v = wte_shard.shape[0]
    offset = jax.lax.axis_index(axis_name) * local_v
    local_ids = input_ids - offset
    in_range = (local_ids >= 0) & (local_ids < local_v)
    # cast the table like nn.Embed(dtype=config.dtype) does, so both head
    # modes compute the stream in the same precision
    rows = wte_shard.astype(config.dtype)[jnp.clip(local_ids, 0, local_v - 1)]
    rows = jnp.where(in_range[..., None], rows, jnp.zeros((), config.dtype))
    return jax.lax.psum(rows, axis_name)


def vocab_parallel_next_token_loss(
    logits_shard: jax.Array, labels: jax.Array, axis_name: str
) -> jax.Array:
    """Mean next-token CE over VOCAB-SHARDED logits ``(..., V/N)`` without
    ever materializing the full-vocab row: global max via ``pmax``, global
    sum-exp and the target logit via ``psum`` — three scalar-ish
    collectives instead of a (..., V) gather. Matches
    :func:`next_token_loss` on the assembled logits (pinned by test)."""
    logits_shard = logits_shard.astype(jnp.float32)
    local_v = logits_shard.shape[-1]
    offset = jax.lax.axis_index(axis_name) * local_v
    # The max shift is numerical stabilization only — its contributions to
    # the CE cancel exactly, so stop_gradient is mathematically exact. Two
    # traps worth recording: (a) pmax has no differentiation rule, so the
    # global max rides an all_gather; (b) the all_gather output is marked
    # device-VARYING, and a varying term in the loss flips the implicit
    # objective to a sum over ranks (jax's pvary-transpose-is-psum
    # convention), scaling EVERY gradient by N — the pmean (an identity on
    # the already-equal maxes) restores the invariant marking.
    m = jax.lax.stop_gradient(
        jax.lax.pmean(
            jnp.max(
                jax.lax.all_gather(jnp.max(logits_shard, axis=-1), axis_name),
                axis=0,
            ),
            axis_name,
        )
    )
    sumexp = jax.lax.psum(
        jnp.sum(jnp.exp(logits_shard - m[..., None]), axis=-1), axis_name
    )
    local_labels = labels - offset
    in_range = (local_labels >= 0) & (local_labels < local_v)
    tgt_local = jnp.take_along_axis(
        logits_shard, jnp.clip(local_labels, 0, local_v - 1)[..., None], axis=-1
    )[..., 0]
    tgt = jax.lax.psum(jnp.where(in_range, tgt_local, 0.0), axis_name)
    return jnp.mean(m + jnp.log(sumexp) - tgt)


def tp_gpt_forward(
    config: GPTConfig,
    params,
    input_ids,
    axis_name: str = "model",
    vocab_parallel: bool = False,
):
    """Full TP decoder forward on a GPTLM param tree sharded per
    :func:`gpt_tp_param_specs`: embeddings → TP blocks (2 psums each) →
    final LN + weight-tied head. Deterministic-only.

    ``vocab_parallel=True`` (pair with ``gpt_tp_param_specs(...,
    vocab_parallel=True)``) additionally shards the tied token table over
    vocab rows: the input lookup goes through
    :func:`vocab_parallel_embed` and the head RETURNS VOCAB-SHARDED logits
    ``(..., V/N)`` — feed them to :func:`vocab_parallel_next_token_loss`,
    which never materializes the full-vocab row. This removes the largest
    replicated matrix (and its model-axis gradient allreduce) from the TP
    step."""
    if config.dropout > 0:
        raise ValueError(
            "tensor-parallel apply runs deterministically; use dropout=0.0"
        )
    if vocab_parallel:
        wte_shard = params["wte"]["embedding"]
        x = vocab_parallel_embed(config, wte_shard, input_ids, axis_name)
        x = x + gpt_position_embed(config, params["wpe"], input_ids)
    else:
        embed = {"wte": params["wte"], "wpe": params["wpe"]}
        x = gpt_embed_apply(config, embed, input_ids)
    for i in range(config.n_layers):
        x = tp_gpt_block_apply(config, params[f"h_{i}"], x, axis_name)
    if vocab_parallel:
        return gpt_head_matmul(config, params["ln_f"], wte_shard, x)
    return gpt_head_apply(config, {"ln_f": params["ln_f"]}, embed, x)


def gpt_tp_param_specs(
    config: GPTConfig, axis_name: str = "model", vocab_parallel: bool = False
):
    """PartitionSpec tree for a GPTLM param tree under Megatron TP:
    q/k/v and mlp_fc kernels column-sharded (output features = head groups),
    out_proj/mlp_proj kernels row-sharded (input features), their output
    biases replicated, everything else (LNs, positions) replicated. The
    tied token table is replicated by default, or vocab-row-sharded with
    ``vocab_parallel=True`` (see :func:`tp_gpt_forward`)."""
    from jax.sharding import PartitionSpec as P

    col = {"kernel": P(None, axis_name), "bias": P(axis_name)}
    row = {"kernel": P(axis_name, None), "bias": P()}
    ln = {"scale": P(), "bias": P()}
    block = {
        "ln_1": ln,
        "attn": {"q_proj": col, "k_proj": col, "v_proj": col, "out_proj": row},
        "ln_2": ln,
        "mlp_fc": col,
        "mlp_proj": row,
    }
    specs = {
        "wte": {"embedding": P(axis_name, None) if vocab_parallel else P()},
        "wpe": {"embedding": P()},
        "ln_f": ln,
    }
    for i in range(config.n_layers):
        specs[f"h_{i}"] = block
    return specs


def make_gpt_tp_stage_fn(
    config: GPTConfig, layers_per_stage: int, model_axis: str = "model"
):
    """Tensor-parallel pipeline stage: each of the stage's blocks applied
    via :func:`tp_gpt_block_apply` on this device's head/feature SHARDS —
    the stage function for a 3-D ``(data, pipe, model)`` composition.
    Stage params carry the ``(layers_per_stage, ...)`` leading axis of
    :func:`make_gpt_stage_fn` with the block dims additionally sharded per
    :func:`gpt_tp_param_specs`. Deterministic-only, like the dense stage."""
    if config.dropout > 0:
        raise ValueError(
            "pipeline stages run deterministically (no dropout rng plumbing);"
            " use a config with dropout=0.0"
        )

    def stage_fn(p, x):
        for j in range(layers_per_stage):
            bp = jax.tree_util.tree_map(lambda t: t[j], p["layers"])
            x = tp_gpt_block_apply(config, bp, x, model_axis)
        return x

    return stage_fn


def make_gpt_pipeline_train_fn(
    config: GPTConfig,
    layers_per_stage: int,
    num_microbatches: int,
    axis_name: str = "pipe",
    params_varying_over: tuple = (),
    stage_fn=None,
):
    """FULL-model 1F1B pipeline training: every parameter gets a gradient.

    Wiring ``parallel.pipeline.make_pipeline_train_fn`` by hand with a
    closed-over head trains a partially-frozen model (embed/wpe/ln_f and the
    weight-tied LM head receive no gradients — see the module comment above).
    This builder closes the gap:

    - **head + final LN**: passed as the schedule's differentiable
      ``loss_params`` — the last stage's loss VJP produces their gradients
      (tied-head gradient lands on ``wte``);
    - **embedding (wte/wpe)**: the schedule returns the pipeline INPUT
      cotangent, chained here through ``jax.vjp`` of ``gpt_embed_apply``;
      the tied ``wte`` gradient sums both contributions.

    Returns ``fn(embed, stacked_stages, final, ids, labels) ->
    (loss, (embed_grads, stage_grads, final_grads))`` for use inside
    ``shard_map`` over the ``axis_name`` mesh axis with
    ``in_specs=(P(), P(axis_name), P(), P(), P())`` and
    ``out_specs=(P(), (P(), P(axis_name), P()))``. When composing with a
    data axis, list it in ``params_varying_over`` (grads come back LOCAL to
    each data shard for pluggable reduction, as in ``trainer.make_step_fn``).
    Pass ``stage_fn=make_gpt_tp_stage_fn(...)`` (with the stage specs'
    block dims sharded per :func:`gpt_tp_param_specs`) to additionally
    tensor-shard each stage over a ``model`` axis — the full 3-D
    ``data × pipe × model`` composition (``tests/test_3d_gpt.py``).
    """
    if stage_fn is None:
        stage_fn = make_gpt_stage_fn(config, layers_per_stage)
    from ..parallel.pipeline import make_pipeline_train_fn

    # loss_params carry ONLY what the head reads — final LN + the tied wte
    # matrix. wpe would otherwise ride along as a structurally-zero dlp
    # accumulator through every scan tick (its real gradient arrives via the
    # input-cotangent path below).
    def mb_loss(lp, y, labels):
        return next_token_loss(
            gpt_head_apply(config, lp["final"], {"wte": lp["wte"]}, y), labels
        )

    pipe = make_pipeline_train_fn(
        stage_fn,
        mb_loss,
        axis_name,
        num_microbatches,
        params_varying_over=params_varying_over,
        loss_has_params=True,
        return_input_grads=True,
    )

    def fn(embed, stacked_stages, final, ids, labels):
        # data-varying copy for the embedding vjp only; the pipeline pcasts
        # its own loss_params internally (pcast-ing twice is an error)
        embed_var = embed
        for ax in params_varying_over:
            embed_var = jax.tree_util.tree_map(
                lambda p: jax.lax.pcast(p, ax, to="varying"), embed_var
            )
        x, embed_vjp = jax.vjp(
            lambda e: gpt_embed_apply(config, e, ids), embed_var
        )
        loss, stage_grads, dlp, dx = pipe(
            stacked_stages, {"wte": embed["wte"], "final": final}, x, labels
        )
        (d_embed_in,) = embed_vjp(dx)
        embed_grads = {
            "wte": jax.tree_util.tree_map(jnp.add, d_embed_in["wte"], dlp["wte"]),
            "wpe": d_embed_in["wpe"],
        }
        return loss, (embed_grads, stage_grads, dlp["final"])

    return fn


# ---- autoregressive decoding (KV cache) ---------------------------------
#
# The reference has no generative path at all; this completes the decoder
# family. TPU-first decode: a fixed-capacity K/V cache per layer (static
# shapes), one-token decode steps that attend to the cache under a
# position mask, and the whole prefill+sample loop as ONE lax.scan inside
# jit — no per-token host dispatch, no dynamic shapes.


def init_gpt_cache(config: GPTConfig, batch: int, max_len: int):
    """Per-layer K/V cache: zeros of (B, max_len, H, D)."""
    head_dim = config.dim // config.n_heads
    shape = (batch, max_len, config.n_heads, head_dim)
    return [
        {
            "k": jnp.zeros(shape, config.dtype),
            "v": jnp.zeros(shape, config.dtype),
        }
        for _ in range(config.n_layers)
    ]


def _apply_dense(cfg, p, h):
    return nn.Dense(p["kernel"].shape[-1], dtype=cfg.dtype).apply({"params": p}, h)


def _apply_ln(cfg, p, h):
    return nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype).apply({"params": p}, h)


def gpt_decode_step(config: GPTConfig, params, cache, tokens, pos):
    """One decode step: ``tokens`` (B,) at position ``pos`` -> (logits (B, V),
    updated cache). Attends to cache positions <= pos (static shapes; the
    mask does the truncation). The input cache is not mutated — a new one is
    returned (so callers can snapshot for beam/speculative branching)."""
    cfg = config
    head_dim = cfg.dim // cfg.n_heads
    max_len = cache[0]["k"].shape[1]

    apply_dense = lambda p, h: _apply_dense(cfg, p, h)
    apply_ln = lambda p, h: _apply_ln(cfg, p, h)

    x = params["wte"]["embedding"][tokens].astype(cfg.dtype)  # (B, dim)
    x = x + params["wpe"]["embedding"][pos].astype(cfg.dtype)

    cache = list(cache)
    for i in range(cfg.n_layers):
        bp = params[f"h_{i}"]
        h = apply_ln(bp["ln_1"], x)
        q = apply_dense(bp["attn"]["q_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        k = apply_dense(bp["attn"]["k_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        v = apply_dense(bp["attn"]["v_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        cache[i] = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache[i]["k"], k[:, None], pos, axis=1
            ),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache[i]["v"], v[:, None], pos, axis=1
            ),
        }
        scores = jnp.einsum(
            "bhd,bthd->bht", q.astype(jnp.float32),
            cache[i]["k"].astype(jnp.float32),
        ) / jnp.sqrt(head_dim)
        valid = jnp.arange(max_len) <= pos
        scores = jnp.where(valid[None, None, :], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum(
            "bht,bthd->bhd", weights, cache[i]["v"].astype(jnp.float32)
        ).astype(cfg.dtype)
        x = x + apply_dense(
            bp["attn"]["out_proj"], ctx.reshape(-1, cfg.dim)
        )
        h = apply_ln(bp["ln_2"], x)
        h = apply_dense(bp["mlp_fc"], h)
        h = nn.gelu(h, approximate=True)
        x = x + apply_dense(bp["mlp_proj"], h)

    x = apply_ln(params["ln_f"], x)
    logits = x @ params["wte"]["embedding"].T.astype(cfg.dtype)
    return logits.astype(jnp.float32), cache


def gpt_decode_step_slots(config: GPTConfig, params, cache, tokens, pos):
    """One decode step with a PER-ROW position vector: row ``b`` feeds
    ``tokens[b]`` at ``pos[b]`` (both (B,)) and attends to its own cache
    prefix ``<= pos[b]``. This is the continuous-batching primitive behind
    ``serving.engine``: slot-batched requests at DIFFERENT decode depths
    share one compiled step — static shapes, with each row's validity mask
    doing its own truncation (Orca-style iteration-level batching). Row
    math is identical to :func:`gpt_decode_step` at the same position
    (pinned by ``tests/test_serving.py``); the scalar-``pos`` function is
    kept separate so its compiled program (and the goldens riding on
    ``generate``) stay byte-stable."""
    cfg = config
    head_dim = cfg.dim // cfg.n_heads
    max_len = cache[0]["k"].shape[1]

    apply_dense = lambda p, h: _apply_dense(cfg, p, h)
    apply_ln = lambda p, h: _apply_ln(cfg, p, h)
    # per-row single-position write at that row's own depth
    row_update = jax.vmap(
        lambda buf, row, p: jax.lax.dynamic_update_slice_in_dim(
            buf, row[None], p, axis=0
        )
    )

    x = params["wte"]["embedding"][tokens].astype(cfg.dtype)  # (B, dim)
    x = x + params["wpe"]["embedding"][pos].astype(cfg.dtype)

    cache = list(cache)
    for i in range(cfg.n_layers):
        bp = params[f"h_{i}"]
        h = apply_ln(bp["ln_1"], x)
        q = apply_dense(bp["attn"]["q_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        k = apply_dense(bp["attn"]["k_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        v = apply_dense(bp["attn"]["v_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        cache[i] = {
            "k": row_update(cache[i]["k"], k, pos),
            "v": row_update(cache[i]["v"], v, pos),
        }
        scores = jnp.einsum(
            "bhd,bthd->bht", q.astype(jnp.float32),
            cache[i]["k"].astype(jnp.float32),
        ) / jnp.sqrt(head_dim)
        valid = jnp.arange(max_len)[None, :] <= pos[:, None]
        scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum(
            "bht,bthd->bhd", weights, cache[i]["v"].astype(jnp.float32)
        ).astype(cfg.dtype)
        x = x + apply_dense(
            bp["attn"]["out_proj"], ctx.reshape(-1, cfg.dim)
        )
        h = apply_ln(bp["ln_2"], x)
        h = apply_dense(bp["mlp_fc"], h)
        h = nn.gelu(h, approximate=True)
        x = x + apply_dense(bp["mlp_proj"], h)

    x = apply_ln(params["ln_f"], x)
    logits = x @ params["wte"]["embedding"].T.astype(cfg.dtype)
    return logits.astype(jnp.float32), cache


def gpt_prefill(config: GPTConfig, params, prompt_ids: jax.Array, max_len: int):
    """Fill the K/V cache for the whole prompt in ONE batched forward
    (position-parallel — the MXU sees (B, T_prompt) matmuls, not T_prompt
    sequential one-token ticks). Returns ``(last_logits (B, V), cache)`` with
    cache positions ``< T_prompt`` populated."""
    cfg = config
    head_dim = cfg.dim // cfg.n_heads
    b, t = prompt_ids.shape
    apply_dense = lambda p, h: _apply_dense(cfg, p, h)
    apply_ln = lambda p, h: _apply_ln(cfg, p, h)

    x = params["wte"]["embedding"][prompt_ids].astype(cfg.dtype)  # (B, T, dim)
    x = x + params["wpe"]["embedding"][jnp.arange(t)][None].astype(cfg.dtype)

    cache = init_gpt_cache(cfg, b, max_len)
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg.n_layers):
        bp = params[f"h_{i}"]
        h = apply_ln(bp["ln_1"], x)
        split = lambda y: y.reshape(b, t, cfg.n_heads, head_dim)
        q = split(apply_dense(bp["attn"]["q_proj"], h))
        k = split(apply_dense(bp["attn"]["k_proj"], h))
        v = split(apply_dense(bp["attn"]["v_proj"], h))
        cache[i] = {
            "k": cache[i]["k"].at[:, :t].set(k.astype(cfg.dtype)),
            "v": cache[i]["v"].at[:, :t].set(v.astype(cfg.dtype)),
        }
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) / jnp.sqrt(head_dim)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum(
            "bhqk,bkhd->bqhd", weights, v.astype(jnp.float32)
        ).astype(cfg.dtype)
        x = x + apply_dense(bp["attn"]["out_proj"], ctx.reshape(b, t, cfg.dim))
        h = apply_ln(bp["ln_2"], x)
        h = apply_dense(bp["mlp_fc"], h)
        h = nn.gelu(h, approximate=True)
        x = x + apply_dense(bp["mlp_proj"], h)

    last = apply_ln(params["ln_f"], x[:, -1])
    logits = last @ params["wte"]["embedding"].T.astype(cfg.dtype)
    return logits.astype(jnp.float32), cache


def gpt_decode_step_paged(config: GPTConfig, params, pool, tables, tokens, pos):
    """:func:`gpt_decode_step_slots` over a PAGED KV pool: per-layer K/V live
    in a shared ``(n_blocks, block_len, H, D)`` block pool and each row's
    logical ``(max_len, H, D)`` cache is stitched through its block TABLE
    (``tables`` (B, max_len // block_len) int32, vLLM/PagedAttention
    layout). Row ``b`` writes ``tokens[b]``'s K/V at physical
    ``(tables[b, pos[b] // L], pos[b] % L)``, then attention reads the
    gathered ``(B, max_len, H, D)`` view — IDENTICAL math to the dense
    slots step from there, so valid positions carry the same bits and the
    ``<= pos`` mask zeroes everything else exactly (garbage blocks hold
    finite values only, and ``0.0 * finite`` contributions are exact
    zeros). Tables are DATA, not structure: alloc/free/copy-on-write on
    the host never retrace this program. Positions past a table's span
    scatter into the reserved garbage block 0 (speculative overrun
    safety), never onto a live block."""
    from ..ops.paged import gather_block_view, scatter_token_rows

    cfg = config
    head_dim = cfg.dim // cfg.n_heads
    block_len = pool[0]["k"].shape[1]
    max_len = tables.shape[1] * block_len

    apply_dense = lambda p, h: _apply_dense(cfg, p, h)
    apply_ln = lambda p, h: _apply_ln(cfg, p, h)

    x = params["wte"]["embedding"][tokens].astype(cfg.dtype)  # (B, dim)
    x = x + params["wpe"]["embedding"][pos].astype(cfg.dtype)

    pool = list(pool)
    for i in range(cfg.n_layers):
        bp = params[f"h_{i}"]
        h = apply_ln(bp["ln_1"], x)
        q = apply_dense(bp["attn"]["q_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        k = apply_dense(bp["attn"]["k_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        v = apply_dense(bp["attn"]["v_proj"], h).reshape(-1, cfg.n_heads, head_dim)
        pool[i] = {
            "k": scatter_token_rows(pool[i]["k"], tables, pos, k),
            "v": scatter_token_rows(pool[i]["v"], tables, pos, v),
        }
        k_view = gather_block_view(pool[i]["k"], tables)  # (B, max_len, H, D)
        v_view = gather_block_view(pool[i]["v"], tables)
        scores = jnp.einsum(
            "bhd,bthd->bht", q.astype(jnp.float32),
            k_view.astype(jnp.float32),
        ) / jnp.sqrt(head_dim)
        valid = jnp.arange(max_len)[None, :] <= pos[:, None]
        scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum(
            "bht,bthd->bhd", weights, v_view.astype(jnp.float32)
        ).astype(cfg.dtype)
        x = x + apply_dense(
            bp["attn"]["out_proj"], ctx.reshape(-1, cfg.dim)
        )
        h = apply_ln(bp["ln_2"], x)
        h = apply_dense(bp["mlp_fc"], h)
        h = nn.gelu(h, approximate=True)
        x = x + apply_dense(bp["mlp_proj"], h)

    x = apply_ln(params["ln_f"], x)
    logits = x @ params["wte"]["embedding"].T.astype(cfg.dtype)
    return logits.astype(jnp.float32), pool


def gpt_prefill_shared(config: GPTConfig, params, suffix_ids: jax.Array, prefix_cache):
    """Prefill only the SUFFIX of a prompt whose first ``P`` tokens already
    have KV in the cache (prefix sharing: ``P`` is block-aligned and the
    prefix chain was filled by an earlier request). ``suffix_ids`` is
    ``(1, t_s)`` at global positions ``P .. P+t_s-1``; ``prefix_cache`` is
    the per-layer ``{"k","v"}: (1, P, H, D)`` view gathered from the block
    pool. Suffix queries attend over ``concat(prefix KV, suffix KV)`` with
    the global causal mask, so the attention reduction for each query spans
    the same ``P + t_s`` keys a full prefill would — only the prefix
    projections are skipped. Returns ``(last_logits (1, V) f32,
    suffix_cache)`` with suffix_cache per-layer ``(1, t_s, H, D)`` K/V to
    scatter into the request's private blocks."""
    cfg = config
    head_dim = cfg.dim // cfg.n_heads
    b, t = suffix_ids.shape
    p_len = prefix_cache[0]["k"].shape[1]
    apply_dense = lambda p, h: _apply_dense(cfg, p, h)
    apply_ln = lambda p, h: _apply_ln(cfg, p, h)

    x = params["wte"]["embedding"][suffix_ids].astype(cfg.dtype)  # (B, t, dim)
    x = x + params["wpe"]["embedding"][p_len + jnp.arange(t)][None].astype(cfg.dtype)

    suffix_cache = []
    # query j sits at global position p_len + j: attends keys 0 .. p_len + j
    causal = (
        jnp.arange(p_len + t)[None, :] <= (p_len + jnp.arange(t))[:, None]
    )
    for i in range(cfg.n_layers):
        bp = params[f"h_{i}"]
        h = apply_ln(bp["ln_1"], x)
        split = lambda y: y.reshape(b, t, cfg.n_heads, head_dim)
        q = split(apply_dense(bp["attn"]["q_proj"], h))
        k = split(apply_dense(bp["attn"]["k_proj"], h))
        v = split(apply_dense(bp["attn"]["v_proj"], h))
        suffix_cache.append(
            {"k": k.astype(cfg.dtype), "v": v.astype(cfg.dtype)}
        )
        k_full = jnp.concatenate(
            [prefix_cache[i]["k"].astype(cfg.dtype), k], axis=1
        )
        v_full = jnp.concatenate(
            [prefix_cache[i]["v"].astype(cfg.dtype), v], axis=1
        )
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk",
            q.astype(jnp.float32), k_full.astype(jnp.float32),
        ) / jnp.sqrt(head_dim)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum(
            "bhqk,bkhd->bqhd", weights, v_full.astype(jnp.float32)
        ).astype(cfg.dtype)
        x = x + apply_dense(bp["attn"]["out_proj"], ctx.reshape(b, t, cfg.dim))
        h = apply_ln(bp["ln_2"], x)
        h = apply_dense(bp["mlp_fc"], h)
        h = nn.gelu(h, approximate=True)
        x = x + apply_dense(bp["mlp_proj"], h)

    last = apply_ln(params["ln_f"], x[:, -1])
    logits = last @ params["wte"]["embedding"].T.astype(cfg.dtype)
    return logits.astype(jnp.float32), suffix_cache


def _sample_token(logits, sub, temperature: float):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(sub, logits / temperature, axis=-1).astype(
        jnp.int32
    )


def decode_tokens(
    config: GPTConfig,
    params,
    cache,
    first: jax.Array,
    t_prompt: int,
    n_steps: int,
    temperature: float = 0.0,
    key: jax.Array = None,
    eos_token_id: int = None,
):
    """The decode half of :func:`generate`, exposed on its own: feed
    ``first`` (B,) at position ``t_prompt`` and run ``n_steps`` one-token
    decode steps as one ``lax.scan``, returning the (B, n_steps) sampled
    ids. Separated so harnesses can jit (and time) the decode scan apart
    from the prefill forward (``experiments.gpt_generate``).

    With ``eos_token_id``, rows that have already emitted EOS keep the
    static scan shape but stop contributing: their subsequent outputs are
    padded with the EOS id. Pre-EOS tokens are bitwise-identical to the
    no-EOS run — the done-mask only rewrites a row's output AFTER its stop,
    never the float math before it (pinned by test)."""
    b = first.shape[0]
    if n_steps <= 0:
        return jnp.zeros((b, 0), jnp.int32)
    if key is None:
        key = jax.random.PRNGKey(0)

    if eos_token_id is None:
        # no-EOS path kept structurally identical to the historical scan so
        # its compiled program (and anything golden-pinned on it) is stable
        def step(carry, i):
            cache, tok, key = carry
            logits, cache = gpt_decode_step(
                config, params, cache, tok, t_prompt + i
            )
            key, sub = jax.random.split(key)
            nxt = _sample_token(logits, sub, temperature)
            return (cache, nxt, key), nxt

        (_, _, _), rest = jax.lax.scan(
            step, (cache, first, key), jnp.arange(n_steps)
        )
        return jnp.moveaxis(rest, 0, 1)

    eos = jnp.int32(eos_token_id)

    def step_eos(carry, i):
        cache, tok, key, done = carry
        logits, cache = gpt_decode_step(config, params, cache, tok, t_prompt + i)
        key, sub = jax.random.split(key)
        nxt = _sample_token(logits, sub, temperature)
        nxt = jnp.where(done, eos, nxt)  # pad rows that stopped earlier
        done = done | (nxt == eos)
        return (cache, nxt, key, done), nxt

    done0 = first == eos
    (_, _, _, _), rest = jax.lax.scan(
        step_eos, (cache, first, key, done0), jnp.arange(n_steps)
    )
    return jnp.moveaxis(rest, 0, 1)


def generate(
    config: GPTConfig,
    params,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: jax.Array = None,
    eos_token_id: int = None,
    cache_len: int = None,
):
    """Autoregressive sampling: batched prefill of the prompt (one forward),
    then ``max_new_tokens`` one-token decode steps as one ``lax.scan`` —
    greedy (``temperature=0``) or temperature sampling. Returns
    (B, max_new_tokens) sampled ids.

    ``eos_token_id`` adds a per-row stop condition: a row that samples EOS
    keeps the static output shape but pads the rest of its row with the EOS
    id (the tokens before the stop are bitwise-identical to the full-length
    run). ``cache_len`` overrides the KV-cache capacity (default: exactly
    ``t_prompt + max_new_tokens``) — a sequential reference call can pin the
    SAME capacity the serving engine decodes against, so reduction shapes
    (and therefore bits) match exactly."""
    b, t_prompt = prompt_ids.shape
    total = t_prompt + max_new_tokens
    assert total <= config.max_position_embeddings
    if max_new_tokens <= 0:
        return jnp.zeros((b, 0), jnp.int32)
    if cache_len is None:
        cache_len = total
    assert cache_len >= total, (cache_len, total)
    if key is None:
        key = jax.random.PRNGKey(0)

    # freshly-imported checkpoints arrive as numpy (import_weights is
    # torch-free); device arrays are required for traced indexing below
    params = jax.tree_util.tree_map(jnp.asarray, params)
    last_logits, cache = gpt_prefill(config, params, prompt_ids, cache_len)

    key, sub = jax.random.split(key)
    first = _sample_token(last_logits, sub, temperature)

    rest = decode_tokens(
        config, params, cache, first, t_prompt, max_new_tokens - 1,
        temperature=temperature, key=key, eos_token_id=eos_token_id,
    )
    return jnp.concatenate([first[:, None], rest], axis=1)
