"""Pallas TPU flash attention — the hot op of every transformer here.

Why a kernel: XLA's attention materializes (or at best tiles) the (T, T)
score matrix through HBM; flash attention never builds it. Each grid program
owns one Q block held in VMEM, streams K/V blocks through VMEM, and keeps the
flash-style running (max, normalizer, accumulator) in registers/VMEM across
the whole K loop — one HBM read per operand, one write of the output, all
matmuls on the MXU at (block_q × d) × (d × block_k) tile shapes.

The online-softmax recurrence is the same one the framework's ring and
Ulysses schedules use (``parallel.sequence``); this kernel is the
single-device / per-shard block engine, so a ring shard can run it on each
block it holds. Causal mode prunes K blocks strictly above the diagonal via
the loop bound (not just masking).

Training: the kernel is wrapped in a ``custom_vjp``. The forward also emits
the per-row log-sum-exp; the backward recomputes attention block-by-block
(a ``lax.scan`` over K blocks — the standard flash backward recurrence
``dS = P ∘ (dO·Vᵀ − D)``), so the score matrix is never materialized on the
backward pass either.

Correctness is pinned against naive einsum attention (padding masks, causal,
both, and grads) in ``tests/test_flash_attention.py``; on CPU the kernel
runs in interpret mode (the test path), on TPU it compiles with Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (TPU lowering)

from ._backend import pallas_interpret

_NEG_INF = float(-1e30)  # finite stand-in: -inf breaks the m-correction math
_LSE_EMPTY = float(1e30)  # lse for fully-masked rows: exp(s - 1e30) == 0
# Additive-mask values at or below this are PADDING (hard-masked keys) and
# are excluded from the softmax by an explicit validity flag rather than by
# relying on exp underflow: a padding value equal to _NEG_INF ties the
# running-max init, where exp(s - new_m) == 1 instead of underflowing —
# an all-padded row would then emit garbage output and leak gradients into
# padded K/V (round-1 advisor finding). Soft biases (ALiBi etc.) are far
# above this threshold and keep exact additive semantics.
_MASK_PAD = float(-1e29)


def resolve_attn_impl(attn_impl: str) -> str:
    """Resolve the ``"auto"`` attention engine at dispatch time.

    On TPU the Pallas kernel compiles natively (Mosaic) and is the fast
    path; everywhere else it would only run in interpret mode — orders of
    magnitude slower than XLA's fused einsum — so "auto" means flash on
    TPU and einsum elsewhere. Explicit "flash"/"einsum" pass through
    untouched (tests pin both engines regardless of backend).
    """
    if attn_impl == "auto":
        return "einsum" if pallas_interpret() else "flash"
    return attn_impl


def _flash_kernel(
    block_q: int,
    block_k: int,
    t: int,
    causal: bool,
    scale: float,
    q_ref,
    k_ref,
    v_ref,
    mask_ref,
    o_ref,
    lse_ref,
):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, d)
    d = q.shape[-1]

    n_blocks = t // block_k
    if causal:
        # K blocks strictly past this Q block's last row contribute nothing
        hi = lax.div((qi + 1) * block_q + block_k - 1, block_k)
        hi = jnp.minimum(hi, n_blocks)
    else:
        hi = n_blocks

    def body(j, carry):
        m, l, acc = carry
        ks = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[0, pl.ds(ks, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ks, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        # the mask arrives as (1, T/block_k, block_k): K block j is ROW j,
        # a dynamic sublane index — Mosaic has no dynamic lane slicing
        mask_blk = mask_ref[0, pl.ds(j, 1), :]  # (1, block_k)
        valid = jnp.broadcast_to(mask_blk > _MASK_PAD, (block_q, block_k))
        s = s + mask_blk
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            valid = valid & (q_pos >= k_pos)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        # invalid (padding / causal-pruned) entries are force-excluded by
        # the validity flag — never by hoping exp underflows (see _MASK_PAD)
        blk_max = jnp.max(jnp.where(valid, s, _NEG_INF), axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.where(valid, jnp.exp(s - new_m), 0.0)
        l = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * correction + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return new_m, l, acc

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, hi, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), _LSE_EMPTY)
    lse_ref[0] = lse.reshape(1, block_q)


def _causal_bias(t_q: int, block_k: int, k_start, dtype=jnp.float32):
    q_pos = lax.broadcasted_iota(jnp.int32, (t_q, block_k), 0)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, (t_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, 0.0, _NEG_INF).astype(dtype)


def _flash_bwd_chunked(scale, causal, block_k, q, k, v, mask, out, lse, do):
    """Standard flash backward, one K block at a time (lax.scan): recompute
    P = exp(S − lse), then dV = Pᵀ dO, dS = P ∘ (dO Vᵀ − D), dQ += dS·K,
    dK = dSᵀ Q — the (T, T) score matrix never exists. Shapes are the folded
    (BH, T, D); mask is (B, T) shared over heads."""
    bh, t, d = q.shape
    b = mask.shape[0]
    h = bh // b
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    do32 = do.astype(jnp.float32)
    D = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)  # (BH, T)

    def block(carry, j):
        dq_acc, dmask_acc = carry
        ks = j * block_k
        k_blk = lax.dynamic_slice_in_dim(k32, ks, block_k, 1)  # (BH, bk, d)
        v_blk = lax.dynamic_slice_in_dim(v32, ks, block_k, 1)
        m_blk = lax.dynamic_slice_in_dim(mask, ks, block_k, 1)  # (B, bk)
        s = (
            jnp.einsum("zqd,zkd->zqk", q32, k_blk) * scale
            + jnp.repeat(m_blk, h, axis=0)[:, None, :]
        )
        if causal:
            s = s + _causal_bias(t, block_k, ks)[None]
        p = jnp.exp(s - lse[:, :, None])  # (BH, T, bk)
        # force-exclude padded keys (mask ≤ _MASK_PAD) instead of relying on
        # exp underflow — mirrors the forward kernel's validity flag
        p = jnp.where(
            jnp.repeat(m_blk > _MASK_PAD, h, axis=0)[:, None, :], p, 0.0
        )
        dp = jnp.einsum("zqd,zkd->zqk", do32, v_blk)
        ds = p * (dp - D[:, :, None])
        dq_acc = dq_acc + jnp.einsum("zqk,zkd->zqd", ds, k_blk) * scale
        dk_blk = jnp.einsum("zqk,zqd->zkd", ds, q32) * scale
        dv_blk = jnp.einsum("zqk,zqd->zkd", p, do32)
        # mask enters s additively, shared over heads and q rows
        dmask_blk = jnp.sum(ds.reshape(b, h, t, block_k), axis=(1, 2))
        dmask_acc = lax.dynamic_update_slice_in_dim(dmask_acc, dmask_blk, ks, 1)
        return (dq_acc, dmask_acc), (dk_blk, dv_blk)

    # the dmask accumulator must carry the inputs' device-variance (e.g. a
    # data mesh axis) or the scan carry types mismatch under shard_map; a
    # zero "tint" derived from do carries it
    tint = (do32 * 0).sum()
    (dq, dmask), (dks, dvs) = lax.scan(
        block,
        (jnp.zeros_like(q32), jnp.zeros_like(mask) + tint),
        jnp.arange(t // block_k),
    )
    dk = jnp.moveaxis(dks, 0, 1).reshape(bh, t, d)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(bh, t, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dmask


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array = None,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Exact attention without materializing the score matrix.

    q/k/v: (B, T, H, D) — the package's layout everywhere else.
    mask: optional (B, T) additive key mask (0 = attend, very negative =
    padding), the same convention as ``parallel.sequence``.
    Differentiable (custom VJP, blockwise backward). Returns (B, T, H, D)
    in q's dtype.
    """
    b, t, h, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, (
        f"T={t} must divide into blocks ({block_q}, {block_k}); pad the"
        " sequence (and mask the pads) first"
    )
    scale = 1.0 / float(d) ** 0.5

    # (B, T, H, D) -> (B*H, T, D): one grid row per (batch, head)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    qf, kf, vf = fold(q), fold(k), fold(v)
    if mask is None:
        mask = jnp.zeros((b, t), jnp.float32)
    mask = mask.astype(jnp.float32)
    # inside shard_map the mask must vary over the mesh as q does: the
    # backward's dmask does (it is built from do), and a custom_vjp
    # cotangent has to have its primal's type — a mask made here (or shared
    # by all workers) would otherwise be invariant
    missing = tuple(jax.typeof(qf).vma - jax.typeof(mask).vma)
    if missing:
        mask = lax.pcast(mask, missing, to="varying")

    kernel = functools.partial(
        _flash_kernel, block_q, block_k, t, causal, scale
    )

    def call_kernel(qf, kf, vf, mask):
        # inside shard_map, pallas_call must declare how its outputs vary
        # over the mesh — exactly as the union of its operands do
        vma = frozenset()
        for operand in (qf, kf, vf, mask):
            vma = vma | jax.typeof(operand).vma
        # TPU block shapes need their last two dims (8, 128)-divisible or
        # equal to the array's: the mask rides as (B, T/block_k, block_k)
        # and the lse as (B*H, 1, T), never as 2-D rows of width T
        out, lse = pl.pallas_call(
            kernel,
            grid=(b * h, t // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0)),
                pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0)),
                # mask is per-batch: integer-divide the (b*h) grid row
                pl.BlockSpec(
                    (1, t // block_k, block_k), lambda bh, qi: (bh // h, 0, 0)
                ),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, t, d), q.dtype, vma=vma),
                jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32, vma=vma),
            ],
            interpret=interpret,
        )(qf, kf, vf, mask.reshape(b, t // block_k, block_k))
        return out, lse.reshape(b * h, t)

    @jax.custom_vjp
    def attn(qf, kf, vf, mask):
        out, _ = call_kernel(qf, kf, vf, mask)
        return out

    def attn_fwd(qf, kf, vf, mask):
        out, lse = call_kernel(qf, kf, vf, mask)
        return out, (qf, kf, vf, mask, out, lse)

    def attn_bwd(res, do):
        qf, kf, vf, mask, out, lse = res
        return _flash_bwd_chunked(
            scale, causal, block_k, qf, kf, vf, mask, out, lse, do
        )

    attn.defvjp(attn_fwd, attn_bwd)
    out = attn(qf, kf, vf, mask)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
