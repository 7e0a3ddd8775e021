"""Pallas TPU flash attention — the hot op of every transformer here.

Why a kernel: XLA's attention materializes (or at best tiles) the (T, T)
score matrix through HBM; flash attention never builds it. Each grid program
owns one Q block, holds K and V of its (batch, head) whole in VMEM, walks
them in K blocks, and keeps the flash-style running (max, normalizer,
accumulator) on chip across the whole K loop — one HBM read per operand, one
write of the output.

The forward (``_flash_kernel``) feeds the MXU as the backward does. Its two
products, S = K·Qᵀ and Oᵀ = Vᵀ·P, take their operands in the input dtype and
accumulate in fp32 (bf16 models run bf16 products, fp32 inputs fp32 ones);
the scale meets the fp32 scores after the product, and P is cast to V's
dtype for the second. The running max, exp, the normaliser (summed from the
fp32 P), the accumulator, the validity flags, the lse and the final division
are fp32. Tiles are transposed, keys on sublanes and queries on lanes, so
the row statistics and the lse are lane-dense rows and Oᵀ is turned once, at
(D, block_q), on the way out. The tile edge is ``tile_edge(T)``, the largest
of 512/256/128 that divides T (else one block of at most 128), the same the
backward takes; explicit ``block_q``/``block_k`` win in both directions. Its
reach is VMEM: K and V held whole, double-buffered, beside the tile's fp32
temporaries, which it asks Mosaic for beyond the 16 MiB default — T = 16384
at D = 128 in bf16 compiles.

The online-softmax recurrence is the same one the framework's ring and
Ulysses schedules use (``parallel.sequence``); this kernel is the
single-device / per-shard block engine, so a ring shard can run it on each
block it holds. Causal mode prunes K blocks strictly above the diagonal via
the loop bound (not just masking).

Training: the kernel is wrapped in a ``custom_vjp``. The forward also emits
the per-row log-sum-exp; the backward is a second Pallas kernel
(``_flash_bwd_kernel``, named ``flash_attention_bwd`` in the compiled
program) that recomputes P = exp(S − lse) tile by tile and runs the standard
flash recurrence ``dS = P ∘ (dO·Vᵀ − D)``, dV = Pᵀ·dO, dQ = dS·K, dK = dSᵀ·Q
with S, P, dP and dS never leaving VMEM: one grid step per (batch, head)
holds q, k, v and dO whole, loops K blocks outside and Q blocks inside
(causal: Q blocks before the K block are skipped by the loop bound), and
emits dq, dk, dv and the additive mask's cotangent per head (summed over
heads outside). Only D = rowsum(dO ∘ O) is an XLA reduction. Every shape
takes this path, on TPU and in interpret mode alike, on the forward's
tiles. Its reach is VMEM: seven (T, D) operands held whole, which it asks
Mosaic for beyond the 16 MiB default — T = 16384 at D = 128 in bf16
compiles, as far as the forward's own whole-K/V residency goes.

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
from jax.experimental.pallas import tpu as pltpu

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


def _keys_on_sublanes(row):
    """A (1, block_k) row of per-key values as the (block_k, 1) column a
    transposed tile wants: the diagonal of the row broadcast down a square
    (exact, any block size)."""
    n = row.shape[-1]
    diagonal = lax.broadcasted_iota(
        jnp.int32, (n, n), 0
    ) == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)


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
    """One Q block against every K block it can see, fed to the MXU as the
    backward feeds it: both products take their operands in the input dtype
    and accumulate in fp32, the scale meets the fp32 scores after the
    product, and the tiles are TRANSPOSED, keys on sublanes and queries on
    lanes — the running max, normaliser and lse are lane-dense rows, the
    softmax reduces down sublanes, and the output gathers as Oᵀ = Vᵀ·P,
    turned once at (d, block_q) on the way out. The running max, exp,
    normaliser, accumulator, validity flags and lse are fp32."""
    qi = pl.program_id(1)
    q = q_ref[0]  # (block_q, d)
    d = q.shape[-1]
    nt = (((1,), (1,)), ((), ()))  # A·Bᵀ
    tn = (((0,), (0,)), ((), ()))  # Aᵀ·B
    tile = (block_k, block_q)

    n_blocks = t // block_k
    if causal:
        # K blocks strictly past this Q block's last row contribute nothing
        hi = lax.div((qi + 1) * block_q + block_k - 1, block_k)
        hi = jnp.minimum(hi, n_blocks)
    else:
        hi = n_blocks

    def body(j, carry):
        m, l, acc = carry  # (1, block_q), (1, block_q), (d, block_q)
        ks = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[0, pl.ds(ks, block_k), :]
        v_blk = v_ref[0, pl.ds(ks, block_k), :]
        # the mask arrives as (1, T/block_k, block_k): K block j is ROW j,
        # a dynamic sublane index — Mosaic has no dynamic lane slicing —
        # with its keys on lanes; this tile wants them on sublanes
        mask_col = _keys_on_sublanes(mask_ref[0, pl.ds(j, 1), :])
        s = jax.lax.dot_general(
            k_blk, q, nt, preferred_element_type=jnp.float32
        ) * scale + mask_col
        valid = jnp.broadcast_to(mask_col > _MASK_PAD, tile)
        if causal:
            k_pos = ks + lax.broadcasted_iota(jnp.int32, tile, 0)
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, tile, 1)
            valid = valid & (q_pos >= k_pos)

        # invalid (padding / causal-pruned) entries are force-excluded by
        # the validity flag — never by hoping exp underflows (see _MASK_PAD)
        blk_max = jnp.max(jnp.where(valid, s, _NEG_INF), axis=0, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.where(valid, jnp.exp(s - new_m), 0.0)
        l = l * correction + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * correction + jax.lax.dot_general(
            v_blk, p.astype(v_blk.dtype), tn,
            preferred_element_type=jnp.float32,
        )  # (d, block_q)
        return new_m, l, acc

    m0 = jnp.full((1, block_q), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, block_q), jnp.float32)
    acc0 = jnp.zeros((d, block_q), jnp.float32)
    m, l, acc = lax.fori_loop(0, hi, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-37)).T.astype(o_ref.dtype)
    lse_ref[0] = jnp.where(
        l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), _LSE_EMPTY
    )


def _flash_bwd_kernel(
    block_q: int,
    block_k: int,
    t: int,
    causal: bool,
    scale: float,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    mask_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    dmask_ref,
    dqt_acc,
):
    """The whole backward of one (batch, head): q, k, v, do sit in VMEM, a
    loop over K blocks holds dK/dV of its block while the loop inside it
    walks the Q blocks — so s, p, dp and ds live and die as
    (block_k, block_q) tiles and each of the five products runs once.

    The tiles are TRANSPOSED, keys on sublanes and queries on lanes: lse and
    D then broadcast as the lane-dense rows they arrive as, and no product
    needs a score-sized operand turned — dQ gathers as dQᵀ = Kᵀ·dS in an
    fp32 VMEM scratch and is turned once, at (block_q, d), on the way out.
    Products take their operands in the input dtype and accumulate in fp32;
    exp, the row terms and every accumulator are fp32."""
    n_q, n_k = t // block_q, t // block_k
    nt = (((1,), (1,)), ((), ()))  # A·Bᵀ
    tn = (((0,), (0,)), ((), ()))  # Aᵀ·B
    tile = (block_k, block_q)
    dqt_acc[...] = jnp.zeros_like(dqt_acc)

    def k_block(j, _):
        ks = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[0, pl.ds(ks, block_k), :]
        v_blk = v_ref[0, pl.ds(ks, block_k), :]
        # the mask arrives with its keys on lanes, (1, block_k); this tile
        # wants them on sublanes
        mask_col = _keys_on_sublanes(mask_ref[0, pl.ds(j, 1), :])
        key_ok = jnp.broadcast_to(mask_col > _MASK_PAD, tile)

        def q_block(i, carry):
            dk, dv, dmask = carry
            qs = pl.multiple_of(i * block_q, block_q)
            q_blk = q_ref[0, pl.ds(qs, block_q), :]
            do_blk = do_ref[0, pl.ds(qs, block_q), :]
            s = jax.lax.dot_general(
                k_blk, q_blk, nt, preferred_element_type=jnp.float32
            ) * scale + mask_col
            valid = key_ok
            if causal:
                k_pos = ks + lax.broadcasted_iota(jnp.int32, tile, 0)
                q_pos = qs + lax.broadcasted_iota(jnp.int32, tile, 1)
                valid = valid & (q_pos >= k_pos)
            # invalid entries are force-excluded by the flag, as in the
            # forward; an all-padded row has lse == _LSE_EMPTY and p == 0
            p = jnp.where(valid, jnp.exp(s - lse_ref[0, pl.ds(i, 1), :]), 0.0)
            dv = dv + jnp.dot(
                p.astype(do_blk.dtype), do_blk,
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                v_blk, do_blk, nt, preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta_ref[0, pl.ds(i, 1), :])
            ds_in = ds.astype(q_blk.dtype)
            dk = dk + jnp.dot(ds_in, q_blk, preferred_element_type=jnp.float32)
            dqt_acc[i] += jax.lax.dot_general(
                k_blk, ds_in, tn, preferred_element_type=jnp.float32
            )  # (d, block_q)
            # the mask enters s additively: its cotangent is ds summed over
            # the query rows (here) and over the heads (outside)
            return dk, dv, dmask + jnp.sum(ds, axis=1, keepdims=True)

        zero = jnp.zeros((block_k, k_blk.shape[-1]), jnp.float32)
        # causal: Q blocks that end before this K block starts see none of it
        lo = lax.div(j * block_k, block_q) if causal else 0
        dk, dv, dmask = lax.fori_loop(
            lo, n_q, q_block, (zero, zero, jnp.zeros((block_k, 1), jnp.float32))
        )
        dk_ref[0, pl.ds(ks, block_k), :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, pl.ds(ks, block_k), :] = dv.astype(dv_ref.dtype)
        dmask_ref[0, pl.ds(j, 1), :] = dmask.reshape(1, block_k)
        return 0

    lax.fori_loop(0, n_k, k_block, 0)

    def turn_dq(i, _):
        qs = pl.multiple_of(i * block_q, block_q)
        dq_ref[0, pl.ds(qs, block_q), :] = (dqt_acc[i].T * scale).astype(
            dq_ref.dtype
        )
        return 0

    lax.fori_loop(0, n_q, turn_dq, 0)


# VMEM a TPU kernel may use unasked (Mosaic's scoped default) and the most
# either kernel asks for: under the 128 MiB of a v5e/v6e core
_VMEM_DEFAULT = 16 * 2**20
_VMEM_MOST = 100 * 2**20


def _vmem_params(resident: int):
    """Ask Mosaic for ``resident`` bytes of VMEM where that passes its
    default — long sequences only: the cells' imdb shapes stay under it."""
    if resident <= _VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(resident, _VMEM_MOST))


def tile_edge(t: int) -> int:
    """The edge of the square score tile both kernels walk a sequence of
    ``t`` with. On the chip a tile's fixed cost outweighs what it holds
    (PERF.md §6, PR 25: at T=512 one 512x512 tile takes half the time of
    sixteen 128x128), so it is the largest of 512/256/128 that divides T,
    and one block of at most 128 where none does."""
    return next((e for e in (512, 256, 128) if t % e == 0), min(128, t))


def _flash_bwd(
    scale, causal, h, block_q, block_k, interpret, q, k, v, mask, out, lse, do
):
    """Flash backward as one Pallas kernel (``_flash_bwd_kernel``): p is
    recomputed from the saved lse, dV = Pᵀ dO, dS = P ∘ (dO Vᵀ − D),
    dQ = dS·K, dK = dSᵀ Q, and no score-sized array reaches HBM. Shapes are
    the folded (B*H, T, D); mask is (B, T), shared over heads; returns
    (dq, dk, dv, dmask)."""
    bh, t, d = q.shape
    b = bh // h
    # D = rowsum(dO ∘ O), the softmax backward's row term: (B*H, T) fp32
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # inside shard_map the outputs vary over the mesh as the operands do
    vma = frozenset()
    for operand in (q, k, v, mask, do):
        vma = vma | jax.typeof(operand).vma
    whole = pl.BlockSpec((1, t, d), lambda i: (i, 0, 0))
    # per-row scalars ride as (blocks, block): block i is sublane row i of a
    # lane-dense array (no dynamic lane slicing on TPU) — lse and D by Q
    # block, the mask and its cotangent by K block
    q_rows = (t // block_q, block_q)
    k_rows = (t // block_k, block_k)
    # what the kernel keeps in VMEM: seven (T, D) operands, double-buffered
    # and padded to 128 lanes, the dQᵀ scratch and a few fp32 tiles
    resident = (
        14 * t * max(d, 128) * q.dtype.itemsize
        + 4 * t * d
        + 8 * 4 * block_q * block_k
    )
    dq, dk, dv, dmask = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, block_q, block_k, t, causal, scale
        ),
        grid=(bh,),
        in_specs=[
            whole, whole, whole, whole,
            pl.BlockSpec((1,) + q_rows, lambda i: (i, 0, 0)),
            pl.BlockSpec((1,) + q_rows, lambda i: (i, 0, 0)),
            # mask is per-batch: integer-divide the (b*h) grid row
            pl.BlockSpec((1,) + k_rows, lambda i: (i // h, 0, 0)),
        ],
        out_specs=[
            whole, whole, whole,
            pl.BlockSpec((1,) + k_rows, lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t, d), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh,) + k_rows, jnp.float32, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((t // block_q, d, block_q), jnp.float32)],
        compiler_params=_vmem_params(resident),
        interpret=interpret,
        name="flash_attention_bwd",
    )(
        q, k, v, do,
        lse.reshape((bh,) + q_rows),
        delta.reshape((bh,) + q_rows),
        mask.reshape((b,) + k_rows),
    )
    return dq, dk, dv, dmask.reshape(b, h, t).sum(axis=1)


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
    block_q: int = None,
    block_k: int = None,
    interpret: bool = False,
) -> jax.Array:
    """Exact attention without materializing the score matrix.

    q/k/v: (B, T, H, D) — the package's layout everywhere else.
    mask: optional (B, T) additive key mask (0 = attend, very negative =
    padding), the same convention as ``parallel.sequence``.
    block_q/block_k: the score tile of both kernels; ``None`` is
    ``tile_edge(T)``.
    Differentiable (custom VJP, blockwise backward). Returns (B, T, H, D)
    in q's dtype.
    """
    b, t, h, d = q.shape
    block_q = min(block_q or tile_edge(t), t)
    block_k = min(block_k or tile_edge(t), t)
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

    # what the kernel keeps in VMEM: K and V whole and the Q and O blocks,
    # double-buffered and padded to 128 lanes, and a few fp32 tiles
    resident = (
        4 * (t + block_q) * max(d, 128) * q.dtype.itemsize
        + 8 * 4 * block_q * block_k
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
            compiler_params=_vmem_params(resident),
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
        return _flash_bwd(
            scale, causal, h, block_q, block_k, interpret, *res, do
        )

    attn.defvjp(attn_fwd, attn_bwd)
    out = attn(qf, kf, vf, mask)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
