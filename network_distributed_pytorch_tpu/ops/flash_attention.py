"""Pallas TPU flash attention — the hot op of every transformer here.

Why a kernel: XLA's attention materializes (or at best tiles) the (T, T)
score matrix through HBM; flash attention never builds it. Each grid program
owns one Q block, holds K and V of its heads whole in VMEM, walks them in K
blocks, and keeps the flash-style running (max, normalizer, accumulator) on
chip across the whole K loop — one HBM read per operand, one write of the
output.

Layout: both kernels read and write the model's own ``(B, T, H·D)`` — what
the q/k/v projections emit and the output projection consumes; ``(B, T, H,
D)`` to it is a reshape of contiguous memory — so nothing is transposed,
folded, repeated or re-laid round them. A grid step addresses its heads as a
LANE BLOCK of the last axis through its ``BlockSpec``s, and a block's last
dimension has to be a multiple of 128 lanes or the whole axis, so
``heads_per_block(H, Hkv, D)`` gives the block from the shapes: D a multiple
of 128, one head a block; D dividing 128 with H·D a multiple of 128, 128 // D
heads a block (D = 64: a pair); H·D ≤ 128, every head in the one block. The
heads of a block are walked inside one grid step, each picked out by
zeroing the others' lanes of an operand: a product over the lanes (S = K·Qᵀ,
dP = V·dOᵀ) is then that head's at the passes a 128-deep MXU takes either
way, and a product that keeps the lanes (Oᵀ, dV, dK, dQᵀ) is stored 128
lanes wide. Grouped K/V: ``k`` and ``v`` may bring ``Hkv`` heads, H a
multiple; at D a multiple of 128 the index map reads head ``h // (H //
Hkv)`` in place, dK and dV leave the backward a query head each and are
summed over each group outside. Shapes no lane block serves (D = 96; three
heads of 64; grouped heads narrower than 128) take the one fallback: heads
folded into the batch, ``(B·H, T, D)``, one head the whole last axis, the
same kernels, a shared K/V row and the mask found by integer division of the
grid row.

A value head of its own width: ``v`` may be ``(B, T, Hkv, Dv)`` with Dv not D,
and the output is then ``(B, T, H, Dv)``. The score tile, its mask, running
max, exp, normaliser and lse are a (query head, key head)'s as ever and the
scale stays 1/sqrt(D); the accumulator, Oᵀ = Vᵀ·P, D = rowsum(dO ∘ O), dP =
V·dOᵀ and dV run over Dv lanes, and P is made (and in the backward recomputed)
once however wide V is. q, k, dq and dk ride blocks of the query/key width
and v, o, do and dv blocks of the value width. That is served one head a
block: in place where D and Dv are both multiples of 128, else through the
fold; a lane block of several heads has one width. Where Dv == D the traced
program is the one this file traced before it knew of Dv.

Two benchmark cells run the fallback: ``lfm2_psgd16_t8k``'s attention layer,
(H, Hkv, D) = (32, 8, 64) at T = 8192, where ``heads_per_block`` is None
(grouped heads of 64 lanes), q, k, v and the output are transposed round the
kernels in each pass and a head fills half the lanes; and
``phi4flash_psgd16_t8k``'s three differential-attention layers, (H, Hkv, D,
Dv) = (40, 20, 64, 128): a pair's two softmaxes (q1·k1, q2·k2) each multiply
the value heads [v1 | v2] side by side, so each softmax is computed once.

The forward (``_flash_kernel``) feeds the MXU as the backward does. Its two
products, S = K·Qᵀ and Oᵀ = Vᵀ·P, take their operands in the input dtype and
accumulate in fp32 (bf16 models run bf16 products, fp32 inputs fp32 ones);
the scale meets the fp32 scores after the product, and P is cast to V's
dtype for the second. The running max, exp, the normaliser (summed from the
fp32 P), the accumulator, the validity flags, the lse and the final division
are fp32. Tiles are transposed, keys on sublanes and queries on lanes, so
the row statistics and the lse are lane-dense rows and Oᵀ is turned once, at
(lanes, block_q), on the way out. The tile edge is ``tile_edge(T)``, the
largest of 512/256/128 that divides T (else one block of at most 128), the
same the backward takes; explicit ``block_q``/``block_k`` win in both
directions. Its reach is VMEM: K and V held whole, double-buffered, beside
the tile's fp32 temporaries, which it asks Mosaic for beyond the 16 MiB
default — T = 16384 at D = 128 in bf16 compiles.

The online-softmax recurrence is the same one the framework's ring and
Ulysses schedules use (``parallel.sequence``); this kernel is the
single-device / per-shard block engine, so a ring shard can run it on each
block it holds. Causal mode prunes K blocks strictly above the diagonal via
the loop bound (not just masking). A sliding ``window`` (key j visible to
query i iff 0 <= i - j < window) prunes the other side the same way: the
forward starts its K loop at the first block the Q block's first row can
still see, the backward ends its Q loop at the last block that can still see
the K block, and the tiles on the band's two edges carry the position
compare. The bounds follow the shapes; ``window=None`` (or one that covers
the sequence) is the causal program, unchanged.

A third rule, the first that is not a band: ``blockwise=(half, block)``, the
block-diffusion mask over T = 2 ``half`` rows, a noised copy of a sequence then
its clean copy, both at positions 0..half-1 in blocks of ``block``
(``models/sdar.py``). A noised query sees the noised keys of its own block and
the clean keys of earlier blocks; a clean query the clean keys of its own
block and earlier; no clean query sees a noised key. It is walked by loop
bounds too (``blockwise_key_tiles``, ``blockwise_query_tiles``), a tile lying
in one copy (the tile edge divides ``half``). Forward, query tile q of a copy:
first the clean key tiles every query of the tile sees whole, ``[0, q)`` where
blocks divide tiles, in a loop whose body carries no position compare; then
the tiles on the diagonals, with it: clean tile q, and for a noised query tile
its own noised tile. Backward, the same walk transposed: a clean key tile is
read by the later query tiles of BOTH copies whole and by tile q of both with
the compare, a noised key tile by its own noised query tile and no other. At
half = 8,192 and tiles of 512 that is 288 of the 1,024 tile pairs for 256
tiles' worth of visible pairs, 48 of them compared (three diagonals of 16). A
block that straddles a tile edge or spans tiles widens the diagonals by the
bounds' own floors and ceilings. The compare is two per pair against a row of
block starts (one division a query, not a pair). ``blockwise=None`` traces
the program this file traced before it knew the rule.

Training: the kernel is wrapped in a ``custom_vjp``. The forward also emits
the per-row log-sum-exp; the backward is a second Pallas kernel
(``_flash_bwd_kernel``, named ``flash_attention_bwd`` in the compiled
program) that recomputes P = exp(S − lse) tile by tile and runs the standard
flash recurrence ``dS = P ∘ (dO·Vᵀ − D)``, dV = Pᵀ·dO, dQ = dS·K, dK = dSᵀ·Q
with S, P, dP and dS never leaving VMEM: one grid step per (sequence, lane
block) holds q, k, v, o and dO whole, makes D = rowsum(dO ∘ O) a row a Q
block first, loops K blocks outside and Q blocks inside (causal: Q blocks
before the K block are skipped by the loop bound), and emits dq, dk, dv and
the additive mask's cotangent per lane block (summed over the blocks
outside). Every shape takes this path, on TPU and in interpret mode alike,
on the forward's tiles. Its reach is VMEM: eight (T, lanes) operands held
whole, which it asks Mosaic for beyond the 16 MiB default — T = 16384 at
D = 128 in bf16 compiles, as far as the forward's own whole-K/V residency
goes.

Correctness is pinned against naive einsum attention (padding masks, causal,
both, windows under, at and across the tile edge, grads, every lane block,
the fold, grouped K/V and a value head wider and narrower than the query's) in
``tests/test_flash_attention.py``, the block-wise rule and its walk in
``tests/test_flash_blockwise.py``; on CPU the kernel runs in interpret mode
(the test path), on TPU it compiles with Mosaic.
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


def heads_per_block(h: int, hkv: int, d: int, dv: int = None):
    """How many heads of width ``d`` one lane block of the ``(B, T, H*d)``
    layout holds, or None where no lane block serves the shape and the
    heads are folded into the batch instead. A block's last dimension is a
    multiple of 128 lanes or the whole axis: a head of 128 lanes or a
    multiple is a block of its own (and ``hkv < h`` key/value heads are then
    read in place, head ``i // (h // hkv)``); narrower heads that fill 128
    lanes exactly go 128 // d to a block; heads that all fit in 128 lanes
    are the one block. A value head of its own width ``dv`` is a block of
    its own beside a query/key head that is one, both a multiple of 128
    lanes; several heads a block share one width."""
    if dv not in (None, d):
        return 1 if d % 128 == 0 and dv % 128 == 0 else None
    if d % 128 == 0:
        return 1
    if hkv != h:
        return None
    if h * d <= 128:
        return h
    if 128 % d == 0 and (h * d) % 128 == 0:
        return 128 // d
    return None


def _only_head(x, heads: int, i: int, axis: int = -1):
    """``x`` with every head's share of ``axis`` but head ``i``'s zeroed: a
    product over that axis then sees head ``i`` alone, and one that keeps
    the axis is zero outside it, so the heads of a block add up."""
    if heads == 1:
        return x
    d = x.shape[axis] // heads
    at = lax.broadcasted_iota(jnp.int32, x.shape, axis % x.ndim)
    return jnp.where((at >= i * d) & (at < (i + 1) * d), x, jnp.zeros_like(x))


def _blockwise_seen(blockwise, q_row, k_row, block_q: int, block_k: int):
    """The block-wise rule on one tile, (block_k, block_q) booleans: the tile's
    queries start at row ``q_row`` and its keys at row ``k_row`` of the 2 x
    ``half`` rows (scalars; a tile lies in one copy, so its first row says
    which). A noised query sees the noised keys of its own block and the
    clean keys of earlier blocks, a clean one the clean keys of its own block
    and earlier; no tile of clean queries against noised keys is ever
    visited. One division a query, two compares a pair."""
    half, block = blockwise
    clean_q, clean_k = q_row >= half, k_row >= half
    q_pos = q_row - jnp.where(clean_q, half, 0) + lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
    k_pos = k_row - jnp.where(clean_k, half, 0) + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
    first = lax.div(q_pos, block) * block  # where each query's block starts
    lower = jnp.where(clean_k, 0, first)
    upper = jnp.where(clean_k & jnp.logical_not(clean_q), first, first + block)
    return (k_pos >= lower) & (k_pos < upper)


def _scalar_ops(x):
    """(floor division, select) for the tile walks below: on a traced scalar
    the kernels' own, on a Python int plain arithmetic (the tests read the
    walk off the same functions the kernels call)."""
    if isinstance(x, int):
        return (lambda a, b: a // b), (lambda c, a, b: a if c else b)
    return lax.div, jnp.where


def blockwise_key_tiles(blockwise, qi, block_q: int, block_k: int):
    """The key tiles the forward visits for query tile ``qi`` of ``[noised ;
    clean]`` rows under ``blockwise=(half, block)``, by loop bounds:
    ``(whole, edge, own_lo, own_hi)`` = the clean key tiles ``[0, whole)``
    every query of the tile sees all of (no compare), the clean tiles
    ``[whole, edge)`` on the diagonal (the position compare), and for a noised
    query tile its own blocks' noised tiles ``[own_lo, own_hi)`` (compared
    too; empty for a clean tile). Tile indices count inside a copy; works on
    Python ints and on traced scalars alike."""
    half, block = blockwise
    div, pick = _scalar_ops(qi)
    n_half = half // block_q
    clean = qi >= n_half
    start = (qi - pick(clean, n_half, 0)) * block_q  # the tile's first position
    first = div(start, block) * block  # its first query's block starts here
    last = (div(start + block_q - 1, block) + 1) * block  # its last query's block ends here
    # clean keys below `all_see` are seen by every query, below `some_see` by some
    all_see = pick(clean, first + block, first)
    some_see = pick(clean, last, last - block)
    whole = div(all_see, block_k)
    edge = div(some_see + block_k - 1, block_k)
    own_lo = div(first, block_k)
    own_hi = pick(clean, own_lo, div(last + block_k - 1, block_k))
    return whole, edge, own_lo, own_hi


def blockwise_query_tiles(blockwise, j, block_q: int, block_k: int):
    """The query tiles the backward visits for key tile ``j``, the transpose
    of :func:`blockwise_key_tiles`: ``(part_n, full_n, whole_n, part_c,
    full_c)``, tile indices inside a copy. The noised query tiles ``[part_n,
    full_n)`` and the clean ones ``[part_c, full_c)`` carry the compare; the
    noised ``[whole_n, half)`` and the clean ``[full_c, half)`` see the whole
    key tile. A noised key tile is read by the noised query tiles of its own
    blocks and no other: the other three ranges come back empty."""
    half, block = blockwise
    div, pick = _scalar_ops(j)
    n_half = half // block_q
    clean = j >= half // block_k
    start = (j - pick(clean, half // block_k, 0)) * block_k  # the tile's first position
    first = div(start, block) * block  # its first key's block starts here
    last = (div(start + block_k - 1, block) + 1) * block  # its last key's block ends here
    up = lambda a: div(a + block_q - 1, block_q)
    # a noised query sees a clean key from the block after the key's on, a
    # clean query from the key's own block on
    part_n = div(pick(clean, first + block, first), block_q)
    full_n = up(last)
    return (
        part_n, full_n, pick(clean, full_n, n_half),
        pick(clean, div(first, block_q), n_half), pick(clean, up(last - block), n_half),
    )


def _flash_kernel(
    block_q: int,
    block_k: int,
    t: int,
    causal: bool,
    window: int,  # None: no window
    blockwise,  # None, or (half, block): the block-wise rule over [noised ; clean] rows
    scale: float,
    heads: int,
    q_ref,
    k_ref,
    v_ref,
    mask_ref,
    o_ref,
    lse_ref,
):
    """One Q block of ``heads`` heads, side by side on the lanes, against
    every K block it can see, fed to the MXU as the backward feeds it: both
    products take their operands in the input dtype and accumulate in fp32,
    the scale meets the fp32 scores after the product, and the tiles are
    TRANSPOSED, keys on sublanes and queries on lanes — the running max,
    normaliser and lse are lane-dense rows, the softmax reduces down
    sublanes, and the output gathers as Oᵀ = Vᵀ·P, turned once at
    (lanes, block_q) on the way out. The running max, exp, normaliser,
    accumulator, validity flags and lse are fp32.

    A head is picked out of the block by zeroing the others' lanes of Q:
    S = K·Qᵀ over all the lanes is then that head's, at the passes of a
    128-deep MXU either way; Vᵀ·P comes out for every lane of the block and
    each head keeps its own rows. V's block, the accumulator and the output
    are as wide as the value head, which in a block of one head need not be
    the query/key head's."""
    qi = pl.program_id(2)
    q = q_ref[0]  # (block_q, lanes)
    v_lanes = v_ref.shape[-1]
    q_heads = [_only_head(q, heads, i) for i in range(heads)]
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
    lo = 0
    if window:
        # K blocks that end before this Q block's first row's window starts
        lo = lax.div(jnp.maximum(qi * block_q - (window - 1), 0), block_k)

    def body(j, carry, compared: bool = True):
        ks = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[0, pl.ds(ks, block_k), :]
        v_blk = v_ref[0, pl.ds(ks, block_k), :]
        # the mask arrives as (1, T/block_k, block_k): K block j is ROW j,
        # a dynamic sublane index — Mosaic has no dynamic lane slicing —
        # with its keys on lanes; this tile wants them on sublanes
        mask_col = _keys_on_sublanes(mask_ref[0, pl.ds(j, 1), :])
        valid = jnp.broadcast_to(mask_col > _MASK_PAD, tile)
        if causal:
            k_pos = ks + lax.broadcasted_iota(jnp.int32, tile, 0)
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, tile, 1)
            valid = valid & (q_pos >= k_pos)
            if window:
                valid = valid & (q_pos - k_pos < window)
        if blockwise and compared:
            valid = valid & _blockwise_seen(
                blockwise, qi * block_q, ks, block_q, block_k
            )

        def one_head(q, m, l, acc):
            # m, l: (1, block_q); acc: (v_lanes, block_q)
            s = jax.lax.dot_general(
                k_blk, q, nt, preferred_element_type=jnp.float32
            ) * scale + mask_col
            # invalid (padding / causal-pruned) entries are force-excluded
            # by the validity flag — never by hoping exp underflows (see
            # _MASK_PAD)
            blk_max = jnp.max(
                jnp.where(valid, s, _NEG_INF), axis=0, keepdims=True
            )
            new_m = jnp.maximum(m, blk_max)
            correction = jnp.exp(m - new_m)
            p = jnp.where(valid, jnp.exp(s - new_m), 0.0)
            l = l * correction + jnp.sum(p, axis=0, keepdims=True)
            acc = acc * correction + jax.lax.dot_general(
                v_blk, p.astype(v_blk.dtype), tn,
                preferred_element_type=jnp.float32,
            )
            return new_m, l, acc

        return tuple(
            one_head(q, *state) for q, state in zip(q_heads, carry)
        )

    m0 = jnp.full((1, block_q), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, block_q), jnp.float32)
    acc0 = jnp.zeros((v_lanes, block_q), jnp.float32)
    start = ((m0, l0, acc0),) * heads
    if blockwise:
        # two loops, by bounds: the clean key tiles every query of this tile
        # sees whole, then the tiles on the diagonals, the only ones that
        # carry the position compare: the clean ones at the edge of what the
        # tile sees and, for a noised tile, its own blocks' noised ones
        n_half_k = blockwise[0] // block_k
        whole, edge, own_lo, own_hi = blockwise_key_tiles(blockwise, qi, block_q, block_k)
        seen_whole = lax.fori_loop(
            0, whole, lambda n, c: body(n_half_k + n, c, compared=False), start
        )
        on_edge = edge - whole
        done = lax.fori_loop(
            0, on_edge + own_hi - own_lo,
            lambda n, c: body(
                jnp.where(n < on_edge, n_half_k + whole + n, own_lo + n - on_edge), c
            ),
            seen_whole,
        )
    else:
        done = lax.fori_loop(lo, hi, body, start)
    for i, (m, l, _) in enumerate(done):
        lse_ref[0, i] = jnp.where(
            l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), _LSE_EMPTY
        )
    # of Vᵀ·P's rows a head keeps its own
    out_t = functools.reduce(jnp.add, [
        _only_head(acc / jnp.maximum(l, 1e-37), heads, i, axis=0)
        for i, (_, l, acc) in enumerate(done)
    ])
    o_ref[0] = out_t.T.astype(o_ref.dtype)


def _flash_bwd_kernel(
    block_q: int,
    block_k: int,
    t: int,
    causal: bool,
    window: int,  # None: no window
    blockwise,  # None, or (half, block): the block-wise rule over [noised ; clean] rows
    scale: float,
    heads: int,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    do_ref,
    lse_ref,
    mask_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    dmask_ref,
    dqt_acc,
    delta_acc,
):
    """The whole backward of one lane block of ``heads`` heads of one
    sequence: q, k, v, o, do sit in VMEM, a loop over K blocks holds dK/dV
    of its block while the loop inside it walks the Q blocks — so s, p, dp
    and ds live and die as (block_k, block_q) tiles and each of the five
    products runs once a head.

    The tiles are TRANSPOSED, keys on sublanes and queries on lanes: lse and
    D then broadcast as lane-dense rows, and no product needs a score-sized
    operand turned — dQ gathers as dQᵀ = Kᵀ·dS in an fp32 VMEM scratch and
    is turned once, at (block_q, lanes), on the way out. D = rowsum(dO ∘ O)
    is made here first, a row a Q block and head. Products take their
    operands in the input dtype and accumulate in fp32; exp, the row terms
    and every accumulator are fp32.

    A head is picked out of the block by zeroing the others' lanes of its
    Q, K and dO: the two products over the lanes (S, dP) are then that
    head's, and the three that keep the lanes (dV, dK, dQᵀ) are zero
    outside them, so the heads' add up in the block's accumulators.

    q, k, dq, dk and the dQᵀ scratch are as wide as the query/key head; v, o,
    do and dv as the value head, which in a block of one head need not be
    the same: D and dP run over the value lanes, S over the query/key lanes,
    and P is recomputed once for both."""
    n_q, n_k = t // block_q, t // block_k
    nt = (((1,), (1,)), ((), ()))  # A·Bᵀ
    tn = (((0,), (0,)), ((), ()))  # Aᵀ·B
    tile = (block_k, block_q)
    lanes, v_lanes = q_ref.shape[-1], v_ref.shape[-1]
    if blockwise:
        n_half_q = blockwise[0] // block_q
    dqt_acc[...] = jnp.zeros_like(dqt_acc)

    def row_terms(i, _):
        qs = pl.multiple_of(i * block_q, block_q)
        rows = pl.ds(qs, block_q)
        prod_t = (
            do_ref[0, rows, :].astype(jnp.float32)
            * o_ref[0, rows, :].astype(jnp.float32)
        ).T  # (lanes, block_q)
        for h in range(heads):
            delta_acc[h, pl.ds(i, 1), :] = jnp.sum(
                _only_head(prod_t, heads, h, axis=0), axis=0, keepdims=True
            )
        return 0

    lax.fori_loop(0, n_q, row_terms, 0)

    def k_block(j, _):
        ks = pl.multiple_of(j * block_k, block_k)
        k_blk = k_ref[0, pl.ds(ks, block_k), :]
        k_heads = [_only_head(k_blk, heads, h) for h in range(heads)]
        v_blk = v_ref[0, pl.ds(ks, block_k), :]
        # the mask arrives with its keys on lanes, (1, block_k); this tile
        # wants them on sublanes
        mask_col = _keys_on_sublanes(mask_ref[0, pl.ds(j, 1), :])
        key_ok = jnp.broadcast_to(mask_col > _MASK_PAD, tile)

        def q_block(i, carry, compared: bool = True):
            dk, dv, dmask = carry
            qs = pl.multiple_of(i * block_q, block_q)
            q_blk = q_ref[0, pl.ds(qs, block_q), :]
            do_blk = do_ref[0, pl.ds(qs, block_q), :]
            valid = key_ok
            if causal:
                k_pos = ks + lax.broadcasted_iota(jnp.int32, tile, 0)
                q_pos = qs + lax.broadcasted_iota(jnp.int32, tile, 1)
                valid = valid & (q_pos >= k_pos)
                if window:
                    valid = valid & (q_pos - k_pos < window)
            if blockwise and compared:
                valid = valid & _blockwise_seen(
                    blockwise, qs, ks, block_q, block_k
                )
            for h in range(heads):
                q_h = _only_head(q_blk, heads, h)
                do_h = _only_head(do_blk, heads, h)
                s = jax.lax.dot_general(
                    k_heads[h], q_h, nt, preferred_element_type=jnp.float32
                ) * scale + mask_col
                # invalid entries are force-excluded by the flag, as in the
                # forward; an all-padded row has lse == _LSE_EMPTY and p == 0
                p = jnp.where(
                    valid, jnp.exp(s - lse_ref[0, h, pl.ds(i, 1), :]), 0.0
                )
                dv = dv + jnp.dot(
                    p.astype(do_h.dtype), do_h,
                    preferred_element_type=jnp.float32,
                )
                dp = jax.lax.dot_general(
                    v_blk, do_h, nt, preferred_element_type=jnp.float32
                )
                ds = p * (dp - delta_acc[h, pl.ds(i, 1), :])
                ds_in = ds.astype(q_h.dtype)
                dk = dk + jnp.dot(
                    ds_in, q_h, preferred_element_type=jnp.float32
                )
                dqt_acc[i] += jax.lax.dot_general(
                    k_heads[h], ds_in, tn, preferred_element_type=jnp.float32
                )  # (lanes, block_q)
                # the mask enters s additively: its cotangent is ds summed
                # over the query rows and the block's heads (here) and over
                # the head blocks (outside)
                dmask = dmask + jnp.sum(ds, axis=1, keepdims=True)
            return dk, dv, dmask

        # dK and dV start from zero: one array a width, so one where the
        # value head is as wide as the key's
        zero = {
            width: jnp.zeros((block_k, width), jnp.float32)
            for width in {lanes, v_lanes}
        }
        # causal: Q blocks that end before this K block starts see none of it
        lo = lax.div(j * block_k, block_q) if causal else 0
        hi = n_q
        if window:
            # Q blocks that start after this K block's last key's window ends
            last = (j + 1) * block_k + window - 2  # the last row that sees it
            hi = jnp.minimum(lax.div(last, block_q) + 1, n_q)
        start = (zero[lanes], zero[v_lanes], jnp.zeros((block_k, 1), jnp.float32))
        if blockwise:
            # two loops, by bounds, the forward's walk transposed: the query
            # tiles that see this whole key tile (for a clean one: the later
            # tiles of both copies), then the tiles on the diagonals with the
            # position compare (for a noised one: its own blocks' noised
            # tiles, and no other)
            part_n, full_n, whole_n, part_c, full_c = blockwise_query_tiles(
                blockwise, j, block_q, block_k
            )
            noised_whole = n_half_q - whole_n
            seen_whole = lax.fori_loop(
                0, noised_whole + n_half_q - full_c,
                lambda n, c: q_block(
                    jnp.where(n < noised_whole, whole_n + n, n_half_q + full_c + n - noised_whole),
                    c, compared=False,
                ),
                start,
            )
            noised_edge = full_n - part_n
            dk, dv, dmask = lax.fori_loop(
                0, noised_edge + full_c - part_c,
                lambda n, c: q_block(
                    jnp.where(n < noised_edge, part_n + n, n_half_q + part_c + n - noised_edge), c
                ),
                seen_whole,
            )
        else:
            dk, dv, dmask = lax.fori_loop(lo, hi, q_block, start)
        dk_ref[0, pl.ds(ks, block_k), :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, pl.ds(ks, block_k), :] = dv.astype(dv_ref.dtype)
        dmask_ref[0, 0, pl.ds(j, 1), :] = dmask.reshape(1, block_k)
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
    default: long sequences, and a block of two heads at 512x512 tiles,
    whose fp32 temporaries are live side by side."""
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


def _vma(*operands):
    """How a kernel's outputs vary over the mesh inside ``shard_map``:
    ``pallas_call`` must declare it, and it is as the union of its operands
    do."""
    vma = frozenset()
    for operand in operands:
        vma = vma | jax.typeof(operand).vma
    return vma


def _shares(q, k, mask):
    """How many rows of q share one row of k/v, how many lane blocks of q
    one of k/v, and how many rows of q one row of the mask: the integer
    divisions of the kernels' index maps."""
    return (
        q.shape[0] // k.shape[0],
        q.shape[2] // k.shape[2],
        q.shape[0] // mask.shape[0],
    )


def _value_lanes(k, v, lanes: int) -> int:
    """The width of v's lane block beside k's of ``lanes``: v brings k's
    heads, each its own width."""
    return v.shape[2] * lanes // k.shape[2]


def _flash_fwd(
    scale, causal, window, blockwise, lanes, heads, block_q, block_k, interpret,
    q, k, v, mask,
):
    """The forward kernel over the layout both kernels address: q
    (N, T, Hq·D), k (Nkv, T, Hkv·D) and v (Nkv, T, Hkv·Dv), mask (B, T); N
    is a multiple of Nkv and of B, and Hq of Hkv. The grid is (row of q,
    lane block, Q block), a lane block being ``heads`` heads, ``lanes`` wide
    in q and k and ``v_lanes`` in v and the output; the key/value block and
    the mask row a query block shares are found by integer division in the
    index maps. Returns the output, (N, T, Hq·Dv), and the lse,
    (N, Hq, 1, T)."""
    n, t, width = q.shape
    n_blocks = width // lanes
    v_lanes = _value_lanes(k, v, lanes)
    per_row, per_block, per_mask = _shares(q, k, mask)
    vma = _vma(q, k, v, mask)
    # what the kernel keeps in VMEM: K and V whole and the Q and O blocks,
    # double-buffered and padded to 128 lanes, and a few fp32 tiles a head
    resident = (
        2 * (t + block_q) * (max(lanes, 128) + max(v_lanes, 128))
        * q.dtype.itemsize
        + heads * 8 * 4 * block_q * block_k
    )
    kv_index = lambda i, hb, qi: (i // per_row, 0, hb // per_block)
    # TPU block shapes need their last two dims (8, 128)-divisible or equal
    # to the array's: the mask rides as (B, T/block_k, block_k) and the lse
    # as (N, Hq, 1, T), never as 2-D rows of width T
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q, block_k, t, causal, window, blockwise, scale, heads
        ),
        grid=(n, n_blocks, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, lanes), lambda i, hb, qi: (i, qi, hb)),
            pl.BlockSpec((1, t, lanes), kv_index),
            pl.BlockSpec((1, t, v_lanes), kv_index),
            pl.BlockSpec(
                (1, t // block_k, block_k),
                lambda i, hb, qi: (i // per_mask, 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, v_lanes), lambda i, hb, qi: (i, qi, hb)),
            pl.BlockSpec(
                (1, heads, 1, block_q), lambda i, hb, qi: (i, hb, 0, qi)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, t, n_blocks * v_lanes), q.dtype, vma=vma),
            jax.ShapeDtypeStruct(
                (n, n_blocks * heads, 1, t), jnp.float32, vma=vma
            ),
        ],
        compiler_params=_vmem_params(resident),
        interpret=interpret,
    )(q, k, v, mask.reshape(-1, t // block_k, block_k))


def _flash_bwd(
    scale, causal, window, blockwise, lanes, heads, block_q, block_k, interpret,
    q, k, v, mask, out, lse, do,
):
    """Flash backward as one Pallas kernel (``_flash_bwd_kernel``) over the
    forward's layout and lane blocks: p is recomputed from the saved lse,
    dV = Pᵀ dO, dS = P ∘ (dO Vᵀ − D), dQ = dS·K, dK = dSᵀ Q, and no
    score-sized array reaches HBM. dK and dV leave the kernel a query head
    each and the heads that share a key/value head are summed here, as the
    mask's cotangent is over every head; returns (dq, dk, dv, dmask)."""
    n, t, width = q.shape
    n_blocks = width // lanes
    v_lanes = _value_lanes(k, v, lanes)
    per_row, per_block, per_mask = _shares(q, k, mask)
    vma = _vma(q, k, v, mask, do)
    # q, k, dq and dk ride blocks of the query/key width, v, o, do and dv of
    # the value width
    whole = lambda wide: pl.BlockSpec((1, t, wide), lambda i, hb: (i, 0, hb))
    kv_whole = lambda wide: pl.BlockSpec(
        (1, t, wide), lambda i, hb: (i // per_row, 0, hb // per_block)
    )
    # per-row scalars ride as (blocks, block): block i is sublane row i of a
    # lane-dense array (no dynamic lane slicing on TPU) — the lse by Q
    # block, the mask and its cotangent by K block
    q_rows = (t // block_q, block_q)
    k_rows = (t // block_k, block_k)
    # what the kernel keeps in VMEM: eight (T, lanes) operands,
    # double-buffered and padded to 128 lanes, the dQᵀ scratch and a few
    # fp32 tiles a head
    resident = (
        8 * t * (max(lanes, 128) + max(v_lanes, 128)) * q.dtype.itemsize
        + 4 * t * lanes
        + heads * 8 * 4 * block_q * block_k
    )
    dq, dk, dv, dmask = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, block_q, block_k, t, causal, window, blockwise,
            scale, heads,
        ),
        grid=(n, n_blocks),
        in_specs=[
            whole(lanes), kv_whole(lanes), kv_whole(v_lanes),
            whole(v_lanes), whole(v_lanes),
            pl.BlockSpec((1, heads) + q_rows, lambda i, hb: (i, hb, 0, 0)),
            pl.BlockSpec((1,) + k_rows, lambda i, hb: (i // per_mask, 0, 0)),
        ],
        out_specs=[
            whole(lanes), whole(lanes), whole(v_lanes),
            pl.BlockSpec((1, 1) + k_rows, lambda i, hb: (i, hb, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            jax.ShapeDtypeStruct(q.shape, k.dtype, vma=vma),
            jax.ShapeDtypeStruct(out.shape, v.dtype, vma=vma),
            jax.ShapeDtypeStruct((n, n_blocks) + k_rows, jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((t // block_q, lanes, block_q), jnp.float32),
            pltpu.VMEM((heads,) + q_rows, jnp.float32),
        ],
        compiler_params=_vmem_params(resident),
        interpret=interpret,
        name="flash_attention_bwd",
    )(
        q, k, v, out, do,
        lse.reshape((n, -1) + q_rows),
        mask.reshape((-1,) + k_rows),
    )
    if per_row * per_block > 1:
        # the query heads of a group each brought a dK and a dV of the one
        # key/value head they read
        kv_heads = k.shape[2] * heads // lanes
        shared = lambda x, like: x.reshape(
            like.shape[0], per_row, t, kv_heads, per_block,
            like.shape[2] // kv_heads,
        ).sum(axis=(1, 4)).reshape(like.shape)
        dk, dv = shared(dk, k), shared(dv, v)
    dmask = dmask.reshape(mask.shape[0], per_mask * n_blocks, t).sum(axis=1)
    return dq, dk, dv, dmask


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_k", "interpret", "window", "blockwise"
    ),
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
    window: int = None,
    blockwise: tuple = None,
) -> jax.Array:
    """Exact attention without materializing the score matrix.

    q: (B, T, H, D) — the package's layout everywhere else; k:
    (B, T, Hkv, D) and v: (B, T, Hkv, Dv) with H a multiple of Hkv: query
    head ``i`` reads key/value head ``i // (H // Hkv)``. A value head may be
    wider or narrower than the query/key head; the scale is ``1/sqrt(D)``.
    mask: optional (B, T) additive key mask (0 = attend, very negative =
    padding), the same convention as ``parallel.sequence``.
    block_q/block_k: the score tile of both kernels; ``None`` is
    ``tile_edge(T)``.
    window: sliding-window attention, causal by definition: key j is
    visible to query i iff ``0 <= i - j < window``. Key blocks wholly
    outside the band are skipped by the kernels' loop bounds. A window that
    covers the sequence is ``causal=True``, the same program.
    blockwise: ``(half, block)``, the block-diffusion rule over T = 2 * half
    rows ``[noised copy ; clean copy]`` (the module's text); neither causal
    nor a window; hidden tiles are skipped by the kernels' loop bounds.
    Differentiable (custom VJP, blockwise backward). Returns (B, T, H, Dv)
    in q's dtype.
    """
    b, t, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    if h % hkv or k.shape != (b, t, hkv, d) or v.shape != (b, t, hkv, dv):
        raise ValueError(
            f"q {q.shape} needs k (B, T, Hkv, D) and v (B, T, Hkv, Dv) with H"
            f" a multiple of Hkv; got {k.shape} and {v.shape}"
        )
    if blockwise is not None:
        half, block = blockwise
        if causal or window is not None or t != 2 * half or block < 1 or half % block:
            raise ValueError(
                f"blockwise={blockwise}: (half, block) over T = 2 * half rows,"
                f" whole blocks, and neither causal nor a window; got T={t},"
                f" causal={causal}, window={window}"
            )
    # under the block-wise rule a tile lies in one copy: it divides the half
    whole = t if blockwise is None else blockwise[0]
    block_q = min(block_q or tile_edge(whole), whole)
    block_k = min(block_k or tile_edge(whole), whole)
    assert whole % block_q == 0 and whole % block_k == 0, (
        f"T={whole} must divide into blocks ({block_q}, {block_k}); pad the"
        " sequence (and mask the pads) first"
    )
    scale = 1.0 / float(d) ** 0.5
    if window is not None:
        if window < 1:
            raise ValueError(f"window={window}: a query sees at least itself")
        causal, window = True, (window if window < t else None)

    heads = heads_per_block(h, hkv, d, dv)
    if heads is None:
        # no lane block serves these heads: (B, T, H, D) -> (B*H, T, D),
        # one row a (batch, head)
        heads = 1
        rows = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, t, x.shape[-1])
        unrows = lambda x: x.reshape(b, h, t, dv).transpose(0, 2, 1, 3)
    else:
        # the model's own layout: heads side by side on the last axis, as
        # the projections emit them
        rows = lambda x: x.reshape(b, t, -1)
        unrows = lambda x: x.reshape(b, t, h, dv)
    if mask is None:
        mask = jnp.zeros((b, t), jnp.float32)
    mask = mask.astype(jnp.float32)
    # inside shard_map the mask must vary over the mesh as q does: the
    # backward's dmask does (it is built from do), and a custom_vjp
    # cotangent has to have its primal's type — a mask made here (or shared
    # by all workers) would otherwise be invariant
    missing = tuple(jax.typeof(q).vma - jax.typeof(mask).vma)
    if missing:
        mask = lax.pcast(mask, missing, to="varying")

    static = (
        scale, causal, window, blockwise, heads * d, heads, block_q, block_k,
        interpret,
    )

    @jax.custom_vjp
    def attn(q, k, v, mask):
        return _flash_fwd(*static, q, k, v, mask)[0]

    def attn_fwd(q, k, v, mask):
        out, lse = _flash_fwd(*static, q, k, v, mask)
        return out, (q, k, v, mask, out, lse)

    def attn_bwd(res, do):
        return _flash_bwd(*static, *res, do)

    attn.defvjp(attn_fwd, attn_bwd)
    return unrows(attn(rows(q), rows(k), rows(v), mask))
