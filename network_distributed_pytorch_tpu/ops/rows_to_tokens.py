"""Pallas TPU add of rows into their tokens: ``out[token[r]] += rows[r]``.

What it is for: the two places of ``parallel.moe.held_experts_moe`` that add
(rows, D) into (T, D) by token index — the weighted rows of the experts added
back into their tokens (``moe.combine``) and the cotangent of the gather that
brought the tokens to the rows (``moe.gather``).

Why a kernel (``zeros((t, d)).at[token].add(rows)`` has the same contract):
XLA lowers a scatter-add of whole rows to a loop over the rows, ~135 ns a row
on the v5e whatever the width — 2.7 ms a call alone and 3.3 in the step for
24,576 rows of 2304 fp32 where the bytes need 0.37, 1.1 for 8,192 rows of 2688
where they need 0.1; the kernel takes 0.64 and 0.26 (PERF.md section 6, PR 45).
The same rows the other way, a gather, run at the time of their bytes: nothing
is wrong with where the rows lie, only with how the add walks them.

How they lie: the assignments are sorted by expert, each expert's rows a run
after the last's (``sizes`` says how long each is), and inside a run by token.
So the rows of one run whose tokens fall in one tile of tokens are one
contiguous range, and a tile of tokens has at most one range a run. The kernel
walks the token tiles (the grid); a tile's (tokens, D) fp32 accumulator stays in
VMEM while every range that belongs to it is read, 32 rows a copy from where
they lie in HBM, two copies in flight, and added row by row, then it is written
once, in the rows' dtype. The ranges are a table of ``runs x tiles + 1`` row
numbers made with compares and a cumulative sum (scalar-prefetched with the
token of every row); no sort, no copy of the rows.

A row past the runs' end (``sum(sizes) <= r``) is never read into a sum, so
what its memory holds (zeros, NaNs) is nothing to the result; a token tile
without a row is written as zeros.

One ``pallas_call``, ``name="tokens_from_rows"``. The two public functions are
each other's transpose, and each ``jax.custom_vjp``'s cotangent is the other:
:func:`tokens_from_rows` (forward the kernel, cotangent the gather) and
:func:`rows_of_tokens` (forward the gather, cotangent the kernel). On any
backend but TPU they are ``.at[token].add(mode="drop")`` and
``.at[token].get(mode="fill")`` under jax's own differentiation; the kernel
runs there in interpret mode when asked (``tests/test_rows_to_tokens.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._backend import pallas_interpret
from .flash_attention import _VMEM_DEFAULT, _VMEM_MOST, _vma  # what a call may ask Mosaic for; a kernel's vma
from .grouped_matmul import _as_all

_ROWS = 32     # the rows of one copy: whole (8, 128) fp32 and (16, 128) bf16 tiles
_TOKENS = 512  # the most tokens of a tile: its fp32 accumulator at D = 2688 is 5.5 MB


def _ranges(token, sizes, tiles: int, tile_tokens: int):
    """(runs * tiles + 1,): entry ``run * tiles + tile`` is the row at which
    the rows of that run with a token of that tile start, the next entry the
    row they end before. A live row's key is run-major with the tile below
    it, and the rows lie sorted by it: the counts of the keys, summed up,
    are the rows' own numbers."""
    runs = sizes.shape[0]
    run = jnp.sum(lax.iota(jnp.int32, token.shape[0])[:, None] >= jnp.cumsum(sizes)[None, :], axis=1)
    key = jnp.where(run < runs, run * tiles + token // tile_tokens, runs * tiles)
    counts = jnp.sum(key[:, None] == lax.iota(jnp.int32, runs * tiles)[None, :], axis=0, dtype=jnp.int32)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])


def _kernel(runs, tile_tokens, align, ranges, token, rows, out, acc, landing, arrived, *staged):
    """One tile of tokens: every range of rows that belongs to it, run after
    run, ``_ROWS`` rows a copy (a copy starts on a whole tile of the rows'
    layout, so it may hold rows before the range and after it: they are not
    walked), the next copy in flight while this one's rows are added."""
    tile, tiles = pl.program_id(0), pl.num_programs(0)
    m, block = rows.shape[0], landing.shape[1]
    first_token = tile * tile_tokens
    acc[...] = jnp.zeros_like(acc)

    def span(run):
        """The range of ``run`` here, and the row its first copy starts at."""
        start, end = ranges[run * tiles + tile], ranges[run * tiles + tile + 1]
        return start, end, start // align * align

    def copies(run):
        start, end, first = span(jnp.minimum(run, runs - 1))
        return jnp.where(end > start, (end - first + block - 1) // block, 0)

    def after(run, copy):
        """The (run, copy) that follows: the run's next copy, or the first
        of the next run that has rows here; ``runs`` when none is left."""
        return lax.while_loop(
            lambda at: (at[0] < runs) & (at[1] >= copies(at[0])), lambda at: (at[0] + 1, 0), (run, copy + 1)
        )

    def rows_of(run, copy):
        """The rows copy ``copy`` of ``run`` holds (the last copy of the
        array is moved back to end with it) and those of them to add."""
        start, end, first = span(run)
        first = first + copy * block
        return jnp.minimum(first, m - block), jnp.maximum(start, first), jnp.minimum(end, first + block)

    def transfer(run, copy, slot):
        held_from, _, _ = rows_of(run, copy)
        source = rows.at[pl.ds(pl.multiple_of(held_from, align), block)]
        return pltpu.make_async_copy(source, landing.at[slot], arrived.at[slot])

    def add(at):
        run, copy, slot = at
        ahead = after(run, copy)

        @pl.when(ahead[0] < runs)
        def _():
            transfer(*ahead, 1 - slot).start()

        transfer(run, copy, slot).wait()
        held_from, lo, hi = rows_of(run, copy)
        source = landing.at[slot]
        if staged:  # a packed dtype: a row alone is read from its fp32 copy
            (source,) = staged
            source[...] = landing[slot].astype(jnp.float32)

        def add_row(row, _):
            at_token = pl.ds(token[row] - first_token, 1)
            acc[at_token, :] = acc[at_token, :] + source[pl.ds(row - held_from, 1), :]

        lax.fori_loop(lo, hi, add_row, None)
        return (*ahead, 1 - slot)

    begin = after(0, -1)

    @pl.when(begin[0] < runs)
    def _():
        transfer(*begin, 0).start()

    lax.while_loop(lambda at: at[0] < runs, add, (*begin, 0))
    out[...] = acc[...].astype(out.dtype)


# jitted, as grouped_matmul's are: one trace and one lowering serve every call of one signature
@functools.partial(jax.jit, static_argnums=(0, 1))
def _add(t, interpret, rows, token, sizes):
    """(m, d) rows, their tokens and the runs' sizes to (t, d) in the rows'
    dtype, fp32 sums."""
    (m, d), runs = rows.shape, sizes.shape[0]
    block = min(_ROWS, m)
    if m % block:  # no layer's shape: rows are added past the runs, to whole copies
        pad = block - m % block
        rows, token, m = jnp.pad(rows, ((0, pad), (0, 0))), jnp.pad(token, (0, pad), constant_values=t), m + pad
    tile_tokens = min(_TOKENS, t)
    tiles = pl.cdiv(t, tile_tokens)
    fp32 = rows.dtype == jnp.float32
    align = 8 * 4 // rows.dtype.itemsize
    landing = 2 * block * d * rows.dtype.itemsize
    resident = 4 * tile_tokens * d + 2 * tile_tokens * d * rows.dtype.itemsize + landing + (0 if fp32 else 4 * block * d)
    return pl.pallas_call(
        functools.partial(_kernel, runs, tile_tokens, align),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile_tokens, d), lambda tile, *_: (tile, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile_tokens, d), jnp.float32),
                pltpu.VMEM((2, block, d), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                *([] if fp32 else [pltpu.VMEM((block, d), jnp.float32)]),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), rows.dtype, vma=_vma(rows, token, sizes)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(max(resident + 2 * 2**20, _VMEM_DEFAULT), _VMEM_MOST),
        ),
        interpret=interpret,
        name="tokens_from_rows",
    )(_ranges(token, sizes, tiles, tile_tokens), token, rows)


# each is the other's transpose: t and the kernel's mode ride both as static arguments
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _tokens_from_rows(t, interpret, rows, token, sizes):
    return _add(t, interpret, rows, token, sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rows_of_tokens(t, interpret, x, token, sizes):
    return x.at[token].get(mode="fill", fill_value=0)


def _tokens_from_rows_fwd(t, interpret, rows, token, sizes):
    return _tokens_from_rows(t, interpret, rows, token, sizes), (token, sizes)


def _tokens_from_rows_bwd(t, interpret, residuals, cotangent):
    return _rows_of_tokens(t, interpret, cotangent, *residuals), None, None


def _rows_of_tokens_fwd(t, interpret, x, token, sizes):
    return _rows_of_tokens(t, interpret, x, token, sizes), (token, sizes)


def _rows_of_tokens_bwd(t, interpret, residuals, cotangent):
    return _tokens_from_rows(t, interpret, cotangent, *residuals), None, None


_tokens_from_rows.defvjp(_tokens_from_rows_fwd, _tokens_from_rows_bwd)
_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


def tokens_from_rows(
    rows: jax.Array,   # (m, d) fp32 or bf16, each run's rows after the last's, a run's by token
    token: jax.Array,  # (m,) int32 the token of each row, ``t`` for a row past the runs
    sizes: jax.Array,  # (runs,) int32 rows of each run; their sum may be under m
    t: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``zeros((t, d)).at[token].add(rows, mode="drop")`` summed in fp32, in
    ``rows``'s dtype. Differentiable in ``rows`` (the cotangent is
    :func:`rows_of_tokens` of the output's).

    The rows of a run lie by token, a token at most ``runs`` times in all;
    a row past the runs carries token ``t`` and adds nowhere, whatever its
    memory holds. ``interpret=None`` lets the backend decide
    (``ops._backend.pallas_interpret``): the kernel on TPU, the indexed add
    elsewhere; ``True`` runs the kernel in the Pallas interpreter.
    """
    assert rows.shape[0] == token.shape[0] and token.dtype == sizes.dtype == jnp.int32, (rows.shape, token.shape)
    if interpret is None and pallas_interpret():
        return jnp.zeros((t, rows.shape[1]), jnp.float32).at[token].add(rows, mode="drop").astype(rows.dtype)
    return _tokens_from_rows(t, bool(interpret), *_as_all(rows, token, sizes))


def rows_of_tokens(
    x: jax.Array,      # (t, d) tokens
    token: jax.Array,  # (m,) int32 as tokens_from_rows takes it
    sizes: jax.Array,  # (runs,) int32
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``x.at[token].get(mode="fill", fill_value=0)``: (m, d), a row whose
    token is ``t`` zeros. Differentiable in ``x``: the cotangent is
    :func:`tokens_from_rows` of the rows', summed in fp32 and cast to
    ``x``'s dtype once. ``interpret`` as there."""
    assert token.dtype == sizes.dtype == jnp.int32
    if interpret is None and pallas_interpret():
        return x.at[token].get(mode="fill", fill_value=0)
    return _rows_of_tokens(x.shape[0], bool(interpret), *_as_all(x, token, sizes))
