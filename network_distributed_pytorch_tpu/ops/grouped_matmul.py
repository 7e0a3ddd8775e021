"""Pallas TPU grouped matmul: ``out[rows of group i] = lhs[rows of group i] @ rhs[i]``.

What it is for: the routed experts of ``parallel.moe.held_experts_moe``. The
assignments that landed on this rank lie sorted by expert, each expert's rows
after the last's with nothing between, and ``group_sizes`` says how many each
has; one call is one of an expert's products for all experts at once.

Why a kernel (``lax.ragged_dot`` has the same contract): XLA's lowering of
``ragged_dot`` on the v5e took ~17 ms a call at the expert layer's shapes
(PR 27), and the layout that answered it — every expert's rows padded to
whole 512-row blocks, a block's weights copied out by a one-hot product —
computed three to four times the rows that exist and wrote a copy of the
weights per block. Here the rows stay as they lie:

- the grid's row extent is the number of VISITS, a traced value: a visit is
  one (row tile, group) pair with rows in common, so a tile that straddles
  two experts is visited once for each, under a mask of the group's rows, and
  a tile past the last group's last row is not visited at all;
- a visit's weight tile is addressed in place, ``rhs[group_of[visit]]`` in the
  ``BlockSpec``'s index map through scalar-prefetched metadata: no copy;
- products take their operands in the operands' dtype and accumulate in fp32.

The scheme (visits, scalar-prefetched ``group_of`` / ``tile_of``, the store
mask) is that of ``jax.experimental.pallas.ops.tpu.megablox``. The library's
own ``gmm`` / ``tgmm`` could not be called: their ``pallas_call``s declare no
``vma`` on ``out_shape``, which ``shard_map(check_vma=True)`` — where every
training step here runs — refuses, and take no ``name=``. What differs
besides: the metadata in a dozen lines (no sharded-groups offset, no
``existing_out``), the weight gradient's rows masked in the operands' dtype
and contracted in place (``Aᵀ·B`` by ``dot_general``, no fp32 round trip and
no explicit transpose), tiles from the shapes (:func:`tile`).

Rows past the last group's end (``sum(group_sizes) < m``) cost no visit, so
the kernel leaves whatever memory held there: :func:`grouped_matmul` SELECTS
them to zero, in the output and in ``lhs``'s cotangent (never a multiplication
by zero: the memory may hold NaNs).

Three ``pallas_call``s, each with its ``name=``: ``grouped_matmul`` (rows by
their group's matrix, forward), ``grouped_matmul_nt`` (the same by its
transpose: ``lhs``'s cotangent) and ``grouped_matmul_tn`` (each group's
``lhsᵀ · cotangent``: ``rhs``'s). On any backend but TPU the public function
is ``lax.ragged_dot`` under jax's own differentiation; the kernels run there
in interpret mode when asked (``tests/test_grouped_matmul.py``).
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

_NN = (((1,), (0,)), ((), ()))  # A·B
_NT = (((1,), (1,)), ((), ()))  # A·Bᵀ
_TN = (((0,), (0,)), ((), ()))  # Aᵀ·B


def tile(d: int, most: int = 1024) -> int:
    """The tile of a contracted or produced dimension of ``d``: the largest
    multiple of 128 from 256 up to ``most`` that divides it, and the whole of
    it where it is no wider than ``most`` or no such multiple divides it
    (1856 = 14.5 x 128 is one tile: a block may be as wide as its array
    whatever the width). So no tile meets a ragged edge and no product needs
    a mask over its contraction."""
    if d <= most:
        return d
    return next((e for e in range(most - most % 128, 128, -128) if d % e == 0), d)


def _spans(group_sizes, tm: int, empty: int):
    """The row each group starts at, the row it ends before, and how many
    row tiles of ``tm`` it has rows in (``empty`` for a group with none)."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    return starts, ends, jnp.where(group_sizes > 0, -(-ends // tm) - starts // tm, empty)


def row_tiles(group_sizes: jax.Array, row_tile: int) -> jax.Array:
    """The visits one product makes: the row extent of its kernel's grid.
    ``m // row_tile`` when the groups fill the rows and end on tile edges,
    one more for every group that starts inside a tile, fewer by every tile
    past the last group's end."""
    return jnp.sum(_spans(group_sizes, row_tile, 0)[2])


def _visits(group_sizes, m: int, tm: int, empty: bool):
    """The kernels' scalar-prefetched metadata: ``offsets`` (g + 1,) the row
    each group starts at and the last ends before; per visit the group
    (``group_of``) and the row tile (``tile_of``), groups in order and a
    group's tiles in order, so a tile's visits are consecutive; and the
    number of visits. ``empty``: a group without rows is visited once all the
    same (its weight gradient has to be written: zeros). The arrays are as
    long as the visits can be; entries past their number are never read."""
    g, tiles_m = group_sizes.shape[0], m // tm
    starts, ends, tiles = _spans(group_sizes, tm, int(empty))
    visit_ends = jnp.cumsum(tiles)
    visit = jnp.arange(tiles_m + g - 1, dtype=jnp.int32)
    group_of = jnp.minimum(jnp.sum(visit[:, None] >= visit_ends[None, :], axis=1), g - 1)
    tile_of = jnp.clip(starts[group_of] // tm + visit - (visit_ends - tiles)[group_of], 0, tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
    return tuple(v.astype(jnp.int32) for v in (offsets, group_of, tile_of)), visit_ends[-1]


def _rows_of_group(tm, visit, offsets, group_of, tile_of):
    """(tm, 1): which rows of this visit's tile belong to its group."""
    group = group_of[visit]
    row = tile_of[visit] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= offsets[group]) & (row < offsets[group + 1])


def _rows_kernel(tm, dims, offsets, group_of, tile_of, lhs, rhs, out, acc):
    """One visit of ``lhs @ rhs[group]`` (or its transpose, by ``dims``):
    grid (n tile, visit, k tile), k innermost; the group's rows of the tile
    are stored over what the tile's earlier visit left."""
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += lax.dot_general(lhs[...], rhs[...], dims, preferred_element_type=jnp.float32)

    @pl.when(k_i == pl.num_programs(2) - 1)
    def _():
        mine = _rows_of_group(tm, visit, offsets, group_of, tile_of)
        out[...] = jnp.where(mine, acc[...].astype(out.dtype), out[...])


def _groups_kernel(tm, offsets, group_of, tile_of, lhs, rhs, out, acc):
    """One visit of ``lhs[rows of group]ᵀ @ rhs[rows of group]``: grid (n
    tile, k tile, visit), visits innermost; a group's visits are summed and
    written when the next visit is another group's."""
    visit, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_of[visit]

    @pl.when((visit == 0) | (group_of[jnp.maximum(visit - 1, 0)] != group))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(offsets[group + 1] > offsets[group])
    def _():
        mine = _rows_of_group(tm, visit, offsets, group_of, tile_of)
        only = lambda ref: jnp.where(mine, ref[...], jnp.zeros_like(ref))
        acc[...] += lax.dot_general(only(lhs), only(rhs), _TN, preferred_element_type=jnp.float32)

    @pl.when((visit == last) | (group_of[jnp.minimum(visit + 1, last)] != group))
    def _():
        out[...] = acc[...].astype(out.dtype)


def _params(*tiles_and_itemsizes):
    """The three grid axes' semantics (n tiles are independent; visits and k
    tiles carry the accumulator) and the VMEM the call keeps: every
    ``(rows, columns, itemsize)`` operand tile double-buffered, the last one
    being the fp32 accumulator, counted three times over for the product's
    value and the masked store beside it."""
    *operands, acc = (r * c * b for r, c, b in tiles_and_itemsizes)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=min(max(2 * sum(operands) + 3 * acc, _VMEM_DEFAULT), _VMEM_MOST),
    )


# jitted, as the library's are: a step calls each product once a layer and again in every
# recomputation, and one trace and one lowering of a kernel serve all the calls of one signature
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _rows_by_groups(tm, interpret, transposed, out_dtype, lhs, rhs, group_sizes):
    """(m, k) rows by (g, k, n) matrices — ``transposed``: (g, n, k) — to
    (m, n) in ``out_dtype``; rows past the groups' end are not written."""
    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tk, tn = tile(k), tile(n)
    metadata, n_visits = _visits(group_sizes, m, tm, empty=False)
    if transposed:
        rhs_spec = pl.BlockSpec((None, tn, tk), lambda n_i, v, k_i, _, group_of, __: (group_of[v], n_i, k_i))
    else:
        rhs_spec = pl.BlockSpec((None, tk, tn), lambda n_i, v, k_i, _, group_of, __: (group_of[v], k_i, n_i))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm, _NT if transposed else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_visits, k // tk),
            in_specs=[pl.BlockSpec((tm, tk), lambda n_i, v, k_i, _, __, tile_of: (tile_of[v], k_i)), rhs_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, _, __, tile_of: (tile_of[v], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype, vma=_vma(lhs, rhs, group_sizes)),
        compiler_params=_params(
            (tm, tk, lhs.dtype.itemsize), (tk, tn, rhs.dtype.itemsize),
            (tm, tn, jnp.dtype(out_dtype).itemsize), (tm, tn, 4),
        ),
        interpret=interpret,
        name="grouped_matmul_nt" if transposed else "grouped_matmul",
    )(*metadata, lhs, rhs)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _groups_of_rows(tm, interpret, out_dtype, lhs, rhs, group_sizes):
    """(m, k) and (m, n) rows to each group's (k, n) product over its own
    rows: (g, k, n) in ``out_dtype``; a group without rows gets zeros."""
    (m, k), n, g = lhs.shape, rhs.shape[1], group_sizes.shape[0]
    tk, tn = tile(k), tile(n)
    metadata, n_visits = _visits(group_sizes, m, tm, empty=True)
    return pl.pallas_call(
        functools.partial(_groups_kernel, tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, n_visits),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, v, _, __, tile_of: (tile_of[v], k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, v, _, __, tile_of: (tile_of[v], n_i)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda n_i, k_i, v, _, group_of, __: (group_of[v], k_i, n_i)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype, vma=_vma(lhs, rhs, group_sizes)),
        compiler_params=_params(
            (tm, tk, lhs.dtype.itemsize), (tm, tn, rhs.dtype.itemsize),
            (tk, tn, jnp.dtype(out_dtype).itemsize), (tk, tn, 4),
        ),
        interpret=interpret,
        name="grouped_matmul_tn",
    )(*metadata, lhs, rhs)


def _live(rows: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``rows`` with every row past the groups' end selected to zero."""
    live = jnp.arange(rows.shape[0])[:, None] < jnp.sum(group_sizes)
    return jnp.where(live, rows, jnp.zeros_like(rows))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _product(tm, interpret, lhs, rhs, group_sizes):
    return _live(_rows_by_groups(tm, interpret, False, jnp.float32, lhs, rhs, group_sizes), group_sizes)


def _product_fwd(tm, interpret, lhs, rhs, group_sizes):
    return _product(tm, interpret, lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _product_bwd(tm, interpret, residuals, cotangent):
    lhs, rhs, group_sizes = residuals
    # the backward's products take their operands in the forward's dtype, as the forward's do
    cotangent = cotangent.astype(lhs.dtype)
    d_lhs = _rows_by_groups(tm, interpret, True, lhs.dtype, cotangent, rhs, group_sizes)
    d_rhs = _groups_of_rows(tm, interpret, rhs.dtype, lhs, cotangent, group_sizes)
    return _live(d_lhs, group_sizes), d_rhs, None


_product.defvjp(_product_fwd, _product_bwd)


def _as_all(*operands):
    """``operands``, each varying over the mesh as all of them together do.
    A custom_vjp's cotangents have their primals' types: inside shard_map
    every operand has to vary as the cotangents, made from all of them, will."""
    varying = _vma(*operands)

    def as_all(operand):
        missing = tuple(varying - jax.typeof(operand).vma)
        return lax.pcast(operand, missing, to="varying") if missing else operand

    return tuple(as_all(operand) for operand in operands)


def grouped_matmul(
    lhs: jax.Array,          # (m, k) rows, each group's after the last's
    rhs: jax.Array,          # (g, k, n) one matrix a group, lhs's dtype
    group_sizes: jax.Array,  # (g,) int32 rows of each group; their sum may be under m
    row_tile: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[group of row r]`` in fp32, (m, n); a row past
    the last group's end is zero. Differentiable in ``lhs`` and ``rhs``
    (cotangents in their dtypes, fp32 accumulation).

    ``row_tile``: the rows of a tile (it has to divide ``m``; a shorter
    ``lhs`` is one tile). Tiles of ``k`` and ``n`` follow the shapes
    (:func:`tile`). ``interpret=None`` lets the backend decide
    (``ops._backend.pallas_interpret``): the kernels on TPU, ``lax.ragged_dot``
    elsewhere; ``True`` runs the kernels in the Pallas interpreter.
    """
    assert lhs.dtype == rhs.dtype and lhs.shape[1] == rhs.shape[1], (lhs.dtype, rhs.dtype, lhs.shape, rhs.shape)
    assert rhs.shape[0] == group_sizes.shape[0] and group_sizes.dtype == jnp.int32
    if interpret is None and pallas_interpret():
        return _live(lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32), group_sizes)
    tm = min(row_tile, lhs.shape[0])
    assert lhs.shape[0] % tm == 0, (lhs.shape, row_tile)
    return _product(tm, bool(interpret), *_as_all(lhs, rhs, group_sizes))
