"""Pallas TPU kernels for Mamba-1's selective scan (``ops/selective_scan.py``
has the recurrence): the steps walked inside a kernel with the (N, channels)
state in VMEM, forward and backward.

Why kernels: as two nested ``lax.scan``s an iteration cost 1.0-1.5 us for six
vector operations and an ``exp`` an element of an 80-vreg state, because every
trip sliced ``x_t``, ``delta_t``, ``B_t``, ``C_t`` out of HBM arrays and wrote
``y_t`` back, and the backward stacked a chunk's (32, 16, 5120) states through
HBM (PERF.md section 5, PR 49: 8.4 + 19.7 + 16.6 ms a step at 2.5% of the
scan's roofline). Here nothing of size (t, c, n) leaves VMEM:

- grid (batch, block of channels, block of time): the channels lie on the
  lanes, :func:`channel_block` of them a step, the state's N indices on the
  sublanes; the time blocks are innermost and sequential, so the (N, block)
  state is a scratch that lives across them; ``x``, ``delta``, ``y`` come and
  go as (time block, block) tiles the pipeline fetches ahead;
- ``B`` and ``C`` arrive with time on the lanes, (N, 128) a step; what scales
  the state's rows needs ``B_t`` down the sublanes and alike on every lane,
  which a prologue makes once a block by a gather along the lanes: (time
  block, N, 128) in VMEM, a step's slab tiled over the block's lanes. The
  prologue is a loop, and so is the epilogue that lays ``dB``, ``dC`` back:
  as 128 statically cut columns a block they were 0.4 ms a call faster and
  768 equations a kernel more to trace and lower in every run's set-up
  (PERF.md section 6, PR 50: ``setup_s`` past its bound);
- a step is ``S <- exp(delta_t a) S + (delta_t x_t) B_t``, ``y_t = sum_n S
  C_t`` in a ``lax.fori_loop``; the skip ``d x`` rides the block's epilogue;
- the forward leaves the state each time block STARTED from, (T / block, N,
  C), the only residual beside the inputs; the backward walks the time blocks
  last to first, runs a block's steps forward again from that state into a
  (time block + 1, N, block) scratch, then in reverse with the carried
  cotangent ``dS``. What sums over n leaves a step as a (1, block) row; what
  sums over the channels (``dB_t``, ``dC_t``) leaves it as an (N, 128) partial
  whose lanes the epilogue adds and lays into (N, 128), one such
  partial a block of channels: the caller adds them.

Everything inside is fp32, ``y`` and ``dx`` rounded once to ``x``'s dtype;
``selective_scan``'s ``STATE_DTYPE`` reaches the kernels as the dtype the
decay and the state are rounded to (:func:`_brought_in`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _VMEM_DEFAULT, _VMEM_MOST, _vma  # what a call may ask Mosaic for; a kernel's vma

_LANES = 128
_SUBLANES = 8
_ROWS = 16  # rows of one sublane tile of a 16-bit dtype: what a time block is whole tiles of
TIME_BLOCK = 128  # steps a grid step walks: the boundary states are (T / 128, N, C), 21 MB at the cell's shape
UNROLL = 8  # steps a trip of the kernels' walks
_LONG_TRIP = 32  # steps a trip where a step is one latency and no arithmetic: laying b, c down the sublanes, dB, dC onto the lanes


def serves(t: int, ch: int, n: int) -> bool:
    """Whether Mosaic's tiles serve the shape: the channels whole lane blocks,
    the state's indices whole sublane tiles. Any T is padded to whole time
    blocks."""
    return t > 0 and ch % _LANES == 0 and n % _SUBLANES == 0


def channel_block(ch: int) -> int:
    """The lanes a grid step takes: the widest of 1024, 512, 256, 128 that
    divides ``ch`` (PERF.md section 6, PR 50, forward / with every cotangent
    at (1, 8192, 5120, 16): 256 lanes 2.87 / 9.57 ms, 512 2.22 / 7.54, 1024
    1.94 / 6.89, 1280 1.89 / 6.87, 2560 2.07 / 8.77, 5120 2.53 / 10.56)."""
    return next(lanes for lanes in (1024, 512, 256, 128) if ch % lanes == 0)


def time_block(t: int, chunk=None) -> int:
    """The steps a grid step walks: ``chunk`` (``TIME_BLOCK`` where none is
    given) in whole sublane tiles, no more than ``t`` needs."""
    whole = lambda v: -(-v // _ROWS) * _ROWS
    return min(whole(chunk or TIME_BLOCK), whole(t), _LANES)  # b and c lay a time block over one lane block


def _over_lanes(slab, lanes: int):
    """An (N, 128) slab side by side over ``lanes`` lanes."""
    return slab if lanes == _LANES else jnp.concatenate([slab] * (lanes // _LANES), axis=1)


def _lane_partial(v):
    """(N, lanes) -> (N, 128): the lane blocks added, what is left of a sum over the channels."""
    parts = [v[:, i : i + _LANES] for i in range(0, v.shape[1], _LANES)]
    return functools.reduce(jnp.add, parts)


def _down_the_sublanes(b_ref, c_ref, b_rows, c_rows):
    """b, c (1, 1, N, 128), time on the lanes -> ``b_rows``, ``c_rows`` (tb, N,
    128): step t's N values down the sublanes, alike on every lane: a gather
    along the lanes whose every index is t. In a loop, not 128 cut columns: the
    kernels are traced and lowered in every run's set-up."""
    b, c = b_ref[0, 0].astype(b_rows.dtype), c_ref[0, 0].astype(c_rows.dtype)

    def lay(t, _):
        lane = jnp.full(b.shape, t, jnp.int32)
        b_rows[t] = jnp.take_along_axis(b, lane, axis=1)
        c_rows[t] = jnp.take_along_axis(c, lane, axis=1)
        return _

    _walk(b_rows.shape[0], lay, 0, _LONG_TRIP)


def _onto_the_lanes(db_partials, dc_partials, db_ref, dc_ref):
    """db, dc partials (tb, N, 128) -> ``db_ref``, ``dc_ref`` (1, 1, 1, N, 128):
    each step's lanes added, the sums side by side with time on the lanes."""
    shape = db_ref.shape[3:]
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    place = lambda t, out, partials: jnp.where(lane == t, jnp.sum(partials[t], axis=1, keepdims=True), out)
    both = lambda t, outs: (place(t, outs[0], db_partials), place(t, outs[1], dc_partials))
    zeros = jnp.zeros(shape, db_partials.dtype)
    db, dc = _walk(db_partials.shape[0], both, (zeros, zeros), _LONG_TRIP)
    db_ref[0, 0, 0], dc_ref[0, 0, 0] = db.astype(db_ref.dtype), dc.astype(dc_ref.dtype)


def _row(ref, t):
    return ref[pl.ds(t, 1), :]


def _walk(steps: int, step, carry, most: int = 0):
    """``step(t, carry)`` for t in [0, steps), ``UNROLL`` (or up to ``most``)
    steps a trip: Mosaic unrolls a loop whole or not at all, and a trip's
    steps are what its scheduler overlaps."""
    unroll = math.gcd(steps, most or UNROLL)

    def trip(i, carry):
        for j in range(unroll):
            carry = step(i * unroll + j, carry)
        return carry

    return lax.fori_loop(0, steps // unroll, trip, carry)


def _brought_in(sd, x_ref, delta_ref, a_ref, b_ref, c_ref, delta_rows, fed, b_rows, c_rows):
    """A time block's prologue, the same in both kernels: delta and ``delta
    x`` as fp32 rows, b and c down the sublanes. -> x (tb, lanes) fp32, a (N,
    lanes), ``decay(t)`` = ``exp(delta_t a)`` and ``advance(t, S)``, one step
    of the recurrence. ``sd`` is ``selective_scan``'s ``STATE_DTYPE``: a 16-bit
    row cannot be addressed alone, so the kernels hold everything in fp32 and
    what it lowers is the decay and the state, rounded to it and back (nothing
    at fp32)."""
    f32, lanes = jnp.float32, fed.shape[1]
    near = lambda v: v.astype(sd).astype(f32)
    x = x_ref[0].astype(f32)
    delta_rows[...] = delta_ref[0].astype(f32)
    fed[...] = delta_rows[...] * x
    _down_the_sublanes(b_ref, c_ref, b_rows, c_rows)
    a = a_ref[...]
    decay = lambda t: near(jnp.exp(_row(delta_rows, t) * a))
    advance = lambda t, s: near(decay(t) * s + _row(fed, t) * _over_lanes(b_rows[t], lanes))
    return x, a, near, decay, advance


def _forward_kernel(
    sd, x_ref, delta_ref, a_ref, b_ref, c_ref, d_ref, y_ref, start_ref, state, b_rows, c_rows, delta_rows, fed, y_rows,
):
    """One time block of one block of channels. x, delta, y (1, tb, lanes); a
    (N, lanes); b, c (1, 1, N, tb in whole lane blocks); d (1, lanes); start
    (1, 1, N, lanes): the state the block starts from. Scratch, fp32: the
    state (N, lanes), b and c down the sublanes (tb, N, 128), delta, ``delta
    x`` and y's rows (tb, lanes)."""
    tb, lanes = fed.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    start_ref[0, 0] = state[...]
    x, _, _, _, advance = _brought_in(sd, x_ref, delta_ref, a_ref, b_ref, c_ref, delta_rows, fed, b_rows, c_rows)

    def step(t, s):
        s = advance(t, s)
        y_rows[pl.ds(t, 1), :] = jnp.sum(s * _over_lanes(c_rows[t], lanes), axis=0, keepdims=True)
        return s

    state[...] = _walk(tb, step, state[...])
    y_ref[0] = (y_rows[...] + x * d_ref[...]).astype(y_ref.dtype)


def _backward_kernel(
    sd, x_ref, delta_ref, a_ref, b_ref, c_ref, d_ref, start_ref, dy_ref,
    dx_ref, ddelta_ref, da_ref, db_ref, dc_ref, dd_ref,
    dstate, states, b_rows, c_rows, delta_rows, fed, dy_rows, fed_rows, decay_rows, db_partials, dc_partials,
):
    """One time block of one block of channels, the time blocks arriving last
    to first. Inputs as the forward's, ``start`` the state the block starts
    from, dy as y. Outputs: dx, ddelta (1, tb, lanes); da (1, N, lanes) and dd
    (1, 1, lanes), resident over the time blocks; db, dc (1, 1, 1, N, tb in
    whole lane blocks), this block of channels' share. Scratch, fp32: the
    carried cotangent ``dS`` (N, lanes); the block's states (tb + 1, N, lanes),
    slot t + 1 after step t; b, c down the sublanes; delta, ``delta x``, dy,
    ``sum_n dS B`` and ``sum_n g a`` as rows (tb, lanes); the (tb, N, 128)
    partials of db, dc."""
    f32 = jnp.float32
    tb, lanes = fed.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, f32)
        da_ref[...] = jnp.zeros(da_ref.shape, f32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, f32)

    x, a, near, decay, advance = _brought_in(sd, x_ref, delta_ref, a_ref, b_ref, c_ref, delta_rows, fed, b_rows, c_rows)
    dy_rows[...] = dy_ref[0].astype(f32)

    def again(t, s):
        s = advance(t, s)
        states[t + 1] = s
        return s

    states[0] = start_ref[0, 0]
    _walk(tb, again, states[0])

    def back(i, carry):
        ds, da = carry
        t = tb - 1 - i
        dy_t = _row(dy_rows, t)
        ds = ds + dy_t * _over_lanes(c_rows[t], lanes)
        dc_partials[t] = _lane_partial(dy_t * states[t + 1])
        db_partials[t] = _lane_partial(ds * _row(fed, t))
        fed_rows[pl.ds(t, 1), :] = jnp.sum(ds * _over_lanes(b_rows[t], lanes), axis=0, keepdims=True)
        kept = decay(t)
        g = ds * states[t] * kept
        decay_rows[pl.ds(t, 1), :] = jnp.sum(g * a, axis=0, keepdims=True)
        return near(ds * kept), da + g * _row(delta_rows, t)

    ds, da = _walk(tb, back, (dstate[...], jnp.zeros(dstate.shape, f32)))
    dstate[...] = ds
    da_ref[0] += da
    dy, delta = dy_rows[...], delta_rows[...]
    dd_ref[0] += jnp.sum(dy * x, axis=0, keepdims=True)
    ddelta_ref[0] = (decay_rows[...] + fed_rows[...] * x).astype(ddelta_ref.dtype)
    dx_ref[0] = (fed_rows[...] * delta + dy * d_ref[...]).astype(dx_ref.dtype)
    _onto_the_lanes(db_partials, dc_partials, db_ref, dc_ref)


# --- the calls ---------------------------------------------------------------


class _Blocks:
    """The ``BlockSpec``s of a grid (batch, block of channels, block of time)
    over x (B, T, C), a (N, C) and b (B, T / tb, N, tb in whole lane blocks),
    the time blocks first to last or, ``reverse``, last to first, and one
    ``pallas_call`` over it."""

    def __init__(self, x, a, tb: int, reverse: bool = False):
        self.bsz, self.t, self.ch = x.shape
        self.n, self.tb, self.lanes = a.shape[0], tb, channel_block(x.shape[2])
        self.width = _LANES  # of a time block laid on the lanes
        last = self.t // tb - 1
        self.at = (lambda i: last - i) if reverse else (lambda i: i)

    def tile(self):  # x, delta, y and their cotangents
        return pl.BlockSpec((1, self.tb, self.lanes), lambda b, j, i: (b, self.at(i), j))

    def rates(self):  # a
        return pl.BlockSpec((self.n, self.lanes), lambda b, j, i: (0, j))

    def skip(self):  # d (1, C)
        return pl.BlockSpec((1, self.lanes), lambda b, j, i: (0, j))

    def projections(self):  # b, c: (B, T / tb, N, tb in whole lane blocks)
        return pl.BlockSpec((1, 1, self.n, self.width), lambda b, j, i: (b, self.at(i), 0, 0))

    def boundary(self):  # (B, T / tb, N, C)
        return pl.BlockSpec((1, 1, self.n, self.lanes), lambda b, j, i: (b, self.at(i), 0, j))

    def summed(self, rows: int):  # (B, rows, C), resident over the time blocks
        return pl.BlockSpec((1, rows, self.lanes), lambda b, j, i: (b, 0, j))

    def shares(self):  # (B, C / lanes, T / tb, N, tb in whole lane blocks)
        return pl.BlockSpec((1, 1, 1, self.n, self.width), lambda b, j, i: (b, j, self.at(i), 0, 0))

    def call(self, kernel, name, interpret, operands, like, scratch):
        """``operands`` and ``like`` pair each array (or shape and dtype) with its spec."""
        arrays = [v for v, _ in operands]
        vma = _vma(*arrays)
        size = lambda shape, dtype: math.prod(shape) * jnp.dtype(dtype).itemsize
        blocks = sum(size(spec.block_shape, v.dtype) for v, spec in (*operands, *like))
        # every block double-buffered, the scratch, and a few (tb, lanes) fp32 values of the prologue and epilogue alive at once
        resident = 2 * blocks + sum(size(s.shape, s.dtype) for s in scratch) + 6 * size((self.tb, self.lanes), jnp.float32)
        return pl.pallas_call(
            kernel,
            grid=(self.bsz, self.ch // self.lanes, self.t // self.tb),
            in_specs=[spec for _, spec in operands],
            out_specs=[spec for _, spec in like],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma) for v, _ in like],
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),  # the state lives across the time blocks
                vmem_limit_bytes=min(max(resident, _VMEM_DEFAULT), _VMEM_MOST),
            ),
            interpret=interpret,
            name=name,
        )(*arrays)


def _inputs(at, x, delta, a, b, c, d):
    return [(x, at.tile()), (delta, at.tile()), (a, at.rates()), (b, at.projections()), (c, at.projections()), (d, at.skip())]


# jitted, as qk_rope's launchers are: one trace and one lowering of a kernel serve the forward pass and its recomputation
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def forward(tb: int, interpret: bool, sd, x, delta, a, b, c, d):
    """x, delta (B, T, C), T whole time blocks; a (N, C) fp32; b, c (B, T / tb,
    N, tb in whole lane blocks); d (1, C) fp32; ``sd`` the state's dtype -> y
    as x and the (B, T / tb, N, C) fp32 states the time blocks start from."""
    at = _Blocks(x, a, tb)
    f32, struct, vmem = jnp.float32, jax.ShapeDtypeStruct, pltpu.VMEM
    rows, slabs = vmem((tb, at.lanes), f32), vmem((tb, at.n, _LANES), f32)
    return at.call(
        functools.partial(_forward_kernel, sd), "selective_scan", interpret,
        _inputs(at, x, delta, a, b, c, d),
        [(struct(x.shape, x.dtype), at.tile()), (struct((at.bsz, at.t // tb, at.n, at.ch), f32), at.boundary())],
        [vmem((at.n, at.lanes), f32), slabs, slabs, rows, rows, rows],
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def backward(tb: int, interpret: bool, sd, x, delta, a, b, c, d, starts, dy):
    """The forward's operands, its boundary states and y's cotangent -> the
    cotangents of x (its dtype), delta (its dtype), a (B, N, C), b and c (B, C
    / lanes, T / tb, N, tb in whole lane blocks) and d (B, 1, C), the last four
    fp32 and still to be added over their leading axes."""
    at = _Blocks(x, a, tb, reverse=True)
    f32, struct, vmem = jnp.float32, jax.ShapeDtypeStruct, pltpu.VMEM
    rows, slabs = vmem((tb, at.lanes), f32), vmem((tb, at.n, _LANES), f32)
    share = struct((at.bsz, at.ch // at.lanes, at.t // tb, at.n, at.width), f32)
    return at.call(
        functools.partial(_backward_kernel, sd), "selective_scan_bwd", interpret,
        [*_inputs(at, x, delta, a, b, c, d), (starts, at.boundary()), (dy, at.tile())],
        [
            (struct(x.shape, x.dtype), at.tile()), (struct(delta.shape, delta.dtype), at.tile()),
            (struct((at.bsz, at.n, at.ch), f32), at.summed(at.n)), (share, at.shares()), (share, at.shares()),
            (struct((at.bsz, 1, at.ch), f32), at.summed(1)),
        ],
        [vmem((at.n, at.lanes), f32), vmem((tb + 1, at.n, at.lanes), f32), slabs, slabs, *[rows] * 5, slabs, slabs],
    )
