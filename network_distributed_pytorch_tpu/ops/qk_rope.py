"""The per-head RMSNorm of q and k and their rotary turn as one pass: on TPU
one Pallas kernel forward and one backward under one ``jax.custom_vjp``, q and
k in one call. The XLA lines it stands in for are the models' own
(``models/layers.normed_and_turned``: ``RMSNorm``, ``rotary``, the cast), which
run off the TPU and for shapes :func:`serves` declines.

Why a kernel: the work is elementwise and a trinity layer's 151 MB of reads
and writes need 0.18 ms, but as XLA it was 0.8 ms forward, 1.1 recomputed and
1.7 backward a layer (PERF.md §5, PR 40: ``attn.rope`` 4.16 + 5.59 + 8.27 ms
a step), none of it arithmetic the chip is short of:

- the norm went through (B, T, H, D) views in fp32 of a bf16 (B, T, H D)
  array, and ``split`` / ``concatenate`` of a head's halves were copies. Here
  q and k are read **flat, as the projections emit them**, and written flat,
  as ``ops/flash_attention.py`` reads them; a head's D lanes are whole lane
  blocks (D 128, 256) or two heads one lane block (D 64), and a half meets
  its other half by a lane rotation in VMEM;
- cos and sin were evaluated inside the fusions. Here they come as two fp32
  tables (T, rotary_dim / 2), built once a call by the caller from the
  embedding's own frequencies (``models/layers.rope_tables``), and the kernel
  reads a T tile of them laid over a lane block (:func:`_lane_tables`).

Grid (batch, tile of T, group of heads): a step takes the key heads of one
lane block and the query heads that read them. Arithmetic is fp32 inside the
kernels from the inputs as they are, one rounding at each output. The
backward recomputes the normed rows from the pass's own inputs, so the
residuals are the inputs (q and k as projected, the two scales, the tables)
and nothing of size (B, T, H, D) in fp32 is kept; the scales' cotangents are
summed in fp32 in a block that stays resident over the whole grid.

The turn, for a head whose first ``rotary_dim`` = 2h lanes turn (pair i with
i + h, HuggingFace's ``rotate_half``): ``y = n C + roll(n, +h) S_up +
roll(n, -h) S_down`` with ``C`` = cos over the turning lanes and 1 over the
others, ``S_up`` = sin on each head's upper half and ``S_down`` = -sin on its
lower half, 0 elsewhere; where 2h is the lane block the two rotations are one
and ``S = S_up + S_down``. Its transpose, for the cotangent: ``dn = dy C +
roll(dy S_up, -h) + roll(dy S_down, +h)``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _VMEM_DEFAULT, _VMEM_MOST, _vma

_TILE = 512  # rows of T a grid step takes (PERF.md §6, PR 46: the microbenchmark that chose it)
_LANES = 128  # a lane block
_SUBLANES = 16  # rows of one sublane tile of a 16-bit dtype


def tile_of(t: int) -> int:
    """The rows of T a grid step takes: whole sublane tiles, ``_TILE`` at most."""
    return min(_TILE, t // _SUBLANES * _SUBLANES)


def serves(t: int, hq: int, hk: int, d: int, rotary_dim: int) -> bool:
    """Whether Mosaic's tiles serve the shape: a head whole lane blocks or two
    heads one lane block, the key heads whole lane blocks and the query heads
    whole groups over them, T whole tiles, and the turning lanes
    (``rotary_dim``, 0 where the layer carries no positions) whole pairs
    inside the head's first lane block."""
    heads = d % _LANES == 0 or (2 * d == _LANES and hk % 2 == 0)
    turn = rotary_dim % 2 == 0 and 0 <= rotary_dim <= min(d, _LANES)
    return heads and turn and hq % hk == 0 and t >= _SUBLANES and t % tile_of(t) == 0


def _lane_tables(cos, sin, d: int):
    """(T, h) cos and sin as the kernel's tables, (T, 128) each, a head's
    pattern side by side over the lane block: ``(C, S)`` where the halves are
    half a lane block apart, else ``(C, S_up, S_down)``."""
    t, h = cos.shape
    width = min(d, _LANES)  # a head's lanes inside the block
    still, zeros = jnp.ones((t, width - 2 * h), cos.dtype), jnp.zeros((t, width - h), cos.dtype)
    over = lambda *parts: jnp.tile(jnp.concatenate(parts, axis=1), (1, _LANES // width))
    c = over(cos, cos, still)
    if 2 * h == _LANES:
        return c, over(-sin, sin)
    return c, over(zeros[:, :h], sin, zeros[:, : width - 2 * h]), over(-sin, zeros)


def _turn(n, h: int, tables, back: bool = False):
    """(rows, unit) fp32 with its first lane block turned by the tables (the
    cotangent's way with ``back``); the other lanes, and everything where
    there are no tables, pass."""
    if not tables:
        return n
    first = n[:, :_LANES]
    c, *s = tables
    far = _LANES - h  # a rotation by -h
    if len(s) == 1:
        turned = pltpu.roll(first * s[0], h, 1) if back else pltpu.roll(first, h, 1) * s[0]
    elif back:
        turned = pltpu.roll(first * s[0], far, 1) + pltpu.roll(first * s[1], h, 1)
    else:
        turned = pltpu.roll(first, h, 1) * s[0] + pltpu.roll(first, far, 1) * s[1]
    turned = first * c + turned
    return turned if n.shape[1] == _LANES else jnp.concatenate([turned, n[:, _LANES:]], axis=1)


def _head_mean(v, d: int):
    """The mean over each head's ``d`` lanes of (rows, unit), as (rows, 1)
    where the unit is one head and (rows, unit) where it is two."""
    if v.shape[1] == d:
        return jnp.mean(v, axis=1, keepdims=True)
    low = lax.broadcasted_iota(jnp.int32, v.shape, 1) < d
    first = jnp.sum(jnp.where(low, v, 0.0), axis=1, keepdims=True)
    second = jnp.sum(jnp.where(low, 0.0, v), axis=1, keepdims=True)
    return jnp.where(low, first, second) * (1.0 / d)


def _units(ref, d: int):
    """The lane slices of a block a unit at a time: one head, or two of 64."""
    unit = max(d, _LANES)
    return [slice(u * unit, (u + 1) * unit) for u in range(ref.shape[2] // unit)]


def _kernel(d, h, n_tables, eps, xq_ref, xk_ref, sq_ref, sk_ref, *refs):
    """One group of heads over one tile: x, q's (1, tile, group q heads' lanes)
    and k's (1, tile, a lane block or a head); s (1, unit) fp32, the learned
    scale over a unit's lanes; the tables (tile, 128) fp32; y as x."""
    f32 = jnp.float32
    tables = [ref[...] for ref in refs[:n_tables]]
    yq_ref, yk_ref = refs[n_tables:]
    for x_ref, s_ref, y_ref in ((xq_ref, sq_ref, yq_ref), (xk_ref, sk_ref, yk_ref)):
        for lanes in _units(x_ref, d):
            x = x_ref[0, :, lanes].astype(f32)
            normed = x * lax.rsqrt(_head_mean(x * x, d) + eps) * s_ref[...]
            y_ref[0, :, lanes] = _turn(normed, h, tables).astype(y_ref.dtype)


def _bwd_kernel(d, h, n_tables, eps, xq_ref, xk_ref, sq_ref, sk_ref, *refs):
    """The forward's blocks, then y's cotangents as x; dx as x; ds (1, unit)
    fp32 each, resident over the whole grid."""
    f32 = jnp.float32
    tables = [ref[...] for ref in refs[:n_tables]]
    dyq_ref, dyk_ref, dxq_ref, dxk_ref, dsq_ref, dsk_ref = refs[n_tables:]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dsq_ref[...] = jnp.zeros(dsq_ref.shape, f32)
        dsk_ref[...] = jnp.zeros(dsk_ref.shape, f32)

    sides = ((xq_ref, sq_ref, dyq_ref, dxq_ref, dsq_ref), (xk_ref, sk_ref, dyk_ref, dxk_ref, dsk_ref))
    for x_ref, s_ref, dy_ref, dx_ref, ds_ref in sides:
        for lanes in _units(x_ref, d):
            x = x_ref[0, :, lanes].astype(f32)
            inv = lax.rsqrt(_head_mean(x * x, d) + eps)
            normed = x * inv
            d_scaled = _turn(dy_ref[0, :, lanes].astype(f32), h, tables, back=True)  # the cotangent of normed * s
            ds_ref[...] += jnp.sum(d_scaled * normed, axis=0, keepdims=True)
            d_normed = d_scaled * s_ref[...]
            dx = inv * (d_normed - normed * _head_mean(d_normed * normed, d))
            dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)


# --- the calls ---------------------------------------------------------------


class _Blocks:
    """The ``BlockSpec``s of a grid (batch, tile of T, group of heads) over q
    (B, T, H_q d) and k (B, T, H_k d), and one ``pallas_call`` over it."""

    def __init__(self, q, k, d, tile):
        self.bsz, self.t, _ = q.shape
        self.d, self.tile, self.unit = d, tile, max(d, _LANES)
        self.groups = k.shape[2] // self.unit
        self.q_lanes = q.shape[2] // self.groups

    def heads(self, lanes):
        return pl.BlockSpec((1, self.tile, lanes), lambda b, i, g: (b, i, g))

    def qk(self, q, k):
        return [(q, self.heads(self.q_lanes)), (k, self.heads(self.unit))]

    def table(self):
        return pl.BlockSpec((self.tile, _LANES), lambda b, i, g: (i, 0))

    def whole(self):
        return pl.BlockSpec((1, self.unit), lambda b, i, g: (0, 0))

    def scale(self, s):
        """(d,) as (1, unit) fp32: over both heads of a lane block where two share one."""
        return jnp.tile(s.astype(jnp.float32), self.unit // self.d).reshape(1, self.unit), self.whole()

    def summed(self, ds, like):
        """:func:`scale`'s transpose, in the parameter's dtype."""
        return jnp.sum(ds.reshape(-1, self.d), axis=0).astype(like.dtype)

    def call(self, kernel, name, interpret, operands, like):
        """``operands`` and ``like`` pair each array (or shape and dtype) with its spec."""
        arrays = [x for x, _ in operands]
        vma = _vma(*arrays)
        blocks = sum(math.prod(spec.block_shape) * jnp.dtype(x.dtype).itemsize for x, spec in (*operands, *like))
        # every block double-buffered, and a unit's fp32 rows live a dozen at a time
        resident = 2 * blocks + 12 * self.tile * self.unit * 4
        return pl.pallas_call(
            kernel,
            grid=(self.bsz, self.t // self.tile, self.groups),
            in_specs=[spec for _, spec in operands],
            out_specs=[spec for _, spec in like],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma) for x, _ in like],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,  # the summed cotangents stay resident over them
                vmem_limit_bytes=min(max(resident, _VMEM_DEFAULT), _VMEM_MOST),
            ),
            interpret=interpret,
            name=name,
        )(*arrays)


def _tables_of(at, cos, sin):
    """``h`` and the kernel's tables as operands; 0 and none where the layer carries no positions."""
    if cos is None:
        return 0, []
    return cos.shape[1], [(table, at.table()) for table in _lane_tables(cos, sin, at.d)]


# jitted, as gated_delta_frame's launchers are: one trace and one lowering of a kernel serve the layers' calls
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _forward(d, tile, interpret, eps, dtype, q, k, q_scale, k_scale, cos, sin):
    at = _Blocks(q, k, d, tile)
    h, tables = _tables_of(at, cos, sin)
    out = lambda x: jax.ShapeDtypeStruct(x.shape, dtype)
    return at.call(
        functools.partial(_kernel, d, h, len(tables), eps), "qk_rope", interpret,
        [*at.qk(q, k), at.scale(q_scale), at.scale(k_scale), *tables],
        at.qk(out(q), out(k)),
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _backward(d, tile, interpret, eps, q, k, q_scale, k_scale, cos, sin, dyq, dyk):
    at = _Blocks(q, k, d, tile)
    h, tables = _tables_of(at, cos, sin)
    summed = (jax.ShapeDtypeStruct((1, at.unit), jnp.float32), at.whole())
    dq, dk, dsq, dsk = at.call(
        functools.partial(_bwd_kernel, d, h, len(tables), eps), "qk_rope_bwd", interpret,
        [*at.qk(q, k), at.scale(q_scale), at.scale(k_scale), *tables, *at.qk(dyq, dyk)],
        [*at.qk(q, k), summed, summed],
    )
    return dq, dk, at.summed(dsq, q_scale), at.summed(dsk, k_scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _normed_and_turned(d, tile, interpret, eps, dtype, q, k, q_scale, k_scale, cos, sin):
    return tuple(_forward(d, tile, interpret, eps, dtype, q, k, q_scale, k_scale, cos, sin))


def _fwd(d, tile, interpret, eps, dtype, *operands):
    return _normed_and_turned(d, tile, interpret, eps, dtype, *operands), operands


def _bwd(d, tile, interpret, eps, dtype, operands, cotangents):
    return (*_backward(d, tile, interpret, eps, *operands, *cotangents), None, None)  # the tables carry no gradient


_normed_and_turned.defvjp(_fwd, _bwd)


def normed_and_turned(
    q, k, q_scale, k_scale, cos: Optional[jax.Array], sin: Optional[jax.Array], eps: float, dtype, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """q (B, T, H_q, D) and k (B, T, H_k, D), each head ``x rsqrt(mean(x^2) +
    eps) scale`` (``scale`` (D,), what multiplies: ``1 + w`` where the norm is
    zero-centred), then its first ``2 h`` lanes turned at position t by
    ``cos[t]`` and ``sin[t]`` ((T, h) fp32, the embedding's factor inside;
    ``None``: the layer carries no positions), in ``dtype`` and in the
    inputs' shapes. For shapes :func:`serves` takes; ``interpret`` runs the
    kernels in the Pallas interpreter."""
    flat = lambda x: x.reshape(x.shape[:2] + (-1,))
    yq, yk = _normed_and_turned(
        q.shape[-1], tile_of(q.shape[1]), bool(interpret), float(eps), jnp.dtype(dtype), flat(q), flat(k), q_scale, k_scale, cos, sin
    )
    return yq.reshape(q.shape), yk.reshape(k.shape)
