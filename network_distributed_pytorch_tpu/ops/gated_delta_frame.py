"""What frames the gated delta rule in its mixer, as two fused passes: before
the rule the causal depthwise conv, its silu and the l2 norms of q and k
(``in``), after it the gated RMSNorm (``out``); :func:`framed_rule` is both
round the rule. On TPU each pass is one Pallas kernel forward and one
backward; on every other backend, and for shapes the kernels' tiles do not
serve, the XLA lines the mixer had (``_in_xla``, ``_out_xla``) under jax's own
differentiation.

Why kernels: the work is elementwise and a layer's 469 MB of reads and
writes need 0.6 ms, but as XLA it was 20 ms a layer-step (PERF.md §5, PR 40:
``gdn.frame`` 39.2 + ``gdn.conv`` 20.6 ms a step), none of it arithmetic:

- ``qkvz`` leaves its projection in HuggingFace's grouped column order (per
  key head its q, its k, then the v and the z of its ``r`` value heads), and
  ``split`` / ``concatenate`` de-interleave it as copies and re-interleave
  the cotangent. Here a key head's lanes are **read in place**: a grid step
  takes the head's q, k and v lanes (or its z lanes) of ``qkvz`` through the
  ``BlockSpec``s' index maps and writes q, k and v flat, (B, T, H d), as
  ``ops/gated_delta_kernel.py`` reads them;
- the conv was a pad and K sublane-misaligned slices of a (T, 8192) array.
  Here a step holds a tile of T with one sublane tile of the tile before it
  (zeros before the sequence), and a shift is a sublane rotation in VMEM;
- the norms went through (B, T, H, d) views in fp32, each a relayout copy
  that converts. Here a head's d lanes are whole lane blocks of the tile.

Grid (key head, batch, tile of T). Arithmetic is fp32 inside the kernels from
the inputs as they are, one rounding at each output. Each backward recomputes
the forward's values from the pass's inputs (``out`` also keeps the rule's
``o``, its input); the cotangents of ``conv_kernel`` and ``norm_scale`` are
summed in fp32 in a block that stays resident over the batch and T axes (and
the heads, for ``norm_scale``). One ``jax.custom_vjp`` goes round both passes
and the rule, which jax differentiates itself inside it, so that ``qkvz``'s
cotangent is written once, a key head's whole group of lanes at a time
(:func:`_framed_kernels_bwd`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._backend import pallas_interpret
from .flash_attention import _VMEM_DEFAULT, _VMEM_MOST, _vma
from .ssd import causal_conv1d

_TILE = 512  # rows of T a grid step takes (PERF.md §6, PR 42: the microbenchmark that chose it)
_HALO = 16  # rows of the neighbouring tile a step reads: one sublane tile of a 16-bit dtype
_L2_EPS = 1e-6


def serves(t: int, r: int, dk: int, dv: int, taps: int) -> bool:
    """Whether Mosaic's tiles serve the shape: a head's lanes whole 128-lane
    blocks, key and value heads of one width and one or two value heads a key
    head (a group's v and z lanes are then whole blocks of their own width), a
    tile of T whole sublane tiles of a 16-bit dtype, and the conv's reach into
    the tile before no more than one such tile."""
    return dk == dv and dk % 128 == 0 and 2 % r == 0 and t >= _HALO and 1 <= taps <= _HALO + 1


def tile_of(t: int) -> int:
    """The rows of T a grid step takes: whole sublane tiles, ``_TILE`` at most."""
    return min(_TILE, t // _HALO * _HALO)


# --- the XLA lines: the fallback, and the tests' other side -----------------


def _in_xla(qkvz, conv_kernel, hk, r, dk, dv):
    bsz, t, _ = qkvz.shape
    key_dim, f32 = hk * dk, jnp.float32
    q, k, v, _ = jnp.split(qkvz.reshape(bsz, t, hk, -1), [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    flat = lambda x: x.reshape(bsz, t, -1)
    with jax.named_scope("gdn.conv"):
        qkv = jax.nn.silu(causal_conv1d(jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1), conv_kernel, None))
    q, k, v = jnp.split(qkv, [key_dim, 2 * key_dim], axis=-1)
    with jax.named_scope("gdn.frame"):
        l2norm = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + _L2_EPS)
        q = (l2norm(q.reshape(bsz, t, hk, dk).astype(f32)) * dk ** -0.5).astype(qkvz.dtype)
        k = l2norm(k.reshape(bsz, t, hk, dk).astype(f32)).astype(qkvz.dtype)
    return q, k, v.reshape(bsz, t, hk * r, dv)


def _out_xla(o, qkvz, norm_scale, eps, hk):
    bsz, t, hv, dv = o.shape
    f32 = jnp.float32
    *_, z = jnp.split(qkvz.reshape(bsz, t, hk, -1), [dv, 2 * dv, 2 * dv + hv // hk * dv], axis=-1)
    with jax.named_scope("gdn.frame"):  # Qwen3NextRMSNormGated: the norm first, then the gate
        o = o.astype(f32)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps) * norm_scale
        o = (o * jax.nn.silu(z.reshape(bsz, t, hv, dv).astype(f32))).astype(qkvz.dtype)
    return o.reshape(bsz, t, hv * dv)


# --- the kernels -------------------------------------------------------------


def _rows(first, n):
    """(n, 1) positions in the sequence of ``n`` rows from ``first``."""
    return first + lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def _back(x, s):
    """Row ``u`` of the result is row ``u - s`` of ``x`` (rows, d): a sublane
    rotation; the ``s`` rows that wrap are the caller's to leave out."""
    return pltpu.roll(x, s % x.shape[0], 0) if s % x.shape[0] else x


def _head(j, d, q_ref, k_ref, v_ref):
    """Where head ``j`` of a key head lies in its three blocks, (ref, lanes):
    its q, its k, then its ``r`` value heads side by side."""
    return (q_ref, slice(0, d)) if j == 0 else (k_ref, slice(0, d)) if j == 1 else (v_ref, slice((j - 2) * d, (j - 1) * d))


def _read(j, d, q_ref, k_ref, v_ref):
    ref, lanes = _head(j, d, q_ref, k_ref, v_ref)
    return ref[0, :, lanes]


def _conv(shifted, w_ref, lanes):
    """``y_t = sum_j kernel[j] x_(t - K + 1 + j)`` in ``causal_conv1d``'s
    order, from ``shifted[s]``, whose row ``t`` is ``x_(t - s)``."""
    taps, c = w_ref.shape[0], None
    for tap in range(taps):
        term = shifted[taps - 1 - tap] * w_ref[tap:tap + 1, lanes]
        c = term if c is None else c + term
    return c


def _in_kernel(r, d, xq_ref, xk_ref, xv_ref, bq_ref, bk_ref, bv_ref, w_ref, q_ref, k_ref, v_ref):
    """One key head over one tile: x, its q, k (1, tile, d) and v (1, tile,
    r d) lanes of ``qkvz``; b, the HALO rows ahead of the tile; w (K,
    (2 + r) d) fp32, the head's taps; q, k and v as x."""
    f32 = jnp.float32
    started = pl.program_id(2) > 0  # zeros before the sequence
    for j in range(2 + r):
        before = jnp.where(started, _read(j, d, bq_ref, bk_ref, bv_ref).astype(f32), 0.0)
        rows = jnp.concatenate([before, _read(j, d, xq_ref, xk_ref, xv_ref).astype(f32)], axis=0)
        c = _conv([_back(rows, s)[_HALO:] for s in range(w_ref.shape[0])], w_ref, slice(j * d, (j + 1) * d))
        s = c * jax.nn.sigmoid(c)
        if j < 2:
            s = s * lax.rsqrt(jnp.sum(s * s, axis=1, keepdims=True) + _L2_EPS)
        if j == 0:
            s = s * d ** -0.5
        ref, lanes = _head(j, d, q_ref, k_ref, v_ref)
        ref[0, :, lanes] = s.astype(ref.dtype)


def _in_bwd_kernel(
    r, d, t, xq_ref, xk_ref, xv_ref, bq_ref, bk_ref, bv_ref, aq_ref, ak_ref, av_ref, w_ref,
    dq_ref, dk_ref, dv_ref, daq_ref, dak_ref, dav_ref, dz_ref, dx_ref, dw_ref,
):
    """The forward's blocks; a, the HALO rows behind the tile (a tap reaches
    forward in the cotangent); the outputs' cotangents d, and da behind the
    tile; dz (1, tile, r d), what the ``out`` pass found for the z lanes; dx
    (1, tile, (2 + 2 r) d), ``qkvz``'s cotangent, the key head's whole group
    of lanes; dw (K, (2 + r) d) fp32, resident over batch and T."""
    tile, taps, f32 = xq_ref.shape[1], w_ref.shape[0], jnp.float32
    at = _rows(pl.program_id(2) * tile - _HALO, tile + 2 * _HALO)
    inside = (at >= 0) & (at < t)  # a tile past the end of T holds anything; so does a neighbour that is not there

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, f32)

    for j in range(2 + r):
        lanes = slice(j * d, (j + 1) * d)
        rows = [_read(j, d, *refs) for refs in ((bq_ref, bk_ref, bv_ref), (xq_ref, xk_ref, xv_ref), (aq_ref, ak_ref, av_ref))]
        rows = jnp.where(inside, jnp.concatenate(rows, axis=0).astype(f32), 0.0)
        # the forward again over the tile and the HALO rows behind it, whose conv outputs this tile's x reaches
        shifted = [_back(rows, s)[_HALO:] for s in range(taps)]
        c = _conv(shifted, w_ref, lanes)
        gate = jax.nn.sigmoid(c)
        s = c * gate
        ds = jnp.concatenate([_read(j, d, dq_ref, dk_ref, dv_ref), _read(j, d, daq_ref, dak_ref, dav_ref)], axis=0).astype(f32)
        if j == 0:
            ds = ds * d ** -0.5
        if j < 2:  # y = s / |s|: ds = (dy - y (y . dy)) / |s|
            inv = lax.rsqrt(jnp.sum(s * s, axis=1, keepdims=True) + _L2_EPS)
            ds = inv * (ds - s * (inv * inv * jnp.sum(ds * s, axis=1, keepdims=True)))
        dc = jnp.where(inside[_HALO:], ds * (gate * (1.0 + c * (1.0 - gate))), 0.0)
        # x_t feeds c_(t + K - 1 - j) through kernel[j]: the same sum over taps with the rows shifted the other way
        dx_ref[0, :, lanes] = _conv([_back(dc, -shift)[:tile] for shift in range(taps)], w_ref, lanes).astype(dx_ref.dtype)
        dw = [jnp.sum(dc[:tile] * shifted[taps - 1 - tap][:tile], axis=0, keepdims=True) for tap in range(taps)]
        dw_ref[:, lanes] += jnp.concatenate(dw, axis=0)
    dx_ref[0, :, (2 + r) * d:] = dz_ref[0]


def _normed_and_gated(eps, o, z):
    """``o / rms(o)`` with ``1 / rms(o)``, and ``silu(z)`` with ``sigmoid(z)``."""
    inv = lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
    gate = jax.nn.sigmoid(z)
    return o * inv, inv, z * gate, gate


def _out_kernel(r, d, eps, o_ref, z_ref, scale_ref, y_ref):
    """One key head's value heads over one tile: o and y (1, tile, r d), z
    (1, tile, r d) its z lanes of ``qkvz``, scale (1, d) fp32."""
    f32 = jnp.float32
    for j in range(r):
        lanes = slice(j * d, (j + 1) * d)
        normed, _, opened, _ = _normed_and_gated(eps, o_ref[0, :, lanes].astype(f32), z_ref[0, :, lanes].astype(f32))
        y_ref[0, :, lanes] = (normed * scale_ref[...] * opened).astype(y_ref.dtype)


def _out_bwd_kernel(r, d, eps, t, o_ref, z_ref, scale_ref, dy_ref, do_ref, dz_ref, dscale_ref):
    """The forward's blocks and y's cotangent; do and dz as o and z; dscale
    (1, d) fp32, resident over the whole grid."""
    tile, f32 = o_ref.shape[1], jnp.float32
    inside = _rows(pl.program_id(2) * tile, tile) < t

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dscale_ref[...] = jnp.zeros(dscale_ref.shape, f32)

    scale = scale_ref[...]
    for j in range(r):
        lanes = slice(j * d, (j + 1) * d)
        z = z_ref[0, :, lanes].astype(f32)
        normed, inv, opened, gate = _normed_and_gated(eps, o_ref[0, :, lanes].astype(f32), z)
        dy = dy_ref[0, :, lanes].astype(f32)
        d_scaled = dy * opened  # the cotangent of normed * scale
        dz = dy * normed * scale * (gate * (1.0 + z * (1.0 - gate)))
        dscale_ref[...] += jnp.sum(jnp.where(inside, d_scaled * normed, 0.0), axis=0, keepdims=True)
        d_normed = d_scaled * scale
        do = inv * (d_normed - normed * jnp.mean(d_normed * normed, axis=1, keepdims=True))
        do_ref[0, :, lanes] = do.astype(do_ref.dtype)
        dz_ref[0, :, lanes] = dz.astype(dz_ref.dtype)


# --- the calls ---------------------------------------------------------------


def _grouped(w, hk, r, d):
    """(K, [q | k | v]) in the conv's flat order as (K, H_k (2 + r) d): a key
    head's taps side by side, as its lanes lie in ``qkvz``."""
    taps = w.shape[0]
    q, k, v = jnp.split(w, [hk * d, 2 * hk * d], axis=1)
    by_head = [q.reshape(taps, hk, d), k.reshape(taps, hk, d), v.reshape(taps, hk, r * d)]
    return jnp.concatenate(by_head, axis=2).reshape(taps, -1)


def _flat(w, hk, r, d):
    """:func:`_grouped`'s inverse."""
    taps = w.shape[0]
    q, k, v = jnp.split(w.reshape(taps, hk, (2 + r) * d), [d, 2 * d], axis=2)
    return jnp.concatenate([x.reshape(taps, -1) for x in (q, k, v)], axis=1)


class _Blocks:
    """The ``BlockSpec``s of a grid (key head, batch, tile of T) over
    ``qkvz`` (B, T, H_k (2 + 2 r) d), and one ``pallas_call`` over it."""

    def __init__(self, qkvz, hk, r, tile):
        self.bsz, self.t, lanes = qkvz.shape
        self.hk, self.r, self.tile, self.d = hk, r, tile, lanes // (hk * (2 + 2 * r))
        per_tile, last = tile // _HALO, pl.cdiv(self.t, _HALO) - 1
        self.here = lambda i: i
        self.before = lambda i: jnp.maximum(i * per_tile - 1, 0)  # the first tile's is not read
        self.after = lambda i: jnp.minimum((i + 1) * per_tile, last)  # past the end it is masked

    def lanes_of_group(self, first, heads, rows=None, at=None):
        """``heads`` heads' lanes of ``qkvz`` from head ``first`` of a key
        head's group ``[q | k | v_1.. | z_1..]``, as blocks that wide."""
        at = at or self.here
        return pl.BlockSpec(
            (1, rows or self.tile, heads * self.d),
            lambda h, b, i: (b, at(i), (h * (2 + 2 * self.r) + first) // heads),
        )

    def qkv_of_group(self, qkvz, rows=None, at=None):
        """A key head's q, k and v lanes of ``qkvz``, an operand each."""
        return [(qkvz, self.lanes_of_group(first, heads, rows, at)) for first, heads in ((0, 1), (1, 1), (2, self.r))]

    def heads(self, width, rows=None, at=None):
        """A key head's ``width`` lanes of a flat (B, T, H_k width)."""
        at = at or self.here
        return pl.BlockSpec((1, rows or self.tile, width), lambda h, b, i: (b, at(i), h))

    def qkv(self, q, k, v, rows=None, at=None):
        """q, k (B, T, H_k d) and v (B, T, H_v d) flat, a key head's lanes of each."""
        return [(q, self.heads(self.d, rows, at)), (k, self.heads(self.d, rows, at)), (v, self.heads(self.r * self.d, rows, at))]

    def taps(self, rows):
        """A key head's taps of the grouped (K, H_k (2 + r) d)."""
        return pl.BlockSpec((rows, (2 + self.r) * self.d), lambda h, b, i: (0, h))

    def whole(self, shape):
        return pl.BlockSpec(shape, lambda h, b, i: (0,) * len(shape))

    def call(self, kernel, name, interpret, operands, like):
        """``operands`` and ``like`` pair each array (or shape and dtype) with its spec."""
        arrays = [x for x, _ in operands]
        vma = _vma(*arrays)
        blocks = sum(math.prod(spec.block_shape) * jnp.dtype(x.dtype).itemsize for x, spec in (*operands, *like))
        # every block double-buffered, and a head's fp32 rows live a dozen or two at a time
        resident = 2 * blocks + 24 * (self.tile + 2 * _HALO) * self.d * 4
        return pl.pallas_call(
            kernel,
            grid=(self.hk, self.bsz, pl.cdiv(self.t, self.tile)),
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


# jitted, as gated_delta_kernel's launchers are: one trace and one lowering of a kernel serve the layers' calls
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _in_forward(hk, r, tile, interpret, qkvz, conv_kernel):
    at = _Blocks(qkvz, hk, r, tile)
    w = _grouped(conv_kernel.astype(jnp.float32), hk, r, at.d)
    key = jax.ShapeDtypeStruct((at.bsz, at.t, hk * at.d), qkvz.dtype)
    value = jax.ShapeDtypeStruct((at.bsz, at.t, hk * r * at.d), qkvz.dtype)
    return at.call(
        functools.partial(_in_kernel, r, at.d), "gdn_frame_in", interpret,
        [*at.qkv_of_group(qkvz), *at.qkv_of_group(qkvz, _HALO, at.before), (w, at.taps(w.shape[0]))],
        at.qkv(key, key, value),
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _in_backward(hk, r, tile, interpret, qkvz, conv_kernel, dq, dk, dv, dz):
    at = _Blocks(qkvz, hk, r, tile)
    w = _grouped(conv_kernel.astype(jnp.float32), hk, r, at.d)
    dx, dw = at.call(
        functools.partial(_in_bwd_kernel, r, at.d, at.t), "gdn_frame_in_bwd", interpret,
        [
            *at.qkv_of_group(qkvz), *at.qkv_of_group(qkvz, _HALO, at.before), *at.qkv_of_group(qkvz, _HALO, at.after),
            (w, at.taps(w.shape[0])),
            *at.qkv(dq, dk, dv), *at.qkv(dq, dk, dv, _HALO, at.after), (dz, at.heads(r * at.d)),
        ],
        [(qkvz, at.heads((2 + 2 * r) * at.d)), (jax.ShapeDtypeStruct(w.shape, jnp.float32), at.taps(w.shape[0]))],
    )
    return dx, _flat(dw, hk, r, at.d).astype(conv_kernel.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _out_forward(hk, r, tile, interpret, eps, o, qkvz, norm_scale):
    at = _Blocks(qkvz, hk, r, tile)
    scale = norm_scale.astype(jnp.float32).reshape(1, at.d)
    value = at.heads(r * at.d)
    (y,) = at.call(
        functools.partial(_out_kernel, r, at.d, eps), "gdn_frame_out", interpret,
        [(o, value), (qkvz, at.lanes_of_group(2 + r, r)), (scale, at.whole(scale.shape))],
        [(jax.ShapeDtypeStruct(o.shape, qkvz.dtype), value)],
    )
    return y


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _out_backward(hk, r, tile, interpret, eps, o, qkvz, norm_scale, dy):
    at = _Blocks(qkvz, hk, r, tile)
    scale = norm_scale.astype(jnp.float32).reshape(1, at.d)
    value = at.heads(r * at.d)
    do, dz, dscale = at.call(
        functools.partial(_out_bwd_kernel, r, at.d, eps, at.t), "gdn_frame_out_bwd", interpret,
        [(o, value), (qkvz, at.lanes_of_group(2 + r, r)), (scale, at.whole(scale.shape)), (dy, value)],
        [(o, value), (o, value), (jax.ShapeDtypeStruct(scale.shape, jnp.float32), at.whole(scale.shape))],
    )
    return do, dz, dscale.reshape(norm_scale.shape).astype(norm_scale.dtype)


def _passes(rule, hk, r, tile, interpret, eps, qkvz, conv_kernel, norm_scale, g, beta):
    """``in``, the rule, ``out``: the value, and what the backward keeps."""
    bsz, t, _ = qkvz.shape
    d = norm_scale.shape[0]
    with jax.named_scope("gdn.frame"):
        q, k, v = _in_forward(hk, r, tile, interpret, qkvz, conv_kernel)
    with jax.named_scope("gdn.rule"):
        o, rule_back = jax.vjp(rule, q.reshape(bsz, t, hk, d), k.reshape(bsz, t, hk, d), v.reshape(bsz, t, hk * r, d), g, beta)
    o = o.reshape(bsz, t, hk * r * d)
    with jax.named_scope("gdn.frame"):
        y = _out_forward(hk, r, tile, interpret, eps, o, qkvz, norm_scale)
    return y, (qkvz, conv_kernel, norm_scale, o, rule_back)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _framed_kernels(rule, hk, r, tile, interpret, eps, qkvz, conv_kernel, norm_scale, g, beta):
    return _passes(rule, hk, r, tile, interpret, eps, qkvz, conv_kernel, norm_scale, g, beta)[0]


def _framed_kernels_bwd(rule, hk, r, tile, interpret, eps, residuals, dy):
    """One custom VJP round both passes and the rule between them, jax's own
    through the rule, so that ``qkvz``'s cotangent is written once: the
    ``out`` backward hands its z lanes to the ``in`` backward, which writes
    every lane. As two custom VJPs each wrote (B, T, 12288) with zeros for the
    other's lanes and jax added the two inside ``in_proj_qkvz``'s backward
    products (PERF.md §6, PR 42: 4.1 ms a step)."""
    qkvz, conv_kernel, norm_scale, o, rule_back = residuals
    with jax.named_scope("gdn.frame"):
        do, dz, d_norm_scale = _out_backward(hk, r, tile, interpret, eps, o, qkvz, norm_scale, dy)
    with jax.named_scope("gdn.rule"):
        dq, dk, dv, dg, dbeta = rule_back(do.reshape(do.shape[:2] + (hk * r, -1)))
    flat = lambda x: x.reshape(x.shape[:2] + (-1,))
    with jax.named_scope("gdn.frame"):
        d_qkvz, d_conv_kernel = _in_backward(hk, r, tile, interpret, qkvz, conv_kernel, flat(dq), flat(dk), flat(dv), dz)
    return d_qkvz, d_conv_kernel, d_norm_scale, dg, dbeta


_framed_kernels.defvjp(_passes, _framed_kernels_bwd)


def framed_rule(
    rule, qkvz, conv_kernel, norm_scale, g, beta, eps: float, hk: int, r: int, dk: int, dv: int,
    interpret: Optional[bool] = None,
):
    """``out(rule(*in(qkvz), g, beta), qkvz)``, (B, T, H_v d_v) in ``qkvz``'s
    dtype, for ``rule(q, k, v, g, beta) -> o`` (``ops.gated_delta.
    gated_delta_rule`` with its chunk), its ops under the scope ``gdn.rule``:

    - ``in``: from ``qkvz`` (B, T, H_k (2 d_k + 2 r d_v)) in the grouped
      column order and ``conv_kernel`` (K, [q | k | v]) in the flat one, q =
      l2norm(silu(conv q)) d_k^-1/2 and k = l2norm(silu(conv k)), (B, T, H_k,
      d_k), and v = silu(conv v), (B, T, H_v, d_v);
    - ``out``: ``rmsnorm(o) norm_scale silu(z)`` per head, the norm BEFORE the
      gate (``Qwen3NextRMSNormGated``), from the rule's ``o`` (B, T, H_v,
      d_v), the z lanes of ``qkvz`` and ``norm_scale`` (d_v,).

    ``interpret=None`` lets the backend decide (``ops._backend.pallas_interpret``):
    on TPU the kernels where their tiles serve the shape, elsewhere and
    otherwise the XLA lines; ``True`` runs the kernels in the Pallas
    interpreter, ``False`` the kernels whatever traces them."""
    t, taps = qkvz.shape[1], conv_kernel.shape[0]
    if interpret is None and (pallas_interpret() or not serves(t, r, dk, dv, taps)):
        q, k, v = _in_xla(qkvz, conv_kernel, hk, r, dk, dv)
        with jax.named_scope("gdn.rule"):
            o = rule(q, k, v, g, beta)
        return _out_xla(o, qkvz, norm_scale, eps, hk)
    return _framed_kernels(rule, hk, r, tile_of(t), bool(interpret), float(eps), qkvz, conv_kernel, norm_scale, g, beta)
