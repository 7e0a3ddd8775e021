"""Mamba-1's selective scan (S6, Gu & Dao 2023): the recurrence whose decay is
its own for every channel and state index, so it has no matmul form.

Per sequence, with state ``S`` of shape (C, N), ``S_{-1} = 0``::

    S_t[c, n] = exp(delta_t[c] * a[c, n]) * S_{t-1}[c, n] + delta_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n S_t[c, n] * C_t[n] + d_skip[c] * x_t[c]

Mamba-2's scan (``ops/ssd.py``) has ONE decay a head for a whole (P, N)
state, which is what makes a chunk a masked (chunk, chunk) matmul; here a
chunk would need C * N = 81,920 decay matrices, so the state is walked.

On TPU, at shapes ``serves`` takes, ``selective_scan`` is two Pallas kernels
under one ``jax.custom_vjp`` (``ops/selective_scan_kernel.py``): the steps
walked inside a kernel with the (N, block of channels) fp32 state in VMEM
across sequential time blocks, forward and, from the (T / block, N, C) states
the time blocks start from, backward; nothing of size (t, c, n) leaves VMEM.
On the chip at (1, 8192, 5120, 16) that is PERF.md section 6, PR 50's
microbenchmark against the walk's 8 ms forward and 31 with every cotangent.

Off the TPU, and for shapes the kernels' tiles do not take, it is the plain
walk: a ``lax.scan`` over chunks of ``CHUNK`` steps that carries the (B, N, C)
state in fp32, channels on the lanes; the chunk's body is under
``jax.checkpoint``, so the backward pass holds one chunk's (chunk, N, C)
states and the forward none; inside a chunk the steps are a second
``lax.scan``, one step of the recurrence as written an iteration, ``UNROLL``
iterations a loop trip (``lax.associative_scan`` over the pairs ``(exp(delta
a), delta x B)`` of a chunk took 19 and 75 ms where this takes 8 and 31:
PERF.md section 6, PR 48). Plain ``jax``: it differentiates as such, it is
what the CPU tests ran before the kernels, and the oracle's twin.

Either way no (T, C, N) array is ever alive (2.7 GB in fp32 at T = 8192, C =
5120); ``delta``, ``a``, every ``exp``, the state and the sum over n are fp32
whatever ``x``'s dtype (a decay rounded to bf16 compounds over a sequence);
``y`` leaves in ``x``'s dtype. ``benchmark/reference/phi4flash.py`` writes the
same recurrence on its own, over (C, N) in fp32 at full precision.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import selective_scan_kernel as kernel
from ._backend import pallas_interpret
from .grouped_matmul import _as_all  # inside shard_map a custom_vjp's operands vary as its cotangents will
from .selective_scan_kernel import serves

CHUNK = 32  # steps a chunk of the plain walk: what its backward holds alive is one chunk's (CHUNK, B, N, C) fp32 states (10 MB at 5120 x 16)
UNROLL = 8  # steps a trip of the plain walk's inner loop
STATE_DTYPE = jnp.float32  # of delta, the decay, the state and y's sum: tests and the benchmark's control lower it


def selective_scan(
    x: jax.Array,       # (B, T, C) the conv's output
    delta: jax.Array,   # (B, T, C) step sizes, already softplus'd (> 0)
    a: jax.Array,       # (C, N) negative decay rates, -exp(A_log)
    b: jax.Array,       # (B, T, N) input projections
    c: jax.Array,       # (B, T, N) output projections
    d_skip: jax.Array,  # (C,) skip weights
    chunk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``y`` (B, T, C) in ``x``'s dtype. ``chunk`` is the steps a chunk of the
    walk or a time block of the kernels takes (``CHUNK`` / ``TIME_BLOCK`` where
    none is given, no more than T needs). ``T`` need not divide by it: the tail
    is padded with ``delta = 0`` steps, which neither decay nor feed the state.

    ``interpret=None`` lets the backend decide (``ops._backend.pallas_interpret``):
    on TPU, at shapes ``serves`` takes, the two kernels; elsewhere the plain
    walk. ``True`` runs the kernels in the Pallas interpreter, ``False`` the
    kernels whatever traces them; a shape ``serves`` declines is walked."""
    _, t, ch = x.shape
    if not serves(t, ch, a.shape[1]) or (interpret is None and pallas_interpret()):
        return _walked(x, delta, a, b, c, d_skip, min(chunk or CHUNK, t))
    f32 = jnp.float32
    tb = kernel.time_block(t, chunk)
    pad = -t % tb
    grow = (lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0)))) if pad else (lambda v: v)
    # b, c a time block at a time with time on the lanes of one lane block, (B, T / tb, N, 128)
    blocks = lambda v: jnp.pad(
        grow(v).reshape(v.shape[0], (t + pad) // tb, tb, v.shape[2]).swapaxes(2, 3), ((0, 0), (0, 0), (0, 0), (0, 128 - tb))
    )
    operands = _as_all(grow(x), grow(delta), a.astype(f32).T, blocks(b), blocks(c), d_skip.astype(f32)[None])
    return _kernels(tb, bool(interpret), jnp.dtype(STATE_DTYPE), *operands)[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _kernels(tb, interpret, state_dtype, x, delta, a_nc, b, c, d):
    return kernel.forward(tb, interpret, state_dtype, x, delta, a_nc, b, c, d)[0]


def _kernels_fwd(tb, interpret, state_dtype, *operands):
    y, starts = kernel.forward(tb, interpret, state_dtype, *operands)
    return y, (*operands, starts)


def _kernels_bwd(tb, interpret, state_dtype, residuals, dy):
    _, _, _, b, c, _, _ = residuals
    dx, ddelta, da, db, dc, dd = kernel.backward(tb, interpret, state_dtype, *residuals, dy)
    return dx, ddelta, da.sum(0), db.sum(1).astype(b.dtype), dc.sum(1).astype(c.dtype), dd.sum(0)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _walked(x, delta, a, b, c, d_skip, chunk: int):
    """The recurrence as two nested ``lax.scan``s, plain ``jax``."""
    bsz, t, ch = x.shape
    n = a.shape[1]
    f32 = STATE_DTYPE
    pad = -t % chunk
    grow = (lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0)))) if pad else (lambda v: v)
    # chunks and their steps ahead of time, (chunks, chunk, B, .): what the two loops walk
    steps = lambda v: grow(v).reshape(bsz, (t + pad) // chunk, chunk, v.shape[-1]).transpose(1, 2, 0, 3)
    a_nc = a.astype(f32).T  # (N, C): channels on the lanes, the state's index on the sublanes

    def one_step(state, inputs):
        x_t, delta_t, b_t, c_t = inputs  # (B, C), (B, C), (B, N), (B, N)
        delta_t = delta_t.astype(f32)
        fed = (delta_t * x_t.astype(f32))[:, None, :] * b_t.astype(f32)[:, :, None]
        state = jnp.exp(delta_t[:, None, :] * a_nc) * state + fed
        return state, jnp.sum(state * c_t.astype(f32)[:, :, None], axis=1)

    def one_chunk(state, inputs):
        return lax.scan(one_step, state, inputs, unroll=min(UNROLL, chunk))

    state0 = jnp.zeros((bsz, n, ch), f32)
    varying = tuple(jax.typeof(x).vma)  # inside shard_map fresh zeros are invariant, the carry is not
    if varying:
        state0 = lax.pcast(state0, varying, to="varying")
    _, y = lax.scan(jax.checkpoint(one_chunk), state0, (steps(x), steps(delta), steps(b), steps(c)))
    y = y.reshape(t + pad, bsz, ch).transpose(1, 0, 2)[:, :t]
    return (y + x.astype(f32) * d_skip.astype(f32)).astype(x.dtype)
