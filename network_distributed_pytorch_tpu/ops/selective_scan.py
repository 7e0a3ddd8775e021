"""Mamba-1's selective scan (S6, Gu & Dao 2023): the recurrence whose decay is
its own for every channel and state index, so it has no matmul form.

Per sequence, with state ``S`` of shape (C, N), ``S_{-1} = 0``::

    S_t[c, n] = exp(delta_t[c] * a[c, n]) * S_{t-1}[c, n] + delta_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n S_t[c, n] * C_t[n] + d_skip[c] * x_t[c]

Mamba-2's scan (``ops/ssd.py``) has ONE decay a head for a whole (P, N)
state, which is what makes a chunk a masked (chunk, chunk) matmul; here a
chunk would need C * N = 81,920 decay matrices, so the state is walked.

``selective_scan`` is a ``lax.scan`` over chunks of ``CHUNK`` steps that
carries the (B, N, C) state in fp32, channels on the lanes; the chunk's body
is under ``jax.checkpoint``, so the backward pass holds one chunk's (chunk,
N, C) states and the forward none: no (T, C, N) array is ever alive (2.7 GB
in fp32 at T = 8192, C = 5120). Inside a chunk the steps are a second
``lax.scan``, one step of the recurrence as written an iteration, ``UNROLL``
iterations a loop trip. On the chip at (1, 8192, 5120, 16) that is 8 ms a
forward scan and 31 ms with every cotangent, where ``lax.associative_scan``
over the pairs ``(exp(delta a), delta x B)`` of a chunk took 19 and 75
(PERF.md section 6, PR 48): the compiler keeps a step's state on the chip,
and the associative form moves log2(chunk) levels of (chunk, N, C) arrays
through memory. ``delta``, ``a``, every ``exp``, the state and the sum over n
are fp32 whatever ``x``'s dtype (a decay rounded to bf16 compounds over a
sequence); ``y`` leaves in ``x``'s dtype. Plain ``jax``: it differentiates as
such. ``benchmark/reference/phi4flash.py`` writes the same recurrence on its
own, over (C, N) in fp32 at full precision.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 32  # steps a chunk: what the backward holds alive is one chunk's (CHUNK, B, N, C) fp32 states (10 MB at 5120 x 16)
UNROLL = 8  # steps a trip of the inner loop
STATE_DTYPE = jnp.float32  # of delta, the decay, the state and y's sum: tests and the benchmark's control lower it


def selective_scan(
    x: jax.Array,       # (B, T, C) the conv's output
    delta: jax.Array,   # (B, T, C) step sizes, already softplus'd (> 0)
    a: jax.Array,       # (C, N) negative decay rates, -exp(A_log)
    b: jax.Array,       # (B, T, N) input projections
    c: jax.Array,       # (B, T, N) output projections
    d_skip: jax.Array,  # (C,) skip weights
    chunk: Optional[int] = None,
) -> jax.Array:
    """``y`` (B, T, C) in ``x``'s dtype. ``chunk`` is ``min(CHUNK, T)`` where
    none is given. ``T`` need not divide by it: the tail is padded with
    ``delta = 0`` steps, which neither decay nor feed the state."""
    bsz, t, ch = x.shape
    n = a.shape[1]
    f32 = STATE_DTYPE
    chunk = min(chunk or CHUNK, t)
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
