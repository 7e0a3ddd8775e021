"""The gated delta rule (Gated DeltaNet, Yang et al. 2024) in chunks: the
recurrence of a linear-attention layer whose state update READS the state.

Per value head with state ``S`` of shape (d_k, d_v), ``S_0 = 0``::

    S <- exp(g_t) S        d_t = beta_t (v_t - S^T k_t)        S <- S + k_t d_t^T        o_t = S^T q_t

``gated_delta_rule`` computes it in chunks of ``chunk`` steps (the WY / UT
form, HuggingFace's ``torch_chunk_gated_delta_rule``). Inside a chunk, with
``gamma_i = sum_{s<=i} g_s``::

    A = -strict_tril((beta K) K^T * exp(gamma_i - gamma_j))        T = (I - A)^-1
    U = T (beta V)        W = T (beta K * exp gamma)

and with the state ``S`` that enters the chunk::

    V' = U - W S        O = (Q * exp gamma) S + tril(Q K^T * exp(gamma_i - gamma_j)) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

Every ``exp`` has a non-positive argument. ``A`` is strictly lower triangular,
so ``A^chunk = 0`` and ``T = (I + A)(I + A^2)(I + A^4)...``: ``log2(chunk)``
factors, batched matmuls in place of a ``chunk``-step substitution loop
(:func:`unit_lower_inverse`; its cotangent is ``T^T dT T^T``, two products).
Between chunks a ``lax.scan`` carries the state; its body holds only what
needs the state (``W S`` and ``K^T V'``: a matmul INSIDE the carry, where
``ops.ssd``'s is a scalar decay and an add) and emits ``V'`` and the state
before each chunk, so ``O`` is batched products outside the loop.

``g``, its cumulative sums, every ``exp`` and the solve are fp32; the large
products take their operands in ``v``'s dtype and accumulate in fp32. The
recurrence as written is ``benchmark/reference/qwen3_next.py``'s.

What is a kernel and what is XLA. The rule has two halves. The **chunk-local
stage** (:func:`chunk_local`: from q, k, v, gamma, beta to ``U``, ``W``, ``K
e^(gamma_C - gamma)``, ``Q e^gamma`` and ``tril(Q K^T decay)`` — with ``K
K^T``, the decay, ``A``, the inverse, ``beta V``, ``beta K e^gamma`` on the
way) needs no state, so every chunk and head is independent: on TPU it is one
Pallas kernel forward and one backward (``ops/gated_delta_kernel.py``; those
matrices never leave VMEM, and q, k, v are read in the model's layout), on
every other backend, and for shapes the kernels' tiles do not serve, the XLA
einsums of :func:`_chunk_local_xla` under jax's own differentiation (but for
the inverse's two-product rule). The kernels' ``jax.custom_vjp`` goes round
the stage alone and keeps the stage's inputs and the fp32 inverse ``T`` (as
``unit_lower_inverse`` keeps it): the backward kernel forms ``A``'s pieces
again from the inputs and never the inverse's products. **Everything that
touches the state stays XLA**, differentiated by jax: gamma's ``cumsum``, the
``lax.scan`` over chunks and ``O``'s two products. The carry is still a
``lax.scan`` because a kernel that keeps the (d_k, d_v) state in VMEM across
a sequential chunk axis is another frame (``ops/ssd.py``'s scan shares it:
ROADMAP.md Speed) with its own backward; the scan's body was 14 ms of the
rule's 109 a step where the chunk-local stage was 70 (PERF.md §5, PR 33).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ._backend import pallas_interpret
from .gated_delta_kernel import chunk_local_backward, chunk_local_forward, serves

_HIGHEST = lax.Precision.HIGHEST


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I - a)^-1`` for ``a`` (..., C, C) strictly lower triangular, fp32:
    the product of the factors ``I + a^(2^i)`` (``a^C = 0`` ends the series)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inverse, power = eye + a, a
    for _ in range(max(c - 1, 1).bit_length() - 1):  # factors up to a^(2^n) with 2^(n+1) > c - 1: every power below c
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
    return inverse


def _unit_lower_inverse_fwd(a):
    inverse = unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, cotangent):
    # d(I - a)^-1 = T da T, so the cotangent of a is T^T dT T^T
    t = jnp.swapaxes(inverse, -1, -2)
    return (jnp.matmul(jnp.matmul(t, cotangent, precision=_HIGHEST), t, precision=_HIGHEST),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _layouts(k, v, chunk):
    """A key head with its r value heads ahead of time: (B, nc, H_k, [R,] C, ...)
    of k-like (B, T, H_k, d) and v-like (B, T, H_v[, d]) arrays."""
    bsz, t, hk, _ = k.shape
    nc, r = t // chunk, v.shape[2] // hk
    by_key = lambda x: jnp.moveaxis(x.reshape(bsz, nc, chunk, hk, x.shape[3]), 2, 3)
    by_value = lambda x: jnp.moveaxis(x.reshape((bsz, nc, chunk, hk, r) + x.shape[3:]), 2, 4)
    return by_key, by_value


def _chunk_local_xla(chunk, q, k, v, gamma, beta):
    """:func:`chunk_local` as XLA einsums: ``A`` (B, nc, H_k, R, C, C), its
    inverse, ``beta V`` and ``beta K e^gamma`` are arrays."""
    dtype, f32 = v.dtype, jnp.float32
    by_key, by_value = _layouts(k, v, chunk)
    qh, kh, vh = by_key(q), by_key(k), by_value(v)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))  # (.., C, C)
    # K K^T and Q K^T once a key head; its r value heads differ by beta and the decay alone
    kk = jnp.einsum("bchid,bchjd->bchij", kh, kh, preferred_element_type=f32)[:, :, :, None]
    qk = jnp.einsum("bchid,bchjd->bchij", qh, kh, preferred_element_type=f32)[:, :, :, None]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strict, -(beta[..., :, None] * kk * decay), 0.0)
    solve = unit_lower_inverse(a).astype(dtype)  # T (B, nc, H_k, R, C, C)

    k_rows = kh[:, :, :, None].astype(f32)  # (B, nc, H_k, 1, C, d_k): every value head of the key head
    from_start = jnp.exp(gamma)  # each step's decay since the chunk began
    beta_v = (vh.astype(f32) * beta[..., None]).astype(dtype)
    beta_k_decayed = (k_rows * (beta * from_start)[..., None]).astype(dtype)
    u = jnp.einsum("bchrij,bchrjd->bchrid", solve, beta_v, preferred_element_type=f32)
    w = jnp.einsum("bchrij,bchrjd->bchrid", solve, beta_k_decayed, preferred_element_type=f32).astype(dtype)
    k_to_end = (k_rows * jnp.exp(gamma[..., -1:] - gamma)[..., None]).astype(dtype)
    q_from_start = (qh[:, :, :, None].astype(f32) * from_start[..., None]).astype(dtype)
    return u, w, k_to_end, q_from_start, (qk * decay).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunk_local_kernel(chunk, interpret, q, k, v, gamma, beta):
    return chunk_local_forward(chunk, interpret, q, k, v, gamma, beta)[:5]


def _chunk_local_kernel_fwd(chunk, interpret, *inputs):
    *outputs, inverse = chunk_local_forward(chunk, interpret, *inputs)
    return tuple(outputs), (*inputs, inverse)


def _chunk_local_kernel_bwd(chunk, interpret, residuals, cotangents):
    return chunk_local_backward(chunk, interpret, *residuals, cotangents)


_chunk_local_kernel.defvjp(_chunk_local_kernel_fwd, _chunk_local_kernel_bwd)


def chunk_local(q, k, v, gamma, beta, chunk: int, interpret: Optional[bool] = None):
    """Everything a chunk computes without the state, from q and k (B, T,
    H_k, d_k) and v (B, T, H_v, d_v) in one dtype, T a multiple of ``chunk``,
    and gamma, beta (B, nc, H_k, R, C) fp32: ``U`` (B, nc, H_k, R, C, d_v)
    fp32 and, in v's dtype, ``W``, ``K e^(gamma_C - gamma)`` and ``Q e^gamma``
    (B, nc, H_k, R, C, d_k) and ``tril(Q K^T decay)`` (B, nc, H_k, R, C, C).

    ``interpret=None`` lets the backend decide (``ops._backend.pallas_interpret``):
    on TPU the Pallas kernels where their tiles serve the shape, elsewhere and
    otherwise the XLA stage under jax's own differentiation; ``True`` runs the
    kernels in the Pallas interpreter, ``False`` the kernels whatever traces them."""
    r = v.shape[2] // k.shape[2]
    if interpret is None and (pallas_interpret() or not serves(chunk, r, k.shape[3], v.shape[3])):
        return _chunk_local_xla(chunk, q, k, v, gamma, beta)
    return _chunk_local_kernel(chunk, bool(interpret), q, k, v, gamma, beta)


def gated_delta_rule(
    q: jax.Array,     # (B, T, H_k, d_k) queries, normalised and scaled by the caller
    k: jax.Array,     # (B, T, H_k, d_k) keys, normalised by the caller
    v: jax.Array,     # (B, T, H_v, d_v) values
    g: jax.Array,     # (B, T, H_v) log-decay of each step, <= 0, fp32
    beta: jax.Array,  # (B, T, H_v) write strength of each step, in (0, 1), fp32
    chunk: int = 64,
) -> jax.Array:
    """The rule's outputs ``o`` (B, T, H_v, d_v) in ``v``'s dtype. Value head
    ``h`` reads key head ``h // (H_v // H_k)`` (q and k repeated, as
    HuggingFace's ``repeat_interleave``, without the copy: ``K K^T`` and ``Q
    K^T`` are made once a key head). ``T`` need not divide by ``chunk``: the
    tail is padded with ``g = 0, beta = 0`` steps, which neither decay nor
    write the state."""
    bsz, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    assert hv == hk * r and k.shape == q.shape and g.shape == beta.shape == (bsz, t, hv)
    dtype, f32 = v.dtype, jnp.float32
    pad = -t % chunk
    if pad:
        grow = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    nc = (t + pad) // chunk

    _, by_value = _layouts(k, v, chunk)
    betah = by_value(beta.astype(f32))                    # (B, nc, H_k, R, C)
    gamma = jnp.cumsum(by_value(g.astype(f32)), axis=-1)  # log-decay from the chunk's start, <= 0
    u, w, k_to_end, q_from_start, qk_decayed = chunk_local(q.astype(dtype), k.astype(dtype), v, gamma, betah, chunk)
    whole = jnp.exp(gamma[..., -1])  # the chunk's whole decay (B, nc, H_k, R)

    def carry_state(state, chunk_in):
        u_c, w_c, k_c, whole_c = chunk_in
        entering = state.astype(dtype)
        fresh = (u_c - jnp.einsum("bhrik,bhrkv->bhriv", w_c, entering, preferred_element_type=f32)).astype(dtype)
        added = jnp.einsum("bhrik,bhriv->bhrkv", k_c, fresh, preferred_element_type=f32)
        # emit V' and the state BEFORE the chunk, as the products outside take them
        return state * whole_c[..., None, None] + added, (fresh, entering)

    state0 = jnp.zeros((bsz, hk, r, dk, dv), f32)
    varying = tuple(jax.typeof(v).vma)  # inside shard_map fresh zeros are invariant, the carry is not
    if varying:
        state0 = lax.pcast(state0, varying, to="varying")
    chunks_first = lambda x: jnp.moveaxis(x, 1, 0)
    _, (fresh, before) = lax.scan(
        carry_state, state0,
        (chunks_first(u), chunks_first(w), chunks_first(k_to_end), chunks_first(whole)),
    )
    fresh, before = jnp.moveaxis(fresh, 0, 1), jnp.moveaxis(before, 0, 1)

    # O = (Q * exp gamma) S + tril(Q K^T * decay) V'
    out = jnp.einsum("bchrik,bchrkv->bchriv", q_from_start, before, preferred_element_type=f32)
    out = out + jnp.einsum("bchrij,bchrjv->bchriv", qk_decayed, fresh, preferred_element_type=f32)
    out = jnp.moveaxis(out, 4, 2).reshape(bsz, nc * chunk, hv, dv)
    return out[:, :t].astype(dtype)
