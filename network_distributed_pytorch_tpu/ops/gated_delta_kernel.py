"""Pallas TPU kernels for the chunk-local stage of the gated delta rule:
everything a chunk can compute without the state (``ops/gated_delta.py`` has
the mathematics). Forward: from q, k, v, the cumulative log-decay gamma and
beta to ``U``, ``W``, ``K e^(gamma_C - gamma)``, ``Q e^gamma``, ``tril(Q K^T
decay)`` and the fp32 inverse ``T``. Backward: from those inputs, ``T`` and
the five outputs' cotangents to the cotangents of q, k, v, gamma, beta.

Why kernels: as XLA the stage is 14 batched products and a dozen
elementwise passes a layer over 4,096 matrices of 64 x 64, each its own HBM
round trip of 67-134 MB (PERF.md, PR 33: 8.6 ms of a 12.9 ms layer-pass
where its inputs and outputs need 0.4), and its backward as many again. Here
``K K^T``, ``Q K^T``, the decay matrix, ``A``, the powers of ``A``, ``beta
V``, ``beta K e^gamma`` and their cotangents are values in VMEM:

- grid (batch, key head, block of chunks), every axis parallel; a step takes
  one key head with its ``r`` value heads (they share ``K K^T``) over
  :func:`chunks_a_step` chunks;
- q, k and v are read, and their cotangents written, in the model's layout
  in place, (B, T, H d) seen as (B, chunks, C, H d): a block is one head's
  lanes of some chunks, through the ``BlockSpec``'s index map, so the rule
  transposes none of them;
- the inverse is the doubling product ``(I + A)(I + A^2)(I + A^4)...`` of
  ``unit_lower_inverse``, in fp32 at full precision, with the two products of
  a factor as one: the state is ``[A^(2^n) | inverse so far]``, (C, 2C), and
  ``A^(2^n) @ state`` squares the power and extends the inverse in one
  product a full MXU tile wide (six products a head at C = 64 where the
  XLA form has ten of half the width);
- **the chains of one step are written side by side**: a product waits
  ~120 cycles for the last one of its chain, so the step's ``n x r`` chains
  advance a product each in turn, in program order (PERF.md, PR 38: 5.6 ms a
  call with one chain after the other, 2.2 with eight in turn; the
  scheduler does not find that order itself, unrolled or not);
- gamma and beta arrive with time on the lanes, (r, C) a chunk; what scales
  rows needs them down the sublanes, which a product with the identity and
  a lane reduction gives exactly;
- the backward keeps ``unit_lower_inverse``'s two-product rule, ``dA = T^T dT
  T^T`` at full precision, on the transposed side: ``dT^T`` comes straight
  from ``beta V dU^T + beta K e^gamma dW^T``, so ``dA^T = T dT^T T`` is two
  plain products and no fp32 matrix is ever transposed; ``K K^T`` is
  symmetric, and what ``dA^T`` sums to down its columns are the (1, C) rows
  the cotangents of gamma and beta leave in.

The five outputs leave in the layouts the rule's ``lax.scan`` and ``O``'s two
products consume; ``T`` leaves with a key head's ``r`` inverses side by side
on the lanes, (C, r C), so that at r = 2 and C = 64 no lane of the 67 MB is
padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _VMEM_DEFAULT, _VMEM_MOST, _vma  # what a call may ask Mosaic for; a kernel's vma

_HIGHEST = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # A·B
_NT = (((1,), (1,)), ((), ()))  # A·Bᵀ
_TN = (((0,), (0,)), ((), ()))  # Aᵀ·B


def chunks_a_step(nc: int, most: int = 8) -> int:
    """How many chunks one grid step takes: the largest divisor of ``nc`` up
    to ``most``: with the key head's value heads, the chains of products a
    step advances in turn (PERF.md §6, PR 38, forward / backward ms a call at
    r = 2 before the last three outputs moved in: one chunk 3.51 / 2.55, two
    2.44 / 2.00, four 2.22 / 1.73, eight 2.14 / 1.61)."""
    return next(n for n in range(min(most, nc), 0, -1) if nc % n == 0)


def serves(chunk: int, r: int, dk: int, dv: int) -> bool:
    """Whether Mosaic's tiles serve the shape: a head's lanes of k and v and
    a key head's inverses are whole 128-lane blocks, a chunk whole sublane
    tiles of a 16-bit dtype. The interpreter takes any shape."""
    return dk % 128 == 0 and dv % 128 == 0 and (r * chunk) % 128 == 0 and chunk % 16 == 0


def _inverses(strictly_lower, eye):
    """``(I - a)^-1`` of each strictly lower triangular (C, C) fp32 matrix,
    ``unit_lower_inverse``'s factors at its precision: a ``state`` holds
    ``[a^(2^n) | (I + a)...(I + a^(2^(n-1)))]``, and the power times the
    state is the next power beside what the factor adds to the inverse. The
    matrices advance a product each in turn."""
    c = eye.shape[0]
    inverse_half = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1) >= c
    states = [jnp.concatenate([a, eye], axis=1) for a in strictly_lower]
    for _ in range(max(c - 1, 1).bit_length()):  # I + a^(2^n) for every 2^n below c
        products = [jnp.dot(s[:, :c], s, precision=_HIGHEST, preferred_element_type=jnp.float32) for s in states]
        states = [p + jnp.where(inverse_half, s, 0.0) for p, s in zip(products, states)]
    return [s[:, c:] for s in states]


def _step_values(c):
    """(C, C) row and column numbers, the identity, and what turns a chunk's
    (1, C) row of step values down the sublanes, (C, 1), and back: exact,
    one term a sum."""
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (row == col).astype(jnp.float32)
    as_column = lambda x: jnp.sum(eye * x, axis=1, keepdims=True)
    as_row = lambda x: jnp.sum(eye * x, axis=0, keepdims=True)
    return row, col, eye, as_column, as_row


def _forward_kernel(r, q_ref, k_ref, v_ref, gamma_ref, beta_ref, u_ref, w_ref, k_end_ref, q_start_ref, qk_ref, t_ref):
    """One key head's stage over the step's chunks: blocks q and k (1, n, C,
    d_k), v (1, n, C, r d_v), gamma and beta (1, n, 1, r, C); u (1, n, 1, r,
    C, d_v), w, k_end and q_start (1, n, 1, r, C, d_k), qk (1, n, 1, r, C, C),
    t (1, n, 1, C, r C)."""
    n, c = k_ref.shape[1:3]
    dv = v_ref.shape[3] // r
    dtype, f32 = w_ref.dtype, jnp.float32
    row, col, eye, as_column, _ = _step_values(c)
    heads, strictly_lower = [], []
    for i in range(n):
        q, k = q_ref[0, i], k_ref[0, i]
        kk = lax.dot_general(k, k, _NT, preferred_element_type=f32)  # both once a key head
        qk = lax.dot_general(q, k, _NT, preferred_element_type=f32)
        q_rows, k_rows = q.astype(f32), k.astype(f32)
        for j in range(r):
            gamma_row = gamma_ref[0, i, 0, j:j + 1, :]
            gamma, beta = as_column(gamma_row), as_column(beta_ref[0, i, 0, j:j + 1, :])
            decay = jnp.exp(jnp.where(row >= col, gamma - gamma_row, -jnp.inf))
            heads.append((i, j, gamma, beta))
            strictly_lower.append(jnp.where(row > col, -(beta * kk * decay), 0.0))
            qk_ref[0, i, 0, j] = (qk * decay).astype(dtype)
            q_start_ref[0, i, 0, j] = (q_rows * jnp.exp(gamma)).astype(dtype)
            k_end_ref[0, i, 0, j] = (k_rows * jnp.exp(gamma_row[:, c - 1:] - gamma)).astype(dtype)
    for (i, j, gamma, beta), inverse in zip(heads, _inverses(strictly_lower, eye)):
        t_ref[0, i, 0, :, j * c:(j + 1) * c] = inverse
        solve = inverse.astype(dtype)
        beta_v = (v_ref[0, i, :, j * dv:(j + 1) * dv].astype(f32) * beta).astype(dtype)
        beta_k_decayed = (k_ref[0, i].astype(f32) * (beta * jnp.exp(gamma))).astype(dtype)
        u_ref[0, i, 0, j] = jnp.dot(solve, beta_v, preferred_element_type=f32)
        w_ref[0, i, 0, j] = jnp.dot(solve, beta_k_decayed, preferred_element_type=f32).astype(dtype)


def _backward_kernel(
    r, q_ref, k_ref, v_ref, gamma_ref, beta_ref, t_ref, du_ref, dw_ref, dk_end_ref, dq_start_ref, dqk_ref,
    dq_ref, dk_ref, dv_ref, dgamma_ref, dbeta_ref,
):
    """The forward's blocks and the cotangents' in the same layouts: du, dw,
    dk_end, dq_start and dqk as u, w, k_end, q_start and qk; dq, dk and dv as
    q, k and v; dgamma and dbeta as gamma and beta. The inverse's matrices
    are held transposed from ``dT^T`` on (module docstring)."""
    n, c = k_ref.shape[1:3]
    dv = v_ref.shape[3] // r
    dtype, f32 = dw_ref.dtype, jnp.float32
    row, col, _, as_column, as_row = _step_values(c)
    last = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    dot = functools.partial(lax.dot_general, preferred_element_type=f32)
    heads, inverses, d_inverses = [], [], []
    for i in range(n):
        k32 = k_ref[0, i].astype(f32)
        for j in range(r):
            gamma_row, beta_row = gamma_ref[0, i, 0, j:j + 1, :], beta_ref[0, i, 0, j:j + 1, :]
            gamma, beta = as_column(gamma_row), as_column(beta_row)
            from_start = jnp.exp(gamma)
            v32 = v_ref[0, i, :, j * dv:(j + 1) * dv].astype(f32)
            du, dw = du_ref[0, i, 0, j].astype(dtype), dw_ref[0, i, 0, j]
            inverse = t_ref[0, i, 0, :, j * c:(j + 1) * c]
            solve = inverse.astype(dtype)
            # dT^T = (beta V) dU^T + (beta K e^gamma) dW^T
            d_inverses.append(
                dot((v32 * beta).astype(dtype), du, _NT) + dot((k32 * (beta * from_start)).astype(dtype), dw, _NT)
            )
            inverses.append(inverse)
            heads.append((gamma_row, beta_row, gamma, beta, from_start, v32, dot(solve, du, _TN), dot(solve, dw, _TN)))
    # dA^T = T dT^T T, the chains a product each in turn
    halfway = [dot(t, d, _NN, precision=_HIGHEST) for t, d in zip(inverses, d_inverses)]
    d_a = [dot(h, t, _NN, precision=_HIGHEST) for h, t in zip(halfway, inverses)]
    for i in range(n):
        q, k = q_ref[0, i], k_ref[0, i]
        q32, k32 = q.astype(f32), k.astype(f32)
        kk = lax.dot_general(k, k, _NT, preferred_element_type=f32)  # symmetric: its own transpose
        qk = lax.dot_general(q, k, _NT, preferred_element_type=f32)
        dq, dk = jnp.zeros(q32.shape, f32), jnp.zeros(k32.shape, f32)
        d_kk, d_qk = jnp.zeros((c, c), f32), jnp.zeros((c, c), f32)
        for j in range(r):
            gamma_row, beta_row, gamma, beta, from_start, v32, d_beta_v, d_beta_k_decayed = heads[i * r + j]
            # A^T = -(strictly upper of) beta_j kk decay^T, decay^T[i, j] = exp(gamma_j - gamma_i)
            decay_t = jnp.exp(jnp.where(col >= row, gamma_row - gamma, -jnp.inf))
            d_scaled = jnp.where(col > row, -d_a[i * r + j], 0.0)
            through_beta = d_scaled * kk * decay_t
            through_decay = through_beta * beta_row
            d_kk = d_kk + d_scaled * beta_row * decay_t
            # Q K^T decay, as the forward has it
            decay = jnp.exp(jnp.where(row >= col, gamma - gamma_row, -jnp.inf))
            d_qk_decayed = dqk_ref[0, i, 0, j].astype(f32)
            d_qk = d_qk + d_qk_decayed * decay
            through_qk_decay = d_qk_decayed * qk * decay
            # K e^(gamma_C - gamma) and Q e^gamma
            to_end = jnp.exp(gamma_row[:, c - 1:] - gamma)
            d_k_end, d_q_start = dk_end_ref[0, i, 0, j].astype(f32), dq_start_ref[0, i, 0, j].astype(f32)
            of_the_end = jnp.sum(d_k_end * k32, axis=1, keepdims=True) * to_end  # (C, 1)
            of_the_start = jnp.sum(d_q_start * q32, axis=1, keepdims=True) * from_start
            of_the_keys = jnp.sum(d_beta_k_decayed * k32, axis=1, keepdims=True) * from_start  # d(beta e^gamma) e^gamma
            d_beta = jnp.sum(d_beta_v * v32, axis=1, keepdims=True) + of_the_keys
            d_gamma = (
                of_the_keys * beta + of_the_start - of_the_end
                + jnp.sum(through_qk_decay - through_decay, axis=1, keepdims=True)
            )
            dbeta_ref[0, i, 0, j:j + 1, :] = jnp.sum(through_beta, axis=0, keepdims=True) + as_row(d_beta)
            dgamma_ref[0, i, 0, j:j + 1, :] = (
                jnp.sum(through_decay - through_qk_decay, axis=0, keepdims=True) + as_row(d_gamma)
                + jnp.where(last, jnp.sum(of_the_end, axis=0, keepdims=True), 0.0)
            )
            dv_ref[0, i, :, j * dv:(j + 1) * dv] = (d_beta_v * beta).astype(dtype)
            dk = dk + d_beta_k_decayed * (beta * from_start) + d_k_end * to_end
            dq = dq + d_q_start * from_start
        d_kk, d_qk = d_kk.astype(dtype), d_qk.astype(dtype)  # d(K K^T)^T, which K takes from both sides; d(Q K^T)
        dq_ref[0, i] = (dq + dot(d_qk, k, _NN)).astype(dtype)
        dk_ref[0, i] = (dk + dot(d_kk, k, _NN) + dot(d_kk, k, _TN) + dot(d_qk, q, _TN)).astype(dtype)


def _call(kernel, name, chunk, r, interpret, operands, like):
    """One ``pallas_call`` over (batch, key head, block of chunks): each
    operand, and each output (``like``: shape and dtype), is k-like (B, nc, C,
    H d), a block one head's lanes, or per head (B, nc, H_k, ...), a block
    everything of one head."""
    bsz, nc, _, lanes = operands[0].shape
    hk = operands[-1].shape[2]
    n = chunks_a_step(nc)
    vma = _vma(*operands)

    def spec(x):
        if len(x.shape) == 4:
            return pl.BlockSpec((1, n, chunk, x.shape[3] // hk), lambda b, h, i: (b, i, 0, h))
        tail = x.shape[3:]
        return pl.BlockSpec((1, n, 1) + tail, lambda b, h, i: (b, i, h) + (0,) * len(tail))

    # every block double-buffered, and each of the n r chains' fp32 values live side by side (a dozen (C, 2C) tiles)
    blocks = sum(n * x.size // (bsz * nc * hk) * jnp.dtype(x.dtype).itemsize for x in (*operands, *like))
    resident = 2 * blocks + 16 * n * r * chunk * max(2 * chunk, lanes // hk) * 4
    return pl.pallas_call(
        functools.partial(kernel, r),
        grid=(bsz, hk, nc // n),
        in_specs=[spec(x) for x in operands],
        out_specs=[spec(x) for x in like],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma) for x in like],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=min(max(resident, _VMEM_DEFAULT), _VMEM_MOST),
        ),
        interpret=interpret,
        name=name,
    )(*operands)


def _by_chunk(x, chunk):
    """(B, T, H, d) in the model's layout as (B, nc, C, H d): no copy."""
    bsz, t, h, d = x.shape
    return x.reshape(bsz, t // chunk, chunk, h * d)


# jitted, as grouped_matmul's launchers are: a step calls the stage once a layer and again in every
# recomputation, and one trace and one lowering of the kernel serve all the calls of one signature
@functools.partial(jax.jit, static_argnums=(0, 1))
def chunk_local_forward(chunk, interpret, q, k, v, gamma, beta):
    """q and k (B, T, H_k, d_k) and v (B, T, H_v, d_v) in one dtype, T a
    multiple of ``chunk``; gamma and beta (B, nc, H_k, r, C) fp32. Returns
    ``ops.gated_delta.chunk_local``'s five and ``T`` (B, nc, H_k, C, r C)
    fp32."""
    dk, dv = k.shape[3], v.shape[3]
    r = v.shape[2] // k.shape[2]
    out = lambda tail, dtype: jax.ShapeDtypeStruct(gamma.shape[:3] + tail, dtype)
    per_key = out((r, chunk, dk), v.dtype)
    return _call(
        _forward_kernel, "gated_delta_chunk_local", chunk, r, interpret,
        (_by_chunk(q, chunk), _by_chunk(k, chunk), _by_chunk(v, chunk), gamma, beta),
        (out((r, chunk, dv), jnp.float32), per_key, per_key, per_key, out((r, chunk, chunk), v.dtype),
         out((chunk, r * chunk), jnp.float32)),
    )


@functools.partial(jax.jit, static_argnums=(0, 1))
def chunk_local_backward(chunk, interpret, q, k, v, gamma, beta, inverse, cotangents):
    """The cotangents of q, k, v, gamma and beta, in their shapes and dtypes,
    from the forward's operands, its ``T`` and the cotangents of its five
    outputs (``U``'s fp32, the others' in v's dtype)."""
    r = v.shape[2] // k.shape[2]
    q_chunks, k_chunks, v_chunks = _by_chunk(q, chunk), _by_chunk(k, chunk), _by_chunk(v, chunk)
    dq, dk, dv, dgamma, dbeta = _call(
        _backward_kernel, "gated_delta_chunk_local_bwd", chunk, r, interpret,
        (q_chunks, k_chunks, v_chunks, gamma, beta, inverse, *cotangents), (q_chunks, k_chunks, v_chunks, gamma, beta),
    )
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), dgamma, dbeta
