"""Fused Pallas TPU kernels for the PowerSGD compress/decompress pipeline.

Why kernels: the XLA lowering of one PowerSGD round runs ~5 separate HBM
round-trips per matrix bucket — the error-feedback add, the ``P = M·Q``
matmul, the Gram-Schmidt ``fori_loop`` (which re-reads the whole P every
iteration, ``ops.orthogonalize``), the ``Q = Mᵀ·P̂`` matmul, and the
decompress ``P̂·Qᵀ`` + residual subtract (``parallel/reducers.py``). Each of
the three kernels here fuses one compute span between two collectives into a
single HBM round-trip per bucket:

- :func:`fused_ef_compress` — ``M = G + E`` (the error-feedback add) in
  VMEM, then ``P = M·Q`` on the MXU. ``M`` is written back once because the
  later stages (``Q = Mᵀ·P̂``, the residual) re-read it.
- :func:`fused_orthogonalize_project` — Gram-Schmidt on P held VMEM-resident
  across all r iterations (absorbing ``ops.pallas_orthogonalize``), then
  ``Q = Mᵀ·P̂`` on the MXU while P̂ is still in VMEM.
- :func:`fused_decompress_residual` — ``out = P̂·Qᵀ`` on the MXU and the
  error-feedback residual ``mem = M − out`` in the same pass: M is read
  once, both outputs stream out.

All three are batched over a shape-group stack ``(g, n, m)`` — the reducer
already buckets same-shaped matrices (``PowerSGDReducer._shape_groups``), so
the grid dimension is the bucket member index and each program owns one
matrix. Accumulation is fp32 on the MXU (``preferred_element_type``)
regardless of the wire/compression dtype, so bf16-wire runs keep fp32
error-feedback accumulation.

VMEM budget: each program holds one (n, m) matrix plus its (n, r)/(m, r)
factors — fine for conv/dense kernels (the largest ResNet-50 bucket is
3·3·512·512 ≈ 9.4 MB fp32); matrices beyond ~VMEM (16 MB/core) should stay
on the XLA path. On CPU the kernels run in interpret mode (the test path),
like ``ops.flash_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (TPU lowering)

def _out_struct(shape, dtype, vma):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _vma_union(*operands):
    # inside shard_map, pallas_call must declare how its outputs vary over
    # the mesh — exactly as the union of its operands do
    vma = frozenset()
    for op in operands:
        if op is not None:
            vma = vma | jax.typeof(op).vma
    return vma


def _spec(n, m):
    return pl.BlockSpec((1, n, m), lambda g: (g, 0, 0))


# ---------------------------------------------------------------------------
# kernel bodies — each program owns one (n, m) matrix of the group stack
# ---------------------------------------------------------------------------


def _ef_compress_kernel(g_ref, e_ref, q_ref, m_ref, p_ref):
    m = g_ref[0] + e_ref[0]  # error-feedback add, in VMEM
    m_ref[0] = m.astype(m_ref.dtype)
    p = lax.dot_general(
        m.astype(jnp.float32), q_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    p_ref[0] = p.astype(p_ref.dtype)


def _compress_kernel(m_ref, q_ref, p_ref):
    p = lax.dot_general(
        m_ref[0].astype(jnp.float32), q_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    p_ref[0] = p.astype(p_ref.dtype)


def _orthogonalize_project_kernel(n, r, eps, p_ref, m_ref, phat_ref, q_ref):
    # Gram-Schmidt, VMEM-resident across all r iterations: exactly the
    # reference recurrence (reducer.py:183-191, ops.orthogonalize) —
    # normalize column i with sqrt(Σc²)+eps, subtract its projection from
    # every LATER column. The carry is the whole (n, r) matrix; it never
    # leaves VMEM until the single write below.
    def body(i, p):
        col = lax.dynamic_slice(p, (0, i), (n, 1))
        norm = jnp.sqrt(jnp.sum(col * col)) + eps
        coln = col / norm
        proj = jnp.sum(p * coln, axis=0, keepdims=True)  # (1, r)
        later = lax.broadcasted_iota(jnp.int32, (1, r), 1) > i
        p = p - coln * jnp.where(later, proj, 0.0)
        return lax.dynamic_update_slice(p, coln, (0, i))

    phat = lax.fori_loop(0, r, body, p_ref[0].astype(jnp.float32))
    phat_ref[0] = phat.astype(phat_ref.dtype)
    # Q = Mᵀ·P̂ while P̂ is still VMEM-resident: contract the n axis
    q = lax.dot_general(
        m_ref[0].astype(jnp.float32), phat,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    q_ref[0] = q.astype(q_ref.dtype)


def _decompress_residual_kernel(p_ref, q_ref, m_ref, out_ref, mem_ref):
    # out = P̂·Qᵀ (contract the rank axis) and the error-feedback residual
    # mem = M − out in one pass over M
    approx = lax.dot_general(
        p_ref[0].astype(jnp.float32), q_ref[0].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    out_ref[0] = approx.astype(out_ref.dtype)
    mem_ref[0] = (m_ref[0].astype(jnp.float32) - approx).astype(mem_ref.dtype)


# ---------------------------------------------------------------------------
# public wrappers — stacked (g, n, m) group batches, grid over g
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_ef_compress(
    grads: jax.Array,
    q: jax.Array,
    residuals: jax.Array = None,
    *,
    interpret: bool = False,
):
    """``M = grads (+ residuals)``, ``P = M·Q`` — one HBM round-trip.

    grads/residuals: (g, n, m) stacked matrices; q: (g, m, r). Returns
    ``(m, p)`` with m = (g, n, m) in grads' dtype and p = (g, n, r) in the
    promoted grads/q dtype (fp32 MXU accumulation either way). With
    ``residuals=None`` the error-feedback add is skipped and ``m`` is
    ``grads`` itself (the extra-power-iteration path re-compresses the mean
    matrix, which has no residual to add).
    """
    g, n, m = grads.shape
    r = q.shape[-1]
    p_dtype = jnp.result_type(grads.dtype, q.dtype)
    if residuals is None:
        vma = _vma_union(grads, q)
        p = pl.pallas_call(
            _compress_kernel,
            grid=(g,),
            in_specs=[_spec(n, m), _spec(m, r)],
            out_specs=_spec(n, r),
            out_shape=_out_struct((g, n, r), p_dtype, vma),
            interpret=interpret,
        )(grads, q)
        return grads, p
    vma = _vma_union(grads, residuals, q)
    return pl.pallas_call(
        _ef_compress_kernel,
        grid=(g,),
        in_specs=[_spec(n, m), _spec(n, m), _spec(m, r)],
        out_specs=[_spec(n, m), _spec(n, r)],
        out_shape=[
            _out_struct((g, n, m), grads.dtype, vma),
            _out_struct((g, n, r), p_dtype, vma),
        ],
        interpret=interpret,
    )(grads, residuals, q)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def fused_orthogonalize_project(
    p: jax.Array,
    m: jax.Array,
    eps: float = 1e-8,
    *,
    interpret: bool = False,
):
    """VMEM-resident Gram-Schmidt on P, then ``Q = Mᵀ·P̂`` — one round-trip.

    p: (g, n, r) reduced P factors; m: (g, n, m) send matrices. Returns
    ``(p_hat, q)`` with p_hat = (g, n, r) in p's dtype and q = (g, m, r) in
    the promoted m/p dtype.
    """
    g, n, r = p.shape
    mm = m.shape[-1]
    vma = _vma_union(p, m)
    q_dtype = jnp.result_type(m.dtype, p.dtype)
    return pl.pallas_call(
        functools.partial(_orthogonalize_project_kernel, n, r, eps),
        grid=(g,),
        in_specs=[_spec(n, r), _spec(n, mm)],
        out_specs=[_spec(n, r), _spec(mm, r)],
        out_shape=[
            _out_struct((g, n, r), p.dtype, vma),
            _out_struct((g, mm, r), q_dtype, vma),
        ],
        interpret=interpret,
    )(p, m)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_decompress_residual(
    p: jax.Array,
    q: jax.Array,
    m: jax.Array,
    *,
    interpret: bool = False,
):
    """``out = P̂·Qᵀ`` and the EF residual ``mem = M − out`` — one pass.

    p: (g, n, r) orthogonalized factors; q: (g, m, r) reduced Q factors;
    m: (g, n, m) send matrices. Returns ``(out, mem)``, both (g, n, m) in
    m's dtype — the residual is accumulated in fp32 before the final cast,
    so a bf16 wire dtype never degrades the error-feedback memory math.
    """
    g, n, r = p.shape
    mm = m.shape[-1]
    vma = _vma_union(p, q, m)
    return pl.pallas_call(
        _decompress_residual_kernel,
        grid=(g,),
        in_specs=[_spec(n, r), _spec(mm, r), _spec(n, mm)],
        out_specs=[_spec(n, mm), _spec(n, mm)],
        out_shape=[
            _out_struct((g, n, mm), m.dtype, vma),
            _out_struct((g, n, mm), m.dtype, vma),
        ],
        interpret=interpret,
    )(p, q, m)
