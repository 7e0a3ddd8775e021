"""Pallas TPU kernel for the PowerSGD Gram-Schmidt orthogonalization.

Why a kernel: the XLA lowering of the sequential-column recurrence
(``ops.orthogonalize``) is a ``fori_loop`` whose every iteration reads and
writes the whole (n, r) matrix through HBM. This kernel keeps the matrix
resident in **VMEM** across all r iterations — one HBM read, one HBM write,
r compute rounds on the VPU — which is the right shape for PowerSGD's tall
skinny P matrices (n up to ~10⁵, r ∈ [1, 32]).

Layout: the matrix is processed transposed, (r, n) — the long axis lands on
the 128-lane dimension and r sits on sublanes, so a whole column of the
original matrix is one contiguous VMEM row. The math is exactly the
reference recurrence (``reducer.py:183-191``): normalize column i with
``sqrt(Σc²)+eps``, subtract its projection from every LATER column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_schmidt_kernel(r: int, eps: float, m_ref, out_ref):
    out_ref[:] = m_ref[:]

    def body(i, carry):
        row = out_ref[pl.ds(i, 1), :]  # (1, n) — one original column
        norm = jnp.sqrt(jnp.sum(row * row)) + eps
        rown = row / norm
        # projections of every column onto the normalized one: (r, 1)
        proj = jnp.sum(out_ref[:] * rown, axis=1, keepdims=True)
        later = lax.broadcasted_iota(jnp.int32, (r, 1), 0) > i
        out_ref[:] = out_ref[:] - jnp.where(later, proj, 0.0) * rown
        out_ref[pl.ds(i, 1), :] = rown
        return carry

    lax.fori_loop(0, r, body, 0)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def orthogonalize_pallas(
    matrix: jax.Array, eps: float = 1e-8, interpret: bool = False
) -> jax.Array:
    """Drop-in replacement for ``ops.orthogonalize`` on TPU.

    ``interpret=True`` runs the Pallas interpreter (for CPU tests)."""
    n, r = matrix.shape
    mt = matrix.T  # (r, n): lanes = n
    out = pl.pallas_call(
        functools.partial(_gram_schmidt_kernel, r, eps),
        # inside shard_map the output varies over the mesh as the input does
        out_shape=jax.ShapeDtypeStruct(
            (r, n), matrix.dtype, vma=jax.typeof(matrix).vma
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(mt)
    return out.T
