"""Collective wrappers over the real shard_map/psum path on 8 virtual devices
(the reference's collectives are NCCL calls it could only test on a lab
cluster; SURVEY §4 'distributed-without-a-cluster')."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from network_distributed_pytorch_tpu.parallel import (
    DATA_AXIS,
    all_gather,
    all_reduce_mean,
    all_reduce_sum,
    make_mesh,
)
from network_distributed_pytorch_tpu.parallel.comm import (
    axis_index,
    axis_size,
    fence,
)


def test_all_reduce_sum_and_mean(devices):
    mesh = make_mesh()
    x = jnp.arange(8.0).reshape(8, 1)  # one row per device

    def f(xs):
        return all_reduce_sum(xs, DATA_AXIS), all_reduce_mean(xs, DATA_AXIS)

    s, m = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=(P(DATA_AXIS), P(DATA_AXIS)))
    )(x)
    np.testing.assert_allclose(np.asarray(s), np.full((8, 1), 28.0))
    np.testing.assert_allclose(np.asarray(m), np.full((8, 1), 3.5))


def test_all_gather(devices):
    mesh = make_mesh()
    x = jnp.arange(8.0).reshape(8, 1)

    def f(xs):
        g = all_gather(xs, DATA_AXIS)  # (8, 1, 1) on each device
        return g.reshape(1, -1)

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS)))(x)
    np.testing.assert_allclose(np.asarray(g), np.tile(np.arange(8.0), (8, 1)))


def test_axis_helpers(devices):
    mesh = make_mesh()

    def f(xs):
        return xs * 0 + axis_size(DATA_AXIS), xs * 0 + axis_index(DATA_AXIS)

    size, idx = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=(P(DATA_AXIS), P(DATA_AXIS)))
    )(jnp.zeros((8, 1)))
    np.testing.assert_allclose(np.asarray(size), np.full((8, 1), 8.0))
    np.testing.assert_allclose(np.asarray(idx)[:, 0], np.arange(8.0))


def test_single_process_fallbacks():
    # axis_name=None -> identity / stack-of-one (reducer.py:193-195, tensor_buffer.py:64-69)
    x = jnp.arange(4.0)
    np.testing.assert_array_equal(np.asarray(all_reduce_sum(x, None)), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(all_reduce_mean(x, None)), np.asarray(x))
    assert all_gather(x, None).shape == (1, 4)
    assert axis_size(None) == 1
    assert axis_index(None) == 0


def test_mesh_shape_validation():
    import pytest

    with pytest.raises(ValueError):
        make_mesh(axis_sizes=(3,), axis_names=("data",))


def _bits(x):
    """uint bit-pattern view — equality here is BITWISE, not allclose."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[x.dtype.itemsize])


def test_fence_preserves_values():
    a, b = jnp.arange(4.0), jnp.ones((2, 3))
    fa = fence(a)
    np.testing.assert_array_equal(_bits(fa), _bits(a))
    fa, fb = fence(a, b)
    np.testing.assert_array_equal(_bits(fa), _bits(a))
    np.testing.assert_array_equal(_bits(fb), _bits(b))
    assert fence() == ()


def test_fence_is_transparent_to_grad():
    # a fenced payload sits on the differentiated path wherever a reducer
    # runs under grad, so grad(f ∘ fence) must equal grad(f)
    def f(x):
        return jnp.sum(fence(x) ** 2)

    x = jnp.arange(5.0)
    np.testing.assert_array_equal(
        _bits(jax.grad(f)(x)), _bits(jax.grad(lambda x: jnp.sum(x**2))(x))
    )
