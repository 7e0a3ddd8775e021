"""The grouped-matmul kernels (``ops/grouped_matmul.py``) in interpret mode on
the CPU: against ``lax.ragged_dot`` (what the public function is off the
chip) and against a plain loop over the groups, outputs and both gradients;
the visits the kernels make against a count by hand; the tiles the shapes of
the two expert layers get. The kernels compile for the chip in
``test_tpu_lowering.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from network_distributed_pytorch_tpu.ops.grouped_matmul import _visits, grouped_matmul, row_tiles, tile

TM = 32  # the row tile of every case: m = 128 is four of them


def plain_loop(lhs, rhs, sizes):
    """Each group's rows by its matrix, one ``dot`` a group, fp32 at full
    precision; rows past the last group stay zero."""
    out, start = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32), 0
    for group, size in enumerate(sizes):
        rows = slice(start, start + size)
        product = jnp.dot(lhs[rows].astype(jnp.float32), rhs[group].astype(jnp.float32), precision="highest")
        out, start = out.at[rows].set(product), start + size
    return out


def visits_by_hand(sizes, tm):
    """Every (row tile, group) pair with a row in common, groups in order."""
    pairs, start = [], 0
    for group, size in enumerate(sizes):
        pairs += [(tile_, group) for tile_ in range(start // tm, -(-(start + size) // tm))] if size else []
        start += size
    return pairs


# sizes of the four groups over m = 128 rows in tiles of 32, (k, n), dtype
CASES = [
    pytest.param((32, 32, 32, 32), (64, 128), jnp.float32, id="groups_on_tile_edges"),
    pytest.param((24, 0, 40, 64), (64, 128), jnp.float32, id="an_empty_group"),
    pytest.param((10, 75, 3, 40), (64, 128), jnp.float32, id="a_group_straddles_three_row_tiles"),
    pytest.param((10, 30, 0, 25), (64, 128), jnp.float32, id="rows_past_the_end"),
    pytest.param((0, 0, 0, 0), (64, 128), jnp.float32, id="no_rows_at_all"),
    pytest.param((0, 0, 128, 0), (64, 128), jnp.float32, id="every_row_in_one_group"),
    pytest.param((10, 75, 3, 20), (128, 1856), jnp.float32, id="n_1856_is_one_tile"),
    pytest.param((10, 75, 3, 20), (2688, 128), jnp.float32, id="k_2688_is_three_tiles"),
    pytest.param((10, 75, 3, 20), (128, 256), jnp.bfloat16, id="bfloat16_operands_fp32_accumulation"),
]


def operands(sizes, shape, dtype, m=128):
    k, n = shape
    ks = jax.random.split(jax.random.PRNGKey(sum(sizes) + k), 3)
    lhs = jax.random.normal(ks[0], (m, k)).astype(dtype)
    rhs = (jax.random.normal(ks[1], (len(sizes), k, n)) / np.sqrt(k)).astype(dtype)
    # the cotangent of every output row, the rows past the end too
    weights = jax.random.normal(ks[2], (m, n))
    return lhs, rhs, weights


@pytest.mark.parametrize("sizes,shape,dtype", CASES)
def test_kernel_matches_ragged_dot_and_a_plain_loop(sizes, shape, dtype):
    lhs, rhs, weights = operands(sizes, shape, dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    kernel = lambda l, r: grouped_matmul(l, r, group_sizes, row_tile=TM, interpret=True)
    ragged = lambda l, r: grouped_matmul(l, r, group_sizes, row_tile=TM)  # off the chip: lax.ragged_dot
    loop = lambda l, r: plain_loop(l, r, sizes)
    got = kernel(lhs, rhs)
    assert got.dtype == jnp.float32 and got.shape == (128, shape[1])
    tolerance = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(rtol=1e-5, atol=1e-5)
    # bf16 operands multiply exactly in fp32: the sums alone differ by their order
    np.testing.assert_allclose(got, loop(lhs, rhs), **tolerance)
    np.testing.assert_allclose(got, ragged(lhs, rhs), **tolerance)
    assert not np.asarray(got[sum(sizes):]).any()  # rows past the end: zeros by selection
    loss = lambda f: lambda l, r: jnp.sum(f(l, r) * weights)
    d_kernel, d_ragged, d_loop = (jax.grad(loss(f), argnums=(0, 1))(lhs, rhs) for f in (kernel, ragged, loop))
    for mine, theirs in zip(d_kernel + d_kernel, d_ragged + d_loop):
        assert mine.dtype == dtype and mine.shape == theirs.shape
        # a bf16 cotangent is the fp32 one rounded once, and the kernel rounds the output's first
        np.testing.assert_allclose(
            mine.astype(jnp.float32), theirs.astype(jnp.float32), **(tolerance if dtype == jnp.float32 else dict(rtol=0.05, atol=0.05))
        )
    assert not np.asarray(d_kernel[0][sum(sizes):]).any()  # and so is their cotangent
    empty = [g for g, size in enumerate(sizes) if not size]
    assert not np.asarray(d_kernel[1][jnp.asarray(empty, jnp.int32)]).any()  # a group without rows: zeros, written


@pytest.mark.parametrize("sizes,shape,dtype", CASES[:6])
def test_the_visits_are_the_tiles_with_rows_and_rows_past_the_end_cost_none(sizes, shape, dtype):
    group_sizes = jnp.asarray(sizes, jnp.int32)
    by_hand = visits_by_hand(sizes, TM)
    (offsets, group_of, tile_of), n_visits = _visits(group_sizes, 128, TM, empty=False)
    assert int(n_visits) == len(by_hand) == int(row_tiles(group_sizes, TM))
    assert list(zip(np.asarray(tile_of)[: len(by_hand)], np.asarray(group_of)[: len(by_hand)])) == by_hand
    assert list(np.asarray(offsets)) == [0] + list(np.cumsum(sizes))
    # no visit is of a tile past the last row a group has
    assert all(tile_ * TM < sum(sizes) for tile_, _ in by_hand)
    # the weight gradient's visits: every group once at least, so that its zeros are written
    (_, group_of, tile_of), n_visits = _visits(group_sizes, 128, TM, empty=True)
    assert int(n_visits) == len(by_hand) + sum(1 for size in sizes if not size)
    assert sorted(set(np.asarray(group_of)[: int(n_visits)])) == list(range(len(sizes)))
    assert 0 <= int(tile_of.min()) and int(tile_of.max()) < 128 // TM


def test_garbage_past_the_end_never_reaches_a_result():
    """The kernels do not visit the rows past the last group: NaNs there, in
    ``lhs`` and in the cotangent, leave the output, both gradients and the
    rows' own cotangent finite, the last exactly zero."""
    sizes = (10, 30, 0, 25)
    lhs, rhs, weights = operands(sizes, (64, 128), jnp.float32)
    lhs, weights = lhs.at[65:].set(jnp.nan), weights.at[65:].set(jnp.nan)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    kernel = lambda l, r: grouped_matmul(l, r, group_sizes, row_tile=TM, interpret=True)
    out, (d_lhs, d_rhs) = jax.value_and_grad(lambda l, r: jnp.sum(jnp.where(jnp.isnan(weights), 0.0, kernel(l, r) * weights)), (0, 1))(lhs, rhs)
    assert np.isfinite(out) and np.isfinite(np.asarray(d_rhs)).all() and np.isfinite(np.asarray(d_lhs)).all()
    assert not np.asarray(d_lhs[65:]).any() and not np.asarray(kernel(lhs, rhs)[65:]).any()


@pytest.mark.parametrize(
    "d,want",
    [(2688, 896), (1856, 1856), (2048, 1024), (1024, 1024), (3712, 3712), (6144, 1024), (24, 24), (1536, 768)],
    ids=lambda v: str(v),
)
def test_tiles_follow_the_shapes(d, want):
    """Nemotron's (2688, 1856) and trinity's (2048, 1024) experts, and what
    else a width may be: a tile divides its dimension or is the whole of it."""
    assert tile(d) == want and d % tile(d) == 0 and (tile(d) % 128 == 0 or tile(d) == d)


def test_off_the_chip_the_public_function_is_ragged_dot():
    sizes = jnp.asarray((10, 75, 3, 20), jnp.int32)
    lhs, rhs, _ = operands((10, 75, 3, 20), (64, 128), jnp.float32)
    program = str(jax.make_jaxpr(lambda l, r: grouped_matmul(l, r, sizes))(lhs, rhs))
    assert "ragged_dot" in program and "pallas_call" not in program
    kernels = str(jax.make_jaxpr(lambda l, r: grouped_matmul(l, r, sizes, interpret=True))(lhs, rhs))
    assert "pallas_call" in kernels and "ragged_dot" not in kernels
    np.testing.assert_allclose(
        grouped_matmul(lhs, rhs, sizes), lax.ragged_dot(lhs, rhs, sizes).at[108:].set(0.0), rtol=1e-6, atol=1e-6
    )
