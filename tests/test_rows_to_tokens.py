"""The add of rows into their tokens (``ops/rows_to_tokens.py``) in interpret
mode on the CPU: the kernel against ``.at[token].add(mode="drop")`` in fp32
(what the public function is off the chip), and the value and cotangents of
the two public functions against jax's own differentiation of the indexed
forms. The kernel compiles for the chip in ``test_tpu_lowering.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from network_distributed_pytorch_tpu.ops.rows_to_tokens import _TOKENS, _ranges, rows_of_tokens, tokens_from_rows


def assignments(t, m, tokens_of_runs, nan=True, d=32, dtype=jnp.float32, seed=0):
    """Rows as the expert layer lays them: each run's tokens ascending, run
    after run, then rows that carry token ``t`` and NaNs in their memory."""
    sizes = np.array([len(tokens) for tokens in tokens_of_runs], np.int32)
    landed = int(sizes.sum())
    assert landed <= m and all(list(tokens) == sorted(set(tokens)) for tokens in tokens_of_runs)
    token = np.full((m,), t, np.int32)
    token[:landed] = np.concatenate([np.asarray(tokens, np.int32) for tokens in tokens_of_runs])
    rows = np.random.default_rng(seed).standard_normal((m, d)).astype(np.float32)
    rows[landed:] = np.nan if nan else 0.0
    return jnp.asarray(rows).astype(dtype), jnp.asarray(token), jnp.asarray(sizes)


def routed(t, runs, top_k, experts, seed=0):
    """Each token on ``top_k`` of ``experts`` experts, the first ``runs`` held."""
    rng = np.random.default_rng(seed)
    picks = np.stack([rng.permutation(experts)[:top_k] for _ in range(t)])
    return [np.nonzero((picks == run).any(axis=1))[0] for run in range(runs)]


def indexed_add(rows, token, t):
    """The scatter-add the kernel stands for, in fp32, NaNs past the runs left out."""
    live = jnp.where((token < t)[:, None], rows.astype(jnp.float32), 0.0)
    return jnp.zeros((t, rows.shape[1]), jnp.float32).at[token].add(live, mode="drop")


T = 2 * _TOKENS + 76  # three tiles of tokens, the last a ragged one
CASES = {
    "a_token_on_every_run": (64, 256, [range(64)] * 4),  # top_k = held: each token four times
    "routed_rows_of_t": (T, T, routed(T, 4, 3, 16)),  # 0.75 T rows land
    "routed_rows_of_3t": (T, 3 * T, routed(T, 6, 5, 12, seed=1)),  # 2.5 T: a token on up to five runs
    "a_tile_of_tokens_without_a_row": (T, T, [range(5, 40), range(2 * _TOKENS, T), range(0, 7)]),
    "every_row_on_one_tile": (T, T, [range(_TOKENS, _TOKENS + 300), range(_TOKENS + 100, 2 * _TOKENS)]),
    "no_rows_at_all": (T, 64, [[], []]),
    "an_empty_run_between_two": (40, 96, [range(3, 33), [], range(0, 40, 3)]),
    "rows_no_multiple_of_a_copy": (40, 50, [range(10), range(5, 30)]),
    "every_row_live": (48, 64, [range(0, 32), range(16, 48)]),
}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("d", [288, 336], ids=["d_2304_over_8", "d_2688_over_8"])
def test_the_kernel_adds_what_the_indexed_add_adds(case, d):
    t, m, runs = CASES[case]
    rows, token, sizes = assignments(t, m, runs, d=d)
    got = tokens_from_rows(rows, token, sizes, t, interpret=True)
    assert got.shape == (t, d) and got.dtype == jnp.float32
    # a token's terms are few: the sums differ by their order alone
    np.testing.assert_allclose(got, indexed_add(rows, token, t), rtol=0, atol=2e-6)


@pytest.mark.parametrize("case", ["routed_rows_of_3t", "an_empty_run_between_two"])
def test_bf16_rows_are_summed_in_fp32_and_rounded_once(case):
    t, m, runs = CASES[case]
    rows, token, sizes = assignments(t, m, runs, d=128, dtype=jnp.bfloat16)
    got = tokens_from_rows(rows, token, sizes, t, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got, indexed_add(rows, token, t).astype(jnp.bfloat16))


def test_off_the_chip_the_public_functions_are_the_indexed_forms():
    t, m, runs = CASES["routed_rows_of_t"]
    rows, token, sizes = assignments(t, m, runs, nan=False)
    np.testing.assert_array_equal(tokens_from_rows(rows, token, sizes, t), indexed_add(rows, token, t))
    x = jax.random.normal(jax.random.PRNGKey(0), (t, 32))
    np.testing.assert_array_equal(rows_of_tokens(x, token, sizes), x.at[token].get(mode="fill", fill_value=0))
    jaxpr = str(jax.make_jaxpr(lambda r, x: (tokens_from_rows(r, token, sizes, t), rows_of_tokens(x, token, sizes)))(rows, x))
    assert "scatter-add" in jaxpr and "pallas_call" not in jaxpr and "custom_vjp" not in jaxpr


def test_the_ranges_are_each_runs_rows_of_each_tile():
    t, m, runs = CASES["a_tile_of_tokens_without_a_row"]
    _, token, sizes = assignments(t, m, runs)
    tiles = -(-t // _TOKENS)
    ranges = np.asarray(_ranges(token, sizes, tiles, _TOKENS))
    assert ranges.shape == (len(runs) * tiles + 1,) and ranges[0] == 0 and ranges[-1] == sizes.sum()
    starts = np.concatenate([[0], np.cumsum(sizes)])
    for run, tokens in enumerate(runs):
        for tile in range(tiles):
            inside = [i for i, tok in enumerate(tokens) if tok // _TOKENS == tile]
            first, last = ranges[run * tiles + tile], ranges[run * tiles + tile + 1]
            assert last - first == len(inside)
            assert not inside or first == starts[run] + inside[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("case", ["routed_rows_of_t", "routed_rows_of_3t", "a_token_on_every_run"])
def test_value_and_cotangents_equal_jaxs_own_of_the_indexed_forms(case, dtype):
    """Through both functions at once, as the layer uses them: tokens to rows,
    something not linear, rows back to tokens. Each one's cotangent is the
    other, so the gather's runs the kernel too; in bf16 the kernel's sums
    are fp32 rounded once where jax's scatter-add rounds every add."""
    t, m, runs = CASES[case]
    weights, token, sizes = assignments(t, m, runs, nan=False, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (t, 32)).astype(dtype)
    weights = weights.astype(dtype)

    def through(gather, add):
        def loss(x, weights):
            rows = gather(x) * jnp.tanh(weights)
            return jnp.sum(jnp.sin(add(rows).astype(jnp.float32)))
        return jax.value_and_grad(loss, argnums=(0, 1))(x, weights)

    kernel = through(lambda x: rows_of_tokens(x, token, sizes, interpret=True), lambda r: tokens_from_rows(r, token, sizes, t, interpret=True))
    plain = through(
        lambda x: x.at[token].get(mode="fill", fill_value=0),
        lambda r: jnp.zeros((t, 32), jnp.float32).at[token].add(r, mode="drop").astype(dtype),
    )
    tolerance = dict(rtol=0, atol=1e-5) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    for got, want in zip(jax.tree_util.tree_leaves(kernel), jax.tree_util.tree_leaves(plain)):
        assert got.dtype == want.dtype
        np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), **tolerance)


def test_the_gathers_cotangent_in_bf16_is_the_fp32_sum_rounded_once():
    t, m, runs = CASES["a_token_on_every_run"]
    cotangent, token, sizes = assignments(t, m, runs, nan=False, dtype=jnp.bfloat16)
    x = jnp.zeros((t, 32), jnp.bfloat16)
    _, pull = jax.vjp(lambda x: rows_of_tokens(x, token, sizes, interpret=True), x)
    (d_x,) = pull(cotangent)
    assert d_x.dtype == jnp.bfloat16
    np.testing.assert_array_equal(d_x, indexed_add(cotangent, token, t).astype(jnp.bfloat16))


def test_a_second_derivative_goes_through_both():
    t, m, runs = CASES["every_row_live"]
    rows, token, sizes = assignments(t, m, runs, nan=False)
    kernel = lambda r: jnp.sum(tokens_from_rows(r, token, sizes, t, interpret=True) ** 3)
    plain = lambda r: jnp.sum(indexed_add(r, token, t) ** 3)
    direction = jnp.ones_like(rows)
    second = lambda f: jax.grad(lambda r: jnp.vdot(jax.grad(f)(r), direction))(rows)  # reverse over reverse
    np.testing.assert_allclose(second(kernel), second(plain), rtol=1e-5, atol=1e-5)


def test_inside_shard_map_each_worker_adds_its_own_rows():
    """Where every training step runs (``check_vma=True``): the kernel's
    output declares how it varies over the mesh, rows and tokens a worker's
    own, and the cotangents type as their primals do."""
    t, m, runs = CASES["an_empty_run_between_two"]
    workers = [assignments(t, m, [np.asarray(r) for r in runs], nan=False, seed=s) for s in (0, 1)]
    rows, token, sizes = (jnp.stack(leaves) for leaves in zip(*workers))
    x = jax.random.normal(jax.random.PRNGKey(2), (t, 32))  # shared by the workers
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def worker(interpret, x, rows, token, sizes):
        def loss(x, rows):
            back = tokens_from_rows(rows * rows_of_tokens(x, token[0], sizes[0], interpret=interpret), token[0], sizes[0], t, interpret=interpret)
            return jnp.sum(jnp.sin(back))
        value, (d_x, d_rows) = jax.value_and_grad(loss, argnums=(0, 1))(x, rows[0])
        return value[None], jax.lax.psum(d_x, "data"), d_rows[None]

    specs = dict(mesh=mesh, in_specs=(P(), P("data"), P("data"), P("data")), out_specs=(P("data"), P(), P("data")), check_vma=True)
    kernel = jax.jit(jax.shard_map(lambda *a: worker(True, *a), **specs))(x, rows, token, sizes)
    plain = jax.jit(jax.shard_map(lambda *a: worker(None, *a), **specs))(x, rows, token, sizes)
    for got, want in zip(kernel, plain):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
