"""``held_experts_moe``'s first chunk follows the expected load (CPU, small
sizes): ``parallel.moe.chunk_rows`` is a rule over the layer's own shapes — T
rows where a rank holds a small share of the experts (every caller before
mellum), 3/2 of the expected load where it holds a large one. How many chunks
held live rows follows from ``held`` and the rule (the benchmark's
``moe_chunks`` reads it so). The later chunks stay what they were: the rare
heavy load, never a drop."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.ops.rows_to_tokens import rows_of_tokens, tokens_from_rows
from network_distributed_pytorch_tpu.parallel import moe
from network_distributed_pytorch_tpu.parallel.moe import chunk_rows, held_experts_moe

T, D, F = 64, 16, 8


def layer(e, held, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, shape, scale: jax.random.normal(k, shape) * scale
    return normal(ks[0], (T, D), 1.0), {
        "router": normal(ks[1], (D, e), 0.5), "gate": normal(ks[2], (len(held), D, F), 0.3),
        "up": normal(ks[3], (len(held), D, F), 0.3), "down": normal(ks[4], (len(held), F, D), 0.3),
    }


def routed(x, p, held, top_k, block_rows=8):
    return held_experts_moe(
        x, x, p["router"], jnp.zeros((p["router"].shape[1],)), p["up"], p["down"], held, top_k,
        block_rows=block_rows, w_gate=p["gate"], score="softmax",
    )


def dense(x, p, held, top_k):
    """The layer by a loop over the held experts, every token with its weight."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.softmax(x @ p["router"], axis=-1)
        picked, chosen = jax.lax.top_k(scores, top_k)
        weights = picked / picked.sum(-1, keepdims=True)
        out = jnp.zeros_like(x)
        for slot, expert in enumerate(held):
            weight = jnp.where(chosen == expert, weights, 0.0).sum(-1)
            out = out + weight[:, None] * ((jax.nn.silu(x @ p["gate"][slot]) * (x @ p["up"][slot])) @ p["down"][slot])
    return out


# (t, top_k, held, e) of the cells' expert layers -> expected load over t, rows over t
CALLERS = [
    pytest.param((8192, 6, 8, 128), 0.375, 1, id="nemotron"),
    pytest.param((8192, 8, 8, 128), 0.5, 1, id="trinity"),
    pytest.param((8192, 10, 16, 512), 0.3125, 1, id="qwen3next"),
    pytest.param((8192, 4, 8, 64), 0.5, 1, id="lfm2"),
    pytest.param((8192, 8, 16, 64), 2.0, 3, id="mellum-4-chip-share"),
    pytest.param((8192, 8, 8, 64), 1.0, 1.5, id="mellum-8-chip-share"),
]


@pytest.mark.parametrize("shape,load,chunk", CALLERS)
def test_the_chunk_is_t_rows_for_the_four_earlier_callers_and_follows_the_load_for_mellum(shape, load, chunk):
    t, top_k, n_held, e = shape
    assert t * top_k * n_held / e == load * t
    assert chunk_rows(t, top_k, n_held, e) == chunk * t  # whole 512-row tiles at these sizes
    assert chunk_rows(t, top_k, n_held, e) >= 1.5 * load * t or chunk == 1


def test_the_chunk_is_whole_row_tiles_and_never_more_than_every_assignment():
    assert chunk_rows(100, 2, 4, 16) == 512  # T rows, one 512-row tile
    assert chunk_rows(100, 2, 4, 16, block_rows=8) == 104
    assert chunk_rows(64, 8, 16, 64, block_rows=8) == 192  # 3/2 of 128
    assert chunk_rows(64, 2, 2, 2, block_rows=8) == 128  # 3/2 of the load is 192: no more than T * top_k there can be
    assert chunk_rows(64, 1, 4, 4, block_rows=8) == 64  # every token lands once: T rows hold them


def test_at_one_expert_in_eight_with_a_quarter_held_the_first_chunk_holds_the_load():
    """Mellum's numbers at a small size: top 2 of 16 (one assignment in eight an
    expert's), 4 held (a quarter): 32 expected of 128, chunk 3/2 of it."""
    e, held, top_k = 16, (0, 1, 2, 3), 2
    x, p = layer(e, held)
    out, counters = routed(x, p, held, top_k)
    assert chunk_rows(T, top_k, len(held), e, 8) == 64  # T: the load is T/2
    assert int(counters["held"].sum()) <= 64 and int(counters["dropped"]) == 0  # one chunk
    np.testing.assert_allclose(out, dense(x, p, held, top_k), rtol=2e-4, atol=2e-5)
    # the four-chip share of a router that sends every token to 8 of 16: expected 2 T, chunk 3 T
    held, top_k = tuple(range(4)), 8
    x, p = layer(e, held, seed=1)
    out, counters = routed(x, p, held, top_k)
    landed = int(counters["held"].sum())
    assert chunk_rows(T, top_k, len(held), e, 8) == 3 * T and T < landed <= 3 * T  # past T rows, inside the chunk
    assert int(counters["row_tiles"]) >= -(-landed // 8) and int(counters["dropped"]) == 0  # its products reach every row
    np.testing.assert_allclose(out, dense(x, p, held, top_k), rtol=2e-4, atol=2e-5)


def test_a_load_pushed_past_the_chunk_goes_on_in_later_chunks_and_drops_nothing():
    """All tokens alike: every token picks the same experts, so a held expert
    that is picked takes T assignments. With four held and top 4 of them
    picked the load is 4 T where 3/2 of the expected is 1.5 T."""
    e, held, top_k = 16, (0, 1, 2, 3), 4
    x, p = layer(e, held, seed=2)
    x = jnp.broadcast_to(x[:1], x.shape)
    # the router prefers the held experts for this token, so all four are picked by every token
    p["router"] = p["router"].at[:, :4].set(jnp.outer(x[0], jnp.ones(4)) * 0.5)
    out, counters = routed(x, p, held, top_k)
    rows = chunk_rows(T, top_k, len(held), e, 8)
    assert rows == 96  # 3/2 of T * 4 * 4/16
    assert int(counters["held"].sum()) == 4 * T and int(counters["absent"]) == 0
    assert -(-4 * T // rows) == 3 and int(counters["dropped"]) == 0  # three chunks held live rows
    assert int(counters["row_tiles"]) == rows // 8  # the first chunk is full: the later ones took the rest
    np.testing.assert_allclose(out, dense(x, p, held, top_k), rtol=2e-4, atol=2e-5)
    # and the gradient goes through the later chunks as through the first
    loss = lambda fn: lambda x, p: jnp.sum(jnp.sin(fn(x, p)))
    got = jax.grad(loss(lambda x, p: routed(x, p, held, top_k)[0]), argnums=(0, 1))(x, p)
    want = jax.grad(loss(lambda x, p: dense(x, p, held, top_k)), argnums=(0, 1))(x, p)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4)


# (e, held, top_k, every token alike) -> chunks that hold live rows
KERNEL_CASES = [
    pytest.param(16, (0, 1, 2, 3), 2, False, 1, id="a_quarter_of_t_in_a_chunk_of_t"),
    pytest.param(16, (0, 1, 2, 3), 8, False, 1, id="two_t_in_a_chunk_of_3t"),
    pytest.param(16, (0, 1, 2, 3), 4, True, 3, id="four_t_pushed_into_later_chunks"),
]


@pytest.mark.parametrize("e, held, top_k, alike, chunks", KERNEL_CASES)
def test_with_the_kernel_the_layer_computes_what_the_indexed_add_computes(monkeypatch, e, held, top_k, alike, chunks):
    """The layer as the chip runs it but for the compiler (the add of rows
    into tokens, forward and as the gather's cotangent, is the Pallas kernel,
    here in the interpreter) against the layer as the CPU runs it
    (``.at[token].add``): output, counters and gradients to fp32 rounding —
    a token's few terms may be summed in another order, nothing else."""
    x, p = layer(e, held, seed=3)
    if alike:  # every token picks the four held experts: 4 T rows where the chunk holds 1.5 T
        x = jnp.broadcast_to(x[:1], x.shape) + 1e-3 * x
        p["router"] = p["router"].at[:, :4].set(jnp.outer(x[0], jnp.ones(4)) * 0.5)

    def value_counters_and_gradients():
        value = lambda x, p: (lambda out, counters: (jnp.sum(jnp.sin(out)), (out, counters)))(*routed(x, p, held, top_k))
        return jax.value_and_grad(value, argnums=(0, 1), has_aux=True)(x, p)

    (_, (want, want_counters)), want_grads = value_counters_and_gradients()
    monkeypatch.setattr(moe, "rows_of_tokens", functools.partial(rows_of_tokens, interpret=True))
    monkeypatch.setattr(moe, "tokens_from_rows", functools.partial(tokens_from_rows, interpret=True))
    (_, (got, got_counters)), got_grads = value_counters_and_gradients()

    rows = chunk_rows(T, top_k, len(held), e, 8)
    assert max(-(-int(got_counters["held"].sum()) // rows), 1) == chunks and int(got_counters["dropped"]) == 0
    for name in want_counters:
        np.testing.assert_array_equal(got_counters[name], want_counters[name])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads), jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_the_counters_tree_is_the_layers_own():
    from network_distributed_pytorch_tpu.models.mellum import mellum_tiny
    from network_distributed_pytorch_tpu.models.layers import zero_counters

    zeros = zero_counters(mellum_tiny().config)
    assert sorted(zeros) == [f"layer_{i}" for i in range(4)]
    assert sorted(zeros["layer_0"]) == ["absent", "dropped", "held", "row_tiles"]
    x, p = layer(16, (0, 1, 2, 3))
    _, counters = routed(x, p, (0, 1, 2, 3), 2)
    assert jax.tree_util.tree_structure(counters) == jax.tree_util.tree_structure(zeros["layer_0"])
    assert all(c.dtype == jnp.int32 for c in jax.tree_util.tree_leaves(counters))
