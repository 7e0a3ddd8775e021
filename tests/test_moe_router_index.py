"""The router's index work as compares and one sort, against the gathers and
scatters it replaced (CPU).

``held_experts_moe`` indexed T*top_k scalars by data four times: the chosen
scores (``take_along_axis``), the slot of each chosen expert (a table lookup),
the count of each slot (a scatter-add of ones) and the weights in sorted order
(``weights[order]``). Each is restated in ``parallel/moe.py`` without a gather
or a scatter (``_picked``, ``_slots``, ``_counts``, ``_sorted_by_slot``). The
four lines as they were are kept here as the plain reference: each restatement
alone against its line, and the whole layer (output, counters, every gradient)
against the layer with the four lines put back.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.parallel import moe

D, F = 16, 8


# ---- the four lines as they were ----------------------------------------------


def indexed_picked(scores, chosen):
    return jnp.take_along_axis(scores, chosen, axis=-1)


def indexed_slots(chosen, held, e):
    slot_of = np.full((e,), len(held), np.int32)
    slot_of[list(held)] = np.arange(len(held))
    return jnp.asarray(slot_of)[chosen]


def indexed_counts(slots, n):
    return jnp.zeros((n,), jnp.int32).at[slots].add(1)


def indexed_sorted_by_slot(slots, weights):
    order = jnp.argsort(slots, stable=True)
    return order, weights[order]


def with_the_four_lines(monkeypatch, e):
    monkeypatch.setattr(moe, "_picked", indexed_picked)
    monkeypatch.setattr(moe, "_slots", lambda chosen, held: indexed_slots(chosen, held, e))
    monkeypatch.setattr(moe, "_counts", indexed_counts)
    monkeypatch.setattr(moe, "_sorted_by_slot", indexed_sorted_by_slot)


# ---- each restatement alone ---------------------------------------------------


def routed(t, e, k, seed=0):
    """Scores with ties nowhere, and the top k of each row."""
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(seed), (t, e)))
    return scores, jax.lax.top_k(scores, k)[1]


@pytest.mark.parametrize("t, e, k", [(128, 16, 2), (32, 8, 3), (40, 512, 10)])
def test_the_pick_and_its_cotangent_are_the_gathers(t, e, k):
    scores, chosen = routed(t, e, k)
    np.testing.assert_array_equal(moe._picked(scores, chosen), indexed_picked(scores, chosen))
    mark = jnp.cos(jnp.arange(t * k, dtype=jnp.float32)).reshape(t, k)  # a cotangent that differs by place
    grad = lambda pick: jax.grad(lambda s: jnp.sum(pick(s, chosen) * mark))(scores)
    np.testing.assert_array_equal(grad(moe._picked), grad(indexed_picked))
    assert int(jnp.sum(grad(moe._picked) != 0)) == t * k


@pytest.mark.parametrize("e, k, held", [(16, 2, (0, 1, 2, 3)), (8, 3, (1, 4, 6)), (512, 10, tuple(range(5, 21)))])
def test_the_slots_and_their_counts_are_the_table_s_and_the_scatter_add_s(e, k, held):
    _, chosen = routed(64, e, k, seed=1)
    slots = moe._slots(chosen.reshape(-1), held)
    assert slots.dtype == jnp.int32
    np.testing.assert_array_equal(slots, indexed_slots(chosen.reshape(-1), held, e))
    counts = moe._counts(slots, len(held) + 1)
    assert counts.dtype == jnp.int32 and int(counts.sum()) == 64 * k
    np.testing.assert_array_equal(counts, indexed_counts(slots, len(held) + 1))


def test_the_sorted_weights_custom_vjp_is_the_gather_s():
    """Against ``jax.grad`` of ``weights[order]``: nine slots over 600
    assignments, so every slot is a long run of ties the stable sort keeps in
    place."""
    slots = jax.random.randint(jax.random.PRNGKey(2), (600,), 0, 9)
    weights = jax.random.uniform(jax.random.PRNGKey(3), (600,))
    order, carried = moe._sorted_by_slot(slots, weights)
    want_order, want = indexed_sorted_by_slot(slots, weights)
    assert order.dtype == jnp.int32
    np.testing.assert_array_equal(order, want_order)
    np.testing.assert_array_equal(carried, want)
    mark = jnp.cos(jnp.arange(600, dtype=jnp.float32))
    grad = lambda by_slot: jax.grad(lambda w: jnp.sum(jnp.sin(by_slot(slots, w)[1]) * mark))(weights)
    np.testing.assert_array_equal(grad(moe._sorted_by_slot), grad(indexed_sorted_by_slot))
    program = str(jax.make_jaxpr(lambda: grad(moe._sorted_by_slot))())
    assert "gather" not in program and "scatter" not in program and program.count(" sort[") == 2
    assert "gather" in str(jax.make_jaxpr(lambda: grad(indexed_sorted_by_slot))())


# ---- the whole layer ------------------------------------------------------------

TINY = dict(t=128, e=16, k=2, held=(0, 1, 2, 3))  # the three models' test-tier expert layers
ODD = dict(t=32, e=8, k=3, held=(1, 4, 6))  # held is no prefix of the experts, and as many as a token takes
LAYERS = [
    pytest.param(dict(TINY, gated=False, score="sigmoid", scaling=2.5), id="nemotron_tiny"),
    pytest.param(dict(TINY, gated=True, score="sigmoid", scaling=2.826), id="afmoe_tiny"),
    pytest.param(dict(TINY, gated=True, score="softmax", scaling=1.0), id="qwen3next_tiny"),
    pytest.param(dict(ODD, gated=False, score="sigmoid", scaling=2.5), id="held_146_sigmoid"),
    pytest.param(dict(ODD, gated=True, score="softmax", scaling=1.0), id="held_146_softmax"),
]


def select_bias(traffic, e, k, held):
    """A bias for each kind of batch: none, one that moves some picks, one that
    sends every token to absent experts only, one that sends every token to
    held experts only (T * k assignments land: the later chunks are entered)."""
    if traffic == "no_bias":
        return jnp.zeros((e,))
    if traffic == "bias":
        return jax.random.normal(jax.random.PRNGKey(7), (e,)) * 0.3
    absent = [i for i in range(e) if i not in held]
    return jnp.zeros((e,)).at[jnp.asarray(absent[:k] if traffic == "all_absent" else list(held[:k]))].set(10.0)


def value_counters_and_gradients(layer, traffic, jit):
    """``(out, counters, gradients to x, router_kernel, w_in, w_out, w_gate,
    the layer's jaxpr)`` with ``moe``'s helpers as they stand when called."""
    t, e, k, held = layer["t"], layer["e"], layer["k"], layer["held"]
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    shapes = [(t, D), (D, e), (len(held), D, F), (len(held), F, D), (len(held), D, F)]
    operands = [jax.random.normal(key, s) * 0.5 for key, s in zip(keys, shapes)]
    bias = select_bias(traffic, e, k, held)

    def layer_of(x, router, w_in, w_out, w_gate):
        return moe.held_experts_moe(
            x, x, router, bias, w_in, w_out, held, k, layer["scaling"], block_rows=8,
            w_gate=w_gate if layer["gated"] else None, score=layer["score"],
        )

    def loss(*operands):
        out, counters = layer_of(*operands)
        return jnp.sum(jnp.sin(out)), (out, counters)

    step = jax.value_and_grad(loss, argnums=range(5), has_aux=True)
    (_, (out, counters)), grads = (jax.jit(step) if jit else step)(*operands)
    return out, counters, grads, str(jax.make_jaxpr(layer_of)(*operands))


def both_ways(layer, traffic, monkeypatch, jit):
    mine = value_counters_and_gradients(layer, traffic, jit)
    with monkeypatch.context() as patched:
        with_the_four_lines(patched, layer["e"])
        indexed = value_counters_and_gradients(layer, traffic, jit)
    assert mine[3].count("gather") < indexed[3].count("gather")  # the four lines were back
    return mine[:3], indexed[:3]


@pytest.mark.parametrize("traffic", ["no_bias", "bias", "all_absent", "past_T"])
@pytest.mark.parametrize("layer", LAYERS)
def test_the_layer_equals_the_layer_with_the_four_lines_put_back(layer, traffic, monkeypatch):
    """To the bit, primitive by primitive (no jit round the whole: inside one
    XLA is free to add a token's k picks in another order, below)."""
    (out, counters, grads), (want_out, want_counters, want_grads) = both_ways(layer, traffic, monkeypatch, jit=False)
    t, k = layer["t"], layer["k"]
    assert set(counters) == {"held", "absent", "dropped", "row_tiles"}
    for name in counters:
        assert counters[name].dtype == jnp.int32
        np.testing.assert_array_equal(counters[name], want_counters[name], err_msg=name)
    landed = int(counters["held"].sum())
    assert int(counters["dropped"]) == 0 and landed + int(counters["absent"]) == t * k
    if traffic == "all_absent":
        assert landed == 0 and not np.any(out) and not np.any(grads[2])
    if traffic == "past_T":
        assert landed == t * k > t
    np.testing.assert_array_equal(out, want_out)
    for name, got, want in zip(("x", "router_kernel", "w_in", "w_out", "w_gate"), grads, want_grads):
        np.testing.assert_array_equal(got, want, err_msg=name)
    some_landed = traffic != "all_absent"
    assert np.any(grads[1]) == some_landed and np.any(grads[4]) == (layer["gated"] and some_landed)


@pytest.mark.parametrize("layer", LAYERS)
def test_under_one_jit_the_layer_is_the_same_to_rounding(layer, monkeypatch):
    """As the step runs it. XLA merges the pick's reduce over E with the sum
    over the k picks that normalises them, and may add three picks in another
    order than the gather's program does: an ulp of a weight (seen on the CPU
    at k = 3; at k = 2 there is one order only). Integers stay exact."""
    (out, counters, grads), (want_out, want_counters, want_grads) = both_ways(layer, "bias", monkeypatch, jit=True)
    for name in counters:
        np.testing.assert_array_equal(counters[name], want_counters[name], err_msg=name)
    for name, got, want in zip(("out", "x", "router_kernel", "w_in", "w_out", "w_gate"), (out,) + grads, (want_out,) + want_grads):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6 * float(jnp.max(jnp.abs(want))), err_msg=name)
