"""The plain references against the repo's own models, at tiny sizes in
float32 on the CPU: same loss, same gradients; and Algorithm 2 written out
over W simulated workers against the system's step on a W-device mesh is what
the rehearsal's ``correct`` covers (test_run.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, compose, traffic
from benchmark.reference import ef_momentum
from benchmark.reference_check import compare_trees, rank_deficient


def _cos(a, b):
    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


@pytest.mark.parametrize("cell_name", ["imdb_psgd16_b16", "cifar_psgd4_b128"])
def test_reference_loss_and_gradients_match_the_model(cell_name):
    cell = cells.cell(cell_name)
    cfg = compose.resolved(cell["config"], cell["workload"], rehearsal=True)
    builder = cells.module("builders", cell["config"]["builder"])
    model = builder.model_of(cfg)
    variables = builder.init_fn_of(model, cfg)(jax.random.PRNGKey(0))
    n = 8
    if "seq_len" in cfg:
        params, state = variables, {}
        pool = traffic.token_sequences({**cfg["traffic"], "seq_len": cfg["seq_len"], "length_median": 12},
                                       cfg["vocab_size"], 0)
        batch = {k: jnp.asarray(v[:n]) for k, v in pool.items()}
        loss_fn = builder.loss_fn_of(model)
    else:
        from network_distributed_pytorch_tpu.experiments.common import image_classifier_loss

        params, state = variables["params"], {"batch_stats": variables["batch_stats"]}
        # the zero-initialised last batch-norm scale of every block hides the branch: wake it up
        params = jax.tree_util.tree_map(lambda x: x + 0.1, params)
        x, y = traffic.images({**cfg["traffic"], "image_shape": cfg["image_shape"], "num_classes": 10}, 0)
        batch = (jnp.asarray(x[:n]), jnp.asarray(y[:n]))
        loss_fn = image_classifier_loss(model, has_batch_stats=True)
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, state, batch)
    ref = cells.module("reference", cell["config"]["builder"]).make_loss_and_grads(cfg)
    ref_loss, ref_grads, _ = ref(params, state, batch)
    assert float(ref_loss) == pytest.approx(float(loss), rel=1e-5)
    whole = np.sqrt(sum(float(jnp.vdot(g, g)) for g in jax.tree_util.tree_leaves(grads)))
    for got, want in zip(jax.tree_util.tree_leaves(ref_grads), jax.tree_util.tree_leaves(grads)):
        if float(jnp.linalg.norm(want)) > 1e-4 * whole:  # not a gradient that is zero but for rounding
            assert _cos(got, want) > 0.9999
            assert float(jnp.linalg.norm(got) / jnp.linalg.norm(want)) == pytest.approx(1.0, abs=1e-3)


def test_unpack_qs_follows_leaf_order():
    leaves = [np.zeros((5, 3)), np.zeros((7,)), np.zeros((2, 2, 4))]
    flat = np.arange(3 * 2 + 4 * 2, dtype=np.float32)
    qs = ef_momentum.unpack_qs(flat, leaves, rank=2, matricize="last")
    assert [q.shape for q in qs] == [(3, 2), (4, 2)]
    assert qs[1][0, 0] == 6


def test_rank_deficient_send_is_held_to_a_bound_only():
    rng = np.random.default_rng(0)
    column = rng.standard_normal((6, 1)).astype(np.float32)
    rank_one = np.concatenate([column, -column], axis=1)  # a two-label classifier's gradient
    q = rng.standard_normal((2, 2)).astype(np.float32)
    assert rank_deficient([rank_one], q, "last", 1e-4)
    assert not rank_deficient([rng.standard_normal((6, 2)).astype(np.float32)], q, "last", 1e-4)
    assert rank_deficient([np.zeros((6, 2), np.float32)], q, "last", 1e-4)


def test_compare_trees_catches_a_wrong_tensor():
    rng = np.random.default_rng(1)
    want = [rng.standard_normal((4, 4)).astype(np.float32) for _ in range(3)]
    scale = [float(np.linalg.norm(w)) for w in want]
    args = (scale, [False] * 3, list("abc"), 0.1, 0.05)
    ok = compare_trees("t", [w * 1.001 for w in want], want, *args)
    assert ok["ok"] and ok["compared"] == 3 and ok["worst_off"] < 0.002
    halved = compare_trees("t", [want[0] * 0.5] + want[1:], want, *args)
    assert not halved["ok"] and "a:" in halved["failures"][0]
    noisy = [w + 0.2 * rng.standard_normal(w.shape).astype(np.float32) for w in want]
    assert not compare_trees("t", noisy, want, *args)["ok"]
    # a small residual of a large send is judged on the send's scale
    small = [0.01 * w for w in want]
    off = [0.012 * w for w in want]
    assert compare_trees("t", off, small, *args)["ok"]
    # a tensor held to a bound only may point anywhere, but may not blow up
    loose = (scale, [True, False, False], list("abc"), 0.1, 0.05)
    assert compare_trees("t", [-want[0]] + want[1:], want, *loose)["ok"]
    assert not compare_trees("t", [want[0] * 100] + want[1:], want, *loose)["ok"]
