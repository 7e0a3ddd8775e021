"""afmoe (Trinity) as the system trains it, on the CPU at small sizes: the
whole model's logits, loss and gradients and one PowerSGD step against the
benchmark's plain reference, the step's counters on the loop's spans, the
experiment's public entry in ``launch.py``, and the cell's rehearsal. The
layers are in ``test_afmoe.py``."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, compose
from benchmark.reference import afmoe as reference
from benchmark.reference import ef_momentum
from network_distributed_pytorch_tpu.models.afmoe import AfmoeConfig, afmoe_tiny
from network_distributed_pytorch_tpu.models.layers import (
    BUFFERS, FULL, SLIDING, balanced_expert_bias, next_token_lm_loss, zero_counters,
)
from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "trinity_psgd16_t8k"


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


def reference_cfg(c: AfmoeConfig) -> dict:
    """The model's config under the configuration file's (HuggingFace's) keys."""
    return dict(
        hidden_size=c.hidden_size, layer_types=list(c.layer_types), num_dense_layers=c.num_dense_layers,
        rms_norm_eps=c.norm_eps, num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads,
        head_dim=c.head_dim, sliding_window=c.sliding_window, rope_theta=c.rope_theta,
        num_experts_per_tok=c.experts_per_token, route_scale=c.route_scale, held_experts=list(c.held_experts),
    )


def seeded(model, seq_len):
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, seq_len + 1), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    # norm scales off 1, so that all four norms of a block and the head norms count
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params
    )
    return params, batch


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
@pytest.mark.parametrize("balanced", [False, True], ids=["no_buffers", "balanced_bias"])
@pytest.mark.parametrize("seq_len", [64, 40], ids=["four_windows", "ragged"])
def test_model_loss_and_gradients_match_the_plain_reference(seq_len, balanced, attn_impl):
    """A dense layer, then sliding, full, sliding, sliding with experts, as
    the cell's cut; T = 64 is four windows of 16. Every gradient, the four
    norms and the q/k norms among them; with an ``expert_bias`` that moves
    the routing, and with none; and the step's counters are the reference's
    own routing, expert for expert."""
    model = afmoe_tiny(remat=True, attn_impl=attn_impl)
    params, batch = seeded(model, seq_len)
    model_state = {BUFFERS: balanced_expert_bias(model, params, batch["input_ids"])} if balanced else {}
    (loss, state), grads = jax.jit(jax.value_and_grad(next_token_lm_loss(model), has_aux=True))(
        params, model_state, batch
    )
    want_loss, want_grads, want_state = reference.make_loss_and_grads(reference_cfg(model.config))(
        params, model_state, batch
    )
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert worst_relative(grads, want_grads) < 1e-4
    counters = state[STEP_COUNTERS]
    assert sorted(counters) == ["layer_1", "layer_2", "layer_3", "layer_4"] == sorted(zero_counters(model.config))
    for name, c in counters.items():
        assert int(c["dropped"]) == 0 and int(c["held"].sum() + c["absent"]) == 2 * seq_len * 2
        np.testing.assert_array_equal(c["held"], want_state["step_counters"][name]["held"])
        assert int(c["absent"]) == int(want_state["step_counters"][name]["absent"])
    if balanced:
        carried = jax.tree_util.tree_map(np.array_equal, state[BUFFERS], model_state[BUFFERS])
        assert all(jax.tree_util.tree_leaves(carried))  # handed on as they came


def test_a_router_scored_in_bfloat16_shows_in_the_counters_not_in_the_gradients_limits():
    """A router whose inputs are rounded to bf16 picks other experts for some
    tokens. The gradients stay inside the benchmark's per-tensor limit, so
    that limit does not see it; here, where the model computes in fp32, the
    held experts' counters differ from the reference's routing and are equal
    to it without the rounding (the test above). In the cell, whose residual
    stream is bf16, the program's own counters differ as much (PERF.md
    section 6)."""
    from benchmark.reference_check import TOLERANCES
    from network_distributed_pytorch_tpu.parallel import moe

    model = afmoe_tiny(held_experts=tuple(range(16)), experts_per_token=4)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 257), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    loss_and_grads = jax.value_and_grad(next_token_lm_loss(model), has_aux=True)
    _, want_grads, want_state = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    full = moe.held_experts_moe

    def rounded_router(x, router_in, router_kernel, *rest, **kw):
        low = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
        return full(x, low(router_in), low(router_kernel), *rest, **kw)

    moe.held_experts_moe = rounded_router
    try:
        (_, state), grads = loss_and_grads(params, {}, batch)
    finally:
        moe.held_experts_moe = full
    off = jax.tree_util.tree_map(
        lambda g, w: float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-30)), grads, want_grads
    )
    assert max(jax.tree_util.tree_leaves(off)) < TOLERANCES["update_each"]
    moved = sum(
        int(np.abs(np.asarray(c["held"]) - np.asarray(want_state["step_counters"][name]["held"])).sum())
        for name, c in state[STEP_COUNTERS].items()
    )
    assert moved > 0


def test_logits_match_and_a_swap_of_the_layer_kinds_does_not():
    """The model's logits against the reference's, and against the reference
    with every layer's kind swapped (rotary and window where the model has
    none, none where it has them): the first must hold, the second fail."""
    model = afmoe_tiny()
    params, batch = seeded(model, 64)
    logits, _ = model.apply({"params": params}, batch["input_ids"])
    cfg = reference_cfg(model.config)
    swapped = dict(cfg, layer_types=[FULL if kind == SLIDING else SLIDING for kind in cfg["layer_types"]])
    with jax.default_matmul_precision("highest"):
        want, other = (
            jnp.stack([reference._logits(params, ids, c) for ids in batch["input_ids"]]) for c in (cfg, swapped)
        )
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.linalg.norm(logits - other) / jnp.linalg.norm(other)) > 1e-2


def rehearsal_cell():
    cell = cells.cell(CELL)
    return cell, compose.resolved(cell["config"], cell["workload"], rehearsal=True)


def test_one_powersgd_step_matches_algorithm_2_over_the_plain_reference():
    """``make_train_step`` (PowerSGD rank 2, EF momentum) on two workers at
    the rehearsal sizes against ``reference/ef_momentum.run``."""
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    mesh = make_mesh(devices=jax.devices()[:2])
    built = cells.module("builders", "afmoe").build(cfg, 5, mesh)
    batch = jax.device_get(next(built.batches(0)))
    params0 = jax.device_get(built.state.params)
    # as the harness hands it on: worker 0's model state, the balanced buffers in it
    model_state0 = jax.tree_util.tree_map(lambda x: x[0], jax.device_get(built.state.model_state))
    assert any(np.asarray(b).any() for b in jax.tree_util.tree_leaves(model_state0[BUFFERS]))
    q0 = np.asarray(jax.device_get(built.state.reducer_state.q_memory))
    state, loss = built.step(built.state, batch)
    per_worker = cfg["per_chip_batch"]
    shards = [[jax.tree_util.tree_map(lambda x, w=w: x[w * per_worker:(w + 1) * per_worker], batch) for w in range(2)]]
    out = ef_momentum.run(
        cells.module("reference", "afmoe").make_loss_and_grads(cfg), params0, model_state0, q0, shards,
        cfg["reducer"], cfg["learning_rate"], cfg["momentum"],
    )
    assert abs(float(loss) - out["losses"][0]) < 1e-5
    first = out["after_first"]
    assert built.step.bits_per_step // 8 - first["wire_bytes"] == 4  # the loss all-reduce
    whole = np.sqrt(sum(float(np.vdot(d, d)) for d in first["delta"]))
    for got, want in zip(jax.tree_util.tree_leaves(jax.device_get(state.momenta)), first["delta"]):
        assert np.linalg.norm(got - want) <= 1e-4 * whole
    for got, want in zip(jax.tree_util.tree_leaves(jax.device_get(state.params)), first["params"]):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    counters = jax.device_get(state.model_state[STEP_COUNTERS])
    assert len(counters) == 4 and all(c["held"].shape == (2, 4) and not c["dropped"].any() for c in counters.values())


def test_train_loop_puts_the_steps_counters_on_its_loss_sync_span():
    from network_distributed_pytorch_tpu.experiments.common import train_loop
    from network_distributed_pytorch_tpu.observe.sinks import MemorySink
    from network_distributed_pytorch_tpu.observe.telemetry import Telemetry
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    built = cells.module("builders", "afmoe").build(cfg, 1, make_mesh(devices=jax.devices()[:1]))
    sink = MemorySink()
    train_loop(
        built.step, built.state, built.batches, epochs=1, telemetry=Telemetry([sink]),
        on_step_end=lambda epoch, done, state: done >= 3,
    )
    syncs = [r for r in sink.of_kind("span") if r["name"] == "step/loss_sync"]
    assert len(syncs) == 3
    tokens = cfg["per_chip_batch"] * cfg["seq_len"] * cfg["num_experts_per_tok"]
    for record in syncs:
        json.dumps(record)  # plain lists and ints: a JSON sink can write it
        assert sorted(record["counters"]) == ["layer_1", "layer_2", "layer_3", "layer_4"]
        for layer in record["counters"].values():
            assert sum(layer["held"][0]) + layer["absent"][0] == tokens and layer["dropped"] == [0]


def test_the_experiment_runs_through_its_public_entry_in_launch():
    from network_distributed_pytorch_tpu import launch
    from network_distributed_pytorch_tpu.experiments import powersgd_afmoe

    assert launch.EXPERIMENTS["powersgd_afmoe"] is powersgd_afmoe.run
    out = launch.main([
        "powersgd_afmoe", "--global-batch", "8", "--reducer-rank", "2", "--lr", "5e-5",
        "--epochs", "1", "--max-steps-per-epoch", "3", "--log-every", "0",
    ])
    assert out["experiment"] == "powersgd_afmoe" and out["steps"] == 3 and np.isfinite(out["final_loss"])
    assert out["model"]["layer_types"].count("full_attention") == 1 and out["model"]["sliding_window"] == 16
    assert out["last_step_assignments"]["dropped"] == 0
    assert out["last_step_assignments"]["held"] + out["last_step_assignments"]["absent"] == 8 * 64 * 2 * 4


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed", "2147483659",
         "--seconds", "0.5", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    # the three counts (the chunk's two since PR 44); no device metric from a CPU
    assert set(last["metrics"]) == {"expert_load_max_over_mean", "moe_chunks", "moe_chunk_fill_pct"}
    assert last["metrics"]["moe_chunks"]["value"] == 1.0
    assert "0 dropped" in done.stdout
