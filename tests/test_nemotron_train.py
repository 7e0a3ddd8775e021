"""Nemotron-H as the system trains it, on the CPU at small sizes: the whole
model's loss and gradients and one PowerSGD step against the benchmark's plain
reference, the step's counters on the loop's spans, the experiment's public
entry, and the cell's rehearsal. The layers are in ``test_nemotron_h.py``."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, compose
from benchmark.reference import ef_momentum
from benchmark.reference import nemotron_h as reference
from network_distributed_pytorch_tpu.models.layers import next_token_lm_loss
from network_distributed_pytorch_tpu.models.nemotron_h import NemotronHConfig, nemotron_h_tiny
from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


def reference_cfg(c: NemotronHConfig) -> dict:
    """The model's config under the configuration file's (HuggingFace's) keys."""
    return dict(
        mamba_num_heads=c.mamba_heads, mamba_head_dim=c.mamba_head_dim, n_groups=c.mamba_groups,
        ssm_state_size=c.state_size, norm_eps=c.norm_eps, num_experts_per_tok=c.experts_per_token,
        routed_scaling_factor=c.routed_scaling, held_experts=list(c.held_experts),
        num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads, head_dim=c.head_dim,
        hybrid_override_pattern=c.pattern,
    )


@pytest.mark.parametrize("seq_len", [64, 60], ids=["whole_chunks", "ragged_tail"])
def test_model_loss_and_gradients_match_the_plain_reference(seq_len):
    model = nemotron_h_tiny(remat=True)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, seq_len + 1), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    (loss, state), grads = jax.jit(jax.value_and_grad(next_token_lm_loss(model), has_aux=True))(params, {}, batch)
    want_loss, want_grads, _ = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert worst_relative(grads, want_grads) < 1e-4
    counters = state[STEP_COUNTERS]
    assert sorted(counters) == ["layer_1", "layer_3", "layer_6", "layer_8"]
    for c in counters.values():
        assert int(c["dropped"]) == 0 and int(c["held"].sum() + c["absent"]) == 2 * seq_len * 2


def rehearsal_cell():
    cell = cells.cell("nemotron_psgd16_t8k")
    return cell, compose.resolved(cell["config"], cell["workload"], rehearsal=True)


def test_one_powersgd_step_matches_algorithm_2_over_the_plain_reference():
    """``make_train_step`` (PowerSGD rank 2, EF momentum) on two workers at
    the rehearsal sizes against ``reference/ef_momentum.run``."""
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    mesh = make_mesh(devices=jax.devices()[:2])
    built = cells.module("builders", "nemotron_h").build(cfg, 5, mesh)
    batch = jax.device_get(next(built.batches(0)))
    params0 = jax.device_get(built.state.params)
    q0 = np.asarray(jax.device_get(built.state.reducer_state.q_memory))
    state, loss = built.step(built.state, batch)
    per_worker = cfg["per_chip_batch"]
    shards = [[jax.tree_util.tree_map(lambda x, w=w: x[w * per_worker:(w + 1) * per_worker], batch) for w in range(2)]]
    out = ef_momentum.run(
        cells.module("reference", "nemotron_h").make_loss_and_grads(cfg), params0, {}, q0, shards,
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
    assert all(c["held"].shape == (2, 4) and not c["dropped"].any() for c in counters.values())


def test_train_loop_puts_the_steps_counters_on_its_loss_sync_span():
    from network_distributed_pytorch_tpu.experiments.common import train_loop
    from network_distributed_pytorch_tpu.observe.sinks import MemorySink
    from network_distributed_pytorch_tpu.observe.telemetry import Telemetry
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    built = cells.module("builders", "nemotron_h").build(cfg, 1, make_mesh(devices=jax.devices()[:1]))
    sink = MemorySink()
    train_loop(
        built.step, built.state, built.batches, epochs=1, telemetry=Telemetry([sink]),
        on_step_end=lambda epoch, done, state: done >= 3,
    )
    syncs = [r for r in sink.of_kind("span") if r["name"] == "step/loss_sync"]
    others = [r for r in sink.of_kind("span") if r["name"] != "step/loss_sync"]
    assert len(syncs) == 3 and all(r["counters"] is None for r in others)
    tokens = cfg["per_chip_batch"] * cfg["seq_len"] * cfg["num_experts_per_tok"]
    for record in syncs:
        json.dumps(record)  # plain lists and ints: a JSON sink can write it
        for layer in record["counters"].values():
            assert sum(layer["held"][0]) + layer["absent"][0] == tokens and layer["dropped"] == [0]


def test_the_experiment_runs_through_its_public_entry():
    from network_distributed_pytorch_tpu.experiments import powersgd_nemotron
    from network_distributed_pytorch_tpu.launch import EXPERIMENTS
    from network_distributed_pytorch_tpu.parallel import make_mesh
    from network_distributed_pytorch_tpu.utils.config import ExperimentConfig

    assert EXPERIMENTS["powersgd_nemotron"] is powersgd_nemotron.run
    config = ExperimentConfig(
        training_epochs=1, learning_rate=5e-5, reducer_rank=2, global_batch_size=4, log_every=0
    )
    out = powersgd_nemotron.run(config, mesh=make_mesh(devices=jax.devices()[:2]), max_steps_per_epoch=3)
    assert out["steps"] == 3 and np.isfinite(out["final_loss"])
    assert out["last_step_assignments"]["dropped"] == 0
    assert out["last_step_assignments"]["held"] + out["last_step_assignments"]["absent"] == 4 * 64 * 2 * 4


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "nemotron_psgd16_t8k", "--seed", "2147483659",
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
