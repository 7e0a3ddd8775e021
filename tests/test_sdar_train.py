"""SDAR as the system trains it, on the CPU at small sizes: ``train_lm``'s
defaults (the six earlier callers' loss, batches and counters as they were),
one PowerSGD step of the cell's builder against Algorithm 2 over the plain
reference, the ``masked`` counter on the loop's spans beside the expert
layers', the experiment's public entry in ``launch.py``, the cell's cut and its
rehearsal. The layers and the objective are in ``test_sdar.py``."""

import inspect
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import cells, compose
from benchmark.reference import ef_momentum
from network_distributed_pytorch_tpu.models.layers import Rope, masked_token_loss, next_token_lm_loss, zero_counters
from network_distributed_pytorch_tpu.models.sdar import sdar_tiny
from network_distributed_pytorch_tpu.parallel.moe import chunk_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sdar_psgd16_t8k"
LAYERS = ["layer_0", "layer_1", "layer_2", "layer_3"]

def test_train_lms_defaults_are_the_six_callers_loss_and_batches():
    from network_distributed_pytorch_tpu.experiments import lm

    defaults = {k: v.default for k, v in inspect.signature(lm.train_lm).parameters.items()}
    assert defaults["loss_of"] is next_token_lm_loss and defaults["batches_of"] is lm.next_token_batches
    assert defaults["drawn_ids"] is None and defaults["collections_of"] is None
    ids = np.arange(30, dtype=np.int32).reshape(3, 10)
    pool = lm.next_token_batches(ids, None)  # takes no draw: the six callers' generator ends where it ended
    assert list(pool) == ["input_ids", "labels"]
    np.testing.assert_array_equal(pool["input_ids"], ids[:, :-1])
    np.testing.assert_array_equal(pool["labels"], ids[:, 1:])
    assert lm.LM_EXPERIMENTS[-1] == "powersgd_sdar" and len(lm.LM_EXPERIMENTS) == 7
    # the counters' tree the six start from is the one they started from
    tiny = sdar_tiny().config
    assert all(sorted(c) == ["absent", "dropped", "held", "row_tiles"] for c in zero_counters(tiny).values())
    assert all("masked" in c for c in zero_counters(tiny, masked_token_loss.counters).values())


def rehearsal_cell():
    cell = cells.cell(CELL)
    return cell, compose.resolved(cell["config"], cell["workload"], rehearsal=True)


def test_one_powersgd_step_of_the_cells_builder_matches_algorithm_2():
    """The benchmark's own composition (``builders/sdar.py``) on two workers at
    the rehearsal sizes against ``reference/ef_momentum.run``."""
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    mesh = make_mesh(devices=jax.devices()[:2])
    built = cells.module("builders", "sdar").build(cfg, 5, mesh)
    batch = jax.device_get(next(built.batches(0)))
    assert sorted(batch) == ["input_ids", "loss_weight", "noisy_ids"] and batch["input_ids"].shape == (4, cfg["text_len"])
    assert batch["input_ids"].max() < cfg["mask_token_id"]
    params0 = jax.device_get(built.state.params)
    model_state0 = jax.tree_util.tree_map(lambda x: x[0], jax.device_get(built.state.model_state))
    q0 = np.asarray(jax.device_get(built.state.reducer_state.q_memory))
    state, loss = built.step(built.state, batch)
    per_worker = cfg["per_chip_batch"]
    shards = [[jax.tree_util.tree_map(lambda x, w=w: x[w * per_worker:(w + 1) * per_worker], batch) for w in range(2)]]
    out = ef_momentum.run(
        cells.module("reference", "sdar").make_loss_and_grads(cfg), params0, model_state0, q0, shards,
        cfg["reducer"], cfg["learning_rate"], cfg["momentum"],
    )
    assert abs(float(loss) - out["losses"][0]) < 1e-5 * abs(out["losses"][0])
    first = out["after_first"]
    assert built.step.bits_per_step // 8 - first["wire_bytes"] == 4  # the loss all-reduce
    whole = np.sqrt(sum(float(np.vdot(d, d)) for d in first["delta"]))
    for got, want in zip(jax.tree_util.tree_leaves(jax.device_get(state.momenta)), first["delta"]):
        assert np.linalg.norm(got - want) <= 1e-4 * whole


def test_train_loop_puts_masked_beside_the_layers_counters_on_its_loss_sync_span():
    """What ``masked_token_pct`` reads, and what the accepted readers of the
    expert layers' counters still find where they found it."""
    from benchmark.layer_metrics import masked_token_pct, moe_chunk_fill_pct, scoped
    from network_distributed_pytorch_tpu.experiments.common import train_loop
    from network_distributed_pytorch_tpu.observe.sinks import MemorySink
    from network_distributed_pytorch_tpu.observe.telemetry import Telemetry
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    built = cells.module("builders", "sdar").build(cfg, 1, make_mesh(devices=jax.devices()[:1]))
    sink = MemorySink()
    train_loop(
        built.step, built.state, built.batches, epochs=1, telemetry=Telemetry([sink]),
        on_step_end=lambda epoch, done, state: done >= 3,
    )
    syncs = [r for r in sink.of_kind("span") if r["name"] == "step/loss_sync"]
    assert len(syncs) == 3
    rows = cfg["per_chip_batch"] * cfg["seq_len"]
    for record in syncs:
        json.dumps(record)
        assert sorted(record["counters"]) == LAYERS
        masked = {layer["masked"][0] for layer in record["counters"].values()}
        assert len(masked) == 1 and 0 < masked.pop() < cfg["per_chip_batch"] * cfg["text_len"]
        for layer in record["counters"].values():
            assert sum(layer["held"][0]) + layer["absent"][0] == rows * cfg["num_experts_per_tok"]
            assert layer["dropped"] == [0]

    run = types.SimpleNamespace(
        cfg=cfg, samples_per_step=built.samples_per_step, clean_spans=lambda name: [r for r in syncs if r["name"] == name],
    )
    share = masked_token_pct.read(run)
    assert 25.0 < share < 75.0
    assert len(scoped.step_counters(run)) == 3 and 0 < moe_chunk_fill_pct.read(run) <= 100.0


def test_the_experiment_runs_through_its_public_entry_in_launch():
    from network_distributed_pytorch_tpu import launch
    from network_distributed_pytorch_tpu.experiments import powersgd_sdar

    assert launch.EXPERIMENTS["powersgd_sdar"] is powersgd_sdar.run
    out = launch.main([
        "powersgd_sdar", "--global-batch", "8", "--reducer-rank", "2", "--lr", "5e-5",
        "--epochs", "1", "--max-steps-per-epoch", "3", "--log-every", "0",
    ])
    assert out["experiment"] == "powersgd_sdar" and out["steps"] == 3 and np.isfinite(out["final_loss"])
    assert out["model"]["block_length"] == 4 and out["model"]["mask_token_id"] == 255 and out["model"]["held_experts"] == 4
    assert out["last_step_assignments"]["dropped"] == 0
    # every layer routed the noised copy and the clean one: 2 x 64 rows a sample, top 2, four layers
    assert out["last_step_assignments"]["held"] + out["last_step_assignments"]["absent"] == 8 * 2 * 64 * 2 * 4


def test_the_full_preset_is_the_cells_cut(monkeypatch):
    """``preset="full"`` builds the configuration file's model: the same
    config, and the parameter count the file states, from shapes (nothing is
    placed or run here); its expert layer's chunk is 3/2 of its 16,384 rows."""
    from benchmark.builders import sdar as builder
    from network_distributed_pytorch_tpu.experiments import lm, powersgd_sdar

    cell = cells.cell(CELL)
    cfg = compose.resolved(cell["config"], cell["workload"], rehearsal=False)
    want = builder.model_of(cfg).config
    seen = {}
    monkeypatch.setattr(powersgd_sdar, "train_lm", lambda run_name, model, *rest, **kw: seen.update(config=model.config, **kw) or {})
    config = lm.default_config()
    config.compute_dtype = "bfloat16"
    powersgd_sdar.run(config, preset="full")
    assert seen["config"] == want and seen["drawn_ids"] == cfg["mask_token_id"] == 18991
    assert want.block_length == 4 and want.rope == Rope(1000000.0) and want.head_dim == 128 and want.expert_width == 768
    assert powersgd_sdar.NOISE_FLOOR == cell["workload"]["traffic"]["noise_floor"]
    shapes = jax.eval_shape(builder.model_of(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))["params"]
    count = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(shapes))
    assert count == 456_346_624 and f"{count:,}" in cell["config"]["cut"]["parameters"]
    layer = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(shapes["layer_0"]))
    assert layer == 94_638_336 and "94,638,336" in cell["config"]["cut"]["parameters"]
    assert shapes["layer_0"]["self_attn"]["q_proj"]["kernel"].shape == (2048, 4096)
    assert shapes["layer_0"]["self_attn"]["k_proj"]["kernel"].shape == (2048, 512)
    assert shapes["layer_3"]["mlp"]["experts_gate"].shape == (16, 2048, 768)
    assert shapes["layer_3"]["mlp"]["router"].shape == (2048, 128)
    assert shapes["embed"]["embedding"].shape == (18992, 2048) and shapes["head"].shape == (2048, 18992)
    assert cfg["seq_len"] == 2 * cfg["text_len"] == 16384 and chunk_rows(cfg["seq_len"], 8, 16, 128) == 24576


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
    assert last["correct"] is True and last["failed"] == 0, done.stdout[-3000:]
    # counts only: a rehearsal writes no device number
    assert 30.0 < last["metrics"]["masked_token_pct"]["value"] < 70.0
    assert last["metrics"]["moe_chunks"]["value"] >= 1
    assert not {"attn_blockwise_ms", "attn_blockwise_roofline", "denoise_loss_ms", "step_ms"} & set(last["metrics"])
