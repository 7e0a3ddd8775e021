"""Phi-4-mini-flash-reasoning as the system trains it, on the CPU at small
sizes: the loss and gradients under ``remat`` and with the flash kernels'
fold (interpreted) against the benchmark's plain reference, bf16 products near
it, three PowerSGD steps of the experiment itself against Algorithm 2 over the
reference with the numpy oracle, empty ``STEP_COUNTERS`` on the loop's spans,
the experiment's public entry in ``launch.py``, the full preset, the two
precision controls, and the cell's rehearsal. The layers are in
``test_phi4flash.py``."""

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
from benchmark.reference import phi4flash as reference
from network_distributed_pytorch_tpu.models import phi4flash
from network_distributed_pytorch_tpu.models.layers import next_token_lm_loss
from network_distributed_pytorch_tpu.models.phi4flash import CROSS, FULL, GMU, MAMBA, SLIDING, phi4flash_tiny
from network_distributed_pytorch_tpu.ops import selective_scan
from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "phi4flash_psgd16_t8k"


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


def reference_cfg(c) -> dict:
    """The model's config under the configuration file's (HuggingFace's) keys."""
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads,
        sliding_window=c.sliding_window, layer_norm_eps=c.norm_eps, mamba_d_state=c.state_size,
        mamba_dt_rank=c.dt_rank, layer_indices=list(c.layer_indices),
        published={"num_hidden_layers": c.n_published_layers},
    )


def seeded(model, seq_len):
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, seq_len + 1), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    # biases off 0 and scales off 1, so that each counts
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params
    )
    return params, batch


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
@pytest.mark.parametrize("seq_len", [64, 40], ids=["sixty_four", "ragged"])
def test_model_loss_and_gradients_match_the_plain_reference(seq_len, attn_impl):
    """The cell's cut under ``remat`` in fp32, through ``make_loss_and_grads``
    as the harness calls it: the loss, every gradient leaf, and a model state
    handed on empty. ``flash`` runs the kernels' fold (heads of 16 grouped two
    to one: no lane block serves them) in the Pallas interpreter, the window
    in the sliding layer's call."""
    model = phi4flash_tiny(remat=True, attn_impl=attn_impl)
    params, batch = seeded(model, seq_len)
    (loss, out), grads = jax.jit(jax.value_and_grad(next_token_lm_loss(model), has_aux=True))(params, {}, batch)
    want_loss, want_grads, want_state = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert worst_relative(grads, want_grads) < 1e-3
    assert out == {STEP_COUNTERS: {}} and want_state == {}


def test_bfloat16_products_stay_near_the_fp32_reference():
    """The cell's compute dtype at the test tier's size: a bf16 product rounds
    its operands to 8 bits, a gradient leaf passes some twenty of them and the
    bf16 residual stream, memory and cache. Measured here all leaves as one
    vector are about 1% off the reference and the loss 1e-4: held to 3% and
    2e-3; the parameters stay fp32."""
    model = phi4flash_tiny(remat=True, dtype=jnp.bfloat16)
    params, batch = seeded(model, 64)
    (loss, _), grads = jax.jit(jax.value_and_grad(next_token_lm_loss(model), has_aux=True))(params, {}, batch)
    want_loss, want_grads, _ = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads))
    assert abs(float(loss) - float(want_loss)) < 2e-3
    as_one = lambda tree: jnp.concatenate([leaf.ravel() for leaf in jax.tree_util.tree_leaves(tree)])
    off_all = float(jnp.linalg.norm(as_one(grads) - as_one(want_grads)) / jnp.linalg.norm(as_one(want_grads)))
    assert 1e-4 < off_all < 0.03


def rehearsal_cell():
    cell = cells.cell(CELL)
    return cell, compose.resolved(cell["config"], cell["workload"], rehearsal=True)


def test_three_steps_of_the_experiment_match_algorithm_2_over_the_plain_reference(monkeypatch):
    """``powersgd_phi4flash.run(preset="small")`` itself, three steps on the
    eight-device mesh (``train_lm`` -> ``make_train_step`` with
    ``PowerSGDReducer`` rank 2 -> ``train_loop``), against
    ``reference/ef_momentum.run`` (the numpy PowerSGD oracle over the plain
    reference's gradients) from the same parameters, warm-start Q and batches:
    the three losses (at a learning rate large enough that the second and
    third depend on the updates, and that the loss falls), the bytes on the
    wire, and the parameters after step 1. ``STEP_COUNTERS`` rides empty."""
    from network_distributed_pytorch_tpu.experiments import lm, powersgd_phi4flash

    seen = {}
    real = lm.train_loop

    def spy(step, state, batches, epochs, **kw):
        seen["params0"] = jax.device_get(state.params)
        seen["q0"] = np.asarray(jax.device_get(state.reducer_state.q_memory))
        seen["model_state0"] = jax.device_get(state.model_state)
        first = batches(0)
        seen["batches"] = [jax.device_get(next(first)) for _ in range(3)]
        first.close()
        seen["bytes"] = step.bits_per_step // 8

        def after_first(epoch, done, s):
            if done == 1:
                seen["params1"] = jax.device_get(s.params)
            return False

        state, logger = real(step, state, batches, epochs, on_step_end=after_first, **kw)
        seen["losses"] = [r.loss for r in logger.records]
        seen["model_state3"] = jax.device_get(state.model_state)
        return state, logger

    monkeypatch.setattr(lm, "train_loop", spy)
    config = lm.default_config()
    config.learning_rate, config.reducer_rank, config.log_every, config.seed = 0.05, 2, 0, 7
    out = powersgd_phi4flash.run(config, preset="small", max_steps_per_epoch=3)
    assert out["experiment"] == "powersgd_phi4flash" and out["steps"] == 3
    assert out["model"]["layer_kinds"] == [SLIDING, MAMBA, FULL, GMU, CROSS]
    assert seen["model_state0"] == {STEP_COUNTERS: {}} == seen["model_state3"]  # no expert layer: empty, before and after
    assert out["last_step_assignments"] == {"held": 0, "absent": 0, "dropped": 0}
    model = phi4flash_tiny()
    workers = len(jax.devices())
    shards = [
        [jax.tree_util.tree_map(lambda x, w=w: x[w:w + 1], batch) for w in range(workers)] for batch in seen["batches"]
    ]
    ref = ef_momentum.run(
        reference.make_loss_and_grads(reference_cfg(model.config)), seen["params0"], {},
        seen["q0"], shards, {"rank": 2, "reuse_query": True, "matricize": "last"}, 0.05, 0.9,
    )
    np.testing.assert_allclose(seen["losses"], ref["losses"], rtol=0, atol=2e-5)
    assert seen["losses"][2] < seen["losses"][0] - 1e-3  # the loss falls, and the updates are in it
    assert seen["bytes"] - ref["after_first"]["wire_bytes"] == 4  # the loss all-reduce
    for got, want in zip(jax.tree_util.tree_leaves(seen["params1"]), ref["after_first"]["params"]):
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_one_powersgd_step_of_the_cells_builder_matches_algorithm_2():
    """The benchmark's own composition (``builders/phi4flash.py``) on two
    workers at the rehearsal sizes against ``reference/ef_momentum.run``; the
    (4, d_inner) taps compress at rank 2 of 4, the (d_inner, 4) ``a_log`` too."""
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    mesh = make_mesh(devices=jax.devices()[:2])
    built = cells.module("builders", "phi4flash").build(cfg, 5, mesh)
    batch = jax.device_get(next(built.batches(0)))
    params0 = jax.device_get(built.state.params)
    assert jax.device_get(built.state.model_state) == {STEP_COUNTERS: {}}
    q0 = np.asarray(jax.device_get(built.state.reducer_state.q_memory))
    state, loss = built.step(built.state, batch)
    per_worker = cfg["per_chip_batch"]
    shards = [[jax.tree_util.tree_map(lambda x, w=w: x[w * per_worker:(w + 1) * per_worker], batch) for w in range(2)]]
    out = ef_momentum.run(
        cells.module("reference", "phi4flash").make_loss_and_grads(cfg), params0, {}, q0, shards,
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


def test_train_loop_puts_no_counters_on_its_loss_sync_span():
    from network_distributed_pytorch_tpu.experiments.common import train_loop
    from network_distributed_pytorch_tpu.observe.sinks import MemorySink
    from network_distributed_pytorch_tpu.observe.telemetry import Telemetry
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    built = cells.module("builders", "phi4flash").build(cfg, 1, make_mesh(devices=jax.devices()[:1]))
    sink = MemorySink()
    train_loop(
        built.step, built.state, built.batches, epochs=1, telemetry=Telemetry([sink]),
        on_step_end=lambda epoch, done, state: done >= 3,
    )
    syncs = [r for r in sink.of_kind("span") if r["name"] == "step/loss_sync"]
    assert len(syncs) == 3
    for record in syncs:
        json.dumps(record)
        assert not record.get("counters")  # nothing in this model is data-dependent: no router, no drop


def test_the_experiment_runs_through_its_public_entry_in_launch():
    from network_distributed_pytorch_tpu import launch
    from network_distributed_pytorch_tpu.experiments import lm, powersgd_phi4flash

    assert launch.EXPERIMENTS["powersgd_phi4flash"] is powersgd_phi4flash.run
    assert "powersgd_phi4flash" in lm.LM_EXPERIMENTS
    out = launch.main([
        "powersgd_phi4flash", "--global-batch", "8", "--reducer-rank", "2", "--lr", "5e-5",
        "--epochs", "1", "--max-steps-per-epoch", "3", "--log-every", "0",
    ])
    assert out["experiment"] == "powersgd_phi4flash" and out["steps"] == 3 and np.isfinite(out["final_loss"])
    assert out["model"]["layer_indices"] == [15, 16, 17, 18, 19] and out["model"]["vocab_size"] == 256


def test_the_full_preset_is_the_cells_cut():
    """``preset="full"`` builds the configuration file's model: the same
    config, and the parameter count the file states, from shapes (nothing is
    placed or run here)."""
    from benchmark.builders import phi4flash as builder
    from network_distributed_pytorch_tpu.experiments import lm, powersgd_phi4flash

    cell = cells.cell(CELL)
    cfg = compose.resolved(cell["config"], cell["workload"], rehearsal=False)
    want = builder.model_of(cfg).config
    seen = {}

    def capture(run_name, model, *rest, **kw):
        seen["config"] = model.config
        return {}

    real, powersgd_phi4flash.train_lm = powersgd_phi4flash.train_lm, capture
    try:
        config = lm.default_config()
        config.compute_dtype = "bfloat16"
        powersgd_phi4flash.run(config, preset="full")
    finally:
        powersgd_phi4flash.train_lm = real
    assert seen["config"] == want
    assert (want.head_dim, want.d_inner, want.dt_rank, want.state_size, want.conv_kernel) == (64, 5120, 160, 16, 4)
    shapes = jax.eval_shape(builder.model_of(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))["params"]
    count = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(shapes))
    assert count == 577_199_232 and f"{count:,}" in cell["config"]["cut"]["parameters"]
    assert shapes["embed"]["embedding"].shape == (25008, 2560)


# ---- the two precision controls (PERF.md section 6, PR 48) -----------------------


def bf16_difference(o1, o2, lam, scale, eps, rest, dtype):
    """``models/phi4flash.difference`` with the softmax difference and the
    subln in bf16: what the configuration says is fp32, a precision lower."""
    low = jnp.bfloat16
    o = o1.astype(low) - lam.astype(low) * o2.astype(low)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + jnp.asarray(eps, low))
    return (o * scale.astype(low) * jnp.asarray(rest, low)).astype(dtype)


def attention_leaves(grads):
    return {name: layer["mixer"] for name, layer in grads.items() if "lambda_q1" in layer.get("mixer", {})}


def mamba_leaves(grads):
    return {name: layer["mixer"] for name, layer in grads.items() if "a_log" in layer.get("mixer", {})}


@pytest.mark.parametrize("control", ["scan_state_bf16", "difference_bf16"])
def test_a_precision_below_the_configurations_shows_in_the_gradients(monkeypatch, control):
    """The benchmark's two controls, at the test tier's size in fp32 so that
    nothing else rounds: the scan's state and decay in bf16 moves the Mamba
    layer's leaves, the difference and subln in bf16 the attention layers',
    each hundreds of times further from the reference than the model as built.
    On the chip, at the published widths, the same two controls turn the
    cell's ``correct`` to false under its ``reference_limits``."""
    model = phi4flash_tiny(layer_indices=(15, 16, 17, 18, 19))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    _, want, _ = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    loss = next_token_lm_loss(model)
    picked = mamba_leaves if control == "scan_state_bf16" else attention_leaves
    as_built = jax.grad(lambda p: loss(p, {}, batch)[0])(params)
    sound = worst_relative(picked(as_built), picked(want))
    assert sound < 1e-3
    if control == "scan_state_bf16":
        monkeypatch.setattr(selective_scan, "STATE_DTYPE", jnp.bfloat16)
    else:
        monkeypatch.setattr(phi4flash, "difference", bf16_difference)
    lowered = jax.grad(lambda p: loss(p, {}, batch)[0])(params)
    assert worst_relative(picked(lowered), picked(want)) > max(20 * sound, 3e-3)


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed", "2147483695",
         "--seconds", "0.5", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"] == {}  # traced: per-layer metrics only, none of this cell's is a count
