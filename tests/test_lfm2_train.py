"""LFM2 as the system trains it, on the CPU at small sizes: the whole model's
logits, loss and gradients (the tied head's one leaf among them) against the
benchmark's plain reference in fp32 and in bf16, three PowerSGD steps of the
experiment itself against Algorithm 2 over the reference with the numpy
oracle, the step's counters on the loop's spans, the experiment's public entry
in ``launch.py``, the precision control, and the cell's rehearsal. The layers
are in ``test_lfm2.py``."""

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
from benchmark.reference import lfm2 as reference
from network_distributed_pytorch_tpu.models.layers import BUFFERS, FULL, balanced_expert_bias, next_token_lm_loss, zero_counters
from network_distributed_pytorch_tpu.models.lfm2 import CONV, Lfm2Config, lfm2_tiny
from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2_psgd16_t8k"
EXPERT_LAYERS = ["layer_1", "layer_2", "layer_3", "layer_4"]


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


def reference_cfg(c: Lfm2Config) -> dict:
    """The model's config under the configuration file's (HuggingFace's) keys."""
    return dict(
        hidden_size=c.hidden_size, layer_types=list(c.layer_types), num_dense_layers=c.num_dense_layers,
        norm_eps=c.norm_eps, num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads,
        rope_parameters={"rope_theta": c.rope_theta}, num_experts_per_tok=c.experts_per_token,
        routed_scaling_factor=c.route_scale, held_experts=list(c.held_experts),
    )


def seeded(model, seq_len):
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, seq_len + 1), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    # norm scales off 1, so that every norm counts
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params
    )
    return params, batch


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
@pytest.mark.parametrize("balanced", [False, True], ids=["no_buffers", "balanced_bias"])
@pytest.mark.parametrize("seq_len", [64, 40], ids=["sixty_four", "ragged"])
def test_model_loss_and_gradients_match_the_plain_reference(seq_len, balanced, attn_impl):
    """A dense conv layer, then attention, conv, conv, conv with experts, as
    the cell's cut, under ``remat``, in fp32: the loss, every gradient leaf
    (tight: both sides are fp32, the orders of summation differ), and the
    step's counters, which are the reference's own routing, expert for
    expert; with no buffers (zeros, the published initial value) and with a
    balanced ``expert_bias``, which both sides route by."""
    model = lfm2_tiny(remat=True, attn_impl=attn_impl)
    params, batch = seeded(model, seq_len)
    state = {BUFFERS: balanced_expert_bias(model, params, batch["input_ids"])} if balanced else {}
    (loss, out), grads = jax.jit(jax.value_and_grad(next_token_lm_loss(model), has_aux=True))(params, state, batch)
    want_loss, want_grads, want_state = reference.make_loss_and_grads(reference_cfg(model.config))(params, state, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert worst_relative(grads, want_grads) < 1e-4
    counters = out[STEP_COUNTERS]
    assert sorted(counters) == EXPERT_LAYERS == sorted(zero_counters(model.config))
    for name, c in counters.items():
        assert int(c["dropped"]) == 0 and int(c["held"].sum() + c["absent"]) == 2 * seq_len * 2
        np.testing.assert_array_equal(c["held"], want_state["step_counters"][name]["held"])
        assert int(c["absent"]) == int(want_state["step_counters"][name]["absent"])
    if balanced:  # handed on as it came: nothing updates it
        jax.tree_util.tree_map(np.testing.assert_array_equal, out[BUFFERS], state[BUFFERS])


def test_bfloat16_products_stay_near_the_fp32_reference_and_far_inside_the_benchmarks_limits():
    """The cell's compute dtype at the test tier's size, every expert held.
    Why these limits: a bf16 product rounds its operands to 8 bits (2^-9
    relative), and a gradient leaf passes some twenty such products and the
    bf16 residual stream: measured here all leaves as one vector are 1.3% off
    the reference, the worst leaf outside an expert layer's feed-forward 1.7%
    and the loss 2e-4, so 3%, 3% and 2e-3. The router itself is fp32 at full
    precision, but what it scores has been through bf16, so a few of the 1,024
    assignments (10 here) go to another expert than the reference's; at 64
    assignments an expert one flip is 1.5% of an expert's rows, and the expert
    leaves and the norm in front of them read up to 0.21: held to 0.3, under
    the benchmark's per-tensor limit (0.35), which is taken at 512 rows an
    expert and has PowerSGD's rank-16 truncation inside it besides."""
    from benchmark.reference_check import TOLERANCES

    model = lfm2_tiny(remat=True, dtype=jnp.bfloat16, held_experts=tuple(range(16)))
    params, batch = seeded(model, 64)
    (loss, state), grads = jax.jit(jax.value_and_grad(next_token_lm_loss(model), has_aux=True))(params, {}, batch)
    want_loss, want_grads, want_state = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads))  # fp32 parameters
    assert abs(float(loss) - float(want_loss)) < 2e-3
    as_one = lambda tree: jnp.concatenate([leaf.ravel() for leaf in jax.tree_util.tree_leaves(tree)])
    off_all = float(jnp.linalg.norm(as_one(grads) - as_one(want_grads)) / jnp.linalg.norm(as_one(want_grads)))
    assert 1e-4 < off_all < 0.03
    behind_the_router = lambda tree: {
        name: {k: tree[name][k] for k in ("feed_forward", "ffn_norm")} for name in EXPERT_LAYERS
    }
    elsewhere = lambda tree: {
        name: {k: v for k, v in leaves.items() if name not in EXPERT_LAYERS or k not in ("feed_forward", "ffn_norm")}
        for name, leaves in tree.items()
    }
    assert worst_relative(elsewhere(grads), elsewhere(want_grads)) < 0.03
    assert worst_relative(behind_the_router(grads), behind_the_router(want_grads)) < 0.3 < TOLERANCES["update_each"]
    flipped = sum(
        int(np.abs(np.asarray(c["held"]) - np.asarray(want_state["step_counters"][name]["held"])).sum())
        for name, c in state[STEP_COUNTERS].items()
    )
    assert flipped <= 40  # twice as many counter changes as flips: under 2% of the assignments


def test_logits_match_the_plain_reference_and_the_layer_kinds_shape_the_tree():
    """The model's logits against the reference's, through the tied head. A
    layer's kind decides its leaves (``conv`` or ``self_attn``), its place
    whether its feed-forward is the dense layer or the experts."""
    model = lfm2_tiny()
    params, batch = seeded(model, 64)
    logits, _ = model.apply({"params": params}, batch["input_ids"])
    cfg = reference_cfg(model.config)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference._logits(params, ids, cfg) for ids in batch["input_ids"]])
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-5)
    layers = [f"layer_{i}" for i in range(5)]
    assert sorted(params) == ["embed", "embedding_norm"] + layers and "head" not in params
    assert ["conv" in params[name] for name in layers] == [True, False, True, True, True]
    assert "self_attn" in params["layer_1"] and sorted(params["layer_0"]) == ["conv", "feed_forward", "ffn_norm", "operator_norm"]
    assert sorted(params["layer_0"]["feed_forward"]) == ["down_proj", "gate_proj", "up_proj"]
    assert all("router" in params[name]["feed_forward"] for name in EXPERT_LAYERS)
    # swapped kinds are another model: the reference told so disagrees
    swapped = dict(cfg, layer_types=[FULL, CONV, CONV, CONV, CONV])
    with pytest.raises(KeyError):
        reference._logits(params, batch["input_ids"][0], swapped)


def test_the_tied_leaf_takes_the_gradient_of_the_lookup_and_of_the_head():
    """One (V, h) leaf, used twice. Rows no id looked up still take the head's
    part; the whole leaf's gradient is the reference's, which uses the one
    array twice in plain ``jax.numpy``; and an untied copy of the table as the
    head takes exactly what the tied leaf takes less the lookup's rows."""
    model = lfm2_tiny()
    params, batch = seeded(model, 32)
    loss = next_token_lm_loss(model)
    tied = jax.grad(lambda p: loss(p, {}, batch)[0])(params)["embed"]["embedding"]
    _, want, _ = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    assert float(jnp.linalg.norm(tied - want["embed"]["embedding"]) / jnp.linalg.norm(tied)) < 1e-4
    unseen = np.setdiff1d(np.arange(256), np.asarray(batch["input_ids"]))
    seen = np.unique(np.asarray(batch["input_ids"]))
    assert len(unseen) > 100 and np.abs(np.asarray(tied)[unseen]).max() > 0  # the head's part alone
    # the lookup's part: the tied gradient less the head's, which a table that is only looked up shows
    hidden_of = lambda table: _final_hidden(model, {**params, "embed": {"embedding": table}}, batch["input_ids"])

    def untied(table, head):
        logits = jnp.einsum("bth,vh->btv", hidden_of(table), head, precision="highest")
        picked = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)

    table = params["embed"]["embedding"]
    by_lookup, by_head = jax.grad(untied, argnums=(0, 1))(table, table)
    assert not np.asarray(by_lookup)[unseen].any() and np.abs(np.asarray(by_lookup)[seen]).max() > 0
    np.testing.assert_allclose(tied, by_lookup + by_head, rtol=1e-3, atol=1e-7)


def _final_hidden(model, params, ids):
    """The model's last hidden states: its logits un-projected by the
    pseudo-inverse would lose precision, so the blocks are applied again here,
    by name, as ``Lfm2LM`` applies them."""
    from network_distributed_pytorch_tpu.models.layers import RMSNorm
    from network_distributed_pytorch_tpu.models.lfm2 import Lfm2Block

    cfg = model.config
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(cfg.layer_types):
        x, _ = Lfm2Block(cfg, kind, i < cfg.num_dense_layers).apply({"params": params[f"layer_{i}"]}, x)
    return RMSNorm(cfg.norm_eps).apply({"params": params["embedding_norm"]}, x)


def rehearsal_cell():
    cell = cells.cell(CELL)
    return cell, compose.resolved(cell["config"], cell["workload"], rehearsal=True)


def test_three_steps_of_the_experiment_match_algorithm_2_over_the_plain_reference(monkeypatch):
    """``powersgd_lfm2.run(preset="small")`` itself, three steps on the
    eight-device mesh (``train_lm`` -> ``make_train_step`` with
    ``PowerSGDReducer`` -> ``train_loop``), against
    ``reference/ef_momentum.run`` (the numpy PowerSGD oracle over the plain
    reference's gradients) from the same parameters, warm-start Q and batches
    (no buffers: ``expert_bias`` stays zeros on both sides): the three losses (at a learning rate large enough
    that the second and third depend on the updates), and the parameters the
    reference holds after step 1 against the experiment's after its first."""
    from network_distributed_pytorch_tpu.experiments import lm, powersgd_lfm2

    seen = {}
    real = lm.train_loop

    def spy(step, state, batches, epochs, **kw):
        seen["params0"] = jax.device_get(state.params)
        seen["q0"] = np.asarray(jax.device_get(state.reducer_state.q_memory))
        seen["model_state0"] = jax.tree_util.tree_map(lambda x: x[0], jax.device_get(state.model_state))
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
        return state, logger

    monkeypatch.setattr(lm, "train_loop", spy)
    config = lm.default_config()
    config.learning_rate, config.reducer_rank, config.log_every, config.seed = 0.05, 2, 0, 7
    out = powersgd_lfm2.run(config, preset="small", max_steps_per_epoch=3)
    assert out["experiment"] == "powersgd_lfm2" and out["steps"] == 3
    model = lfm2_tiny()
    workers = len(jax.devices())
    assert sorted(seen["model_state0"]) == [STEP_COUNTERS]  # no buffers: expert_bias stays zeros
    shards = [
        [jax.tree_util.tree_map(lambda x, w=w: x[w:w + 1], batch) for w in range(workers)] for batch in seen["batches"]
    ]
    ref = ef_momentum.run(
        reference.make_loss_and_grads(reference_cfg(model.config)), seen["params0"], seen["model_state0"],
        seen["q0"], shards, {"rank": 2, "reuse_query": True, "matricize": "last"}, 0.05, 0.9,
    )
    np.testing.assert_allclose(seen["losses"], ref["losses"], rtol=0, atol=2e-5)
    assert abs(ref["losses"][2] - ref["losses"][0]) > 1e-3  # the steps moved the loss: the updates are in it
    assert seen["bytes"] - ref["after_first"]["wire_bytes"] == 4  # the loss all-reduce
    for got, want in zip(jax.tree_util.tree_leaves(seen["params1"]), ref["after_first"]["params"]):
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert out["last_step_assignments"]["dropped"] == 0
    assert out["last_step_assignments"]["held"] + out["last_step_assignments"]["absent"] == workers * 64 * 2 * 4


def test_one_powersgd_step_of_the_cells_builder_matches_algorithm_2():
    """The benchmark's own composition (``builders/lfm2.py``) on two workers
    at the rehearsal sizes against ``reference/ef_momentum.run``."""
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell, cfg = rehearsal_cell()
    mesh = make_mesh(devices=jax.devices()[:2])
    built = cells.module("builders", "lfm2").build(cfg, 5, mesh)
    batch = jax.device_get(next(built.batches(0)))
    params0 = jax.device_get(built.state.params)
    model_state0 = jax.tree_util.tree_map(lambda x: x[0], jax.device_get(built.state.model_state))
    assert sorted(model_state0) == [STEP_COUNTERS]  # no buffers: expert_bias stays zeros
    q0 = np.asarray(jax.device_get(built.state.reducer_state.q_memory))
    state, loss = built.step(built.state, batch)
    per_worker = cfg["per_chip_batch"]
    shards = [[jax.tree_util.tree_map(lambda x, w=w: x[w * per_worker:(w + 1) * per_worker], batch) for w in range(2)]]
    out = ef_momentum.run(
        cells.module("reference", "lfm2").make_loss_and_grads(cfg), params0, model_state0, q0, shards,
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
    built = cells.module("builders", "lfm2").build(cfg, 1, make_mesh(devices=jax.devices()[:1]))
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
        assert sorted(record["counters"]) == EXPERT_LAYERS
        for layer in record["counters"].values():
            assert sum(layer["held"][0]) + layer["absent"][0] == tokens and layer["dropped"] == [0]
            assert layer["row_tiles"][0] >= 1  # what moe_row_tile_visits reads


def test_the_experiment_runs_through_its_public_entry_in_launch():
    from network_distributed_pytorch_tpu import launch
    from network_distributed_pytorch_tpu.experiments import powersgd_lfm2

    assert launch.EXPERIMENTS["powersgd_lfm2"] is powersgd_lfm2.run
    out = launch.main([
        "powersgd_lfm2", "--global-batch", "8", "--reducer-rank", "2", "--lr", "5e-5",
        "--epochs", "1", "--max-steps-per-epoch", "3", "--log-every", "0",
    ])
    assert out["experiment"] == "powersgd_lfm2" and out["steps"] == 3 and np.isfinite(out["final_loss"])
    assert out["model"]["layer_types"] == [CONV, FULL, CONV, CONV, CONV] and out["model"]["held_experts"] == 4
    assert out["last_step_assignments"]["dropped"] == 0
    assert out["last_step_assignments"]["held"] + out["last_step_assignments"]["absent"] == 8 * 64 * 2 * 4


def test_the_full_preset_is_the_cells_cut():
    """``preset="full"`` builds the configuration file's model: the same
    config, and the parameter count the file states, from shapes (nothing is
    placed or run here)."""
    from benchmark.builders import lfm2 as builder
    from network_distributed_pytorch_tpu.experiments import lm, powersgd_lfm2

    cell = cells.cell(CELL)
    cfg = compose.resolved(cell["config"], cell["workload"], rehearsal=False)
    want = builder.model_of(cfg).config
    seen = {}

    def capture(run_name, model, *rest, **kw):
        seen["config"] = model.config
        return {}

    real, powersgd_lfm2.train_lm = powersgd_lfm2.train_lm, capture
    try:
        config = lm.default_config()
        config.compute_dtype = "bfloat16"
        powersgd_lfm2.run(config, preset="full")
    finally:
        powersgd_lfm2.train_lm = real
    assert seen["config"] == want and want.head_dim == 64 and want.conv_kernel == 3
    shapes = jax.eval_shape(builder.model_of(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))["params"]
    count = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(shapes))
    assert count == 486_062_208 and f"{count:,}" in cell["config"]["cut"]["parameters"]
    assert shapes["layer_0"]["conv"]["in_proj"]["kernel"].shape == (2048, 6144)
    assert shapes["layer_0"]["feed_forward"]["gate_proj"]["kernel"].shape == (2048, 11776)
    assert shapes["layer_2"]["feed_forward"]["experts_gate"].shape == (8, 2048, 1536)
    assert shapes["embed"]["embedding"].shape == (16384, 2048)


def bf16_angles(x, rope, rotary_dim=None):
    """``models/layers.rotary`` with its angles, cos and sin computed in bf16:
    what the configuration says is fp32, a precision lower."""
    t, d, theta = x.shape[1], x.shape[-1], rope.theta
    low = jnp.bfloat16
    inv_freq = (theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)).astype(low)
    angles = jnp.arange(t, dtype=jnp.float32).astype(low)[:, None] * inv_freq[None, :]
    cos, sin = (f(angles)[None, :, None, :].astype(jnp.float32) for f in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def test_rotary_angles_in_bfloat16_show_in_attentions_gradients(monkeypatch):
    """The precision control (PERF.md section 6, PR 41). At theta 1e6 and
    positions up to 2047 an angle rounded to bf16 is off by up to 4 radians:
    the attention layer's q and k projections then leave the reference by a
    fifth of their gradients' norm where the model as built stays within a
    thousandth. At the test tier's widths a fifth is inside the benchmark's
    per-tensor limits (0.35 and 0.30), so this test is what holds the angles
    to fp32 here; on the chip at the published widths and T = 8192 the same
    control turned the cell's ``correct`` to false by the memories' limit
    alone (q_proj 0.385 and k_proj 0.390 against 0.30; my chip run, PR 41)."""
    from benchmark.reference_check import TOLERANCES
    from network_distributed_pytorch_tpu.models import layers

    model = lfm2_tiny(layer_types=(CONV, FULL), rope_theta=1e6)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 2049), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    _, want, _ = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    attention = lambda g: {k: g["layer_1"]["self_attn"][k] for k in ("q_proj", "k_proj")}
    loss = next_token_lm_loss(model)
    as_built = jax.grad(lambda p: loss(p, {}, batch)[0])(params)
    assert worst_relative(attention(as_built), attention(want)) < 1e-3
    monkeypatch.setattr(layers, "rotary", bf16_angles)  # where the layers' XLA lines look it up
    lowered = jax.grad(lambda p: loss(p, {}, batch)[0])(params)
    assert 0.1 < worst_relative(attention(lowered), attention(want)) < TOLERANCES["update_each"]


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
