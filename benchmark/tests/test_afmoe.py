"""What PR 31 added to the benchmark: the Trinity-Mini configuration file
against its published source, the required-work functions against hand
counts, the three attention readers and the gated experts' on a made-up
run, and the cell's rehearsal. (``test_cells.py`` and ``test_aot_v5e.py`` pick the cell up by
name: it resolves, compiles for v5e and fits.)"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import cells
from benchmark.flops import afmoe as flops
from benchmark.layer_metrics import (
    attn_full_ms, attn_window_ms, attn_window_roofline, moe_experts_roofline, moe_gated_experts_roofline,
)

CELL = "trinity_psgd16_t8k"
SLIDING, FULL = "sliding_attention", "full_attention"
# https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json, the keys that shape the model
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_expert_groups": 1, "num_experts_per_tok": 8, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
    "sliding_window": 2048, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
}
CUT = {"num_hidden_layers": (32, 5), "num_dense_layers": (2, 1), "num_experts": (128, 8), "vocab_size": (200192, 25024)}


def test_the_configuration_keeps_every_published_width_and_says_what_it_cut():
    cfg = cells.cell(CELL)["config"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"]
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here and key in cfg["cut"]
    # the model's own layers 1-5: a leading dense layer, then one whole period sliding, full, sliding, sliding
    assert cfg["layer_types"] == [SLIDING, SLIDING, FULL, SLIDING, SLIDING] and "layer_types" in cfg["cut"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    assert cfg["held_experts"] == list(range(8)) and cfg["router_width"] == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "16 chips" in cfg["deployment"] and cfg["builder"] == "afmoe"
    for said in ("expert_bias", "load_balance_coeff", "weights", "compute_dtype", "optimizer", "remat", "data"):
        assert said in cfg["assumed"]
    # no width may be cut: nothing that ends in _dim or _rank or names a size but the vocabulary's
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k and k != "vocab_size" for k in cfg["reduced"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every number of the catalog's row, unless listed as reduced
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"] if k not in cfg["reduced"]} == {
            k: v for k, v in row["config"].items() if k not in cfg["reduced"]
        }


def test_required_operations_against_hand_counts():
    cfg = cells.cell(CELL)["config"]
    t, w = 8192, 2048
    band = w * t - w * (w - 1) // 2  # query i sees min(i + 1, W) keys
    assert flops.visible_pairs(t, w) == band == sum(min(i + 1, w) for i in range(t)) == 14_681_088
    assert flops.visible_pairs(t) == t * (t + 1) // 2 == flops.visible_pairs(t, 99999)
    assert flops.visible_pairs(64, 16) == sum(min(i + 1, 16) for i in range(64))
    assert flops.expected_assignments_per_token(cfg) == 8 * 8 / 128
    projections = 2 * 2048 * (32 + 2 * 4) * 128 + 2 * 2048 * 4096 + 2 * 4096 * 2048  # q k v, gate, o
    dense = 3 * 2 * 2048 * 6144  # three products
    experts = 2 * 2048 * 128 + 3 * 2 * 2048 * 1024 + 0.5 * 3 * 2 * 2048 * 1024  # router, shared, half an assignment a token
    forward = (
        t * (5 * projections + dense + 4 * experts + 2 * 2048 * 25024)
        + 4 * 128 * 32 * (4 * band + t * (t + 1) // 2)  # four bands and one triangle
    )
    assert flops.forward_flops_per_sample(cfg) == pytest.approx(forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(3 * forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(17.52e12, rel=1e-3)  # 0.528 GFLOP a token outside attention, 1.51 TFLOP inside, forward
    ops, moved = flops.window_attention_cost(cfg, t)
    assert ops == (2 * 4 * 128 + 10 * 128) * band * 32  # forward twice under remat, then the backward's five products
    assert moved == (4 * 32 + 4 * 4) * 128 * t * 2  # q o dO dq at 32 heads, k v dk dv at 4, bf16
    assert ops / 197e12 > moved / 819e9  # the MXU binds: 5.49 ms a layer against 0.37
    assert flops.window_attention_cost(cfg, 2 * t)[0] == 2 * ops  # whole sequences, not a longer one
    # one expert layer's routed part for the 4,096 assignments expected here: three products an expert,
    # forward and twice that backward; three stacked bf16 leaves read twice and their gradients written
    ops, moved = flops.experts_cost(cfg, 4096)
    assert ops == 3 * (3 * 2 * 2048 * 1024) * 4096
    assert moved == 3 * (3 * 8 * 2048 * 1024 * 2) + 5 * 4096 * 2048 * 2
    assert ops / 197e12 > moved / 819e9  # the MXU binds: 0.785 ms a layer against 0.471
    light = flops.experts_cost(cfg, 512)  # an eighth of that: reading the weights binds
    assert light[1] / 819e9 > light[0] / 197e12


def fake_run(ops, cfg=None, counters=()):
    """A run whose trace holds ``ops`` = [(op path, self seconds a step)] and
    whose ``step/loss_sync`` spans carry ``counters``, one step each."""
    cfg = dict(cells.cell(CELL)["config"], per_chip_batch=1) if cfg is None else cfg
    events = [types.SimpleNamespace(op_name=name, self_s=s) for name, s in ops]
    trace = types.SimpleNamespace(per_step=lambda pick: sum(o.self_s for o in events if pick(o)) or None)
    spans = [{"name": "step/loss_sync", "counters": c} for c in counters]
    return types.SimpleNamespace(
        cfg=cfg, trace=trace if ops else None, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        clean_spans=lambda name: [r for r in spans if r["name"] == name],
    )


def landed(per_layer):
    """One step's counters as ``train_loop`` writes them (a leading worker axis)."""
    return {
        f"layer_{i}": {"held": [[n // 8] * 8], "absent": [65536 - n], "dropped": [0]}
        for i, n in enumerate(per_layer, start=1)
    }


def test_the_gated_experts_roofline_on_a_made_up_run():
    ops = [
        ("jit(f)/step.grads/jvp(moe.experts)/dot_general", 0.020),
        ("jit(f)/step.grads/checkpoint/rematted_computation/moe.experts/gather", 0.012),
        ("jit(f)/step.grads/transpose(jvp(moe.experts))/scatter-add", 0.025),
        ("jit(f)/step.grads/jvp(moe.route)/sort", 0.019),
    ]
    steps = [landed([4096, 4000, 4200, 4096]), landed([4096, 4096, 4096, 4096]), landed([4096, 4400, 3800, 4096])]
    run = fake_run(ops, counters=steps)
    # per layer the median load over the steps, 4,096 in each; the MXU binds: nine products of 2 x 2048 x 1024
    least = 4 * 9 * 2 * 2048 * 1024 * 4096 / 197e12
    assert moe_gated_experts_roofline.read(run) == pytest.approx(100 * least / 0.057, rel=1e-9)
    assert 0 < moe_gated_experts_roofline.read(run) < 100
    # nothing to read: untraced, no counters, or a configuration whose experts are not gated
    assert moe_gated_experts_roofline.read(fake_run([], counters=steps)) is None
    assert moe_gated_experts_roofline.read(fake_run(ops)) is None
    nemotron = cells.cell("nemotron_psgd16_t8k")["config"]
    assert moe_gated_experts_roofline.read(fake_run(ops, cfg=nemotron, counters=steps)) is None
    assert moe_experts_roofline.read(fake_run(ops, cfg=nemotron, counters=steps)) > 0  # that one is its reader


def test_readers_on_a_made_up_run():
    run = fake_run([
        ("jit(f)/step.grads/jvp(attn.window)/pallas_call", 0.010),  # the forward proper wraps the scope
        ("jit(f)/step.grads/checkpoint/rematted_computation/attn.window/pallas_call", 0.012),
        ("jit(f)/step.grads/transpose(jvp(attn.window))/flash_attention_bwd", 0.022),
        ("jit(f)/step.grads/jvp(attn.full)/pallas_call", 0.006),
        ("jit(f)/step.grads/transpose(jvp(attn.full))/flash_attention_bwd", 0.010),
        ("jit(f)/step.grads/jvp(attn.rope)/mul", 0.003),
    ])
    assert attn_window_ms.read(run) == pytest.approx(44.0)
    assert attn_full_ms.read(run) == pytest.approx(16.0)
    # four sliding layers, 18 * 128 * 14,681,088 * 32 operations each at 197e12 a second, over 44 ms
    least = 4 * 18 * 128 * 14_681_088 * 32 / 197e12
    assert attn_window_roofline.read(run) == pytest.approx(100 * least / 0.044, rel=1e-9)
    assert 0 < attn_window_roofline.read(run) < 100


def test_readers_return_nothing_where_the_program_has_no_such_scope():
    untraced = fake_run([])
    no_scope = fake_run([("jit(f)/step.grads/jvp(attn.core)/pallas_call", 0.01)])  # the parent's program
    for run in (untraced, no_scope):
        for reader in (attn_window_ms, attn_full_ms, attn_window_roofline):
            assert reader.read(run) is None
    other_model = fake_run([("jit(f)/step.grads/attn.window/x", 0.01)], cfg={"per_chip_batch": 48})
    assert attn_window_roofline.read(other_model) is None  # a configuration with no window to count


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed", "3000000019",
         "--seconds", "0.5", "--trace", "0", "--rehearsal"],
        cwd=cells.CHECKOUT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"wire_bytes_per_step"}  # untraced: the one end-to-end count, no device number
