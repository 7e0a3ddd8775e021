"""What PR 51 added to the benchmark: the SDAR-30B-A3B-Chat configuration file
against its published source and the catalog's row, the required-work
functions against hand counts (at the published widths and at toy ones), the
four new readers (``attn_blockwise_ms``, ``attn_blockwise_roofline``,
``denoise_loss_ms``, ``masked_token_pct``) and the readers the cell shares on
made-up runs — a run of a program that writes no such scope or counter among
them — the manifest's new entries, the pool's noising from the seed, the cell's
own limits between its two readings, and the cell's rehearsal. (``test_cells.py`` and ``test_aot_v5e.py``
pick the cell up by name: it resolves, compiles for v5e and fits.)"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import cells, compose
from benchmark.flops import sdar as flops
from benchmark.layer_metrics import (
    attn_blockwise_ms, attn_blockwise_roofline, attn_rope_ms, denoise_loss_ms, masked_token_pct, moe_chunk_fill_pct,
    moe_chunks, moe_gated_experts_roofline,
)

CELL = "sdar_psgd16_t8k"
NEW = ["attn_blockwise_ms", "attn_blockwise_roofline", "denoise_loss_ms", "masked_token_pct"]
CUT = {"num_hidden_layers": (48, 4), "num_experts": (128, 16), "vocab_size": (151936, 18992)}


def resolved(rehearsal=False):
    cell = cells.cell(CELL)
    return compose.resolved(cell["config"], cell["workload"], rehearsal=rehearsal)


def test_the_configuration_keeps_every_published_width_and_says_what_it_cut():
    cfg = cells.cell(CELL)["config"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"] and cfg["builder"] == "sdar"
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here and key in cfg["cut"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["moe_intermediate_size"], cfg["num_experts_per_tok"], cfg["rope_theta"], cfg["rms_norm_eps"]) == (768, 8, 1000000, 1e-6)
    assert cfg["decoder_sparse_step"] == 1 and cfg["mlp_only_layers"] == [] and cfg["tie_word_embeddings"] is False
    assert cfg["held_experts"] == list(range(16)) and cfg["router_width"] == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]  # the guide's floor
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1 and cfg["block_length"] == 4
    assert cfg["seq_len"] == 2 * cfg["text_len"] == 16384 and cfg["text_len"] % cfg["block_length"] == 0
    assert "8 chips" in cfg["deployment"] and "16,384 assignments" in cfg["deployment"]
    assert "456,346,624" in cfg["cut"]["parameters"] and "not_taken" in cfg["cut"] and cfg["cut"]["fallback_taken"].startswith("none")
    for said in ("block_length", "noise_schedule", "no_shift", "qk_norm", "auxiliary_loss", "weights", "positions", "seq_len"):
        assert said in cfg["assumed"]
    # no width may be cut: nothing that ends in _dim or _rank or names a size but the vocabulary's
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k and k != "vocab_size" for k in cfg["reduced"])
    rehearsal = cfg["rehearsal"]
    assert rehearsal["seq_len"] == 2 * rehearsal["text_len"] and rehearsal["mask_token_id"] == rehearsal["vocab_size"] - 1
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every number of the catalog's row, unless listed as reduced
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"] if k not in cfg["reduced"]} == {
            k: v for k, v in row["config"].items() if k not in cfg["reduced"]
        }
        assert {k: row["config"][k] for k in cfg["reduced"]} == {k: v[0] for k, v in CUT.items()}
        assert set(row["not_given"]) == {"block length", "noise schedule"}  # both under `assumed`


def test_the_manifest_gained_the_configuration_the_cell_and_four_metrics():
    bench = cells.manifest()
    config = next(c for c in bench["configs"] if c["name"] == "sdar-30b-a3b")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert config["file"] == "benchmark/configs/sdar-30b-a3b.json" and config["reduced"] == list(CUT)
    assert config["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    assert entry == {"name": CELL, "config": "sdar-30b-a3b", "traffic": "t8k", "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    cell = cells.cell(CELL)
    assert cell["workload"]["why"] == entry["why"] and cell["workload"]["trace_slice_steps"] == 6
    assert cell["workload"]["traffic"] == {"kind": "lm_sequences", "pool_samples": 256, "zipf_exponent": 1.0, "noise_floor": 0.001}
    assert {m["name"] for m in cell["end_to_end"]} == {"samples_per_s", "step_ms", "peak_hbm_gb", "wire_bytes_per_step", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    # what mellum2's cell reports but the three readers of scopes this model has not, and the four new
    mellum = {m["name"] for m in cells.cell("mellum2_psgd16_t8k")["per_layer"]}
    assert reported == (mellum - {"attn_window_ms", "attn_full_ms", "attn_window_roofline"}) | set(NEW)
    new = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == NEW and new == bench["per_layer"][-4:]
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "step_ms"
    assert [(m["unit"], m["better"], m["source"], m["layer"]) for m in new] == [
        ("ms", "lower", "device_trace", "kernels"), ("%", "higher", "device_trace", "kernels"),
        ("ms", "lower", "device_trace", "step compute"), ("%", "higher", "program_counter", "step compute"),
    ]


def test_required_operations_against_hand_counts():
    cfg = resolved()
    length, d, f, block = 8192, 2048, 768, 4
    pairs = length * length + length * block
    assert flops.visible_pairs(length, block) == pairs == 67_141_632
    # block b of B queries: the noised see B + b B keys, the clean (b + 1) B: B^2 (2 b + 2), summed over L / B blocks
    assert sum(block * block * (2 * b + 2) for b in range(length // block)) == pairs
    assert pairs / (length * (length + 1) / 2) == pytest.approx(2.0, rel=1e-3)  # twice a causal layer's
    projections = 2 * d * (32 + 8) * 128 + 2 * 4096 * d  # q k v, o
    experts = 2 * d * 128 + 1.0 * 6 * d * f  # the router, and one assignment a row expected on the 16 held (8 x 16 / 128)
    layer = 4 * 128 * 32 * pairs + (projections + experts) * 2 * length
    forward = 4 * layer + 2 * d * 18992 * length  # the head on the noised rows only
    assert flops.forward_flops_per_sample(cfg) == pytest.approx(forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(3 * forward, rel=1e-12) == pytest.approx(24.49e12, rel=1e-3)
    ops, moved = flops.blockwise_attention_cost(cfg, length)
    assert ops == pytest.approx(18 * 128 * 32 * pairs, rel=1e-12) == pytest.approx(4.95e12, rel=1e-3)
    assert moved == (4 * 32 + 4 * 4) * 128 * 2 * length * 2  # q, o, dO, dq of 32 heads, k, v, dk, dv of 4, 16,384 rows, bf16
    assert ops / 197e12 > moved / 819e9 and ops / 197e12 == pytest.approx(25.13e-3, rel=1e-3)  # the MXU binds
    assert flops.blockwise_attention_cost(dict(cfg, remat=False), length)[0] == pytest.approx(14 * 128 * 32 * pairs)
    # the experts' count is afmoe's, by this configuration's keys
    ops, moved = flops.experts_cost(cfg, 16384)
    assert ops == 3 * 6 * d * f * 16384 and moved == 3 * 3 * 16 * d * f * 2 + 5 * 16384 * d * 2


def test_required_operations_at_toy_sizes_by_hand():
    cfg = {
        "hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2, "moe_intermediate_size": 3,
        "router_width": 4, "held_experts": [0, 1], "num_experts_per_tok": 2, "vocab_size": 10, "text_len": 4,
        "block_length": 2, "num_hidden_layers": 1, "compute_dtype": "float32", "remat": False,
    }
    pairs = 4 * 4 + 4 * 2  # noised: 2 + 2 + 4 + 4 = 12; clean: 2 + 2 + 4 + 4 = 12
    assert flops.visible_pairs(4, 2) == pairs == 24
    projections = 2 * 4 * (2 + 2) * 2 + 2 * 4 * 4  # q k v 64, o 32
    experts = 2 * 4 * 4 + 1.0 * 6 * 4 * 3  # router 32; 2 x 2 / 4 = one assignment a row: 72
    want = 2 * 4 * 10 * 4 + 4 * 2 * 2 * pairs + (projections + experts) * 8
    assert flops.forward_flops_per_sample(cfg) == want == 320 + 384 + 1600
    assert flops.blockwise_attention_cost(cfg, 4) == (14 * 2 * 2 * pairs, float((8 + 4) * 2 * 8 * 4))


def fake_run(ops, counters=(), cfg=None):
    """A run whose trace holds ``ops`` = [(op path, self seconds a step)] and
    whose ``step/loss_sync`` spans carry ``counters``, a step each."""
    cfg = dict(resolved()) if cfg is None else cfg
    events = [types.SimpleNamespace(op_name=name, self_s=s) for name, s in ops]
    trace = types.SimpleNamespace(per_step=lambda pick: sum(o.self_s for o in events if pick(o)) or None)
    spans = [{"name": "step/loss_sync", "step": i, "counters": c} for i, c in enumerate(counters)]
    return types.SimpleNamespace(
        cfg=cfg, trace=trace if ops else None, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        samples_per_step=1, clean_spans=lambda name: [s for s in spans if s["name"] == name],
    )


M = "jit(sharded_body)/step.grads"
REMAT = f"{M}/transpose(jvp(SdarLM))/step.grads/jvp(SdarLM)/checkpoint/rematted_computation"
BWD = f"{M}/transpose(jvp(SdarLM))/step.grads/jvp(SdarLM)/checkpoint"
OPS = [
    (f"{M}/jvp(SdarLM)/layer_0/self_attn/attn.blockwise/jit(flash_attention)/pallas_call", 0.040),
    (f"{REMAT}/layer_0/self_attn/attn.blockwise/jit(flash_attention)/pallas_call", 0.040),
    (f"{BWD}/layer_0/self_attn/attn.blockwise/jit(flash_attention)/flash_attention_bwd/pallas_call", 0.100),
    (f"{BWD}/layer_0/self_attn/attn.blockwise/jit(flash_attention)/reduce_sum", 0.004),  # dK, dV of a group summed
    (f"{M}/jvp(SdarLM)/layer_0/self_attn/attn.rope/qk_rope/pallas_call", 0.003),
    (f"{M}/jvp(SdarLM)/layer_0/self_attn/q_proj/dot_general", 0.020),  # the projections are nobody's
    (f"{M}/jvp(SdarLM)/denoise.loss/dot_general", 0.006),
    (f"{M}/denoise.loss/reduce_max", 0.002),
    (f"{M}/transpose(jvp(SdarLM))/denoise.loss/dot_general", 0.012),
    (f"{M}/jvp(SdarLM)/layer_0/mlp/moe.experts/moe.products/jit(_rows_by_groups)/grouped_matmul/pallas_call", 0.010),
]


def layer_counters(held, masked=None):
    c = {"held": [held], "absent": [16384 * 8 - sum(held)], "dropped": [0], "row_tiles": [40]}
    return dict(c, masked=[masked]) if masked is not None else c


def test_the_four_readers_on_a_made_up_run():
    counters = [{f"layer_{i}": layer_counters([1024] * 16, masked) for i in range(4)} for masked in (4000, 4100, 4200)]
    run = fake_run(OPS, counters)
    assert attn_blockwise_ms.read(run) == pytest.approx(184.0)  # the three passes and the group's sums
    assert denoise_loss_ms.read(run) == pytest.approx(20.0)  # the head, the cross-entropy, their backward
    assert attn_rope_ms.read(run) == pytest.approx(3.0)
    # four layers' visible pairs at the bf16 peak over the time under the scope
    assert attn_blockwise_roofline.read(run) == pytest.approx(100 * 4 * 25.13e-3 / 0.184, rel=1e-3)
    assert 0 < attn_blockwise_roofline.read(run) < 100
    assert masked_token_pct.read(run) == pytest.approx(100 * 4100 / 8192)  # the median step; every layer carries the count
    # the readers the cell shares find the layers' counters where they were: 16,384 landed of a chunk of 24,576
    assert moe_chunks.read(run) == 1 and moe_chunk_fill_pct.read(run) == pytest.approx(100 * 16384 / 24576)
    assert 0 < moe_gated_experts_roofline.read(run) < 100


def test_the_readers_return_nothing_where_the_program_has_no_such_scope_or_counter():
    untraced = fake_run([])
    parent = fake_run(  # a program without the scopes, whose counters carry no `masked`
        [("jit(f)/step.grads/jvp(MellumLM)/layer_0/attn.full/jit(flash_attention)/pallas_call", 0.01)],
        [{"layer_0": layer_counters([1024] * 16)}],
        cfg=dict(cells.cell("mellum2_psgd16_t8k")["config"], per_chip_batch=1),
    )
    for reader in (attn_blockwise_ms, attn_blockwise_roofline, denoise_loss_ms):
        assert reader.read(untraced) is None and reader.read(parent) is None
    assert masked_token_pct.read(untraced) is None and masked_token_pct.read(parent) is None
    # the scope without the configuration's blocks reads a time and no share
    other = fake_run(OPS[:1], cfg=dict(cells.cell("mellum2_psgd16_t8k")["config"], per_chip_batch=1))
    assert attn_blockwise_ms.read(other) == pytest.approx(40.0) and attn_blockwise_roofline.read(other) is None


def test_the_pool_is_noised_once_from_the_seed_and_never_draws_the_mask():
    from benchmark.builders import sdar as builder

    cfg = resolved(rehearsal=True)
    pool, again, other = builder.noised_pool(cfg, 3000051001), builder.noised_pool(cfg, 3000051001), builder.noised_pool(cfg, 7)
    assert sorted(pool) == sorted(builder.KEYS) and pool["input_ids"].shape == (16, cfg["text_len"])
    for key in builder.KEYS:
        np.testing.assert_array_equal(pool[key], again[key])
    assert not np.array_equal(pool["noisy_ids"], other["noisy_ids"])
    assert pool["input_ids"].max() < cfg["mask_token_id"]
    replaced = pool["loss_weight"] > 0
    np.testing.assert_array_equal(pool["noisy_ids"] == cfg["mask_token_id"], replaced)
    assert 0.3 < replaced.mean() < 0.7 and pool["loss_weight"][replaced].min() >= 1.0


def test_the_cells_own_limits_reach_the_comparison_and_no_other_cell(monkeypatch):
    """``reference_limits`` lie under ``reference_check.py``'s own and between
    the cell's two readings on the chip (the configuration file's
    ``reference_readings``): over the sound program's worst over its seeds,
    under the reading of the control that rounds the rotary angles to bf16,
    which came back ``correct: false`` under them. The second control (the
    flash kernels' scores rounded to bf16) read inside the sound program's own
    spread, so no limit can fail it and none pretends to. The cell's reference
    puts the limits in place when ``compare`` asks it for its loss, at the
    cell's size only (the rehearsal carries none)."""
    from benchmark import reference_check
    from benchmark.reference import sdar as reference

    cell = cells.cell(CELL)
    limits, readings = cell["config"]["reference_limits"], cell["config"]["reference_readings"]
    assert limits == {"update_all": 0.0065, "memory_all": 0.0065}
    sound, control = readings["sound_worst"], readings["rotary_angles_bf16"]
    for name, limit in limits.items():
        assert 1.5 * sound[name] < limit < control[name] / 1.3 and limit < reference_check.TOLERANCES[name], name
    assert control["correct"] is False
    assert sound == {  # the worst of the eight sound runs, a column each
        name: max(run[i] for run in readings["sound_runs"].values())
        for i, name in enumerate(("update_each", "update_all", "memory_each", "memory_all", "change_all"))
    }
    inseparable = readings["flash_scores_bf16"]  # read on the bf16-stream program: 0.0921 sound on its seed
    assert inseparable["correct"] and all(inseparable[name] < 1.35 * sound[name] for name in sound)
    assert readings["bf16_residual_stream"]["update_each"] > 0.34  # what the fp32 stream cured
    own = dict(reference_check.TOLERANCES)
    monkeypatch.setattr(reference_check, "TOLERANCES", dict(own))
    reference.make_loss_and_grads(resolved(rehearsal=True))
    assert reference_check.TOLERANCES == own  # a rehearsal is held to the harness's own
    reference.make_loss_and_grads(resolved())
    assert reference_check.TOLERANCES == {**own, **limits}


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed", "3000000051",
         "--seconds", "0.5", "--trace", "0", "--rehearsal"],
        cwd=cells.CHECKOUT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"wire_bytes_per_step"}  # untraced: the one end-to-end count, no device number
