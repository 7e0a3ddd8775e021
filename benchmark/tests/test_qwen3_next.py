"""What PR 33 added to the benchmark: the Qwen3-Next configuration file
against its published source, the required-work functions against hand
counts, the three Gated-DeltaNet readers and the row-tile reader on a made-up
run, and the cell's rehearsal. (``test_cells.py`` and ``test_aot_v5e.py`` pick
the cell up by name: it resolves, compiles for v5e and fits.)"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import cells
from benchmark.flops import afmoe as afmoe_flops
from benchmark.flops import qwen3_next as flops
from benchmark.layer_metrics import (
    attn_full_ms, gdn_frame_ms, gdn_ms, gdn_roofline, moe_gated_experts_roofline, moe_row_tile_visits,
)

CELL = "qwen3next_psgd16_t8k"
LINEAR, FULL = "linear_attention", "full_attention"
# https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json, the keys that shape the model
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 10,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False,
}
CUT = {"num_hidden_layers": (48, 4), "num_experts": (512, 16), "vocab_size": (151936, 18992)}


def test_the_configuration_keeps_every_published_width_and_says_what_it_cut():
    cfg = cells.cell(CELL)["config"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here and key in cfg["cut"]
    # the model's own layers 0-3: one whole period, three linear layers and the full one
    assert cfg["layer_types"] == [LINEAR, LINEAR, LINEAR, FULL] and "layer_types" in cfg["cut"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == cfg["full_attention_interval"]
    assert cfg["held_experts"] == list(range(16)) and cfg["router_width"] == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "32 chips" in cfg["deployment"] and cfg["builder"] == "qwen3_next"
    for said in ("weights", "fused_leaves", "mtp", "router_aux_loss_coef", "gdn_chunk_size", "compute_dtype",
                 "optimizer", "remat", "data", "positions"):
        assert said in cfg["assumed"]
    assert "424,340,544" in cfg["cut"]["parameters"] and "625,667,136" in cfg["cut"]["not_taken"]
    # no width may be cut: nothing that ends in _dim or _rank or names a size but the vocabulary's
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k and k != "vocab_size" for k in cfg["reduced"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every number of the catalog's row, unless listed as reduced
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"] if k not in cfg["reduced"]} == {
            k: v for k, v in row["config"].items() if k not in cfg["reduced"]
        }


def test_the_manifest_gained_the_cell_and_its_metrics_and_lost_nothing():
    bench = cells.manifest()
    assert [c["name"] for c in bench["configs"]][-1] == "qwen3-next-80b-a3b" and len(bench["configs"]) == 5
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "qwen3-next-80b-a3b", "traffic": "t8k", "chips": 1, "why": bench["workloads"][-1]["why"],
    }
    assert len(bench["workloads"]) == 7 and len(bench["workloads"][-1]["why"]) <= 200
    cell = cells.cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "samples_per_s", "step_ms", "peak_hbm_gb", "wire_bytes_per_step", "setup_s",
    }
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"gdn_ms", "gdn_roofline", "gdn_frame_ms", "moe_row_tile_visits", "attn_full_ms", "mfu_pct",
            "moe_route_ms", "moe_experts_ms", "moe_gated_experts_roofline", "expert_load_max_over_mean"} <= reported
    assert not {"ssd_ms", "attn_window_ms", "moe_experts_roofline", "flash_fwd_roofline"} & reported
    new = {m["name"]: m for m in bench["per_layer"][-4:]}
    assert list(new) == ["gdn_ms", "gdn_roofline", "gdn_frame_ms", "moe_row_tile_visits"]
    assert all(m["moves"] == "step_ms" for m in new.values()) and new["gdn_roofline"]["unit"] == "%"
    assert sorted(new["moe_row_tile_visits"]["workloads"]) == sorted([CELL, "nemotron_psgd16_t8k", "trinity_psgd16_t8k"])


def test_required_operations_against_hand_counts():
    cfg = cells.cell(CELL)["config"]
    t, c, d = 8192, 64, 128
    # one chunk: K K^T and Q K^T a key head; a value head the solve by substitution (C^3 / 3 multiply-adds),
    # T (beta V), T (beta K), W S, Q S, K^T V' and (Q K^T) V'
    chunk = 16 * (2 * 2 * c * c * d) + 32 * (2 * c ** 3 / 3 + 2 * 2 * c * c * d + 3 * 2 * c * d * d + 2 * c * c * d)
    rule = (t // c) * chunk
    assert flops.gated_delta_forward_flops(cfg, t) == pytest.approx(rule, rel=1e-12)
    assert rule / t == pytest.approx(5.33e6, rel=1e-2)  # ~5 MFLOP a token and layer: small beside the projections' 67
    assert flops.gated_delta_forward_flops(cfg, t - 1) == pytest.approx(rule, rel=1e-12)  # a ragged tail is a whole chunk
    linear = 2 * 2048 * 12288 + 2 * 2048 * 64 + 2 * 4096 * 2048 + 2 * 4 * 8192  # qkvz, ba, out, the conv's four taps
    full = 2 * 2048 * (2 * 16 + 2 * 2) * 256 + 2 * 4096 * 2048  # q with its gate, k, v; o
    assert flops.expected_assignments_per_token(cfg) == 10 * 16 / 512
    experts = 2 * 2048 * 512 + 2 * 2048 + 3 * 2 * 2048 * 512 + 0.3125 * 3 * 2 * 2048 * 512  # router, gate, shared, routed
    forward = (
        t * (3 * linear + full + 4 * experts + 2 * 2048 * 18992) + 3 * rule + 4 * 256 * 16 * (t * (t + 1) // 2)
    )
    assert flops.forward_flops_per_sample(cfg) == pytest.approx(forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(3 * forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(11.28e12, rel=1e-3)
    ops, moved = flops.gated_delta_cost(cfg, t)
    assert ops == pytest.approx(3 * rule, rel=1e-12)
    # q, k at 16 heads and v, o at 32 forward; q, k, v, do in and three cotangents out backward, bf16; g, beta and theirs fp32
    assert moved == t * ((2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4 + (4 * 2048 + 3 * 4096) * 2 + 4 * 32 * 4)
    assert ops / 197e12 == pytest.approx(0.665e-3, rel=1e-2) and moved / 819e9 == pytest.approx(0.663e-3, rel=1e-2)
    # the experts' cost is afmoe's, imported: three products an expert, 16 stacked leaves of 2048 x 512
    assert flops.experts_cost is afmoe_flops.experts_cost
    ops, moved = flops.experts_cost(cfg, 2560)
    assert ops == 3 * (3 * 2 * 2048 * 512) * 2560
    assert moved == 3 * (3 * 16 * 2048 * 512 * 2) + 5 * 2560 * 2048 * 2
    assert moved / 819e9 > ops / 197e12  # at 160 rows an expert reading the weights binds: 0.433 ms a layer against 0.245


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


OPS = [
    ("jit(f)/step.grads/jvp(gdn.rule)/dot_general", 0.020),  # the forward proper wraps the scope
    ("jit(f)/step.grads/checkpoint/rematted_computation/gdn.rule/while/body/dot_general", 0.025),
    ("jit(f)/step.grads/transpose(jvp(gdn.rule))/while/body/dot_general", 0.045),
    ("jit(f)/step.grads/jvp(gdn.conv)/mul", 0.004),
    ("jit(f)/step.grads/transpose(jvp(gdn.frame))/reduce", 0.006),
    ("jit(f)/step.grads/checkpoint/rematted_computation/gdn.frame/rsqrt", 0.002),
    ("jit(f)/step.grads/jvp(attn.full)/pallas_call", 0.009),
    ("jit(f)/step.grads/transpose(jvp(attn.full))/flash_attention_bwd", 0.013),
    ("jit(f)/step.grads/jvp(moe.experts)/grouped_matmul", 0.030),
]


def test_the_gated_delta_readers_on_a_made_up_run():
    run = fake_run(OPS)
    assert gdn_ms.read(run) == pytest.approx(90.0)
    assert gdn_frame_ms.read(run) == pytest.approx(12.0)
    assert attn_full_ms.read(run) == pytest.approx(22.0)
    # three linear layers, the MXU binds by a hair: 130.997 GFLOP a layer at 197e12 a second, over 90 ms
    least = 3 * flops.gated_delta_cost(run.cfg, 8192)[0] / 197e12
    assert gdn_roofline.read(run) == pytest.approx(100 * least / 0.090, rel=1e-9)
    assert 0 < gdn_roofline.read(run) < 100


def test_readers_return_nothing_where_the_program_has_no_such_scope_or_counter():
    untraced = fake_run([])
    parent = fake_run([("jit(f)/step.grads/jvp(mamba.ssd)/dot_general", 0.01)])  # a program without the rule
    for run in (untraced, parent):
        for reader in (gdn_ms, gdn_frame_ms, gdn_roofline):
            assert reader.read(run) is None
    other_model = fake_run([("jit(f)/step.grads/gdn.rule/x", 0.01)], cfg={"per_chip_batch": 1, "seq_len": 8192})
    assert gdn_roofline.read(other_model) is None  # a configuration with no linear-attention layer to count
    assert moe_row_tile_visits.read(untraced) is None  # no step carried counters
    before_pr_32 = [{"layer_1": {"held": [[10] * 8], "absent": [5], "dropped": [0]}}]
    assert moe_row_tile_visits.read(fake_run([], counters=before_pr_32)) is None  # counters, but no row_tiles


def landed(row_tiles, workers=1):
    """One step's counters as ``train_loop`` writes them (a leading worker axis)."""
    return {
        f"layer_{i}": {"held": [[160] * 16] * workers, "absent": [79360] * workers, "dropped": [0] * workers,
                       "row_tiles": [n] * workers}
        for i, n in enumerate(row_tiles)
    }


def test_row_tile_visits_is_the_worst_layer_of_a_step_and_the_median_over_steps():
    steps = [landed([20, 21, 20, 19]), landed([20, 20, 20, 20]), landed([23, 19, 19, 19])]
    assert moe_row_tile_visits.read(fake_run([], counters=steps)) == 21  # of the worsts 21, 20, 23
    assert moe_row_tile_visits.read(fake_run([], counters=[landed([13, 12, 13], workers=4)])) == 52  # summed over workers
    # the gated experts' share reads the same spans in this cell: the bytes bind at 160 rows an expert
    run = fake_run(OPS, counters=steps)
    ops, moved = flops.experts_cost(run.cfg, 16 * 160)
    assert moe_gated_experts_roofline.read(run) == pytest.approx(100 * 4 * (moved / 819e9) / 0.030, rel=1e-9)


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
