"""What PR 41 added to the benchmark: the LFM2-24B-A2B configuration file
against its published source, the required-work functions against hand
counts, the short-conv reader (and the gated experts' and the full layer's,
which the cell shares) on a made-up run, the manifest's new entries,
and the cell's rehearsal. (``test_cells.py`` and ``test_aot_v5e.py`` pick the
cell up by name: it resolves, compiles for v5e and fits.)"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import cells
from benchmark.flops import lfm2 as flops
from benchmark.layer_metrics import attn_full_ms, moe_gated_experts_roofline, shortconv_ms

CELL = "lfm2_psgd16_t8k"
CONV, FULL = "conv", "full_attention"
# https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json, the keys that shape the model
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1,
    "use_expert_bias": True,
}
CUT = {"num_hidden_layers": (40, 5), "num_dense_layers": (2, 1), "num_experts": (64, 8), "vocab_size": (65536, 16384)}


def test_the_configuration_keeps_every_published_width_and_says_what_it_cut():
    cfg = cells.cell(CELL)["config"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"]
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here and key in cfg["cut"]
    # the model's own layers 1-5: a leading dense conv layer, then one whole period full, conv, conv, conv
    assert cfg["layer_types"] == [CONV, FULL, CONV, CONV, CONV] and "layer_types" in cfg["cut"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    assert cfg["held_experts"] == list(range(8)) and cfg["router_width"] == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert "8 chips" in cfg["deployment"] and cfg["builder"] == "lfm2"
    assert "486,062,208" in cfg["cut"]["parameters"] and "519,616,640" in cfg["cut"]["parameters"]
    for said in ("tie_word_embeddings", "expert_bias", "route_norm_epsilon", "auxiliary_loss", "weights",
                 "compute_dtype", "positions", "optimizer", "remat", "data", "head_dim"):
        assert said in cfg["assumed"]
    assert "1e-6" in cfg["assumed"]["route_norm_epsilon"] and "TIED" in cfg["assumed"]["tie_word_embeddings"]
    # no width may be cut: nothing that ends in _dim or _rank or names a size but the vocabulary's
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k and k != "vocab_size" for k in cfg["reduced"])
    assert not set(cfg["rehearsal"]) & {"conv_L_cache", "norm_eps", "rope_parameters", "layer_types"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every number of the catalog's row, unless listed as reduced
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"] if k not in cfg["reduced"]} == {
            k: v for k, v in row["config"].items() if k not in cfg["reduced"]
        }
        # the cut's five layer kinds are the published list's entries 1..5
        assert cfg["layer_types"] == row["config"]["layer_types"][1:6]
        assert row["config"]["layer_types"][2:6] == row["config"]["layer_types"][6:10]  # one whole period


def test_the_manifest_gained_the_configuration_the_cell_and_one_metric():
    bench = cells.manifest()
    assert bench["configs"][-1]["name"] == "lfm2-24b-a2b" and bench["configs"][-1]["file"] == "benchmark/configs/lfm2-24b-a2b.json"
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "lfm2-24b-a2b", "traffic": "t8k", "chips": 1, "why": bench["workloads"][-1]["why"],
    }
    assert len(bench["workloads"][-1]["why"]) <= 200 and len(bench["configs"][-1]["why"]) <= 200
    cell = cells.cell(CELL)
    assert cell["workload"]["why"] == bench["workloads"][-1]["why"]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "samples_per_s", "step_ms", "peak_hbm_gb", "wire_bytes_per_step", "setup_s",
    }
    reported = {m["name"] for m in cell["per_layer"]}
    leaves = {f"moe_{leaf}_ms" for leaf in ("score", "sort", "count", "layout", "gather", "products", "combine", "overflow")}
    assert leaves | {
        "shortconv_ms", "attn_full_ms", "mfu_pct", "moe_route_ms", "moe_experts_ms",
        "moe_gated_experts_roofline", "expert_load_max_over_mean", "moe_row_tile_visits", "fwd_ms", "remat_ms",
        "bwd_ms", "grads_ms", "reduce_ms", "update_ms", "unscoped_ms", "device_idle_pct", "compile_s", "step_temp_gb",
    } <= reported
    assert not {"ssd_ms", "gdn_ms", "attn_window_ms", "moe_experts_roofline", "flash_fwd_roofline"} & reported
    # no roofline share for the mix: XLA fuses it into its neighbours, and the share read 110% (PERF.md section 6, PR 41)
    new = [m for m in bench["per_layer"] if m["name"].startswith("shortconv_")]
    assert new == [{
        "name": "shortconv_ms", "unit": "ms", "better": "lower", "source": "device_trace", "layer": "kernels",
        "moves": "step_ms", "workloads": [CELL],
    }]


def test_required_operations_against_hand_counts():
    cfg = cells.cell(CELL)["config"]
    t, d = 8192, 2048
    conv = 2 * d * 6144 + 2 * d * d + 7 * d  # in_proj, out_proj, the mix: two gates, three taps, two adds
    full = 2 * d * (32 + 2 * 8) * 64 + 2 * 32 * 64 * d  # q k v, o
    dense = 3 * 2 * d * 11776  # three products
    assert flops.expected_assignments_per_token(cfg) == 4 * 8 / 64
    experts = 2 * d * 64 + 0.5 * 3 * 2 * d * 1536  # router, half an assignment a token, no shared expert
    outside_attention = 4 * conv + full + dense + 4 * experts + 2 * d * 16384
    assert outside_attention == 405_856_256  # 405.8 MFLOP a token forward
    triangle = 4 * 64 * 32 * (t * (t + 1) // 2)
    assert triangle == pytest.approx(0.2749e12, rel=1e-3)
    forward = t * outside_attention + triangle
    assert flops.forward_flops_per_sample(cfg) == pytest.approx(forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(3 * forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(10.80e12, rel=1e-3)
    # one expert layer's routed part for the 4,096 assignments expected here: flops/afmoe.py's count at width 1536
    ops, moved = flops.experts_cost(cfg, 4096)
    assert ops == 3 * (3 * 2 * d * 1536) * 4096
    assert moved == 3 * (3 * 8 * d * 1536 * 2) + 5 * 4096 * d * 2
    assert ops / 197e12 > moved / 819e9  # the MXU binds: 1.18 ms a layer against 0.66


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


M = "jit(sharded_body)/step.grads"
MIX_OPS = [
    (f"{M}/jvp(Lfm2LM)/layer_0/conv/shortconv.mix/mul", 0.0030),  # the forward proper: the scope bare under flax's jvp
    (f"{M}/transpose(jvp(Lfm2LM))/step.grads/jvp(Lfm2LM)/checkpoint/rematted_computation/layer_0/conv/shortconv.mix/add", 0.0032),
    (f"{M}/transpose(jvp(Lfm2LM))/step.grads/jvp(Lfm2LM)/checkpoint/layer_0/conv/shortconv.mix/pad", 0.0058),
    (f"{M}/jvp(Lfm2LM)/layer_0/conv/in_proj/dot_general", 0.0200),  # the projections are not the mix's
    (f"{M}/jvp(Lfm2LM)/layer_1/self_attn/attn.full/jit(flash_attention)/pallas_call", 0.0060),
    (f"{M}/jvp(Lfm2LM)/layer_1/self_attn/attn.full/transpose", 0.0010),  # the fold's transposes sit under the scope too
    (f"{M}/jvp(Lfm2LM)/layer_1/self_attn/attn.rope/mul", 0.0030),
]


def test_the_short_conv_reader_on_a_made_up_run():
    run = fake_run(MIX_OPS)
    assert shortconv_ms.read(run) == pytest.approx(12.0)  # the three passes under the scope, not the projections
    assert attn_full_ms.read(run) == pytest.approx(7.0)  # the kernel and the fold's transpose under the scope


def test_the_short_conv_reader_returns_nothing_where_the_program_has_no_such_scope():
    untraced = fake_run([])
    parent = fake_run([("jit(f)/step.grads/jvp(gdn.conv)/mul", 0.01)])  # a program without the scope
    for run in (untraced, parent):
        assert shortconv_ms.read(run) is None


def test_the_gated_experts_roofline_reads_this_configuration():
    """``moe_gated_experts_roofline`` takes ``flops/afmoe.py::experts_cost``
    from ``hidden_size``, ``moe_intermediate_size`` and ``held_experts``: the
    keys this configuration keeps."""
    ops = [(f"{M}/jvp(Lfm2LM)/layer_2/feed_forward/moe.experts/moe.products/x", 0.030)]
    step = {
        f"layer_{i}": {"held": [[512] * 8], "absent": [32768 - 4096], "dropped": [0]} for i in range(1, 5)
    }
    run = fake_run(ops, counters=[step, step, step])
    least = 4 * 9 * 2 * 2048 * 1536 * 4096 / 197e12  # nine products of 2 x 2048 x 1536 an assignment, four layers
    assert moe_gated_experts_roofline.read(run) == pytest.approx(100 * least / 0.030, rel=1e-9)
    assert 0 < moe_gated_experts_roofline.read(run) < 100


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed", "3000000041",
         "--seconds", "0.5", "--trace", "0", "--rehearsal"],
        cwd=cells.CHECKOUT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"wire_bytes_per_step"}  # untraced: the one end-to-end count, no device number
