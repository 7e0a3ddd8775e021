"""What PR 48 added to the benchmark: the Phi-4-mini-flash-reasoning
configuration file against its published source, the required-work functions
against hand counts (at the published widths and at toy ones), the seven new
readers on made-up runs — a run of a program that writes no such scope among
them — the cell's own limits for the comparison that decides ``correct``, the
manifest's new entries, and the cell's rehearsal. (``test_cells.py`` and
``test_aot_v5e.py`` pick the cell up by name: it resolves, compiles for v5e
and fits.)"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import cells
from benchmark.flops import phi4flash as flops
from benchmark.layer_metrics import (
    attn_cross_ms, attn_diff_ms, attn_full_ms, attn_window_ms, diff_attn_roofline, gmu_ms, mamba_frame_ms, s6_ms,
    s6_roofline,
)

CELL = "phi4flash_psgd16_t8k"
NEW = ["s6_ms", "s6_roofline", "mamba_frame_ms", "gmu_ms", "attn_cross_ms", "attn_diff_ms", "diff_attn_roofline"]
JOINED = [
    "compile_s", "data_wait_pct", "dispatch_ms", "grads_ms", "mfu_pct", "step_temp_gb", "reduce_ms", "orthogonalize_ms",
    "device_idle_pct", "stage_ms", "update_ms", "unscoped_ms", "fwd_ms", "bwd_ms", "remat_ms", "attn_window_ms",
    "attn_full_ms",
]
# https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json, the keys that shape the model
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash", "num_attention_heads": 40,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
    "mlp_bias": False, "lm_head_bias": False,
}
CUT = {"num_hidden_layers": (32, 5), "vocab_size": (200064, 25008)}


def test_the_configuration_keeps_every_published_width_and_says_what_it_cut():
    cfg = cells.cell(CELL)["config"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here and key in cfg["cut"]
    # the model's own layers 15-19: every kind once, the hand-over inside
    assert cfg["layer_indices"] == [15, 16, 17, 18, 19] and len(cfg["layer_indices"]) == cfg["num_hidden_layers"]
    assert flops.kinds(cfg) == ["sliding_attention", "mamba", "full_attention", "gmu", "cross_attention"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]  # the guide's floor
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"], cfg["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert cfg["mamba_dt_rank"] == -(-cfg["hidden_size"] // 16)
    assert "Eight data-parallel" in cfg["deployment"] and "25,008" in cfg["deployment"] and cfg["builder"] == "phi4flash"
    assert "577,199,232" in cfg["cut"]["parameters"] and "not_taken" in cfg["cut"] and len(cfg["cut"]["table"]) == 4
    for said in ("mamba", "differential_attention", "positions", "head_dim", "weights", "compute_dtype",
                 "tie_word_embeddings", "dropout", "auxiliary_loss", "optimizer", "remat", "data"):
        assert said in cfg["assumed"]
    # no width may be cut: nothing that ends in _dim or _rank or names a size but the vocabulary's
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k and k != "vocab_size" for k in cfg["reduced"])
    assert not set(cfg["rehearsal"]) & {"layer_norm_eps", "layer_indices", "mamba_d_conv", "mamba_expand", "published"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every number of the catalog's row, unless listed as reduced
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"] if k not in cfg["reduced"]} == {
            k: v for k, v in row["config"].items() if k not in cfg["reduced"]
        }
        assert {k: row["config"][k] for k in cfg["reduced"]} == {k: v[0] for k, v in CUT.items()}


def test_the_manifest_gained_the_configuration_the_cell_and_seven_metrics():
    bench = cells.manifest()
    # by name, not by place: the next configuration and cell go after these
    config = next(c for c in bench["configs"] if c["name"] == "phi-4-mini-flash-reasoning")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert config["file"] == "benchmark/configs/phi-4-mini-flash-reasoning.json"
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry == {"name": CELL, "config": "phi-4-mini-flash-reasoning", "traffic": "t8k", "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    cell = cells.cell(CELL)
    assert cell["workload"]["why"] == entry["why"] and cell["workload"]["trace_slice_steps"] == 6
    assert cell["workload"]["traffic"] == {"kind": "lm_sequences", "pool_samples": 256, "zipf_exponent": 1.0}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "samples_per_s", "step_ms", "peak_hbm_gb", "wire_bytes_per_step", "setup_s",
    }
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported == set(NEW) | set(JOINED)
    # a plain layer's count would understate this one's work by a third; no rotary, no experts, no Mamba-2, no rule
    assert not {"attn_window_roofline", "attn_rope_ms", "ssd_ms", "ssd_roofline", "gdn_ms", "flash_fwd_roofline"} & reported
    assert not any(name.startswith("moe_") or name == "expert_load_max_over_mean" for name in reported)
    new = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == NEW and new == bench["per_layer"][-7:]
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "step_ms" and m["source"] == "device_trace"
        assert (m["unit"], m["better"]) == (("%", "higher") if m["name"].endswith("_roofline") else ("ms", "lower"))


def test_the_cells_own_limits_reach_the_comparison_and_no_other_cell(monkeypatch):
    """``reference_limits`` lie under ``reference_check.py``'s own and between
    the cell's two readings on the chip (the configuration file's
    ``reference_readings``): over the sound program's worst over nine runs,
    under both readings of the control that computes the scan's state and decay
    in bf16, which came back ``correct: false`` under them. The second control
    (the difference and the subln in bf16) read inside the sound program's own
    spread, so no limit can fail it and none pretends to. The cell's reference
    puts the limits in place when ``compare`` asks it for its loss, at the
    cell's size only (the rehearsal carries none)."""
    from benchmark import compose, reference_check
    from benchmark.reference import phi4flash as reference

    cell = cells.cell(CELL)
    limits, readings = cell["config"]["reference_limits"], cell["config"]["reference_readings"]
    assert limits == {"update_each": 0.15}
    sound, control = readings["sound_worst"], readings["scan_state_and_decay_bf16"]
    for name, limit in limits.items():
        assert 2 * sound[name] < limit < min(control[name]) / 1.3 and limit < reference_check.TOLERANCES[name]
    assert control["correct"] == [False, False]
    inseparable = readings["subln_and_difference_bf16"]
    assert all(max(inseparable[name]) < 1.05 * sound[name] for name in sound if name.endswith(("_each", "_all")))
    own = dict(reference_check.TOLERANCES)
    monkeypatch.setattr(reference_check, "TOLERANCES", dict(own))
    reference.make_loss_and_grads(compose.resolved(cell["config"], cell["workload"], rehearsal=True))
    assert reference_check.TOLERANCES == own  # a rehearsal is held to the harness's own
    reference.make_loss_and_grads(compose.resolved(cell["config"], cell["workload"], rehearsal=False))
    assert reference_check.TOLERANCES == {**own, **limits}


def test_required_operations_against_hand_counts():
    cfg = dict(cells.cell(CELL)["config"], per_chip_batch=1)
    t, d, f, c, n, r = 8192, 2560, 10240, 5120, 16, 160
    mlp = 3 * 2 * d * f  # gate, up, down
    mamba = 2 * d * 2 * c + 2 * c * (r + 2 * n) + 2 * r * c + 2 * c * d + 7 * c * n  # in, x, dt, out, the scan
    attention = 2 * d * (40 + 2 * 20) * 64 + 2 * 40 * 64 * d  # Wqkv, out
    gmu = 2 * d * c + 2 * c * d
    cross = 2 * d * 40 * 64 + 2 * 40 * 64 * d  # Wq alone, out
    outside_attention = mamba + 2 * attention + gmu + cross + 5 * mlp + 2 * d * 25008
    assert outside_attention == 1_154_580_480  # 1.155 GFLOP a token forward
    band = 512 * t - 512 * 511 // 2  # the sliding layer's visible pairs a head
    triangle = t * (t + 1) // 2
    assert band / triangle == pytest.approx(0.1211, rel=1e-3)  # 16 windows: the band owes 12% of a causal layer's pairs
    # a pair of heads owes 2 hd for q . k and 2 * 2 hd for the doubled value, twice: 12 hd, over 20 pairs
    pairs = 12 * 64 * 20 * (band + 2 * triangle)
    assert 12 * 64 * 20 == 1.5 * (4 * 64 * 40)  # 1.5 times a plain layer of 40 heads of 64
    forward = t * outside_attention + pairs
    assert flops.forward_flops_per_sample(cfg) == pytest.approx(forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(3 * forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(31.65e12, rel=1e-3)
    # the scan of the one Mamba layer: 7 operations a (t, c, n) forward, twice that backward; x, delta, y, B, C and cotangents
    ops, moved = flops.s6_cost(cfg, t)
    assert ops == 3 * 7 * c * n * t
    assert moved == t * (c * (2 * 2 + 4) + 2 * n * 2 + c * (3 * 2 + 8) + 4 * n * 2)
    assert moved / 819e9 > ops / 197e12 and moved / 819e9 == pytest.approx(1.1286e-3, rel=1e-3)  # the bytes bind
    # one layer's flash calls under remat: forward twice (6 hd a head and pair each) and the backward's 15 hd
    for kind, seen in (("sliding_attention", band), ("full_attention", triangle), ("cross_attention", triangle)):
        ops, moved = flops.diff_attention_cost(cfg, t, kind)
        assert ops == pytest.approx((2 * 6 + 15) * 64 * 40 * seen, rel=1e-12)
        assert moved == (6 * 40 + 4 * 20) * 64 * t * 2
        assert ops / 197e12 > moved / 819e9  # the MXU binds
    assert flops.diff_attention_cost(cfg, t, "full_attention")[0] / 197e12 == pytest.approx(11.774e-3, rel=1e-3)
    assert flops.diff_attention_cost(dict(cfg, remat=False), t, "full_attention")[0] == pytest.approx((6 + 15) * 64 * 40 * triangle)


def test_required_operations_at_toy_sizes_by_hand():
    """Two layers of hidden 4 by hand: the published layer 0 (Mamba) and 1 (sliding) of a model of 4."""
    cfg = {
        "hidden_size": 4, "intermediate_size": 6, "num_attention_heads": 2, "num_key_value_heads": 2, "sliding_window": 2,
        "vocab_size": 10, "seq_len": 3, "layer_indices": [0, 1], "published": {"num_hidden_layers": 4},
        "mamba_expand": 2, "mamba_d_state": 3, "mamba_dt_rank": 1, "compute_dtype": "float32", "remat": False,
    }
    assert flops.kinds(cfg) == ["mamba", "sliding_attention"]
    assert [flops.kind_of(i, 4) for i in range(4)] == ["mamba", "sliding_attention", "mamba", "full_attention"]
    mamba = 2 * 4 * 16 + 2 * 8 * 7 + 2 * 1 * 8 + 2 * 8 * 4 + 7 * 8 * 3  # 128 + 112 + 16 + 64 + 168
    sliding = 2 * 4 * (2 + 4) * 2 + 2 * 2 * 2 * 4  # Wqkv 96, out 32
    mlp, head = 6 * 4 * 6, 2 * 4 * 10
    visible = 2 * 3 - 1  # window 2 over 3 tokens: 1 + 2 + 2
    want = 3 * (mamba + sliding + 2 * mlp + head) + 6 * 2 * 2 * visible
    assert flops.forward_flops_per_sample(cfg) == want == 3 * 984 + 120
    assert flops.s6_cost(cfg, 3) == (3 * 7 * 8 * 3 * 3, 3.0 * (8 * 12 + 2 * 3 * 4 + 8 * 20 + 4 * 3 * 4))


def fake_run(ops, cfg=None):
    """A run whose trace holds ``ops`` = [(op path, self seconds a step)]."""
    cfg = dict(cells.cell(CELL)["config"], per_chip_batch=1) if cfg is None else cfg
    events = [types.SimpleNamespace(op_name=name, self_s=s) for name, s in ops]
    trace = types.SimpleNamespace(per_step=lambda pick: sum(o.self_s for o in events if pick(o)) or None)
    return types.SimpleNamespace(
        cfg=cfg, trace=trace if ops else None, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )


M = "jit(sharded_body)/step.grads"
REMAT = f"{M}/transpose(jvp(Phi4FlashLM))/step.grads/jvp(Phi4FlashLM)/checkpoint/rematted_computation"
BWD = f"{M}/transpose(jvp(Phi4FlashLM))/step.grads/jvp(Phi4FlashLM)/checkpoint"
OPS = [
    (f"{M}/jvp(Phi4FlashLM)/layer_1/mixer/mamba.scan/while/body/mul", 0.040),  # the forward proper: the scope bare under flax's jvp
    (f"{REMAT}/layer_1/mixer/mamba.scan/while/body/checkpoint/exp", 0.042),
    (f"{BWD}/layer_1/mixer/mamba.scan/while/body/transpose(checkpoint)/mul", 0.118),
    (f"{M}/jvp(Phi4FlashLM)/layer_1/mixer/mamba.conv/mul", 0.001),
    (f"{M}/jvp(Phi4FlashLM)/layer_1/mixer/mamba.frame/x_proj/dot_general", 0.002),
    (f"{BWD}/layer_1/mixer/mamba.frame/mul", 0.003),
    (f"{M}/jvp(Phi4FlashLM)/layer_1/mixer/in_proj/dot_general", 0.020),  # in_proj and out_proj are nobody's
    (f"{M}/jvp(Phi4FlashLM)/layer_3/mixer/gmu.mix/in_proj/dot_general", 0.004),
    (f"{BWD}/layer_3/mixer/gmu.mix/mul", 0.005),
    (f"{M}/jvp(Phi4FlashLM)/layer_0/mixer/attn.window/jit(flash_attention)/pallas_call", 0.004),
    (f"{M}/jvp(Phi4FlashLM)/layer_2/mixer/attn.full/jit(flash_attention)/pallas_call", 0.012),
    (f"{BWD}/layer_2/mixer/attn.full/jit(flash_attention)/flash_attention_bwd/pallas_call", 0.020),
    (f"{M}/jvp(Phi4FlashLM)/layer_4/mixer/attn.cross/jit(flash_attention)/pallas_call", 0.012),
    (f"{M}/jvp(Phi4FlashLM)/layer_4/mixer/attn.cross/transpose", 0.002),  # the fold's relayouts sit under the scope too
    (f"{M}/jvp(Phi4FlashLM)/layer_4/mixer/attn.diff/concatenate", 0.003),
    (f"{BWD}/layer_0/mixer/attn.diff/mul", 0.004),
]


def test_the_seven_readers_on_a_made_up_run():
    run = fake_run(OPS)
    assert s6_ms.read(run) == pytest.approx(200.0)  # the three passes under the scope
    assert mamba_frame_ms.read(run) == pytest.approx(6.0)  # conv + frame, not the scan, not in_proj
    assert gmu_ms.read(run) == pytest.approx(9.0)
    assert attn_cross_ms.read(run) == pytest.approx(14.0)  # the kernel and the fold's transpose
    assert attn_diff_ms.read(run) == pytest.approx(7.0)
    assert attn_window_ms.read(run) == pytest.approx(4.0) and attn_full_ms.read(run) == pytest.approx(32.0)
    # one Mamba layer's bytes at the HBM peak over the time under the scope
    assert s6_roofline.read(run) == pytest.approx(100 * 1.1286e-3 / 0.200, rel=1e-3)
    assert 0 < s6_roofline.read(run) < 1  # starts well under 1%; a perfect kernel would read about 10
    # the three layers' visible pairs at the bf16 peak over window + full + cross
    least = (1.4257 + 2 * 11.7744) * 1e-3
    assert diff_attn_roofline.read(run) == pytest.approx(100 * least / 0.050, rel=1e-3)
    assert 0 < diff_attn_roofline.read(run) < 100


def test_the_readers_return_nothing_where_the_program_has_no_such_scope():
    untraced = fake_run([])
    parent = fake_run([("jit(f)/step.grads/jvp(NemotronHLM)/layer_0/mixer/mamba.ssd/mul", 0.01)])  # a program without the scopes
    other_cell = fake_run(  # a cell whose configuration has no Mamba-1 keys reads no share
        [(f"{M}/jvp(MellumLM)/layer_0/attn.window/jit(flash_attention)/pallas_call", 0.004)],
        cfg=dict(cells.cell("mellum2_psgd16_t8k")["config"], per_chip_batch=1),
    )
    for reader in (s6_ms, s6_roofline, mamba_frame_ms, gmu_ms, attn_cross_ms, attn_diff_ms, diff_attn_roofline):
        assert reader.read(untraced) is None and reader.read(parent) is None
    assert s6_roofline.read(other_cell) is None and diff_attn_roofline.read(other_cell) is None


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed", "3000000048",
         "--seconds", "0.5", "--trace", "0", "--rehearsal"],
        cwd=cells.CHECKOUT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"wire_bytes_per_step"}  # untraced: the one end-to-end count, no device number
