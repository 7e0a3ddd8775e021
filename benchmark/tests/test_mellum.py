"""What PR 44 added to the benchmark: the Mellum2-12B-A2.5B configuration
file against its published source, the required-work function against a hand
count (at the published widths and at toy ones), the three new readers
(``moe_chunks``, ``moe_chunk_fill_pct``, ``attn_rope_ms``) and the two
roofline readers the cell shares, on made-up runs — a run of a program that
writes no such counter or scope among them — the manifest's new entries, and
the cell's rehearsal. (``test_cells.py`` and ``test_aot_v5e.py`` pick the cell
up by name: it resolves, compiles for v5e and fits.)"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import cells
from benchmark.flops import afmoe as kernel_costs
from benchmark.flops import mellum as flops
from benchmark.layer_metrics import (
    attn_rope_ms, attn_window_ms, attn_window_roofline, moe_chunk_fill_pct, moe_chunks, moe_gated_experts_roofline,
)

CELL = "mellum2_psgd16_t8k"
EIGHT_K = ["nemotron_psgd16_t8k", "trinity_psgd16_t8k", "qwen3next_psgd16_t8k", "lfm2_psgd16_t8k", CELL]
SLIDING, FULL = "sliding_attention", "full_attention"
# https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json, the keys that shape the model
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 8, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "sliding_window": 1024, "tie_word_embeddings": False, "use_sliding_window": True,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
}
CUT = {"num_hidden_layers": (28, 4), "num_experts": (64, 16), "vocab_size": (98304, 12288)}


def test_the_configuration_keeps_every_published_width_and_says_what_it_cut():
    cfg = cells.cell(CELL)["config"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts", "vocab_size"]
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here and key in cfg["cut"]
    # the model's own layers 0-3: one whole period, every layer sparse, no leading dense layer
    assert cfg["layer_types"] == [SLIDING, SLIDING, SLIDING, FULL] and cfg["mlp_layer_types"] == ["sparse"] * 4
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] and {"layer_types", "mlp_layer_types"} <= set(cfg["cut"])
    assert cfg["held_experts"] == list(range(16)) and cfg["router_width"] == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]  # the guide's floor
    assert "4 chips" in cfg["deployment"] and "16,384 assignments" in cfg["deployment"] and cfg["builder"] == "mellum"
    assert "538,531,072" in cfg["cut"]["parameters"] and "not_taken" in cfg["cut"]
    for said in ("qk_norm", "mtp", "auxiliary_loss", "compute_dtype", "weights", "positions", "optimizer", "remat", "data"):
        assert said in cfg["assumed"]
    # no width may be cut: nothing that ends in _dim or _rank or names a size but the vocabulary's
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k and k != "vocab_size" for k in cfg["reduced"])
    assert not set(cfg["rehearsal"]) & {"rms_norm_eps", "rope_parameters", "layer_types", "mlp_layer_types"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every number of the catalog's row, unless listed as reduced
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"] if k not in cfg["reduced"]} == {
            k: v for k, v in row["config"].items() if k not in cfg["reduced"]
        }
        # the cut's four layer kinds are the published list's first period, and the list repeats it
        assert cfg["layer_types"] == row["config"]["layer_types"][:4] == row["config"]["layer_types"][4:8]
        assert set(row["config"]["mlp_layer_types"]) == {"sparse"}


def test_the_manifest_gained_the_configuration_the_cell_and_three_metrics():
    bench = cells.manifest()
    # by name, not by place: the next configuration and cell go after these
    config = next(c for c in bench["configs"] if c["name"] == "mellum2-12b-a2.5b")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert config["file"] == "benchmark/configs/mellum2-12b-a2.5b.json"
    assert entry == {"name": CELL, "config": "mellum2-12b-a2.5b", "traffic": "t8k", "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    cell = cells.cell(CELL)
    assert cell["workload"]["why"] == entry["why"]
    assert cell["workload"]["traffic"] == {"kind": "lm_sequences", "pool_samples": 256, "zipf_exponent": 1.0}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "samples_per_s", "step_ms", "peak_hbm_gb", "wire_bytes_per_step", "setup_s",
    }
    reported = {m["name"] for m in cell["per_layer"]}
    leaves = {f"moe_{leaf}_ms" for leaf in ("score", "sort", "count", "layout", "gather", "products", "combine", "overflow")}
    assert leaves | {
        "attn_window_ms", "attn_window_roofline", "attn_full_ms", "attn_rope_ms", "moe_chunks", "moe_chunk_fill_pct",
        "mfu_pct", "moe_route_ms", "moe_experts_ms", "moe_gated_experts_roofline", "expert_load_max_over_mean",
        "moe_row_tile_visits", "fwd_ms", "remat_ms", "bwd_ms", "grads_ms", "reduce_ms", "update_ms", "unscoped_ms",
        "device_idle_pct", "compile_s", "step_temp_gb",
    } <= reported
    assert not {"ssd_ms", "gdn_ms", "shortconv_ms", "moe_experts_roofline", "flash_fwd_roofline"} & reported
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["moe_chunks"] == {
        "name": "moe_chunks", "unit": "chunks", "better": "lower", "source": "program_counter",
        "layer": "step compute", "moves": "step_ms", "workloads": EIGHT_K,
    }
    assert by_name["moe_chunk_fill_pct"]["workloads"] == EIGHT_K and by_name["moe_chunk_fill_pct"]["unit"] == "%"
    assert by_name["attn_rope_ms"]["workloads"] == EIGHT_K[1:] and by_name["attn_rope_ms"]["source"] == "device_trace"
    assert by_name["attn_window_roofline"]["workloads"] == ["trinity_psgd16_t8k", CELL]


def test_the_cells_own_limits_reach_the_comparison_and_no_other_cell(monkeypatch):
    """``reference_limits`` lie under ``reference_check.py``'s own and between
    the cell's two readings on the chip (as built: 0.043 / 0.035 / 0.0034;
    rotary angles in bf16: 0.167 / 0.209 / 0.0206); the cell's reference puts
    them in place when ``compare`` asks it for its loss, at the cell's size
    only (the rehearsal carries none)."""
    from benchmark import compose, reference_check
    from benchmark.reference import mellum as reference

    cell = cells.cell(CELL)
    limits = cell["config"]["reference_limits"]
    assert limits == {"update_each": 0.10, "memory_each": 0.10, "memory_all": 0.0085}
    sound, control = {"update_each": 0.0431, "memory_each": 0.0347, "memory_all": 0.00345}, {
        "update_each": 0.1666, "memory_each": 0.2089, "memory_all": 0.0206}
    for name, limit in limits.items():
        assert 1.9 * sound[name] < limit < control[name] / 1.6 and limit < reference_check.TOLERANCES[name]
    own = dict(reference_check.TOLERANCES)
    monkeypatch.setattr(reference_check, "TOLERANCES", dict(own))
    reference.make_loss_and_grads(compose.resolved(cell["config"], cell["workload"], rehearsal=True))
    assert reference_check.TOLERANCES == own  # a rehearsal is held to the harness's own
    reference.make_loss_and_grads(compose.resolved(cell["config"], cell["workload"], rehearsal=False))
    assert reference_check.TOLERANCES == {**own, **limits}
    other = cells.cell("lfm2_psgd16_t8k")["config"]
    assert "reference_limits" not in other


def test_required_operations_against_hand_counts():
    cfg = cells.cell(CELL)["config"]
    t, d = 8192, 2304
    projections = 2 * d * (32 + 2 * 4) * 128 + 2 * 32 * 128 * d  # q k v, o: 42,467,328
    assert flops.expected_assignments_per_token(cfg) == 8 * 16 / 64 == 2.0
    experts = 2 * d * 64 + 2.0 * 3 * 2 * d * 896  # router, two assignments a token, no shared expert
    outside_attention = 4 * (projections + experts) + 2 * d * 12288
    assert outside_attention == 326_762_496  # 326.8 MFLOP a token forward
    band = 1024 * t - 1024 * 1023 // 2  # the sliding layers' visible pairs a head
    triangle = t * (t + 1) // 2
    assert band / triangle == pytest.approx(0.2344, rel=1e-3)  # a sliding layer owes 23% of a causal layer's pairs
    attention = 4 * 128 * 32 * (3 * band + triangle)
    forward = t * outside_attention + attention
    assert flops.forward_flops_per_sample(cfg) == pytest.approx(forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(3 * forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(10.84e12, rel=1e-3)
    # one expert layer's routed part for the 16,384 assignments expected here: flops/afmoe.py's count at width 896
    ops, moved = kernel_costs.experts_cost(cfg, 16384)
    assert ops == 3 * (3 * 2 * d * 896) * 16384
    assert moved == 3 * (3 * 16 * d * 896 * 2) + 5 * 16384 * d * 2
    assert ops / 197e12 > moved / 819e9  # the MXU binds: 3.09 ms a layer against 1.19
    # one sliding layer's flash kernels: the band's pairs only, 18 D a pair under remat
    ops, moved = kernel_costs.window_attention_cost(dict(cfg, per_chip_batch=1), t)
    assert ops == 18 * 128 * band * 32 and moved == (4 * 32 + 4 * 4) * 128 * t * 2
    assert ops / 197e12 == pytest.approx(2.94e-3, rel=1e-2)  # 2.94 ms a layer


def test_required_operations_at_toy_widths_by_hand():
    """Two tokens of width 4, one head of 2 over one, two experts of width 3
    of which one is held, top 1, a window of 1, a vocabulary of 5, one sliding
    and one full layer: every term small enough to count on paper."""
    cfg = {
        "hidden_size": 4, "seq_len": 2, "num_attention_heads": 1, "num_key_value_heads": 1, "head_dim": 2,
        "router_width": 2, "held_experts": [0], "num_experts_per_tok": 1, "moe_intermediate_size": 3,
        "vocab_size": 5, "sliding_window": 1, "layer_types": [SLIDING, FULL],
    }
    projections = 2 * 4 * (1 + 2) * 2 + 2 * 2 * 4  # 48 + 16
    experts = 2 * 4 * 2 + 0.5 * 6 * 4 * 3  # router 16, half an assignment a token of 72
    pairs_sliding, pairs_full = 2, 3  # each token itself; the triangle of two
    by_hand = 2 * 4 * 5 * 2 + 2 * 2 * (projections + experts) + 4 * 2 * 1 * (pairs_sliding + pairs_full)
    assert by_hand == 80 + 4 * (64 + 52) + 40 == 584
    assert flops.forward_flops_per_sample(cfg) == by_hand and flops.flops_per_sample(cfg) == 3 * by_hand


def fake_run(ops=(), cfg=None, counters=()):
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


def step_counters(landed, layers=4):
    """One step's counters as ``train_loop`` writes them: a list a worker."""
    layer = {"held": [[landed // 16] * 16], "absent": [65536 - landed], "dropped": [0], "row_tiles": [47]}
    return {f"layer_{i}": dict(layer) for i in range(layers)}


def test_the_chunk_readers_on_made_up_runs():
    """The rows of a chunk come from the program's rule over the
    configuration's shapes (3 T = 24,576 here), the load from ``held``."""
    steady = fake_run(counters=[step_counters(16384), step_counters(16896), step_counters(15872)])
    assert moe_chunks.chunk_rows_of(steady) == 24576
    assert moe_chunks.read(steady) == 1.0
    assert moe_chunk_fill_pct.read(steady) == pytest.approx(100 * 16384 / 24576)  # the median step, 66.7
    # one step of three with one layer past the chunk: the MAXIMUM says so, the median fill does not
    heavy = step_counters(16384)
    heavy["layer_2"] = dict(heavy["layer_2"], held=[[1600] * 16])
    spiked = fake_run(counters=[step_counters(16384), heavy, step_counters(16384)])
    assert moe_chunks.read(spiked) == 2.0
    assert moe_chunk_fill_pct.read(spiked) == pytest.approx(100 * 16384 / 24576)
    # two workers: the fullest worker's chunk is the one that counts
    two = step_counters(16384)
    for layer in two.values():
        layer.update(held=[[1024] * 16, [1100] * 16])
    assert moe_chunk_fill_pct.read(fake_run(counters=[two])) == pytest.approx(100 * 17600 / 24576)
    # a sibling's shapes: lfm2's 8 held of 64 at top 4 expect T / 2, so a chunk is T rows
    lfm2 = dict(cells.cell("lfm2_psgd16_t8k")["config"], per_chip_batch=1)
    assert moe_chunks.chunk_rows_of(fake_run(cfg=lfm2)) == 8192


def test_the_new_readers_return_nothing_where_the_program_has_no_such_rule_counter_or_scope(monkeypatch):
    """The parent's program: no ``parallel.moe.chunk_rows``, a trace without
    ``attn.rope`` (nemotron's attention writes ``attn.core``); a run with no
    counters or no trace at all; a configuration with no expert layer."""
    from network_distributed_pytorch_tpu.parallel import moe

    bare = fake_run()
    dense_model = fake_run(cfg=dict(cells.cell("imdb_psgd16_b16")["config"], per_chip_batch=48),
                           counters=[step_counters(3000)] * 3)
    for run in (bare, dense_model):
        assert moe_chunks.read(run) is None and moe_chunk_fill_pct.read(run) is None
        assert attn_rope_ms.read(run) is None
    parent = fake_run(
        ops=[("jit(f)/step.grads/jvp(NemotronHLM)/layer_5/mixer/attn.core/pallas_call", 0.01)],
        counters=[step_counters(3000)] * 3,
    )
    monkeypatch.delattr(moe, "chunk_rows")
    assert moe_chunks.read(parent) is None and moe_chunk_fill_pct.read(parent) is None
    assert attn_rope_ms.read(parent) is None


M = "jit(sharded_body)/step.grads"
ATTENTION_OPS = [
    (f"{M}/jvp(MellumLM)/layer_0/self_attn/attn.rope/mul", 0.0010),  # the forward proper: the scope bare under flax's jvp
    (f"{M}/transpose(jvp(MellumLM))/step.grads/jvp(MellumLM)/checkpoint/rematted_computation/layer_0/self_attn/attn.rope/cos", 0.0012),
    (f"{M}/transpose(jvp(MellumLM))/step.grads/jvp(MellumLM)/checkpoint/layer_3/self_attn/attn.rope/mul", 0.0018),
    (f"{M}/jvp(MellumLM)/layer_0/self_attn/attn.window/jit(flash_attention)/pallas_call", 0.0300),
    (f"{M}/jvp(MellumLM)/layer_3/self_attn/attn.full/jit(flash_attention)/pallas_call", 0.0200),
    (f"{M}/jvp(MellumLM)/layer_0/self_attn/q_proj/dot_general", 0.0200),  # the projections are nobody's here
]


def test_the_rope_and_window_readers_on_a_made_up_run():
    run = fake_run(ATTENTION_OPS)
    assert attn_rope_ms.read(run) == pytest.approx(4.0)  # the three passes under the scope, both layer kinds
    assert attn_window_ms.read(run) == pytest.approx(30.0)
    # three sliding layers of 2.94 ms of required work each over the 30 ms under attn.window
    band = 1024 * 8192 - 1024 * 1023 // 2
    least = 18 * 128 * band * 32 / 197e12
    assert attn_window_roofline.read(run) == pytest.approx(100 * 3 * least / 0.030, rel=1e-9)
    assert 0 < attn_window_roofline.read(run) < 100


def test_the_gated_experts_roofline_reads_this_configuration():
    """``moe_gated_experts_roofline`` takes ``flops/afmoe.py::experts_cost``
    from ``hidden_size``, ``moe_intermediate_size`` and ``held_experts``, and
    the landed rows from the three counters ``scoped.step_counters`` takes by
    name."""
    ops = [(f"{M}/jvp(MellumLM)/layer_2/mlp/moe.experts/moe.products/x", 0.080)]
    run = fake_run(ops, counters=[step_counters(16384)] * 3)
    least = 4 * 9 * 2 * 2304 * 896 * 16384 / 197e12  # nine products of 2 x 2304 x 896 an assignment, four layers
    assert moe_gated_experts_roofline.read(run) == pytest.approx(100 * least / 0.080, rel=1e-9)
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
