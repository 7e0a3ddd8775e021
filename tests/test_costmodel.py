"""Offline what-if cost model (observe.costmodel) and its observatory.

Unit-pins the calibration math on a synthetic run report, the per-config
prediction components (compression bytes, chunk pipeline depth, sync-period
amortization), the deterministic fabric flip the model exists to predict
(compression wins on a slow fabric, the dense baseline wins on ICI), the
plan document + PredictionEvent pipeline, the predicted-vs-realized join,
the plan-ordered fallback ladder, and the gate's costmodel_error /
missing_baseline plumbing. Also the analytics edge cases the planner
leans on (single-sample percentiles, zero-duration spans, ledgers without
overlap attribution). Everything here is jax-free.
"""

import importlib.util
import json
import math
import os
import sys

import pytest

from network_distributed_pytorch_tpu.observe import analytics, costmodel, runlog
from network_distributed_pytorch_tpu.observe.events import PredictionEvent
from network_distributed_pytorch_tpu.resilience import (
    DEFAULT_LADDER,
    ladder_from_plan,
)
from network_distributed_pytorch_tpu.utils import bandwidth

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        f"_costmodel_test_{name}", os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[f"_costmodel_test_{name}"] = mod
    spec.loader.exec_module(mod)
    return mod


MIB = 1 << 20


def _toy_report(**over):
    """A synthetic run report shaped like scripts/report.py's machine dict:
    10 ms of pure compute (the step/compute span), one fully-exposed 8 MiB
    all-reduce, 80 ms measured step — a comm-dominated 2-worker run."""
    doc = {
        "run_dir": "synthetic",
        "step_p50_s": 0.08,
        "world_size": 2,
        "bandwidth": {
            "total": {"payload_bytes": 8 * MIB, "count": 1},
            "attribution": {"exposed_fraction": 1.0, "n_collectives": 1},
        },
        "compile": {
            "analytic_bytes": 8 * MIB,
            "comm_config": {"reducer": "exactreducer"},
        },
        "mfu": [{"flops_per_step": 2.0e9, "peak_flops_per_s": 1.0e12}],
        "spans": {"by_name": {"step/compute": {"mean_s": 0.01}}},
    }
    doc.update(over)
    return doc


# ---------------------------------------------------------------------------
# canonical configs and join keys
# ---------------------------------------------------------------------------


def test_canonical_config_normalizes_knobs():
    c = costmodel.canonical_config(
        {"reducer": "PowerSGDReducer", "bucket_bytes": None}, name="rung"
    )
    assert c["reducer"] == "powersgd"
    assert c["reducer_rank"] == 1  # powersgd without a rank is rank-1
    assert c["bucket_bytes"] == 0
    assert c["sync_every"] == 1
    assert c["name"] == "rung"
    # exact is the default family, whatever the class name looked like
    assert costmodel.canonical_config({})["reducer"] == "exact"


def test_canonical_config_ignores_retired_knobs():
    """A run record or plan written while payloads could be chunked still
    carries ``comm_chunks`` / ``comm_strategy``: they are read and dropped,
    so the record joins the config it would be today."""
    old = {
        "reducer": "powersgd", "reducer_rank": 4,
        "comm_chunks": 4, "comm_strategy": "ring",
    }
    c = costmodel.canonical_config(old)
    assert "comm_chunks" not in c and "comm_strategy" not in c
    new = {"reducer": "powersgd", "reducer_rank": 4}
    assert c == costmodel.canonical_config(new)
    assert costmodel.config_key(old) == costmodel.config_key(new)
    calib = costmodel.calibrate(_toy_report())
    assert costmodel.predict(calib, old, "1GbE")["predicted_step_s"] == (
        costmodel.predict(calib, new, "1GbE")["predicted_step_s"]
    )


def test_config_key_joins_on_knobs_not_names():
    a = {"name": "compress-low-rank", "reducer": "powersgd", "reducer_rank": 1}
    b = {"name": "toy", "reducer": "PowerSGDReducer", "reducer_rank": 1}
    assert costmodel.config_key(a) == costmodel.config_key(b)
    assert costmodel.config_key(a) != costmodel.config_key(
        {"reducer": "powersgd", "reducer_rank": 2}
    )


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------


def test_calibrate_reads_spans_bytes_and_flops():
    calib = costmodel.calibrate(_toy_report())
    assert calib.step_time_s == pytest.approx(0.08)
    assert calib.compute_s == pytest.approx(0.01)  # the step/compute mean
    assert calib.dense_bytes == 8 * MIB
    assert calib.n_workers == 2
    assert calib.exposed_fraction == 1.0
    assert calib.flops_per_step == 2.0e9
    # effective rate is MFU-scaled: measured FLOPs over measured compute
    assert calib.effective_flops_per_s == pytest.approx(2.0e9 / 0.01)
    assert calib.source_config["reducer"] == "exact"


def test_calibrate_requires_a_step_time():
    with pytest.raises(ValueError):
        costmodel.calibrate({"world_size": 2})


def test_calibrate_source_fabric_subtracts_modeled_comm():
    # a jitted step's collectives retire inside step/compute: with the
    # source fabric named, the modeled exposed comm comes OFF the compute
    # calibration (floored at MIN_COMPUTE_FRACTION of the step)
    report = _toy_report(
        spans={"by_name": {"step/compute": {"mean_s": 0.08}}}
    )
    plain = costmodel.calibrate(report)
    adjusted = costmodel.calibrate(report, source_fabric="1GbE")
    modeled = bandwidth.allreduce_time_s(8 * MIB, 2, "1GbE", n_collectives=1)
    assert plain.compute_s == pytest.approx(0.08)
    assert adjusted.compute_s == pytest.approx(
        max(0.08 - modeled, costmodel.MIN_COMPUTE_FRACTION * 0.08)
    )
    assert adjusted.compute_s < plain.compute_s


def test_calibrate_compressed_source_measures_bytes_fraction():
    # a source run that executed PowerSGD rank-2 moving 2 MiB of an 8 MiB
    # dense gradient calibrates bytes_fraction_per_rank = (2/8)/2
    report = _toy_report(
        bandwidth={
            "total": {"payload_bytes": 2 * MIB, "count": 1},
            "attribution": {"exposed_fraction": 1.0, "n_collectives": 1},
        },
        compile={
            "analytic_bytes": 2 * MIB,
            "dense_grad_bytes": 8 * MIB,
            "comm_config": {"reducer": "powersgd", "reducer_rank": 2},
        },
    )
    calib = costmodel.calibrate(report)
    assert calib.dense_bytes == 8 * MIB
    assert calib.bytes_fraction_per_rank == pytest.approx(0.125)


# ---------------------------------------------------------------------------
# prediction components
# ---------------------------------------------------------------------------


def test_predict_baseline_is_compute_plus_wire_and_latency():
    calib = costmodel.calibrate(_toy_report())
    p = costmodel.predict(calib, {"name": "baseline"}, "1GbE")
    wire = (2.0 * 1 / 2) * (8 * MIB / bandwidth.FABRICS_BYTES_PER_S["1GbE"])
    assert p["wire_s"] == pytest.approx(wire)
    assert p["predicted_step_s"] == pytest.approx(
        0.01 + wire + bandwidth.LATENCY_S["1GbE"]
    )
    assert p["predicted_bytes_per_step"] == 8 * MIB
    assert p["pipeline_depth"] == 1


def test_predict_compression_shrinks_bytes_and_prices_compute():
    calib = costmodel.calibrate(_toy_report())
    p = costmodel.predict(
        calib, {"reducer": "powersgd", "reducer_rank": 1}, "1GbE"
    )
    # rank-1 payload: dense/8 by the default per-rank fraction; P and Q
    # round trips double the per-collective latency
    assert p["predicted_bytes_per_step"] == pytest.approx(MIB)
    assert p["latency_s"] == pytest.approx(2 * bandwidth.LATENCY_S["1GbE"])
    expected_compress = (
        costmodel.POWERSGD_FLOPS_PER_ELEM_PER_RANK * (8 * MIB / 4.0)
    ) / calib.effective_flops_per_s
    assert p["compress_s"] == pytest.approx(expected_compress)


def test_predict_bucket_bytes_sets_depth_and_caps():
    calib = costmodel.calibrate(_toy_report())
    p = costmodel.predict(calib, {"bucket_bytes": 2 * MIB}, "1GbE")
    assert p["pipeline_depth"] == 4  # ceil(8 MiB / 2 MiB)
    tiny = costmodel.predict(calib, {"bucket_bytes": 1}, "1GbE")
    assert tiny["pipeline_depth"] == costmodel.MAX_PIPELINE_DEPTH


def test_predict_sync_every_amortizes_the_round():
    calib = costmodel.calibrate(_toy_report())
    every = costmodel.predict(calib, {}, "1GbE")
    wide = costmodel.predict(calib, {"sync_every": 8}, "1GbE")
    comm_every = every["predicted_step_s"] - every["compute_s"]
    comm_wide = wide["predicted_step_s"] - wide["compute_s"]
    assert comm_wide == pytest.approx(comm_every / 8)
    assert wide["predicted_bytes_per_step"] == pytest.approx(8 * MIB / 8)


def test_predict_rejects_unknown_fabric():
    calib = costmodel.calibrate(_toy_report())
    with pytest.raises(ValueError):
        costmodel.predict(calib, {}, "carrier-pigeon")


def test_fabric_flip_compression_wins_slow_baseline_wins_ici():
    # THE prediction the planner exists for: on 1 GbE the dense 8 MiB wire
    # time (~67 ms) dwarfs the compression compute (~0.3 ms), on ICI the
    # ordering inverts — the same configs, ranked per fabric
    calib = costmodel.calibrate(_toy_report())
    configs = [
        {"name": "baseline"},
        {"name": "compress", "reducer": "powersgd", "reducer_rank": 1},
    ]
    ranked = costmodel.search(
        calib, fabrics=["1GbE", "ICI(v5e)"], configs=configs
    )
    assert ranked["1GbE"][0]["config"]["name"] == "compress"
    assert ranked["ICI(v5e)"][0]["config"]["name"] == "baseline"


# ---------------------------------------------------------------------------
# plan document, events, and the realized join
# ---------------------------------------------------------------------------


def test_build_plan_and_prediction_events():
    calib = costmodel.calibrate(_toy_report())
    plan = costmodel.build_plan(calib, fabrics=["1GbE", "ICI(v5e)"])
    assert plan["schema"] == costmodel.PLAN_SCHEMA
    assert set(plan["fabrics"]) == {"1GbE", "ICI(v5e)"}
    for slot in plan["fabrics"].values():
        ranked = slot["ranked"]
        assert slot["best"] == ranked[0]
        steps = [p["predicted_step_s"] for p in ranked]
        assert steps == sorted(steps)
    # every DEFAULT_LADDER rung is priced and named in the ladder ordering
    assert set(r.name for r in DEFAULT_LADDER) <= set(plan["ladder"]["1GbE"])
    events = costmodel.prediction_events(plan, rank=0)
    assert events and all(isinstance(e, PredictionEvent) for e in events)
    rec = events[0].record()
    assert rec["event"] == "prediction"
    assert rec["config_key"] and rec["predicted_step_s"] > 0


def test_join_realized_matches_on_the_compile_comm_config():
    calib = costmodel.calibrate(_toy_report())
    plan = costmodel.build_plan(calib, fabrics=["1GbE"])
    pred = next(
        p for p in plan["fabrics"]["1GbE"]["ranked"]
        if p["config"]["name"] == "compress-low-rank"
    )
    realized = pred["predicted_step_s"] * 1.10  # realized 10% slower
    report = _toy_report(
        step_p50_s=realized,
        compile={
            "comm_config": {"reducer": "powersgd", "reducer_rank": 1},
        },
    )
    joined = costmodel.join_realized(plan, "1GbE", report)
    assert joined["matched"] is True
    assert joined["config_key"] == pred["config_key"]
    assert joined["error"] == pytest.approx(0.10 / 1.10)
    assert joined["beats_default"] is True  # < the 80 ms source step
    # no such fabric in the plan, or no usable step time -> None
    assert costmodel.join_realized(plan, "10GbE", report) is None
    assert (
        costmodel.join_realized(plan, "1GbE", {"step_p50_s": None}) is None
    )


# ---------------------------------------------------------------------------
# the plan-ordered fallback ladder
# ---------------------------------------------------------------------------


def test_ladder_from_plan_reorders_prunes_and_survives_staleness():
    plan = {"ladder": {"1GbE": ["compress", "ghost-rung", "baseline"]}}
    ordered = ladder_from_plan(plan, "1GbE")
    names = [r.name for r in ordered]
    # plan-named rungs lead (unknown names ignored), the rest keep their
    # static order, nothing is lost
    assert names[:2] == ["compress", "baseline"]
    assert set(names) == set(r.name for r in DEFAULT_LADDER)
    pruned = ladder_from_plan(plan, "1GbE", max_rungs=2)
    assert [r.name for r in pruned] == ["compress", "baseline"]
    # a stale plan without this fabric leaves the ladder untouched
    same = ladder_from_plan(plan, "ICI(v5e)")
    assert [r.name for r in same] == [r.name for r in DEFAULT_LADDER]
    assert [r.name for r in ladder_from_plan({}, "1GbE")] == [
        r.name for r in DEFAULT_LADDER
    ]


# ---------------------------------------------------------------------------
# gate: costmodel_error extraction, the 25% ceiling, missing_baseline
# ---------------------------------------------------------------------------


def test_gate_extracts_costmodel_error_and_enforces_the_ceiling():
    gate = _load_script("gate")
    report = {"costmodel": {"error": 0.07}}
    metrics = gate.extract_metrics(report)
    assert metrics["costmodel_error"] == pytest.approx(0.07)
    ok = gate.costmodel_target_verdict(metrics, report, {})
    assert len(ok) == 1 and not ok[0]["regressed"]
    assert ok[0]["baseline"] == gate.DEFAULT_COSTMODEL_ERROR_TARGET
    bad = gate.costmodel_target_verdict(
        {"costmodel_error": 0.40}, {}, {}
    )
    assert bad[0]["regressed"]
    # a recorded per-round target overrides the default
    custom = gate.costmodel_target_verdict(
        {"costmodel_error": 0.40}, {}, {"costmodel_error_target": 0.5}
    )
    assert not custom[0]["regressed"]


def test_gate_missing_baseline_is_advisory_never_a_keyerror():
    gate = _load_script("gate")
    verdicts = gate.compare(
        {"costmodel_error": 0.1, "step_p50_s": 0.02},
        {"step_p50_s": 0.02},  # a stale baseline, recorded pre-planner
        tolerance=0.2,
    )
    by_metric = {v["metric"]: v for v in verdicts}
    missing = by_metric["costmodel_error"]
    assert missing["missing_baseline"] is True
    assert missing["regressed"] is False
    assert missing["baseline"] is None
    assert not by_metric["step_p50_s"].get("missing_baseline")
    # a metric only the baseline carries is skipped, not inverted
    assert "mfu" not in by_metric


# ---------------------------------------------------------------------------
# report: --compare over two synthetic run dirs
# ---------------------------------------------------------------------------


def _write_toy_run(run_dir, step_s, payload_bytes):
    os.makedirs(run_dir, exist_ok=True)
    m = runlog.new_manifest(os.path.basename(run_dir), world_size=1)
    m.record_spawn(rank=0, incarnation=0, world_size=1, spawned_unix=100.0)
    m.save(run_dir)
    events = [
        {"event": "marker", "kind": "run_start", "ts": 100.0, "ts_mono": 0.0},
        {
            "event": "collective", "label": "toy", "tag": "g", "op": "all-reduce",
            "dtype": "float32", "payload_bytes": payload_bytes, "count": 1,
            "ts": 100.0, "ts_mono": 0.0,
        },
    ]
    t = 0.0
    for i in range(4):
        t += step_s
        events.append({
            "event": "span", "name": "step/compute", "dur_s": step_s * 0.5,
            "depth": 0, "rank": 0, "step": i, "ts": 100.0 + t, "ts_mono": t,
        })
        events.append({
            "event": "step", "step": i, "epoch": 0, "loss": 1.0,
            "step_time_s": step_s, "rank": 0, "ts": 100.0 + t, "ts_mono": t,
        })
    with open(runlog.shard_path(run_dir, 0), "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def test_compare_runs_diffs_step_time_bytes_and_span_shares(tmp_path):
    report = _load_script("report")
    a, b = str(tmp_path / "runA"), str(tmp_path / "runB")
    _write_toy_run(a, step_s=0.02, payload_bytes=4 * MIB)
    _write_toy_run(b, step_s=0.01, payload_bytes=1 * MIB)
    text, doc = report.compare_runs(a, b)
    assert doc["schema"] == 1
    step = doc["metrics"]["step_p50_s"]
    assert step["ratio"] == pytest.approx(0.5, rel=0.05)
    assert doc["metrics"]["bandwidth.total.payload_bytes"]["ratio"] == (
        pytest.approx(0.25)
    )
    assert "step/compute" in doc["span_shares"]
    assert "run compare" in text and "B/A" in text


# ---------------------------------------------------------------------------
# analytics edge cases the planner leans on
# ---------------------------------------------------------------------------


def test_percentile_single_sample_and_empty():
    assert analytics.percentile([0.042], 50) == 0.042
    assert analytics.percentile([0.042], 95) == 0.042
    assert math.isnan(analytics.percentile([], 50))


def test_rank_step_stats_single_step_keeps_the_sample():
    stats = analytics.rank_step_stats(
        [{"event": "step", "rank": 0, "step_time_s": 0.5}]
    )
    # one timed step: drop_first must not divide by an empty window
    assert stats[0]["n"] == 1
    assert stats[0]["p50_s"] == 0.5
    assert stats[0]["mean_s"] == 0.5


def test_span_summary_zero_duration_spans_do_not_divide_by_zero():
    report = _load_script("report")
    spans = report.span_summary([
        {"event": "span", "name": "noop", "dur_s": 0.0, "rank": 0,
         "depth": 0, "ts": 1.0},
    ])
    slot = spans["by_name"]["noop"]
    assert slot["mean_s"] == 0.0 and slot["total_s"] == 0.0
    # a single instant gives zero wall-clock: share is None, not a crash
    assert slot["share"] is None


def test_effective_bandwidth_ledger_without_overlap_or_bytes():
    ledger = [{"tag": "g", "op": "all-reduce", "payload_bytes": 1000.0}]
    # no overlap extract: every byte charged exposed, still a full answer
    bw = analytics.effective_bandwidth(0.01, ledger, n_workers=2, overlap=None)
    assert bw["total"]["achieved_bytes_per_s"] == pytest.approx(1000.0 / 0.01)
    assert bw["attribution"]["n_collectives"] == 0
    # nothing priceable -> None, never a ZeroDivisionError
    assert analytics.effective_bandwidth(0.01, [], n_workers=2) is None
    assert analytics.effective_bandwidth(0.0, ledger, n_workers=2) is None
    assert (
        analytics.effective_bandwidth(
            0.01, [{"tag": "g", "payload_bytes": None}], n_workers=2
        )
        is None
    )
