"""Span-based performance attribution, unit to end-to-end.

Units: the nested host span API (``observe.spans``), the per-phase
MFU/roofline accounting (``observe.mfu``), the ``cost_analysis`` compat
read (``observe.ledger.compiled_cost``), and report.py's span aggregation +
Chrome-trace export — all jax-free.

End-to-end: ``scripts/run_probe.py`` spawns the REAL 2-rank supervised toy
run, and the test asserts the full pipeline: a well-formed Perfetto trace
with nested spans from both ranks and collective instants, a run report
with per-phase MFU + roofline verdict, and ``scripts/gate.py`` exiting
nonzero on an injected MFU regression.
"""

import importlib.util
import json
import os
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from network_distributed_pytorch_tpu.observe.ledger import compiled_cost  # noqa: E402
from network_distributed_pytorch_tpu.observe import mfu, spans  # noqa: E402
from network_distributed_pytorch_tpu.observe.sinks import MemorySink  # noqa: E402
from network_distributed_pytorch_tpu.observe.telemetry import Telemetry  # noqa: E402


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        f"_spans_test_{name}", os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[f"_spans_test_{name}"] = mod
    spec.loader.exec_module(mod)
    return mod


def _mem_telemetry():
    sink = MemorySink()
    return Telemetry([sink]), sink


# ---------------------------------------------------------------------------
# observe.spans: the nested host span API


def test_span_nesting_parent_links_and_order():
    telemetry, sink = _mem_telemetry()
    with spans.span("outer", telemetry=telemetry, step=7):
        with spans.span("inner", telemetry=telemetry, step=7):
            pass
    recs = sink.of_kind("span")
    # a span emits at CLOSE, so the inner record lands first
    assert [r["name"] for r in recs] == ["inner", "outer"]
    inner, outer = recs
    assert inner["parent_id"] == outer["span_id"]
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert outer["parent_id"] is None
    assert inner["step"] == 7
    assert inner["dur_s"] >= 0 and outer["dur_s"] >= inner["dur_s"]
    # emit-time stamps rode along (ts marks the close)
    assert "ts" in inner and "ts_mono" in inner


def test_span_without_recorder_is_safe_and_keeps_nesting():
    # no telemetry anywhere: spans must cost nothing and still nest, so a
    # library span deep in the loader never cares whether a run recorder
    # is ambient
    assert spans.current_span_id() is None
    with spans.span("quiet"):
        outer_id = spans.current_span_id()
        assert outer_id is not None
        with spans.span("quiet/inner"):
            assert spans.current_span_id() != outer_id
        assert spans.current_span_id() == outer_id
    assert spans.current_span_id() is None


def test_recording_makes_telemetry_ambient():
    telemetry, sink = _mem_telemetry()
    with spans.recording(telemetry):
        with spans.span("ambient"):
            pass
    assert [r["name"] for r in sink.of_kind("span")] == ["ambient"]
    # the ambient recorder is restored on exit
    with spans.span("after"):
        pass
    assert len(sink.of_kind("span")) == 1


def test_span_rank_defaults_from_env(monkeypatch):
    telemetry, sink = _mem_telemetry()
    monkeypatch.setenv("RESILIENCE_RANK", "3")
    with spans.span("ranked", telemetry=telemetry):
        pass
    assert sink.of_kind("span")[0]["rank"] == 3
    monkeypatch.delenv("RESILIENCE_RANK")
    with spans.span("unranked", telemetry=telemetry):
        pass
    assert sink.of_kind("span")[1]["rank"] is None


def test_span_stacks_are_thread_local():
    telemetry, sink = _mem_telemetry()
    ready = threading.Event()

    def other():
        with spans.span("thread_b", telemetry=telemetry):
            ready.wait(5.0)

    with spans.recording(telemetry):
        t = threading.Thread(target=other)
        with spans.span("thread_a"):
            t.start()
            ready.set()
            t.join(5.0)
    by_name = {r["name"]: r for r in sink.of_kind("span")}
    # concurrent spans in another thread must NOT parent under thread_a
    assert by_name["thread_b"]["parent_id"] is None
    assert by_name["thread_b"]["depth"] == 0
    assert by_name["thread_a"]["parent_id"] is None


def test_span_emits_even_when_body_raises():
    telemetry, sink = _mem_telemetry()
    with pytest.raises(ValueError, match="boom"):
        with spans.span("doomed", telemetry=telemetry):
            raise ValueError("boom")
    recs = sink.of_kind("span")
    assert [r["name"] for r in recs] == ["doomed"]
    assert spans.current_span_id() is None  # the stack unwound


def test_span_event_record_is_the_base_class_record():
    """``SpanEvent.record()`` is written out by hand for speed: it must stay
    what ``Event.record()`` would build, field for field and in order, or a
    field added to the dataclass goes missing from the run log."""
    from network_distributed_pytorch_tpu.observe.events import Event, SpanEvent

    event = SpanEvent("step/compute", 7, 3, 1, 0.25, step=4, rank=2)
    assert event.record() == Event.record(event)
    assert list(event.record()) == list(Event.record(event))
    telemetry, sink = _mem_telemetry()
    with spans.span("outer", telemetry=telemetry, step=4):
        pass
    assert set(sink.of_kind("span")[0]) - {"ts", "ts_mono"} == set(event.record())


@pytest.mark.parametrize(
    "child_step,expected",
    [(None, "parent's"), (99, 99)],
    ids=["child_without_step_takes_its_parents", "child_with_step_keeps_its_own"],
)
def test_span_step_inheritance_is_per_thread(child_step, expected):
    telemetry, sink = _mem_telemetry()
    barrier = threading.Barrier(2, timeout=5.0)

    def loop(step):
        with spans.span(f"load{step}", telemetry=telemetry, step=step):
            barrier.wait()  # both parents are open at once
            with spans.span(f"load{step}/child", telemetry=telemetry, step=child_step):
                with spans.span(f"load{step}/child/leaf", telemetry=telemetry):
                    pass
            barrier.wait()

    threads = [threading.Thread(target=loop, args=(step,)) for step in (3, 4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(5.0)
        assert not t.is_alive()
    by_name = {r["name"]: r for r in sink.of_kind("span")}
    assert len(by_name) == 6
    for step in (3, 4):
        want = step if expected == "parent's" else expected
        assert by_name[f"load{step}"]["step"] == step
        assert by_name[f"load{step}/child"]["step"] == want
        assert by_name[f"load{step}/child/leaf"]["step"] == want  # through two levels
    # outside any span there is nothing to inherit
    with spans.span("alone", telemetry=telemetry):
        pass
    assert sink.of_kind("span")[-1]["step"] is None


# ---------------------------------------------------------------------------
# observe.mfu: peak tables, roofline classification, event construction


def test_peak_flops_table_lookup():
    assert mfu.peak_flops("TPU v5 lite") == 197e12
    assert mfu.peak_flops("TPU v5p") == 459e12
    # longest-match: "v5 lite" must not resolve via the bare "v5" entry
    assert mfu.peak_flops("tpu v5 litepod-8") == 197e12
    with pytest.raises(ValueError, match="v99"):  # unknown TPU kind: an
        mfu.peak_flops("TPU v99")  # error, never a silent 0.0 peak
    with pytest.raises(ValueError, match="v99"):
        mfu.hbm_bandwidth("TPU v99")
    assert mfu.peak_flops("cpu", platform="cpu") == 0.0  # non-TPU platform
    assert mfu.hbm_bandwidth("TPU v4") == 1228e9


def test_classify_roofline_all_bounds():
    # unknown: no peak to compare against
    assert mfu.classify_roofline(1e12, 1e9, 0.0, 1e12)["bound"] == "unknown"
    # comm-exposed wins over everything once the exposed fraction crosses
    # the threshold — no point tuning kernels when the wire is the wall
    v = mfu.classify_roofline(
        1e12, 1e9, 2e14, 1e12, exposed_comm_fraction=0.7
    )
    assert v["bound"] == "comm-exposed"
    # hbm: arithmetic intensity below the ridge
    v = mfu.classify_roofline(1e9, 1e9, 2e14, 1e12)
    assert v["bound"] == "hbm"
    assert v["arithmetic_intensity"] == pytest.approx(1.0)
    assert v["ridge_flops_per_byte"] == pytest.approx(200.0)
    # compute: intensity above the ridge
    assert mfu.classify_roofline(1e13, 1e9, 2e14, 1e12)["bound"] == "compute"


def test_mfu_event_numbers():
    ev = mfu.mfu_event(
        label="toy", step_time_s=0.01, flops_per_step=2.0e9,
        peak_flops_per_s=1e12, exposed_comm_fraction=1.0,
    )
    assert ev.mfu == pytest.approx(0.2)
    assert ev.bound == "comm-exposed"
    rec = ev.record()
    assert rec["event"] == "mfu" and rec["label"] == "toy"
    assert "mfu" in ev.banner()


def test_mfu_from_compile_records_joins_and_dedupes():
    recs = [
        {"label": "toy", "flops_per_step": 2.0e9, "flops_source": "analytic",
         "device_kind": "toy-sim", "peak_flops_per_s": 1e12},
        {"label": "toy", "flops_per_step": 9.9e9},  # duplicate label: dropped
        {"label": "no-cost"},  # no flops: skipped
    ]
    out = mfu.mfu_from_compile_records(recs, step_time_s=0.01, n_steps=5)
    assert [e.label for e in out] == ["toy"]
    assert out[0].mfu == pytest.approx(0.2)
    assert out[0].n_steps == 5
    # invalid step time: nothing to join against
    assert mfu.mfu_from_compile_records(recs, step_time_s=0.0) == []


# ---------------------------------------------------------------------------
# observe.ledger.compiled_cost: the cost_analysis read


class _FakeCompiled:
    def __init__(self, result=None):
        self._result = result

    def cost_analysis(self):
        return self._result


def test_compiled_cost_keeps_numeric_metrics():
    cost = {"flops": 123.0, "bytes accessed": 456.0, "utilization": "n/a"}
    assert compiled_cost(_FakeCompiled(cost)) == {
        "flops": 123.0, "bytes accessed": 456.0
    }


def test_compiled_cost_without_flops_is_none():
    assert compiled_cost(_FakeCompiled(None)) is None
    # a cost dict with no flops is useless for MFU: normalized to None
    assert compiled_cost(_FakeCompiled({"bytes accessed": 9.0})) is None


# ---------------------------------------------------------------------------
# report.py: span aggregation + Chrome-trace export (unit level)


def _span_rec(name, rank, close, dur, depth=0, span_id=1, parent=None):
    return {
        "event": "span", "name": name, "rank": rank, "t_run": close,
        "dur_s": dur, "depth": depth, "span_id": span_id,
        "parent_id": parent,
    }


def test_span_summary_shares_and_idle():
    report = _load_script("report")
    events = [
        _span_rec("step", 0, 2.0, 1.0),          # covers [1, 2]
        _span_rec("step", 0, 4.0, 1.0),          # covers [3, 4]
        {"event": "step", "rank": 0, "t_run": 5.0, "step_time_s": 1.0},
    ]
    s = report.span_summary(events)
    # rank 0 wall = [2.0, 5.0] from event stamps -> 3 s; idle = wall not
    # covered by depth-0 spans (clamped): [2,2]+[3,4] covered -> 2 s idle
    assert s["total_wall_s"] == pytest.approx(3.0)
    assert s["by_name"]["step"]["count"] == 2
    assert s["by_name"]["step"]["total_s"] == pytest.approx(2.0)
    assert s["by_name"]["step"]["share"] == pytest.approx(2.0 / 3.0)
    assert s["idle_by_rank"]["0"]["idle_s"] == pytest.approx(2.0)
    assert report.span_summary([{"event": "step", "t_run": 1.0}]) is None


def test_chrome_trace_backdates_spans_and_names_processes():
    report = _load_script("report")
    events = [
        _span_rec("outer", 0, 11.0, 2.0, depth=0, span_id=1),
        _span_rec("inner", 0, 10.5, 1.0, depth=1, span_id=2, parent=1),
        {"event": "collective", "rank": 1, "t_run": 10.0, "tag": "g",
         "op": "all-reduce", "payload_bytes": 8, "layer": "reducer"},
        {"event": "failure", "rank": None, "t_run": 12.0, "kind": "crash",
         "message": "boom"},
    ]
    doc = report.chrome_trace(events)
    evs = doc["traceEvents"]
    slices = {e["name"]: e for e in evs if e.get("ph") == "X"}
    # t0 is the earliest span START (11.0 - 2.0 = 9.0), not earliest stamp
    assert slices["outer"]["ts"] == pytest.approx(0.0)
    assert slices["outer"]["dur"] == pytest.approx(2e6)
    assert slices["inner"]["ts"] == pytest.approx(0.5e6)
    assert slices["inner"]["args"]["parent_id"] == 1
    instants = [e for e in evs if e.get("ph") == "i"]
    assert {e["cat"] for e in instants} == {"collective", "failure"}
    # supervisor events land on pid -1; metadata names every process
    assert [e for e in instants if e["cat"] == "failure"][0]["pid"] == -1
    names = {
        e["pid"]: e["args"]["name"] for e in evs
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert names == {-1: "supervisor", 0: "rank 0", 1: "rank 1"}
    assert report.chrome_trace([])["traceEvents"] == []


def _loop_records(rank, stepped_children):
    """One step of the loop's span tree as a shard's records: ``data_load``
    (4 ms, 1 ms of it waiting) over assemble and stage, then ``step`` over
    compute and comm. ``stepped_children=False`` is a log written before
    PR 24: no step on the spans below ``data_load``."""
    base, t = 10 * (rank + 1), 100.0

    def rec(name, span_id, parent, depth, close, dur, step):
        out = {
            "event": "span", "name": name, "span_id": base + span_id,
            "parent_id": None if parent is None else base + parent,
            "depth": depth, "dur_s": dur, "step": step, "rank": rank,
            "ts": 1000.0 + close, "ts_mono": t + close,
        }
        return out

    child_step = 0 if stepped_children else None
    return [
        {"event": "marker", "kind": "run_start", "incarnation": 0,
         "ts": 1000.0, "ts_mono": t},
        rec("data_load/assemble", 2, 1, 1, 0.002, 0.002, child_step),
        rec("data_load/stage", 3, 1, 1, 0.003, 0.001, child_step),
        rec("data_load", 1, None, 0, 0.004, 0.004, 0),
        rec("step/compute", 5, 4, 1, 0.010, 0.006, 0),
        rec("step/comm", 6, 4, 1, 0.012, 0.002, 0),
        rec("step", 4, None, 0, 0.012, 0.008, 0),
    ]


@pytest.mark.parametrize("stepped_children", [False, True], ids=["old_run_log", "new_run_log"])
def test_span_consumers_read_logs_with_and_without_stepped_children(tmp_path, stepped_children):
    """A run log from before child spans took their parent's step still
    loads, and every reader of span records (the merge, the report's span
    summary and Chrome trace, the critical path) gives the same answer on
    both: a container and its stepped children count once."""
    from network_distributed_pytorch_tpu.observe import critpath, runlog

    report = _load_script("report")
    m = runlog.new_manifest("spans", world_size=2)
    for rank in (0, 1):
        m.record_spawn(rank=rank, incarnation=0, world_size=2, spawned_unix=1000.0)
        with open(runlog.shard_path(str(tmp_path), rank), "w") as f:
            for record in _loop_records(rank, stepped_children):
                f.write(json.dumps(record) + "\n")
    m.save(str(tmp_path))
    merged = runlog.merge_run(str(tmp_path))
    recs = [e for e in merged.events if e.get("event") == "span"]
    assert len(recs) == 12 and all("t_run" in e for e in recs)

    summary = report.span_summary(merged.events)
    assert summary["by_name"]["data_load"]["total_s"] == pytest.approx(0.008)
    assert summary["by_name"]["data_load/stage"]["count"] == 2
    slices = [e for e in report.chrome_trace(merged.events)["traceEvents"] if e.get("ph") == "X"]
    assert len(slices) == 12
    assert {e["args"].get("step") for e in slices if e["name"] == "data_load/stage"} == {
        0 if stepped_children else None
    }

    crit = critpath.analyze(merged.events, world_size=2)
    assert crit["n_steps"] == 1
    ev = crit["events"][0]
    # the whole data_load, once: as a leaf (old) or as its children plus
    # the millisecond none of them covers (new)
    assert ev["data_s"] == pytest.approx(0.004)
    assert ev["compute_s"] == pytest.approx(0.006)
    assert ev["comm_s"] == pytest.approx(0.002)
    assert ev["path_s"] == pytest.approx(0.012)


# ---------------------------------------------------------------------------
# end-to-end: 2-rank probe -> trace + MFU report -> gate regression


@pytest.fixture(scope="module")
def probe_artifacts(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("probe")
    run_probe = _load_script("run_probe")
    json_out = str(tmp / "run_report.json")
    trace_out = str(tmp / "toy_trace.json")
    rc = run_probe.main([
        "--out-dir", str(tmp / "toy_run"), "--json-out", json_out,
        "--trace-out", trace_out, "--steps", "4",
    ])
    assert rc == 0
    return json_out, trace_out


def test_probe_trace_is_wellformed_with_nested_spans(probe_artifacts):
    _json_out, trace_out = probe_artifacts
    with open(trace_out) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert evs, "empty trace"
    span_slices = [e for e in evs if e.get("ph") == "X" and e["cat"] == "span"]
    # spans from BOTH worker ranks
    assert {e["pid"] for e in span_slices} == {0, 1}
    # nesting survived the merge: step/compute parents under step
    children = [
        e for e in span_slices
        if e["name"] == "step/compute" and e["args"].get("parent_id")
    ]
    assert children
    parents = {
        (e["pid"], e["args"]["span_id"]): e["name"] for e in span_slices
    }
    for c in children:
        assert parents[(c["pid"], c["args"]["parent_id"])] == "step"
    # the toy all-reduce shows up as collective instants
    assert any(
        e.get("cat") == "collective" and e.get("ph") == "i" for e in evs
    )


def test_probe_report_carries_mfu_and_roofline(probe_artifacts):
    json_out, _trace_out = probe_artifacts
    with open(json_out) as f:
        report = json.load(f)
    recs = report["mfu"]
    assert len(recs) == 1 and recs[0]["label"] == "toy"
    # 2 GF/step at >= 10 ms/step against the 1 TF/s toy peak: mfu lands
    # just under the ideal 0.2 (step time includes checkpoint overhead)
    assert 0.05 < recs[0]["mfu"] <= 0.2
    assert recs[0]["flops_source"] == "analytic"
    # the toy's single all-reduce is fully exposed -> comm-bound verdict
    assert recs[0]["bound"] == "comm-exposed"
    assert recs[0]["exposed_comm_fraction"] == pytest.approx(1.0)
    assert report["mfu_headline"] == pytest.approx(recs[0]["mfu"])
    assert report["spans"]["by_name"]["step"]["count"] == 8  # 2 ranks x 4


def test_gate_fails_on_injected_mfu_regression(probe_artifacts, tmp_path):
    json_out, _trace_out = probe_artifacts
    gate = _load_script("gate")
    with open(json_out) as f:
        report = json.load(f)
    current = report["mfu_headline"]
    # baseline claims 3x the measured MFU — far past the 20% tolerance
    baseline = str(tmp_path / "baseline.json")
    with open(baseline, "w") as f:
        json.dump({"mfu": current * 3.0}, f)
    rc = gate.main([
        "--report", json_out, "--baseline", baseline, "--root", str(tmp_path)
    ])
    assert rc == 1
    # control: gating against an equal baseline passes
    with open(baseline, "w") as f:
        json.dump({"mfu": current}, f)
    assert gate.main([
        "--report", json_out, "--baseline", baseline, "--root", str(tmp_path)
    ]) == 0
    # and a span-share blowup alone fails the gate (absolute tolerance)
    shrunk = dict(report)
    shrunk["spans"] = json.loads(json.dumps(report["spans"]))
    shrunk["spans"]["by_name"]["step"]["share"] = (
        report["spans"]["by_name"]["step"]["share"] - 0.2
    )
    with open(baseline, "w") as f:
        json.dump(shrunk, f)
    assert gate.main([
        "--report", json_out, "--baseline", baseline, "--root", str(tmp_path)
    ]) == 1
