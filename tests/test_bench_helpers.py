"""bench.py's jax-free logic: the peak-FLOPs device map, the artifact
pointer that rides the line, the phase-result merge, and the parent
orchestrator's policy (hard per-phase timeouts, child respawn, a non-zero
exit whenever no TPU was found or a phase failed, cumulative emission) —
driven by scripted fake children, no backend and no subprocess needed. One
exception: ``test_child_refuses_the_cpu`` spawns the REAL measurement child
to pin that it reports the missing chip and exits non-zero."""

import importlib.util
import json
import os
import queue

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench(monkeypatch, **env):
    for k in list(os.environ):
        if k.startswith("BENCH_"):
            monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


def test_peak_flops_device_map(monkeypatch):
    bench = _load_bench(monkeypatch)
    assert bench._peak_flops(_FakeDevice("tpu", "TPU v5 lite")) == 197e12
    assert bench._peak_flops(_FakeDevice("tpu", "TPU v5p")) == 459e12
    assert bench._peak_flops(_FakeDevice("tpu", "TPU v6e")) == 918e12
    # longest-match: "v5 lite" must not resolve via the bare "v5" entry
    assert bench._peak_flops(_FakeDevice("tpu", "tpu v5 litepod-8")) == 197e12
    assert bench._peak_flops(_FakeDevice("cpu", "cpu")) == 0.0  # no entry
    with pytest.raises(ValueError, match="v99"):  # unknown TPU: an error,
        bench._peak_flops(_FakeDevice("tpu", "TPU v99"))  # never a 0.0 peak


def test_artifact_pointers_ride_the_line(monkeypatch):
    """The committed accuracy study surfaces as a compact pointer in the
    bench payload — and nothing else does: an older chip record stapled to
    a new line made CPU rounds read as chip rounds (BENCH_r04/r05)."""
    bench = _load_bench(monkeypatch)
    out = {}
    bench._artifact_pointers(out)
    # ACCURACY_STUDY.json is committed — pointers must decode it
    assert set(out) == {"accuracy_study"}
    assert out["accuracy_study"]["cifar"]["gradient_bytes_ratio"] > 10
    json.dumps(out)  # the line must stay serializable


def test_run_with_deadline(monkeypatch):
    """The child-side phase deadline: a slow phase is abandoned with
    TimeoutError (no SIGKILL needed, the child keeps its backend), a fast
    one returns its data, and a crashing one relays its exception."""
    import time as _time

    import pytest

    bench = _load_bench(monkeypatch)
    assert bench._run_with_deadline("x", lambda: {"a": 1}, 5.0) == {"a": 1}
    with pytest.raises(TimeoutError, match="abandoned"):
        bench._run_with_deadline("slow", lambda: _time.sleep(30), 0.2)

    def boom():
        raise ValueError("inner")

    with pytest.raises(ValueError, match="inner"):
        bench._run_with_deadline("crash", boom, 5.0)


def test_merge_builds_value_and_ratio(monkeypatch):
    bench = _load_bench(monkeypatch)
    out, status = {"value": 0.0, "vs_baseline": 0.0}, {}
    bench._merge(out, "probe", True, {"device": "TPU v5e", "platform": "tpu",
                                      "n_devices": 4}, status)
    assert out["device"] == "TPU v5e" and status["probe"] == "ok"
    assert out["n_devices"] == 4  # the measured device count rides the line
    bench._merge(out, "flagship", True,
                 {"flagship_imgs_per_sec": 1000.0, "step_time_ms": 2.0}, status)
    assert out["value"] == 1000.0  # flagship IS the headline metric
    bench._merge(out, "baseline", True, {"baseline_imgs_per_sec": 250.0}, status)
    assert out["vs_baseline"] == 4.0
    bench._merge(out, "gpt", False, {"error": "boom"}, status)
    assert status["gpt"].startswith("error: boom")
    assert "gpt" not in out  # failed phases contribute no fields


class _FakeChild:
    """Scripted stand-in for bench._ChildProc: a list of events, where an
    event is a dict (phase line), None (EOF), or "hang" (queue.Empty —
    what a compile wedged in C++ looks like to the parent)."""

    spawns = []  # [(phases, script), ...] consumed in order
    killed = []
    timeouts = []  # budget passed to every next_event call, in order

    def __init__(self, phases):
        assert _FakeChild.spawns, f"unexpected spawn for phases={phases}"
        expect, self.script = _FakeChild.spawns.pop(0)
        assert list(phases) == expect, (phases, expect)

    def next_event(self, timeout_s):
        _FakeChild.timeouts.append(round(timeout_s))
        ev = self.script.pop(0)
        if ev == "hang":
            raise queue.Empty()
        return ev

    def kill(self):
        _FakeChild.killed.append(True)


def _ok(phase, **data):
    return {"phase": phase, "ok": True, "data": data}


def _run_orchestrator(bench, tmp_path, spawns, rc=0):
    """``rc``: the exit code the run must end with — 0 only when a TPU was
    found and every phase came back ok."""
    lines = []
    _FakeChild.spawns = spawns
    _FakeChild.killed = []
    _FakeChild.timeouts = []
    bench._ChildProc = _FakeChild
    bench._emit = lambda payload: lines.append(json.loads(json.dumps(payload)))
    # a successful fake TPU run records artifacts/GATE_BASELINE.json — point
    # HERE at pytest's managed tmp dir so orchestrator tests can never write
    # into the repo
    bench.HERE = str(tmp_path)
    assert bench.orchestrate() == rc
    assert not _FakeChild.spawns, "orchestrator under-spawned"
    return lines


def test_orchestrator_happy_path(monkeypatch, tmp_path):
    """One child serves every phase; a cumulative line lands after each;
    the full record is final (partial=False) and the very last line is the
    bounded summary digest of it."""
    bench = _load_bench(monkeypatch)
    all_phases = list(bench.PHASES)
    lines = _run_orchestrator(bench, tmp_path, [(all_phases, [
        _ok("probe", device="TPU v5e", platform="tpu", n_devices=1),
        _ok("flagship", flagship_imgs_per_sec=1000.0, step_time_ms=2.56,
            mfu=0.41, preset="full"),
        _ok("baseline", baseline_imgs_per_sec=100.0),
        _ok("gpt", gpt={"step_time_ms": 50.0, "mfu": 0.35}),
        _ok("fp32arm", fp32_scanned_imgs_per_sec=300.0),
        _ok("overlap", overlap={"combiner_merged": True}),
        _ok("loader", loader_samples_per_s=200000.0, data_load_share=0.03),
        _ok("serving", serving_tokens_per_s_per_chip=800.0,
            kv_capacity_ratio=4.0, p99_decode_ms_per_token=2.0),
        None,
    ])])
    # first line precedes any backend touch and is already valid
    assert lines[0]["partial"] is True and lines[0]["value"] == 0.0
    tail = lines[-2]  # the authoritative full record
    assert tail["partial"] is False
    assert tail["value"] == 1000.0 and tail["vs_baseline"] == 10.0
    assert tail["device"] == "TPU v5e"
    assert tail["gpt"]["mfu"] == 0.35
    assert all(tail["phases"][p] == "ok" for p in bench.PHASES)
    # the LAST line is the bounded summary: same headline numbers, always
    # small enough for a fixed-size stdout tail
    summary = lines[-1]
    assert summary["summary"] is True
    assert summary["value"] == 1000.0 and summary["vs_baseline"] == 10.0
    assert summary["phases"]["flagship"] == "ok"
    assert len(json.dumps(summary)) <= bench._SUMMARY_LIMIT
    # per-phase cumulative lines + first line + full record + summary
    assert len(lines) == 3 + len(bench.PHASES)


def test_orchestrator_survives_hang_and_respawns(monkeypatch, tmp_path):
    """A child wedged mid-flagship (the round-3 killer) costs exactly that
    phase: the parent kills it, respawns for the remainder, and the tail
    line still carries everything else."""
    bench = _load_bench(monkeypatch)
    lines = _run_orchestrator(bench, tmp_path, [
        (list(bench.PHASES), [
            _ok("probe", device="TPU v5e", platform="tpu", n_devices=1),
            "hang",  # flagship compile wedged in C++
        ]),
        (["baseline", "gpt", "fp32arm", "overlap", "loader", "serving"], [
            _ok("baseline", baseline_imgs_per_sec=100.0),
            _ok("gpt", gpt={"step_time_ms": 50.0}),
            _ok("fp32arm", fp32_scanned_imgs_per_sec=300.0),
            _ok("overlap", overlap={"combiner_merged": True}),
            _ok("loader", loader_samples_per_s=200000.0),
            _ok("serving", serving_tokens_per_s_per_chip=800.0),
            None,
        ]),
    ], rc=1)  # a lost phase fails the run: no failure on the way to exit 0
    tail = lines[-1]
    assert tail["phases"]["flagship"].startswith("timeout")
    assert tail["phases"]["baseline"] == "ok"
    assert tail["phases"]["overlap"] == "ok"
    assert tail["value"] == 0.0  # flagship lost → headline honestly absent
    assert _FakeChild.killed  # the wedged child was hard-killed


def test_two_init_failures_end_the_run_nonzero(monkeypatch, tmp_path):
    """No chip: the child reports it, gets exactly one more attempt, and the
    run ends non-zero with the error on the line and every phase unresolved.
    There is no other tier to degrade to, and nothing sets a platform."""
    bench = _load_bench(monkeypatch)
    init_fail = [{"phase": "__init__", "ok": False,
                  "data": {"error": "RuntimeError: no TPU: jax.devices()[0]"
                                    " is 'cpu'"}}]
    all_phases = list(bench.PHASES)
    lines = _run_orchestrator(bench, tmp_path, [
        (all_phases, list(init_fail)),
        (all_phases, list(init_fail)),
    ], rc=1)
    tail = lines[-1]
    assert tail["value"] == 0.0 and "device" not in tail
    assert tail["tpu_error"].startswith("RuntimeError: no TPU")
    assert tail["init_retries"] == 1
    assert all(tail["phases"][p] == "skipped: no TPU" for p in bench.PHASES)
    assert not os.path.exists(tmp_path / "artifacts")  # nothing recorded


def test_orchestrator_counts_silent_child_death_as_init_failure(monkeypatch, tmp_path):
    """A child that dies before emitting ANY marker line (native crash in
    the PJRT client during backend init — no Python exception, no __init__
    report) counts as an init failure instead of burning one phase per
    crash; the second one ends the run."""
    bench = _load_bench(monkeypatch)
    all_phases = list(bench.PHASES)
    lines = _run_orchestrator(bench, tmp_path, [
        (all_phases, [None]),  # EOF with zero events
        (all_phases, [None]),  # again → 2 init failures → the run ends
    ], rc=1)
    tail = lines[-1]
    assert tail["tpu_error"] == "child process died during backend init"
    assert all(tail["phases"][p] == "skipped: no TPU" for p in bench.PHASES)


def test_first_event_budget_includes_init_grace(monkeypatch, tmp_path):
    """A child's FIRST event window covers process start + jax import +
    backend init; later phases in the same child get the bare phase
    budget. A respawned child's first phase gets the grace again."""
    bench = _load_bench(monkeypatch)
    lines = _run_orchestrator(bench, tmp_path, [
        (list(bench.PHASES), [
            _ok("probe", device="TPU v5e", platform="tpu", n_devices=1),
            "hang",  # flagship wedged -> kill -> respawn
        ]),
        (["baseline", "gpt", "fp32arm", "overlap", "loader", "serving"], [
            _ok("baseline", baseline_imgs_per_sec=100.0),
            _ok("gpt", gpt={}),
            _ok("fp32arm", fp32_scanned_imgs_per_sec=300.0),
            _ok("overlap", overlap={}),
            _ok("loader", loader_samples_per_s=200000.0),
            _ok("serving", serving_tokens_per_s_per_chip=800.0),
            None,
        ]),
    ], rc=1)
    t = _FakeChild.timeouts
    g = bench.INIT_GRACE_S
    assert t[0] == bench.PHASE_BUDGET_S["probe"] + g     # child 1, first event
    assert t[1] == bench.PHASE_BUDGET_S["flagship"]      # same child, no grace
    assert t[2] == bench.PHASE_BUDGET_S["baseline"] + g  # respawn, grace again
    assert t[3] == bench.PHASE_BUDGET_S["gpt"]
    assert lines[-1]["phases"]["baseline"] == "ok"


def test_orchestrator_waits_for_abandoned_drain(monkeypatch, tmp_path):
    """After the last phase reports, the parent must NOT kill the child
    immediately: an abandoned phase's daemon thread may still be inside a
    compile. The parent waits for the child's __drain__ report + EOF; the
    kill is a no-op backstop."""
    bench = _load_bench(monkeypatch)
    all_phases = list(bench.PHASES)
    lines = _run_orchestrator(bench, tmp_path, [(all_phases, [
        _ok("probe", device="TPU v5e", platform="tpu", n_devices=1),
        _ok("flagship", flagship_imgs_per_sec=1000.0, step_time_ms=2.56,
            preset="full"),
        _ok("baseline", baseline_imgs_per_sec=100.0),
        {"phase": "gpt", "ok": False,
         "data": {"error": "_PhaseAbandoned: phase gpt exceeded ..."}},
        _ok("overlap", overlap={"combiner_merged": True}),
        _ok("loader", loader_samples_per_s=200000.0),
        _ok("serving", serving_tokens_per_s_per_chip=800.0),
        {"phase": "__drain__", "ok": True,
         "data": {"drained": ["gpt"], "still_alive": []}},
        None,  # child exits on its own AFTER draining
    ])], rc=1)
    full = lines[-2]  # abandoned_drain is full-record detail, not summary
    assert full["abandoned_drain"] == {"drained": ["gpt"], "still_alive": []}
    assert full["phases"]["gpt"].startswith("error")
    assert _FakeChild.killed == [True]  # backstop fired once, after EOF


def test_orchestrator_kills_immediately_on_giveup(monkeypatch, tmp_path):
    """A parent-side timeout means the child is WEDGED — the kill backstop
    must fire without a drain wait (waiting on a wedged child would burn
    the remaining window for nothing)."""
    bench = _load_bench(monkeypatch)
    lines = _run_orchestrator(bench, tmp_path, [
        (list(bench.PHASES), [
            _ok("probe", device="TPU v5e", platform="tpu", n_devices=1),
            _ok("flagship", flagship_imgs_per_sec=1000.0, preset="full"),
            _ok("baseline", baseline_imgs_per_sec=100.0),
            _ok("gpt", gpt={"step_time_ms": 50.0}),
            _ok("fp32arm", fp32_scanned_imgs_per_sec=300.0),
            _ok("loader", loader_samples_per_s=200000.0),
            _ok("serving", serving_tokens_per_s_per_chip=800.0),
            "hang",  # overlap wedged — the LAST pending phase
        ]),
    ], rc=1)
    tail = lines[-1]
    assert tail["phases"]["overlap"].startswith("timeout")
    assert _FakeChild.killed == [True]


def test_run_with_deadline_registers_abandoned_thread(monkeypatch):
    """An abandoned phase's thread lands in _ABANDONED_THREADS so the
    child's end-of-run drain can join it before process exit."""
    import threading as _threading

    bench = _load_bench(monkeypatch)
    bench._ABANDONED_THREADS.clear()
    release = _threading.Event()

    def slow():
        release.wait(10.0)
        return {}

    try:
        bench._run_with_deadline("gpt", slow, 0.05)
    except bench._PhaseAbandoned:
        pass
    else:  # pragma: no cover - the deadline must fire
        raise AssertionError("expected _PhaseAbandoned")
    t = bench._ABANDONED_THREADS.get("gpt")
    assert t is not None and t.is_alive()
    release.set()  # the "compile" finishes; the drain join must succeed
    t.join(5.0)
    assert not t.is_alive()


def test_flops_band_disjoint_windows_unchanged(monkeypatch):
    """At the production CHUNK (>= 8) the two ±2x windows are disjoint and
    the helper reproduces the old classification exactly."""
    bench = _load_bench(monkeypatch)
    assert bench._flops_band(50.0, 50) == "trip"
    assert bench._flops_band(25.0, 50) == "trip"   # lower window edge
    assert bench._flops_band(100.0, 50) == "trip"  # upper window edge
    assert bench._flops_band(1.0, 50) == "once"
    assert bench._flops_band(0.5, 50) == "once"
    assert bench._flops_band(2.0, 50) == "once"
    assert bench._flops_band(7.0, 50) is None      # between the windows
    assert bench._flops_band(0.4, 50) is None      # below both
    assert bench._flops_band(101.0, 50) is None    # above both
    assert bench._flops_band(0.0, 50) is None      # degenerate input


def test_flops_band_small_chunk_overlap_resolved(monkeypatch):
    """The bug: for CHUNK <= 4 the windows [chunk/2, 2*chunk] and [0.5, 2]
    OVERLAP, and the old ``if`` ordering classified every overlap ratio as
    trip-multiplied — silently dividing a count-once flops figure by
    chunk. The helper resolves the overlap by nearest band center in log
    space."""
    bench = _load_bench(monkeypatch)
    # chunk=2: 1.2 is nearer 1 than 2 (the old code called it "trip")
    assert bench._flops_band(1.2, 2) == "once"
    assert bench._flops_band(1.5, 2) == "trip"  # nearer 2 in log space
    assert bench._flops_band(1.9, 2) == "trip"
    # chunk=4: the geometric midpoint of the bands is 2.0 — ties go trip
    assert bench._flops_band(1.9, 4) == "once"
    assert bench._flops_band(2.0, 4) == "trip"
    assert bench._flops_band(2.1, 4) == "trip"
    # chunk=1: bands coincide; either label divides by 1 — same number
    assert bench._flops_band(1.0, 1) == "trip"


def _worst_case_record(bench):
    """A cumulative record padded to every observed maximum at once: long
    error strings at their truncation caps, full per-dispatch time lists,
    the artifact pointer, every phase an error status."""
    out = {
        "metric": "cifar10_resnet50_train_imgs_per_sec",
        "value": 123456.78, "unit": "imgs/sec", "vs_baseline": 1234.567,
        "partial": False, "wall_s": 869.9,
        "device": "TPU v5 litepod-256 " + "d" * 100,
        "platform": "tpu", "n_devices": 256, "preset": "full",
        "flagship_imgs_per_sec": 35000.12, "step_time_ms": 7.3142,
        "flagship_reps": 64,
        "flagship_imgs_per_sec_min": 22800.01,
        "flagship_imgs_per_sec_max": 35000.12,
        "dispatch_times_ms": [round(7.31 + i / 100, 2) for i in range(64)],
        "baseline_imgs_per_sec": 40.25, "baseline_step_time_ms": 6360.2484,
        "baseline_imgs_per_sec_min": 38.11, "baseline_imgs_per_sec_max": 44.92,
        "baseline_passes": [round(38.0 + i / 10, 2) for i in range(16)],
        "mfu": 0.4123, "flops_per_step": 1.039e10,
        "flops_chunk_ratio": 49.97,
        "flops_method": ("hlo scan-trip-multiplied (cross-check "
                         "unavailable: " + "e" * 160)[:160],
        "fp32_scanned_imgs_per_sec": 9000.5,
        "fp32_dispatch_times_ms": [round(28.0 + i, 2) for i in range(16)],
        "tpu_error": "E" * 400,  # the child-side truncation cap
        "abandoned_drain": {"drained": ["gpt", "flagship_crosscheck"],
                            "still_alive": ["overlap"]},
        "concurrent_abandoned": ["gpt"],
        "gpt": {"model": "gpt2-small-124m", "seq_len": 1024, "batch": 8,
                "vocab": 50257, "mfu": 0.3512, "tokens_per_sec": 123456.7,
                "step_time_ms": 66.4, "flops_per_step": 8.76e12,
                "flops_method": "f" * 160},
        "overlap": {"n_async_collectives": 0, "n_overlapped": 0,
                    "compiled_collectives": 3, "combiner_merged": True},
        "accuracy_study": {
            t: {"accuracy_delta_pts": -0.42, "gradient_bytes_ratio": 122.8}
            for t in ("cifar", "imdb", "imdb_wide")
        },
    }
    status = {p: ("error: " + "y" * 200)[:206] for p in bench.PHASES}
    return out, status


def test_compact_summary_bounded_on_worst_case(monkeypatch):
    """The summary line serializes under _SUMMARY_LIMIT even when every
    field of the record is at its maximum size, and still leads with the
    headline numbers."""
    bench = _load_bench(monkeypatch)
    out, status = _worst_case_record(bench)
    summary = bench._compact_summary(out, status)
    line = json.dumps(summary)
    assert len(line) <= bench._SUMMARY_LIMIT, len(line)
    assert summary["summary"] is True
    assert summary["metric"] == out["metric"]
    assert summary["value"] == out["value"]
    assert summary["vs_baseline"] == out["vs_baseline"]
    # unbounded payloads must never ride the summary
    for k in ("dispatch_times_ms", "baseline_passes", "abandoned_drain",
              "accuracy_study"):
        assert k not in summary


def test_compact_summary_parses_from_2000_char_tail(monkeypatch):
    """The driver's failure mode this line exists for: the full record has
    outgrown a 2,000-char stdout tail, so the tail's last COMPLETE line
    must be the summary and must round-trip json.loads."""
    bench = _load_bench(monkeypatch)
    out, status = _worst_case_record(bench)
    full_line = json.dumps(out)
    assert len(full_line) > 2000  # the premise: the record alone overflows
    summary = bench._compact_summary(out, status)
    stream = full_line + "\n" + json.dumps(summary) + "\n"
    tail = stream[-2000:]
    complete = [ln for ln in tail.split("\n") if ln]
    # the first tail entry is the truncated full record — unparseable —
    # but the LAST complete line is the whole summary
    rec = json.loads(complete[-1])
    assert rec == summary
    assert rec["summary"] is True and rec["value"] == out["value"]


def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "gate_under_test", os.path.join(REPO, "scripts", "gate.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_summary_round_trips_through_gate_tail_parser(monkeypatch):
    """The contract the summary line exists for, proved against the REAL
    consumer: a >1,200-char full record plus the bounded summary, cut to a
    2,000-char tail, must still yield the summary — with its headline
    metrics intact — through gate.py's backwards tail scan (the same parser
    the driver's ``parsed`` field and baseline fallback rely on)."""
    bench = _load_bench(monkeypatch)
    gate = _load_gate()
    out, status = _worst_case_record(bench)
    summary = bench._compact_summary(out, status)
    full_line = json.dumps(out)
    assert len(full_line) > bench._SUMMARY_LIMIT  # premise: record overflows
    tail = (full_line + "\n" + json.dumps(summary) + "\n")[-2000:]
    doc = gate._summary_from_lines(tail.split("\n"))
    assert doc == summary  # byte-exact round trip through the tail
    metrics = gate.extract_metrics(doc)
    assert metrics["value"] == out["value"]
    assert metrics["flagship_imgs_per_sec"] == out["flagship_imgs_per_sec"]
    assert metrics["mfu"] == out["mfu"]  # the gate's MFU baseline rides it


def test_orchestrator_emits_summary_on_crash(monkeypatch, tmp_path):
    """An orchestrator-level exception (round 5's "parsed": null: the tail
    ended in a front-truncated full record, no summary) must not skip the
    final emissions: the full record lands with partial=True and the error
    on it, the bounded summary is still the very last line, and the
    exception re-raises so the exit code stays honest."""
    bench = _load_bench(monkeypatch)
    lines = []

    class _Boom:
        def __init__(self, phases):
            raise RuntimeError("injected orchestrator crash")

    bench._ChildProc = _Boom
    bench._emit = lambda payload: lines.append(json.loads(json.dumps(payload)))
    bench.HERE = str(tmp_path)
    with pytest.raises(RuntimeError, match="injected"):
        bench.orchestrate()
    full, summary = lines[-2], lines[-1]
    assert full["partial"] is True  # the crashed round never claims finality
    assert full["orchestrator_error"].startswith("RuntimeError")
    assert all(
        str(v).startswith("skipped: orchestrator error")
        for v in full["phases"].values()
    )
    assert summary["summary"] is True
    assert summary["orchestrator_error"].startswith("RuntimeError")
    assert len(json.dumps(summary)) <= bench._SUMMARY_LIMIT


def test_gate_baseline_records_mfu(monkeypatch, tmp_path):
    """A plain-ok flagship round with a derived MFU records it in
    artifacts/GATE_BASELINE.json so gate.py can compare a run report's
    mfu_headline like-for-like; a round without one omits the key."""
    bench = _load_bench(monkeypatch)
    bench.HERE = str(tmp_path)
    out = {"platform": "cpu", "preset": "small", "value": 50.0,
           "flagship_imgs_per_sec": 50.0, "vs_baseline": 2.0, "mfu": 0.41}
    bench._record_gate_baseline(out, {"flagship": "ok"})
    path = os.path.join(str(tmp_path), "artifacts", "GATE_BASELINE.json")
    with open(path) as f:
        rec = json.load(f)
    assert rec["mfu"] == 0.41 and rec["flagship_imgs_per_sec"] == 50.0
    out.pop("mfu")
    bench._record_gate_baseline(out, {"flagship": "ok"})
    with open(path) as f:
        assert "mfu" not in json.load(f)


def test_gate_baseline_records_mfu_target(monkeypatch, tmp_path):
    """The per-tier MFU floor (bench.MFU_TARGETS / BENCH_MFU_TARGET) is
    published by the flagship phase and recorded into GATE_BASELINE.json
    even when mfu itself was withheld — the target is policy, not
    measurement, and gate.py gates the mfu metric against it."""
    bench = _load_bench(monkeypatch)
    bench.HERE = str(tmp_path)
    assert bench._mfu_target("full") == bench.MFU_TARGETS["full"]
    monkeypatch.setenv("BENCH_MFU_TARGET", "0.33")
    assert bench._mfu_target("small") == 0.33
    monkeypatch.delenv("BENCH_MFU_TARGET")
    out = {"platform": "tpu", "preset": "full", "value": 100.0,
           "flagship_imgs_per_sec": 100.0, "vs_baseline": 2.0,
           "mfu_target": bench._mfu_target("full")}  # no "mfu": withheld
    bench._record_gate_baseline(out, {"flagship": "ok"})
    path = os.path.join(str(tmp_path), "artifacts", "GATE_BASELINE.json")
    with open(path) as f:
        rec = json.load(f)
    assert rec["mfu_target"] == bench.MFU_TARGETS["full"]
    assert "mfu" not in rec


def test_child_refuses_the_cpu():
    """The real measurement child on a host with no chip: it reports the
    missing TPU as an ``__init__`` failure and exits non-zero — it runs no
    phase on the CPU and creates no compile cache."""
    import subprocess
    import sys as _sys

    env = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [_sys.executable, os.path.join(REPO, "bench.py"),
         "--phases", "probe,flagship"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    events = [
        json.loads(line[len("@BENCH@ "):])
        for line in proc.stdout.splitlines() if line.startswith("@BENCH@ ")
    ]
    assert [e["phase"] for e in events] == ["__init__"]
    assert not events[0]["ok"] and "no TPU" in events[0]["data"]["error"]


def test_run_perf_gate_strictness_follows_platform(monkeypatch, tmp_path):
    """The round-end perf gate (TPU rounds only get here): skipped without
    a report/baseline pair, always chip-strict (--strict-device), and a
    nonzero gate exit rides the status."""
    bench = _load_bench(monkeypatch)
    monkeypatch.setattr(bench, "HERE", str(tmp_path))

    out, status = {"platform": "tpu"}, {}
    bench._run_perf_gate(out, status)
    assert status["gate"].startswith("skipped")

    art = tmp_path / "artifacts"
    art.mkdir()
    (art / "run_report.json").write_text("{}")
    (art / "GATE_BASELINE.json").write_text("{}")

    calls = []

    def _fake_run(argv, timeout):
        calls.append(list(argv))

        class _R:
            returncode = 0

        return _R()

    monkeypatch.setattr(bench.subprocess, "run", _fake_run)
    bench._run_perf_gate(out, status)
    assert status["gate"] == "ok"
    assert "--strict-device" in calls[-1] and "--advisory" not in calls[-1]

    def _regressed(argv, timeout):
        class _R:
            returncode = 3

        return _R()

    monkeypatch.setattr(bench.subprocess, "run", _regressed)
    status_bad = {}
    bench._run_perf_gate({"platform": "tpu"}, status_bad)
    assert status_bad["gate"] == "regressed (exit 3)"
