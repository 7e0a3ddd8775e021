"""Memory observatory: footprint shim, sampler, headroom, OOM, gate.

Unit coverage for the device-memory plane (``observe/memory.py`` and its
readers): ``compiled_memory``'s read of ``memory_analysis()``, the
MemorySampler's one-way CPU no-op (probe once, disable forever, zero log
lines), the EWMA headroom detector's warn/critical ladder and its
silent-drop of limitless samples, the live plane's memory gauges, the
guarded step's OOM trap (detect by message, never retry, ranked
post-mortem on disk), the chaos ``oom`` fault, the report's
always-present ``memory`` section with its labeled ``hbm_peak_bytes``
gate scalar, and ``gate.py``'s lower-is-better regression +
device-provenance verdicts. Everything here is CPU-only; the fake
"devices" are plain objects with a ``memory_stats`` method.
"""

import importlib.util
import json
import os
import sys

import pytest

from network_distributed_pytorch_tpu.observe import MemoryEvent, Telemetry
from network_distributed_pytorch_tpu.observe.health import (
    DetectorConfig,
    HealthMonitor,
)
from network_distributed_pytorch_tpu.observe.live import (
    MetricRegistry,
    ingest_record,
)
from network_distributed_pytorch_tpu.observe.memory import (
    compiled_memory,
    MemorySampler,
    build_oom_report,
    device_memory_stats,
    memory_footprint_fields,
    tree_bytes,
    write_oom_report,
)
from network_distributed_pytorch_tpu.resilience import (
    MEMORY_FAULTS,
    ChaosOutOfMemoryError,
    ChaosPlan,
    ChaosStep,
    FaultSpec,
    GuardedStep,
    OutOfMemoryError,
    is_oom_error,
)
from network_distributed_pytorch_tpu.resilience.chaos import INJECTION_SITES

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        f"_memtest_{name}", os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[f"_memtest_{name}"] = mod
    spec.loader.exec_module(mod)
    return mod


class _Sink:
    def __init__(self):
        self.events = []

    def emit(self, event, record):
        self.events.append(event)

    def close(self):
        pass


def _telemetry():
    sink = _Sink()
    return Telemetry(sinks=[sink]), sink


# ---------------------------------------------------------------------------
# compiled_memory: the memory_analysis() read
# ---------------------------------------------------------------------------


class _AttrsAnalysis:
    argument_size_in_bytes = 100
    output_size_in_bytes = 20
    temp_size_in_bytes = 50
    generated_code_size_in_bytes = 5


def _compiled(result):
    class _Compiled:
        def memory_analysis(self):
            return result

    return _Compiled()


def test_compiled_memory_attrs_shape():
    out = compiled_memory(_compiled(_AttrsAnalysis()))
    assert out == {
        "argument_bytes": 100.0,
        "output_bytes": 20.0,
        "temp_bytes": 50.0,
        "generated_code_bytes": 5.0,
    }


def test_compiled_memory_without_analysis_is_none():
    assert compiled_memory(None) is None  # no executable
    assert compiled_memory(_compiled(None)) is None  # backend reports none


def test_footprint_fields_sum_to_peak_and_splat_safely():
    fields = memory_footprint_fields(_compiled(_AttrsAnalysis()))
    assert fields["peak_hbm_bytes"] == 175.0
    assert set(fields) == {
        "argument_bytes", "output_bytes", "temp_bytes",
        "generated_code_bytes", "peak_hbm_bytes",
    }
    # no analysis gives {} (never None) so callers can always **
    assert memory_footprint_fields(None) == {}
    assert memory_footprint_fields(_compiled(None)) == {}


def test_real_compiled_step_footprint_matches_shim():
    """On a real jitted function the ledger-facing helper and the raw shim
    must agree — and on backends that do report, the split sums to the
    published peak."""
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: (x * 2.0).sum()).lower(
        jnp.zeros((8, 8), jnp.float32)
    ).compile()
    fields = memory_footprint_fields(compiled)
    raw = compiled_memory(compiled)
    if raw is None:
        assert fields == {}
    else:
        assert fields["peak_hbm_bytes"] == sum(
            v for k, v in fields.items() if k != "peak_hbm_bytes"
        )


# ---------------------------------------------------------------------------
# live sampler: emits typed events; CPU degrades to a one-way no-op
# ---------------------------------------------------------------------------


class _FakeDevice:
    device_kind = "fake-hbm"

    def __init__(self, stats):
        self.stats = stats
        self.calls = 0

    def memory_stats(self):
        self.calls += 1
        if isinstance(self.stats, Exception):
            raise self.stats
        return self.stats


def test_sampler_emits_memory_events():
    telemetry, sink = _telemetry()
    dev = _FakeDevice(
        {"bytes_in_use": 10.0, "peak_bytes_in_use": 12.0,
         "bytes_limit": 100.0}
    )
    sampler = MemorySampler(telemetry, label="t", rank=3, device=dev)
    event = sampler.sample(5)
    assert isinstance(event, MemoryEvent)
    assert sampler.enabled and sampler.last is event
    rec = sink.events[-1].record()
    assert rec["event"] == "memory"
    assert rec["bytes_in_use"] == 10.0
    assert rec["bytes_limit"] == 100.0
    assert rec["rank"] == 3 and rec["device_kind"] == "fake-hbm"


@pytest.mark.parametrize(
    "stats", [None, {}, NotImplementedError("no stats"), {"other": 1}]
)
def test_sampler_statless_backend_is_one_way_noop(stats):
    """The CPU contract: probe exactly once, disable forever, emit nothing
    — no per-step spam from a backend that will never answer."""
    telemetry, sink = _telemetry()
    dev = _FakeDevice(stats)
    sampler = MemorySampler(telemetry, device=dev)
    assert sampler.sample(0) is None
    assert not sampler.enabled and dev.calls == 1
    for step in range(1, 4):
        assert sampler.sample(step) is None
    assert dev.calls == 1  # never probed again
    assert sink.events == []


def test_device_memory_stats_normalizes_and_filters():
    stats = device_memory_stats(
        _FakeDevice({"bytes_in_use": 5, "bytes_limit": "lots", "junk": 1})
    )
    assert stats == {"bytes_in_use": 5.0}
    assert device_memory_stats(_FakeDevice(RuntimeError("x"))) is None


def test_tree_bytes_counts_array_leaves_only():
    import numpy as np

    tree = {"a": np.zeros((4, 4), np.float32), "b": [np.zeros(2, np.int8)],
            "c": "not an array", "d": None}
    assert tree_bytes(tree) == 4 * 4 * 4 + 2
    assert tree_bytes(None) == 0


# ---------------------------------------------------------------------------
# headroom detector: the OOM precursor
# ---------------------------------------------------------------------------


def test_headroom_ladder_warn_then_critical():
    monitor = HealthMonitor(DetectorConfig(cooldown=0))
    limit = 100.0
    fired = []
    # ramp the occupancy: the EWMA crosses warn well before critical
    for step, frac in enumerate([0.5, 0.7, 0.9, 0.97] + [0.97] * 20):
        fired += monitor.observe_hbm(frac * limit, limit, rank=0, step=step)
    kinds = [(a.alert, a.severity) for a in fired]
    assert ("hbm_headroom", "warn") in kinds
    assert ("hbm_headroom", "critical") in kinds
    assert kinds.index(("hbm_headroom", "warn")) < kinds.index(
        ("hbm_headroom", "critical")
    )


def test_headroom_limitless_samples_dropped_silently():
    """CPU backends report no limit; a fake occupancy of in_use/0 must
    never teach the detector anything."""
    monitor = HealthMonitor(DetectorConfig())
    for limit in (0.0, -1.0, None, float("nan")):
        assert monitor.observe_hbm(50.0, limit, rank=0, step=0) == []


def test_headroom_is_per_rank():
    monitor = HealthMonitor(DetectorConfig(cooldown=0))
    fired = []
    for step in range(8):
        fired += monitor.observe_hbm(97.0, 100.0, rank=1, step=step)
        fired += monitor.observe_hbm(10.0, 100.0, rank=0, step=step)
    assert fired and {a.rank for a in fired} == {1}


def test_live_ingest_memory_gauges():
    reg = MetricRegistry()
    ingest_record(
        reg,
        {"event": "memory", "bytes_in_use": 80.0, "peak_bytes_in_use": 90.0,
         "bytes_limit": 100.0, "rank": 2},
    )
    assert reg.get_gauge("live_hbm_bytes", rank="2") == 80.0
    assert reg.get_gauge("live_hbm_peak_bytes", rank="2") == 90.0
    assert reg.get_gauge("live_hbm_limit_bytes", rank="2") == 100.0


# ---------------------------------------------------------------------------
# OOM forensics: detection, report, the guarded step's trap
# ---------------------------------------------------------------------------


def test_is_oom_error_matches_allocator_messages_only():
    assert is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 bytes"
    ))
    assert is_oom_error(ValueError("xla: Out of memory while running"))
    assert not is_oom_error(RuntimeError("collective timed out"))


def test_oom_error_is_not_a_runtimeerror():
    """The class trick that keeps retry_transient(exceptions=(RuntimeError,))
    from replaying a deterministic OOM (CheckpointUnwritableError
    precedent)."""
    assert not issubclass(OutOfMemoryError, RuntimeError)


def test_build_oom_report_ranks_buffers_and_names_top():
    report = build_oom_report(
        error="E" * 5000, label="t", rank=1, step=7,
        last_memory={"bytes_in_use": 9.0},
        footprint={"temp_bytes": 4.0},
        buffers={"params": 10.0, "ef_memory": 30.0, "bad": float("-1"),
                 "skipped": None},
    )
    assert report["top_buffer"] == "ef_memory"
    names = [b["name"] for b in report["buffers"]]
    assert names == ["ef_memory", "params"]  # desc, negatives/None dropped
    assert len(report["error"]) == 2000  # clipped
    assert report["last_memory"] == {"bytes_in_use": 9.0}
    assert report["step"] == 7


def test_write_oom_report_creates_parent_atomically(tmp_path):
    path = str(tmp_path / "deep" / "oom_report.json")
    out = write_oom_report(build_oom_report(error="x"), path)
    assert out == path
    with open(path) as f:
        assert json.load(f)["kind"] == "oom"
    assert not os.path.exists(path + ".tmp")


def test_guarded_step_traps_oom_never_retries(tmp_path):
    calls = {"n": 0}

    def inner(state, batch):
        calls["n"] += 1
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 1048576 bytes"
        )

    telemetry, sink = _telemetry()
    oom_path = str(tmp_path / "artifacts" / "oom_report.json")
    guard = GuardedStep(
        inner, retries=5, backoff_seconds=0.0, telemetry=telemetry,
        label="train", rank=2,
        footprint={"temp_bytes": 4.0, "peak_hbm_bytes": 4.0},
        buffers_fn=lambda: {"params": 100.0, "activations": 25.0},
        oom_report_path=oom_path,
    )
    with pytest.raises(OutOfMemoryError) as err:
        guard(None, None)
    assert calls["n"] == 1  # an OOM is deterministic: no retry, ever
    assert "forensics" in str(err.value)
    with open(oom_path) as f:
        report = json.load(f)
    assert report["top_buffer"] == "params"
    assert report["rank"] == 2 and report["step"] == 0
    assert report["footprint"]["peak_hbm_bytes"] == 4.0
    assert "RESOURCE_EXHAUSTED" in report["error"]
    failures = [
        e.record() for e in sink.events
        if e.record().get("event") == "failure"
    ]
    assert any(
        f["kind"] == "oom" and "params" in f["message"] for f in failures
    )


def test_guarded_step_oom_minimal_without_hooks(tmp_path):
    """No sampler / footprint / buffers_fn: the guard still detects the
    OOM and writes a (sparse) post-mortem instead of crashing on None."""

    def inner(state, batch):
        raise RuntimeError("Out of memory")

    path = str(tmp_path / "oom.json")
    guard = GuardedStep(inner, retries=1, oom_report_path=path)
    with pytest.raises(OutOfMemoryError):
        guard(None, None)
    with open(path) as f:
        report = json.load(f)
    assert report["top_buffer"] is None
    assert report["buffers"] == [] and report["footprint"] is None


def test_guarded_step_still_retries_transient_runtimeerrors():
    calls = {"n": 0}

    def inner(state, batch):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient fabric hiccup")
        return None, 0.5

    guard = GuardedStep(inner, retries=3, backoff_seconds=0.0)
    assert guard(None, None) == (None, 0.5)
    assert calls["n"] == 3


# ---------------------------------------------------------------------------
# chaos: the injectable allocator death
# ---------------------------------------------------------------------------


def test_oom_fault_registered_as_step_site_memory_group():
    assert MEMORY_FAULTS == ("oom",)
    assert INJECTION_SITES["oom"] == "step"


def test_chaos_step_injects_allocator_shaped_oom():
    step = ChaosStep(
        lambda *a: 0.0,
        ChaosPlan([FaultSpec(kind="oom", step=1, rank=0,
                             payload={"bytes": 2048})]),
        rank=0,
    )
    assert step(None, None) == 0.0  # step 0: clean
    with pytest.raises(ChaosOutOfMemoryError) as err:
        step(None, None)
    # injected == real to every layer above: a RuntimeError whose message
    # carries the allocator marker, so the guard's trap treats it the same
    assert isinstance(err.value, RuntimeError)
    assert is_oom_error(err.value)
    assert "2048" in str(err.value)
    assert step(None, None) == 0.0  # fires exactly once


def test_chaos_oom_through_guarded_step(tmp_path):
    """The game-day wiring in miniature: ChaosStep inside GuardedStep —
    the injected fault surfaces as OutOfMemoryError with forensics, not
    as a retried transient."""
    inner = ChaosStep(
        lambda *a: (None, 0.1),
        ChaosPlan([FaultSpec(kind="oom", step=0, rank=0)]),
        rank=0,
    )
    path = str(tmp_path / "oom.json")
    guard = GuardedStep(inner, retries=4, backoff_seconds=0.0,
                        oom_report_path=path)
    with pytest.raises(OutOfMemoryError):
        guard(None, None)
    assert os.path.exists(path)


# ---------------------------------------------------------------------------
# report: the always-present memory section
# ---------------------------------------------------------------------------


def test_memory_summary_cpu_graceful_predicted_only():
    report = _load_script("report")
    out = report.memory_summary(
        [{"event": "compile", "argument_bytes": 10.0, "temp_bytes": 5.0,
          "peak_hbm_bytes": 15.0}],
        [],
    )
    assert out["measured_available"] is False and out["measured"] is None
    assert out["hbm_peak_bytes"] == 15.0
    assert out["hbm_peak_source"] == "predicted"
    # ...and even with NOTHING the section exists (never vanishes)
    empty = report.memory_summary([], [])
    assert empty == {
        "predicted": None, "measured": None, "measured_available": False,
        "hbm_peak_bytes": None, "hbm_peak_source": None,
    }
    assert report.render_memory_section(empty)  # renders, says unavailable


def test_memory_summary_measured_peak_wins_across_ranks():
    report = _load_script("report")
    out = report.memory_summary(
        [{"event": "compile", "peak_hbm_bytes": 15.0}],
        [
            {"event": "memory", "rank": 0, "bytes_in_use": 40.0,
             "peak_bytes_in_use": 50.0, "bytes_limit": 100.0},
            {"event": "memory", "rank": 1, "bytes_in_use": 70.0,
             "peak_bytes_in_use": 80.0, "bytes_limit": 100.0,
             "device_kind": "toy"},
        ],
    )
    assert out["hbm_peak_source"] == "measured"
    assert out["hbm_peak_bytes"] == 80.0  # max across ranks, not sum
    assert out["measured"]["headroom_frac"] == pytest.approx(0.2)
    assert out["measured"]["per_rank"]["1"]["device_kind"] == "toy"


def test_chrome_trace_memory_counter_track():
    report = _load_script("report")
    doc = report.chrome_trace([
        {"event": "step", "rank": 0, "step": 0, "step_time_s": 0.01,
         "t_run": 1.0},
        {"event": "memory", "rank": 0, "step": 0, "bytes_in_use": 42.0,
         "bytes_limit": 100.0, "t_run": 1.01},
    ])
    counters = [
        e for e in doc["traceEvents"]
        if e.get("ph") == "C" and e.get("cat") == "memory"
    ]
    assert len(counters) == 1
    c = counters[0]
    assert c["name"] == "HBM bytes" and c["pid"] == 0
    assert c["args"]["bytes_in_use"] == 42.0


# ---------------------------------------------------------------------------
# gate: lower-is-better hbm_peak_bytes + device provenance
# ---------------------------------------------------------------------------


def test_gate_extracts_hbm_peak_nested_and_flat():
    gate = _load_script("gate")
    nested = gate.extract_metrics({"memory": {"hbm_peak_bytes": 5.0}})
    flat = gate.extract_metrics({"hbm_peak_bytes": 5.0})
    assert nested["hbm_peak_bytes"] == flat["hbm_peak_bytes"] == 5.0
    # a degraded section (None / 0) contributes nothing
    assert "hbm_peak_bytes" not in gate.extract_metrics(
        {"memory": {"hbm_peak_bytes": None}}
    )


def test_gate_fails_doubled_footprint():
    gate = _load_script("gate")
    verdicts = gate.compare(
        {"hbm_peak_bytes": 2e9}, {"hbm_peak_bytes": 1e9}, tolerance=0.2
    )
    (v,) = verdicts
    assert v["metric"] == "hbm_peak_bytes"
    assert v["regressed"] and v["ratio"] == pytest.approx(2.0)
    # shrinking the footprint is an improvement, not a regression
    ok = gate.compare(
        {"hbm_peak_bytes": 5e8}, {"hbm_peak_bytes": 1e9}, tolerance=0.2
    )
    assert not ok[0]["regressed"]


def test_gate_device_mismatch_advisory_vs_strict():
    gate = _load_script("gate")
    report = {"platform": "cpu"}
    baseline = {"platform": "tpu"}
    (advisory,) = gate.device_mismatch_verdict(report, baseline, strict=False)
    assert advisory["device_mismatch"] and not advisory["regressed"]
    (strict,) = gate.device_mismatch_verdict(report, baseline, strict=True)
    assert strict["regressed"]
    # matching or unattested sides stay silent — no noise verdicts
    assert gate.device_mismatch_verdict(
        {"platform": "TPU "}, {"platform": "tpu"}, strict=True
    ) == []
    assert gate.device_mismatch_verdict({}, baseline, strict=True) == []


def test_gate_platform_falls_back_to_mfu_device_kind():
    gate = _load_script("gate")
    assert gate._platform_of(
        {"mfu": [{"device_kind": "TPU v5e"}]}
    ) == "tpu v5e"
    assert gate._platform_of({"platform": "cpu", "mfu": []}) == "cpu"
    assert gate._platform_of({}) is None


def test_gate_main_device_mismatch_exit_codes(tmp_path):
    gate = _load_script("gate")
    rep = str(tmp_path / "r.json")
    base = str(tmp_path / "b.json")
    with open(rep, "w") as f:
        json.dump({"memory": {"hbm_peak_bytes": 1e9}, "platform": "cpu"}, f)
    with open(base, "w") as f:
        json.dump({"hbm_peak_bytes": 1e9, "platform": "tpu"}, f)
    assert gate.main(["--report", rep, "--baseline", base]) == 0
    assert gate.main(
        ["--report", rep, "--baseline", base, "--strict-device"]
    ) == 1
