"""The reduction from a trace to numbers: on intervals small enough to check
by hand, and on a trace recorded on the chip (``trace/fixtures/``, made by
``trace/record_fixture.py``: three steps of the tiny preset on one v5e)."""

import gzip
import os
from types import SimpleNamespace as NS

import pytest

from benchmark.trace import reduce as R

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "trace", "fixtures")


def test_union_measure_subtract_gaps():
    merged = R.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert merged == [(0, 3), (5, 7)]
    assert R.measure(merged) == 5
    assert R.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert R.subtract([(0, 3), (5, 7)], [(2, 6)]) == [(0, 2), (6, 7)]
    assert R.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert R.clip([(0, 3), (5, 7)], 1, 6) == [(1, 3), (5, 6)]


def test_self_times_take_the_body_out_of_the_while():
    # a while over [0, 10) spanning two ops of 3 and 4, then a lone op
    assert R.self_times([(0, 10), (1, 4), (5, 9), (10, 12)]) == [3, 3, 4, 2]


def test_hlo_names_resolve_through_fusions_and_operands():
    hlo = """HloModule jit_step

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/reduce.powersgd/mul"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %dot.1 = f32[4]{0} convolution(%a, %a), metadata={op_name="jit(step)/step.grads/jvp(M)/dot_general" stack_frame_id=3}
  %fusion.1 = f32[4]{0} fusion(%dot.1), kind=kLoop, calls=%fused_computation.1
  %copy.1 = f32[4]{0} copy(%fusion.1)
  ROOT %ar = f32[4]{0} all-reduce(%copy.1), metadata={op_name="jit(step)/reduce.powersgd/reduce.collective/psum"}
}
"""
    names = R.HloNames(hlo)
    assert R.scope_of(names.op_name("%dot.1")) == "step.grads"
    assert R.scope_of(names.op_name("%fusion.1")) == "reduce.powersgd"  # its body's
    assert R.scope_of(names.op_name("%copy.1")) == "reduce.powersgd"  # its operand's
    assert R.scopes_of(names.op_name("%ar")) == ["reduce.powersgd", "reduce.collective"]
    assert R.scope_of(names.op_name("%nowhere")) == "unscoped"
    assert R.parse_event_name("%ar = f32[4]{0:T(128)} all-reduce(f32[4]{0:T(128)} %copy.1), replica_groups={}") == ("%ar", "all-reduce")
    assert R.parse_event_name("%t = (f32[2]{0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(f32[2]{0} %x)") == ("%t", "copy-start")


def _event(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)


def _planes():
    """Two chips, four executions 100 us apart: the first period is dropped
    (the profiler's start stalls it), two whole periods are left.
    Per period on chip 0 the core runs a matmul 0-40, waits in the
    all-reduce's done 40-60, and runs a fusion 70-80; the asynchronous
    all-reduce itself spans 30-60 (10 of it under the matmul). Busy 0-60 and
    70-80 = 70 of 100; the collective's exposed part is 40-60 = 20. Chip 1:
    the all-reduce ends at 50, so exposed 10 and busy 60."""
    planes = []
    for chip, ar_end in ((0, 60), (1, 50)):
        ops, spans, modules = [], [], []
        for period in range(4):
            t = 1000 + 100 * period
            modules.append(_event("jit_step(1)", t, 80))
            ops += [
                _event("%dot.1 = f32[4]{0} convolution(f32[4]{0} %a)", t, 40),
                _event("%ar-done = f32[4]{0} all-reduce-done(f32[4]{0} %ar-start)", t + 40, ar_end - 40),
                _event("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %dot.1), kind=kLoop", t + 70, 10),
            ]
            spans.append(_event("%ar-start = f32[4]{0} all-reduce-start(f32[4]{0} %copy.1)", t + 30, ar_end - 30))
        if chip == 1:  # a small program beside the step (a batch resharded) is not a step
            modules += [_event("jit__multi_slice(7)", 1000 + 100 * p + 90, 2) for p in range(4)]
        planes.append(NS(name=f"/device:TPU:{chip}", lines=[
            NS(name="XLA Modules", events=modules), NS(name="XLA Ops", events=ops),
            NS(name="Async XLA Ops", events=spans),
        ]))
    # the host's clock runs 15 us ahead: the sync that waits for the program
    # (which ends at 80) ends at 95 on it, and the next fetch fills 95-115
    host = [_event("step/loss_sync", 1000 + 100 * p + 45, 50) for p in range(4)]
    host += [_event("data_load", 1000 + 100 * p + 95, 20) for p in range(4)]
    planes.append(NS(name="/host:CPU", lines=[NS(name="python3", events=host)]))
    return planes


def test_reduction_on_hand_checkable_intervals():
    hlo = """ENTRY %main (a: f32[4]) -> f32[4] {
  %dot.1 = f32[4]{0} convolution(%a, %a), metadata={op_name="jit(step)/step.grads/dot_general"}
  %fusion.1 = f32[4]{0} fusion(%dot.1), kind=kLoop, calls=%f, metadata={op_name="jit(step)/reduce.powersgd/mul"}
  %ar-done = f32[4]{0} all-reduce-done(%ar-start), metadata={op_name="jit(step)/reduce.powersgd/reduce.collective/psum"}
}
"""
    r = R.reduce_planes(_planes(), hlo)
    assert r.steps == 2 and len(r.chips) == 2
    assert r.window_s == pytest.approx(200e-6)
    assert r.chips[0].busy_s == pytest.approx(140e-6) and r.chips[1].busy_s == pytest.approx(120e-6)
    assert r.busy_s == pytest.approx(130e-6)  # mean over the chips
    assert 1 - r.busy_s / r.window_s == pytest.approx(0.35)
    assert r.scope_s("step.grads") == pytest.approx(40e-6)
    assert r.scope_s("reduce.powersgd") == pytest.approx((30e-6 + 20e-6) / 2)  # fusion + the wait, per chip
    assert r.scope_s("reduce.collective") == pytest.approx(15e-6)
    assert r.collective_s() == pytest.approx(25e-6)  # 30 and 20 long
    assert r.collective_exposed_s() == pytest.approx(15e-6)  # mean of 20 and 10
    assert r.scope_s("reduce.exact") is None
    # the host plane is moved onto the device's clock; then chip 0's gaps per
    # period are 60-70 (the host sits in loss_sync) and 80-100 (it fetches)
    assert r.clock_shift_s == pytest.approx(15e-6)
    blamed = dict(r.idle_gaps())
    assert blamed["step/loss_sync"] == pytest.approx(20e-6)
    assert blamed["data_load"] == pytest.approx(40e-6)
    assert r.longest_gaps(1)[0] == ("data_load", pytest.approx(20e-6))
    kinds = dict(r.by_kind())
    assert kinds["step.grads/convolution:dot_general"] == pytest.approx(80e-6)
    assert len(r.breakdown()["device_ops"]) <= 10 and len(r.breakdown()["idle_gaps"]) <= 10


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        R.reduce_planes([NS(name="/host:CPU", lines=[])])


def test_recorded_trace_from_the_chip():
    pb = os.path.join(FIXTURES, "tiny_step.xplane.pb.gz")
    assert os.path.getsize(pb) < 2 * 1024 * 1024
    with gzip.open(os.path.join(FIXTURES, "tiny_step.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    r = R.reduce_file(pb, hlo)
    assert len(r.chips) == 1 and r.steps == 1  # three executions, the first period dropped
    assert 0 < r.busy_s < r.window_s
    report = r.report()
    # at this size the chip waits for the host nearly all the time
    assert 0.5 < report["idle_share"] < 1.0
    assert r.scope_s("step.grads") > 0 and r.scope_s("reduce.powersgd") > 0
    assert sum(report["by_scope_s"].values()) == pytest.approx(r.busy_s, rel=0.02)  # no op spans another here
    is_kernel = lambda tag: (lambda o: o.opcode == "custom-call" and tag in o.op_name)
    assert len(r.calls(is_kernel("jit(flash_attention)"))) == 2  # two layers, one whole step
    assert r.per_step(is_kernel("jit(orthogonalize_pallas)")) > 0
    assert r.collective_s() is None  # one chip: XLA drops the collectives
    # the chip waits while the host fetches the next batch and dispatches the next step
    blamed = dict(r.idle_gaps())
    assert blamed.get("data_load", 0) + blamed.get("step/compute", 0) > 0.5 * (r.window_s - r.busy_s)
    assert 0 < abs(r.clock_shift_s) < 5e-3
