"""The three readers of what the program's spans and scopes say since PR 24
(``stage_ms``, ``update_ms``, ``unscoped_ms``) on a hand-made ``Run``: the
values by hand, and nothing (never an exception) on the records of a program
from before: child spans without a step, a trace without the ``step.update``
scope."""

from types import SimpleNamespace as NS

import pytest

from benchmark import cells
from benchmark.run import Run
from benchmark.trace import reduce as R

DEVICE = ("update_ms", "unscoped_ms")


def read(name, run):
    return cells.module("layer_metrics", name).read(run)


def make_run(spans, trace=None):
    args = NS(seed=0, seconds=1.0, trace=int(trace is not None), rehearsal=False)
    run = Run({"name": "hand-made"}, args)
    # steps 0..4; the profiler touched step 2
    run.steps = [{"index": i, "end": 0.1 * (i + 1), "step_s": 0.1, "loss": 0.0, "sliced": i == 2}
                 for i in range(5)]
    run.spans = spans
    run.trace = trace
    return run


def span(name, step, dur_ms):
    return {"event": "span", "name": name, "step": step, "dur_s": dur_ms * 1e-3}


def new_program_spans():
    """Per step s: data_load 2+s ms, with two stage spans and one to_device
    span under it that carry its step; step 100 ms."""
    out = []
    for s in range(5):
        out += [
            span("data_load/assemble", s, 0.2),
            span("data_load/to_device", s, 0.5 + 0.1 * s),
            span("data_load/stage", s, 0.25),
            span("data_load/stage", s, 0.25),
            span("data_load", s, 2.0 + s),
            span("step/compute", s, 2.0),
            span("step/loss_sync", s, 97.0),
            span("step", s, 100.0),
        ]
    # the fetch that finds the epoch over belongs to no completed step
    out.append(span("data_load/to_device", 5, 50.0))
    return out


def old_program_spans():
    """What the parent commit records: wall time only, and no step on the
    spans opened below ``data_load``."""
    out = []
    for s in range(5):
        out += [
            span("data_load/assemble", None, 0.2), span("data_load/stage", None, 0.25),
            span("data_load", s, 2.0 + s), span("step/compute", s, 2.0),
            span("step/loss_sync", s, 97.0), span("step", s, 100.0),
        ]
    return out


def test_stage_ms_by_hand():
    run = make_run(new_program_spans())
    # steps 0, 1, 3, 4 are outside the slice; two stage spans and the copy:
    # 1.0, 1.1, 1.3, 1.4; upper median
    assert read("stage_ms", run) == pytest.approx(0.5 + 0.5 + 0.3)


@pytest.mark.parametrize("spans", [old_program_spans(), []], ids=["parents_records", "no_records"])
def test_stage_ms_finds_nothing_where_child_spans_carry_no_step(spans):
    assert read("stage_ms", make_run(spans)) is None


def _event(name, start_us, dur_us):
    return NS(name=name, start_ns=int(start_us * 1000), duration_ns=int(dur_us * 1000))


def tiny_reduced(with_update):
    """One chip, four executions of 100 us period: grads 40 us, the update
    10 us, a copy the compiler added 5 us. The window is the second to the
    fourth program start: two steps."""
    update_scope = "step.update/" if with_update else ""
    hlo = f"""ENTRY %main (a: f32[4]) -> f32[4] {{
  %dot.1 = f32[4]{{0}} convolution(%a, %a), metadata={{op_name="jit(step)/step.grads/dot_general"}}
  %fusion.1 = f32[4]{{0}} fusion(%dot.1), kind=kLoop, calls=%f, metadata={{op_name="jit(step)/{update_scope}sub"}}
  %copy-done.1 = f32[4]{{0}} copy-done(%copy-start.1), metadata={{op_name="state.params['w']"}}
}}
"""
    ops, modules = [], []
    for p in range(4):
        base = 1000 + 100 * p
        modules.append(_event("jit_step(1)", base, 60))
        ops += [
            _event("%dot.1 = f32[4]{0} convolution(%a, %a)", base, 40),
            _event("%fusion.1 = f32[4]{0} fusion(%dot.1), kind=kLoop", base + 40, 10),
            _event("%copy-done.1 = f32[4]{0} copy-done(%copy-start.1)", base + 50, 5),
        ]
    plane = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules), NS(name="XLA Ops", events=ops),
    ])
    return R.reduce_planes([plane], hlo)


def test_device_readers_by_hand():
    run = make_run([], tiny_reduced(with_update=True))
    assert run.trace.steps == 2
    assert read("update_ms", run) == pytest.approx(10e-3)
    assert read("unscoped_ms", run) == pytest.approx(5e-3)
    # the scopes and what has none add up to the busy time
    by_scope = run.trace.by_scope()
    assert sum(by_scope.values()) == pytest.approx(run.trace.busy_s)
    assert by_scope["unscoped"] / run.trace.steps == pytest.approx(5e-6)


def test_device_readers_on_the_parents_trace():
    """Without the scope the update is unscoped time, and ``update_ms`` has
    nothing to read."""
    run = make_run([], tiny_reduced(with_update=False))
    assert read("update_ms", run) is None
    assert read("unscoped_ms", run) == pytest.approx(15e-3)


@pytest.mark.parametrize("name", DEVICE)
def test_device_readers_without_a_trace(name):
    assert read(name, make_run(new_program_spans())) is None


def test_the_three_are_reported_where_their_neighbours_are():
    new = {"stage_ms", *DEVICE}
    for cell in ("imdb_psgd16_b16", "imdb_psgd16_b128", "imdb_psgd16_b16_x4"):
        assert new <= {m["name"] for m in cells.cell(cell)["per_layer"]}
    assert not new & {m["name"] for m in cells.cell("cifar_psgd4_b128")["per_layer"]}
