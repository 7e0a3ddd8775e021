"""One run of one cell.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It refuses to start unless jax's first device is a TPU whose
kind ``benchmark/peaks.json`` knows and the device count is the cell's
``chips``; it never sets ``JAX_PLATFORMS``. It builds the cell from its files
(``cells.py``), runs three warm-up steps itself at the cell's one shape, keeps
what the plain reference needs, then hands the step to the program's own
``experiments.common.train_loop`` for ``--seconds`` with the input pipeline
and the per-step loss sync running as users run them. Afterwards it checks
the first three steps against the plain reference and prints, as the LAST
line of stdout, the contract's JSON object. Everything else a reader wants
is on earlier lines and in one JSON file per run under ``chiprun_out/``.

``--rehearsal`` walks the same code at the tiny sizes the cell's files carry,
on the CPU with as many virtual devices as the cell has chips. It prints
counts and ``correct`` and no time, rate, utilisation or idle share.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

from . import cells

WARMUP_STEPS = 3  # also the steps the plain reference is compared on
TRACE_SLICE_STEPS = 20  # consecutive steps the profiler sees in a traced run, unless the workload file says
TRACE_SLICE_AT = 0.4  # the slice starts this far into the window


def process_age_s() -> float:
    """Seconds since this process started (imports and interpreter start
    included), from /proc; 0.0 where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


class Run:
    """Everything one run knows, handed to every metric reader."""

    def __init__(self, cell: Dict, args) -> None:
        self.cell = cell
        self.seed: int = args.seed
        self.seconds: float = args.seconds
        self.traced: bool = bool(args.trace)
        self.rehearsal: bool = args.rehearsal
        self.cfg: Dict = {}
        self.device: Dict = {}
        self.peaks: Dict = {}
        self.samples_per_step = 0
        self.wire_bytes_per_step = 0
        self.flops_per_sample = 0.0
        self.compile_s = 0.0
        self.setup_s = 0.0
        self.window_s = 0.0
        self.steps: List[Dict] = []  # {start, end, step_s, loss, sliced}
        self.spans: List[Dict] = []  # the window's span records
        self.trace = None  # benchmark.trace.reduce.Reduced, traced runs only
        self.memory_peak_bytes = 0
        self.step_temp_bytes = 0  # the step executable's temporaries

    # the steps host-span metrics may use: outside the profiler's slice
    def clean_steps(self) -> List[Dict]:
        return [s for s in self.steps if not s["sliced"]]

    def clean_period(self):
        """(steps, seconds) of the untraced steps' whole periods, end of one
        step to end of the next, so every wait between steps is inside."""
        ends = [s["end"] for s in self.steps]
        clean = [s["index"] for s in self.clean_steps() if s["index"] > 0]
        return len(clean), sum(ends[i] - ends[i - 1] for i in clean)

    def clean_spans(self, name: str) -> List[Dict]:
        clean = {s["index"] for s in self.clean_steps()}
        return [r for r in self.spans if r["name"] == name and r.get("step") in clean]


def require_devices(chips: int, rehearsal: bool):
    import jax

    devices = jax.devices()
    first = devices[0]
    if rehearsal:
        if first.platform != "cpu" or len(devices) != chips:
            sys.exit(f"benchmark: rehearsal wants {chips} CPU devices, found {devices}")
        return devices, {}
    if first.platform != "tpu":
        sys.exit(
            f"benchmark: no TPU: jax.devices()[0] is {first.platform!r}"
            f" ({first.device_kind!r}). A cell is measured on the chip or not at all."
        )
    peaks = cells.peaks(first.device_kind)
    if len(devices) != chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), jax found {len(devices)}")
    return devices, peaks


class CompileWatch:
    """Counts what jax traces and compiles, by the time it happened."""

    def __init__(self) -> None:
        import jax.monitoring

        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, seconds: float, **kwargs) -> None:
        if "compile" in name or "trace" in name:
            self.events.append((time.monotonic(), name, seconds, kwargs.get("fun_name")))

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]


def shapes_of(tree):
    """The tree as shapes with their shardings: what lowering needs, and all
    that is left to hand it once the step has donated the arrays."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree
    )


def compiled_step(step, state_shapes, batch_shapes) -> Dict:
    """The step's executable as the first call built it (jax keeps the
    lowering, the backend keeps the executable: this compiles nothing anew):
    its temporaries' size, and its HLO reconciled with the wire ledger."""
    from network_distributed_pytorch_tpu.utils.hlo_audit import hlo_text_of_compiled

    compiled = step.fn.lower(state_shapes, batch_shapes).compile()
    hlo = hlo_text_of_compiled(compiled)
    memory = compiled.memory_analysis()
    return {
        "hlo_text": hlo,
        "temp_bytes": int(getattr(memory, "temp_size_in_bytes", 0) or 0),
        "memory_analysis": str(memory),
        **step.ledger.reconcile(hlo),
    }


def placement_ok(tree, devices) -> bool:
    """Does every chip hold a shard of every leaf, and allocator bytes?"""
    import jax

    want = {d.id for d in devices}
    for leaf in jax.tree_util.tree_leaves(tree):
        if {s.device.id for s in leaf.addressable_shards} != want:
            return False
    # a backend without allocator statistics (the CPU, in rehearsal) has nothing to say
    return all(d.memory_stats() is None or d.memory_stats().get("bytes_in_use", 0) > 0 for d in devices)


class Window:
    """The measured window, as ``train_loop``'s ``on_step_end`` hook: ends the
    loop at the first step that completes at or after ``seconds``, and in a
    traced run turns the profiler on for a slice of steps in the middle."""

    def __init__(self, run: Run, trace_dir: Optional[str]) -> None:
        self.run = run
        self.trace_dir = trace_dir
        self.t0 = 0.0
        self.ends: List[float] = []
        self.slice_first: Optional[int] = None  # index of the first traced step
        self.slice_last: Optional[int] = None
        self.tracing = False
        self.slice_steps = int(run.cell["workload"].get("trace_slice_steps", TRACE_SLICE_STEPS))

    def start(self) -> None:
        self.t0 = time.monotonic()

    def __call__(self, epoch: int, steps_done: int, state) -> bool:
        import jax

        now = time.monotonic()
        self.ends.append(now)
        if self.trace_dir and not self.run.rehearsal:
            if self.slice_first is None and now - self.t0 >= TRACE_SLICE_AT * self.run.seconds:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # host spans come as TraceAnnotations
                jax.profiler.start_trace(self.trace_dir, profiler_options=options)
                self.tracing, self.slice_first = True, steps_done
            elif self.tracing and steps_done - self.slice_first >= self.slice_steps:
                jax.profiler.stop_trace()
                self.tracing, self.slice_last = False, steps_done - 1
        return time.monotonic() - self.t0 >= self.run.seconds

    def close(self) -> None:
        if self.tracing:
            import jax

            jax.profiler.stop_trace()
            self.tracing, self.slice_last = False, len(self.ends) - 1

    def sliced(self, index: int) -> bool:
        """Steps the profiler touched: the slice, the step whose hook started
        it and the step after its stop (which waits for the trace's write)."""
        if self.slice_first is None:
            return False
        last = self.slice_last if self.slice_last is not None else len(self.ends)
        return self.slice_first - 1 <= index <= last + 1


def measure(run: Run, built, state, window: Window, telemetry, sink) -> Any:
    """The window: the program's own loop, stopped by the clock."""
    from network_distributed_pytorch_tpu.experiments.common import train_loop

    window.start()
    failed_early = None
    try:
        state, logger = train_loop(
            built.step, state, built.batches, epochs=1, log_every=0,
            on_step_end=window, prefetch=2, telemetry=telemetry,
            run_name=run.cell["name"],
        )
        records = logger.records
    except Exception as e:  # a step that raises fails the run, after it is counted
        import traceback

        traceback.print_exc()
        failed_early, records = e, []
    finally:
        window.close()
    spans = sink.of_kind("span")
    step_spans = {r["step"]: r for r in spans if r["name"] == "step"}
    for rec, end in zip(records, window.ends):
        span = step_spans.get(rec.step, {})
        run.steps.append({
            "index": rec.step,
            "end": end - window.t0,
            "step_s": span.get("dur_s", rec.step_time_s),
            "loss": rec.loss,
            "sliced": window.sliced(rec.step),
        })
    run.spans = spans
    run.window_s = (window.ends[-1] - window.t0) if window.ends else 0.0
    return state, failed_early


def verdict(checks: Dict[str, bool]) -> bool:
    for name, ok in checks.items():
        print(f"benchmark: check {name}: {'ok' if ok else 'FAILED'}", flush=True)
    return all(checks.values())


def read_metrics(run: Run, kind: str, counts_only: bool) -> Dict[str, Dict]:
    """Every metric of ``kind`` the cell reports, each from its own reader,
    found by the metric's name. A reader that has nothing to read returns
    None and the metric is left out. A rehearsal keeps only the readers that
    declare themselves a count (``COUNT = True``): nothing a CPU run says is
    a device number."""
    out = {}
    for metric in run.cell[kind]:
        reader = cells.module(cells.READERS[kind], metric["name"])
        if counts_only and not getattr(reader, "COUNT", False):
            continue
        value = reader.read(run)
        if value is not None and math.isfinite(value):
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="python3 -m benchmark.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    cell = cells.cell(args.workload)
    run = Run(cell, args)
    chips = cell["entry"]["chips"]

    from network_distributed_pytorch_tpu import hostenv

    if args.rehearsal:
        hostenv.force_cpu_devices(chips)
    import jax

    devices, run.peaks = require_devices(chips, args.rehearsal)
    first = devices[0]
    run.device = {"platform": first.platform, "kind": first.device_kind, "count": len(devices)}
    cache_dir = hostenv.configure_compile_cache()
    cache_before = hostenv.compile_cache_entries(cache_dir)
    watch = CompileWatch()

    from network_distributed_pytorch_tpu.data import device_prefetch
    from network_distributed_pytorch_tpu.native.build import host_data_tier
    from network_distributed_pytorch_tpu.observe.sinks import MemorySink
    from network_distributed_pytorch_tpu.observe.telemetry import Telemetry
    from network_distributed_pytorch_tpu.parallel import make_mesh
    from network_distributed_pytorch_tpu.parallel.mesh import data_sharding

    from . import compose, reference_check, traffic

    out_dir = os.path.join(cells.CHECKOUT, "chiprun_out", "benchmark", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    tag = f"seed{args.seed}_trace{args.trace}" + ("_rehearsal" if args.rehearsal else "")

    # ---- set-up: build, warm up the cell's one shape, keep what the check needs
    mesh = make_mesh(devices=devices)
    run.cfg = compose.resolved(cell["config"], cell["workload"], args.rehearsal)
    builder = cell["config"]["builder"]
    t = time.monotonic()
    built = cells.module("builders", builder).build(run.cfg, args.seed, mesh)
    build_s = time.monotonic() - t
    run.samples_per_step = built.samples_per_step
    run.wire_bytes_per_step = built.step.bits_per_step // 8
    run.flops_per_sample = cells.module("flops", builder).flops_per_sample(run.cfg)

    # the window's first batches, staged the way train_loop stages them, so
    # that whatever that path compiles is compiled here
    sharding = data_sharding(mesh)
    first_batches = built.batches(0)
    warm_source = [next(first_batches) for _ in range(WARMUP_STEPS)]
    first_batches.close()
    warm = [jax.device_get(b) for b in warm_source]  # the reference's copies
    state = built.state
    kept = reference_check.Kept(state)  # initial parameters and Q, on the host
    state_shapes = shapes_of(state)
    warm_losses = []
    t = time.monotonic()
    for i, batch in enumerate(device_prefetch(iter(warm_source), sharding, depth=0)):
        if i == 0:
            batch_shapes = shapes_of(batch)
        state, loss = built.step(state, batch)
        warm_losses.append(float(jax.device_get(loss)))
        if i == 0:
            kept.after_first_step(state)  # before the next call donates it
            audit = compiled_step(built.step, state_shapes, batch_shapes)
            run.step_temp_bytes = audit["temp_bytes"]
            run.compile_s = time.monotonic() - t
    placed = chips == 1 or placement_ok((state.params, state.memories), devices)
    cache_warm = hostenv.compile_cache_entries(cache_dir)

    # ---- the window
    sink = MemorySink()
    telemetry = Telemetry([sink])
    trace_dir = os.path.join(out_dir, f"{tag}_profile") if run.traced else None
    window = Window(run, trace_dir)
    run.setup_s = process_age_s()
    t_window = time.monotonic()
    state, raised = measure(run, built, state, window, telemetry, sink)
    t_window_end = time.monotonic()
    compiled_inside = watch.between(t_window, t_window_end)
    cache_after = hostenv.compile_cache_entries(cache_dir)
    stats = [d.memory_stats() or {} for d in devices]
    # On this runtime the allocator's peak leaves out the running program's
    # temporaries (PR 22: a step with 13.3 GB of them ran while the allocator
    # peaked at 0.93 GB), so the step executable's own are added to it.
    allocator_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    run.memory_peak_bytes = allocator_peak + audit["temp_bytes"] if allocator_peak else 0
    del state  # the reference needs the room
    gc.collect()  # and the loop's abandoned batch generators close now, not at interpreter exit

    # ---- correctness: the plain reference on the warm-up's batches
    t = time.monotonic()
    ref = reference_check.compare(run, kept, warm, warm_losses, builder, chips)
    reference_check_s = time.monotonic() - t

    losses = [s["loss"] for s in run.steps]
    failed = sum(1 for x in losses if not math.isfinite(x)) + (1 if raised else 0)
    checks = {
        "every loss in the window is finite": failed == 0 and len(losses) > 0,
        "nothing compiled or traced inside the window": not compiled_inside,
        "the window added no compile-cache entry": cache_after == cache_warm,
        "losses 1..3 and the state after step 1 agree with the plain reference": ref["ok"],
        "ledger bytes equal the plain reference's count": ref["wire_ok"],
    }
    if chips > 1:
        checks["ledger bytes equal the compiled HLO's collective bytes"] = bool(
            audit["exact"] and audit["hlo_collective_count"] > 0
        )
        checks["every chip holds shards and allocator bytes"] = placed
    correct = verdict(checks)

    # ---- the trace, reduced
    if run.traced and not args.rehearsal:
        from .trace import reduce as trace_reduce

        run.trace = trace_reduce.reduce_dir(trace_dir, hlo_text=audit["hlo_text"])

    kind = "per_layer" if run.traced else "end_to_end"
    metrics = read_metrics(run, kind, counts_only=args.rehearsal)

    device = dict(run.device, memory_peak_bytes=int(run.memory_peak_bytes))
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(window.ends) + (1 if raised else 0),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()

    # ---- the run's file: the material for PERF.md, none of it on the last line
    step_s = sorted(s["step_s"] for s in run.clean_steps())
    span_totals: Dict[str, float] = {}
    for r in run.spans:
        span_totals[r["name"]] = span_totals.get(r["name"], 0.0) + r["dur_s"]
    detail = {
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "traced": run.traced, "rehearsal": args.rehearsal, "device": device,
        "per_chip_batch": run.cfg["per_chip_batch"],
        "samples_per_step": run.samples_per_step,
        "host_data_tier": host_data_tier(),
        "padding_share": traffic.padding_share(built.pool),
        "checks": checks, "reference": ref["report"],
        "wire_audit": {k: v for k, v in audit.items() if k != "hlo_text"},
        "warm_losses": warm_losses,
        "compile_cache": {"dir": cache_dir, "before": cache_before,
                          "after_warm_up": cache_warm, "after_window": cache_after},
        "compiled_inside_window": [f"{e[1]} {e[3] or ''}".strip() for e in compiled_inside],
        "steps": len(run.steps),
        "trace_slice": [window.slice_first, window.slice_last],
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
    }
    if not args.rehearsal:
        detail.update({
            "setup_s": run.setup_s, "build_s": build_s, "compile_s": run.compile_s,
            "reference_check_s": reference_check_s, "window_s": run.window_s,
            "total_s": process_age_s(),
            "step_p50_ms": 1e3 * step_s[len(step_s) // 2] if step_s else None,
            "step_p95_ms": 1e3 * step_s[min(int(0.95 * len(step_s)), len(step_s) - 1)] if step_s else None,
            "step_times_s": [s["step_s"] for s in run.steps],
            "step_ends_s": [s["end"] for s in run.steps],
            "span_totals_s": span_totals,
            "memory_stats": stats, "allocator_peak_bytes": allocator_peak,
            "metrics": metrics,
        })
        if run.trace is not None:
            detail["trace"] = run.trace.report()
    with open(os.path.join(out_dir, f"{tag}.json"), "w") as f:
        json.dump(detail, f, indent=1, default=str)
    summary = {k: detail[k] for k in (
        "cell", "seed", "per_chip_batch", "steps", "step_p50_ms", "step_p95_ms",
        "setup_s", "build_s", "compile_s", "reference_check_s", "window_s",
        "total_s", "padding_share", "compile_cache", "first_loss", "last_loss",
    ) if detail.get(k) is not None}
    print("benchmark: " + json.dumps(summary, default=str), flush=True)
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0  # the verdict is the line's `correct`, not the exit code


if __name__ == "__main__":
    sys.exit(main())
