"""Benchmark — incremental JSON lines for the driver (it parses the tail).

Runs on a TPU and nowhere else: a child that finds no chip reports it, and
the run ends non-zero with the error on its last line. No number here is
ever taken on the CPU under a device metric's name.

Flagship: CIFAR-10 ResNet-50 training (the reference's entry point A/B model
family, ``ddp_guide_cifar10/ddp_init.py:57-62``). Two arms:

- **baseline emulation**: the reference's configuration translated literally
  — ResNet-50, fp32, exact allreduce-mean, SGD momentum, one host dispatch
  per step (the reference's Python loop,
  ``ddp_guide_cifar10/ddp_init.py:108-125``).
- **flagship**: the same workload the TPU-first way — bfloat16 compute on
  the MXU and the ``lax.scan`` epoch runner (whole step chunks compiled into
  ONE dispatch, ``make_scanned_train_fn``), donated carries.

metric = flagship imgs/sec; vs_baseline = flagship / baseline. Also
reported: **MFU** (XLA cost analysis of the exact executable timed ÷ wall
time ÷ peak bf16 FLOP/s by device_kind) for both the flagship and a
full-shape GPT-2-small (124M, seq 1024, vocab 50257) training step. Every
timed region ends in a fetched result (``utils.timing.wait_result``).

This file is TWO programs, because a chip belongs to one process and a
compile stuck in C++ can only be stopped from outside:

- **Parent orchestrator** (default entry): imports no jax, so it never
  holds the chip. Emits a valid JSON line immediately, then spawns one
  child at a time to run measurement phases in order (probe → flagship →
  baseline → gpt → ...), each under a HARD per-phase deadline — a child
  wedged inside a compile is SIGKILLed, which no in-process watchdog can
  do (``BENCH_r03``: rc=124 with nothing printed). After every phase
  result it re-emits one cumulative, self-contained JSON line, so whenever
  the driver's patience runs out the tail of stdout is the richest
  complete snapshot. A global deadline (default 870 s) is enforced between
  phases; remaining phases are recorded as skipped. The very last line is
  a bounded (≤1,200-char) summary digest so a fixed-size stdout tail
  always ends in one complete, parseable record.
- **Child** (``--phases a,b,...``): initialises the backend, refuses
  anything but a TPU, places the persistent compile cache
  (``hostenv.configure_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` or
  ``<checkout>/.xla_cache``), then runs its phases, printing one
  marker-prefixed JSON line per phase. One child runs many phases (backend
  init is paid once); only after a kill does a fresh child re-pay init for
  the remainder. Each non-probe phase also self-deadlines in a daemon
  thread at its budget minus a margin (``_run_with_deadline``): an
  overlong compile is ABANDONED with an error marker, which keeps the
  initialised backend alive for the remaining phases.

Cells, steady-state windows and a trace reduction are the next
``benchmark`` PR (ROADMAP Speed 1 / Design 1); this file only lost the
tiers that could hide the device.
"""

from __future__ import annotations

import glob
import json
import math
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# steps per scanned dispatch. The flagship times ONE dispatch
# fetch-to-observe, so the host<->chip round trip is amortized over CHUNK
# steps — at 10, that overhead dominated the measurement (two identical
# July runs of this program gave 22.8k and 35.0k imgs/sec). 50 is still
# far below real usage (make_scanned_train_fn dispatches a ~195-step CIFAR
# epoch per call), so the amortization understates, not overstates, the
# runner.
CHUNK = int(os.environ.get("BENCH_CHUNK", "50"))
# the literal-translation baseline pays the host round trip EVERY step by
# design (that's the arm's whole point), so its eager-loop iteration count
# must stay decoupled from CHUNK: at the measured 3.4 s/step, CHUNK=50
# iterations would alone blow the 240 s phase budget
BASELINE_REPS = int(os.environ.get("BENCH_BASELINE_REPS", "8"))
# per-tier MFU floors for the flagship (published as ``mfu_target`` in the
# phase record, the summary, and GATE_BASELINE.json so scripts/gate.py can
# gate the mfu metric against an EXPLICIT target instead of only
# run-over-run drift). Anchored on the two July chip runs of the "full"
# preset (mfu 0.0072 and 0.0047, a 54% run-to-run spread): 0.005 sits at
# the observed midpoint, and the "small" preset's shallow ResNet-18
# carries proportionally less MXU work per byte. Override per-run with
# BENCH_MFU_TARGET. (ROADMAP Speed 2: a cell this idle is too small to be
# a cell; the next benchmark PR replaces it.)
MFU_TARGETS = {"small": 0.002, "full": 0.005}
# absolute ceiling for the data-plane span share at the flagship tier: the
# loader must cost under 5% of the overlapped step loop (ISSUE PR 12
# acceptance). gate.py reads the recorded value as a lower-is-better
# metric AND this target as an absolute bound, mirroring mfu_target.
DATA_LOAD_SHARE_TARGET = 0.05
# absolute floor for the paged KV cache's concurrency win at equal HBM:
# the block pool must admit >= 2x the requests a dense slot cache holds
# in the same device bytes (PR 19 acceptance). gate.py reads the recorded
# kv_capacity_ratio as higher-is-better AND this target as an absolute
# bound, mirroring data_load_share_target.
KV_CAPACITY_RATIO_TARGET = 2.0
# absolute ceiling for the offline cost model's predicted-vs-realized step
# time error (observe.costmodel; ISSUE PR 13 acceptance): the planner's
# predictions must stay within 25% of measured on executed configs.
# gate.py reads the recorded costmodel_error as a lower-is-better metric
# AND this target as an absolute bound, mirroring mfu_target.
COSTMODEL_ERROR_TARGET = 0.25
MARKER = "@BENCH@ "


def _mfu_target(preset: str) -> float:
    env = os.environ.get("BENCH_MFU_TARGET")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    return MFU_TARGETS.get(preset, 0.0)
# global wall budget for the whole orchestration — must undercut the
# driver's own patience (round 3 was killed at rc=124 with nothing printed;
# VERDICT r3 set the bar at <=900 s)
TOTAL_DEADLINE_S = int(os.environ.get("BENCH_TOTAL_DEADLINE_S", "870"))
# per-phase hard deadlines, measured from the previous stdout event. The
# first entry of a child also covers process start + backend init. Cold
# compiles of the big models take minutes; a warm persistent cache
# (hostenv.configure_compile_cache) replays them, and a blown budget skips
# that one phase, never the round.
PHASE_BUDGET_S = {
    "probe": int(os.environ.get("BENCH_PROBE_BUDGET_S", "300")),
    "flagship": int(os.environ.get("BENCH_FLAGSHIP_BUDGET_S", "330")),
    "baseline": int(os.environ.get("BENCH_BASELINE_BUDGET_S", "240")),
    "gpt": int(os.environ.get("BENCH_GPT_BUDGET_S", "420")),
    "fp32arm": int(os.environ.get("BENCH_FP32ARM_BUDGET_S", "240")),
    "overlap": int(os.environ.get("BENCH_OVERLAP_BUDGET_S", "240")),
    "loader": int(os.environ.get("BENCH_LOADER_BUDGET_S", "150")),
    "serving": int(os.environ.get("BENCH_SERVING_BUDGET_S", "240")),
}
# priority order under the global deadline: the headline pair first, then
# the GPT MFU row (verdict item), then the decomposition arm, then the
# AOT-only overlap evidence, then the loader-isolation arm (host-only —
# cheap, but it must never displace a device measurement), then the
# serving arm (small-model inference — last because the training-path
# numbers are the round's headline)
PHASES = (
    "probe", "flagship", "baseline", "gpt", "fp32arm", "overlap", "loader",
    "serving",
)
# extra wait on a child's FIRST event only: process start, jax import and
# backend init all precede it, so a child that hangs at init is told apart
# from one that blew its first phase's budget.
INIT_GRACE_S = int(os.environ.get("BENCH_INIT_GRACE_S", "300"))

# Driver-facing JSON lines flow through the observe sinks (the same event
# model the experiments log through). observe is jax-free by design, so the
# parent orchestrator still imports no jax. RawEvent keeps each payload
# verbatim — no "event" wrapper, no timestamp — so the driver's tail parser
# sees byte-identical lines.
from network_distributed_pytorch_tpu.observe import (  # noqa: E402
    RawEvent,
    StreamJsonSink,
    Telemetry,
)

_PARENT_TELEMETRY = Telemetry([StreamJsonSink(sys.stdout)])
_CHILD_TELEMETRY = Telemetry([StreamJsonSink(sys.stdout, prefix=MARKER)])


def _emit(payload: dict) -> None:
    _PARENT_TELEMETRY.emit(RawEvent(payload))


# ---------------------------------------------------------------------------
# child: backend init + measurement phases
# ---------------------------------------------------------------------------


def _child_emit(phase: str, ok: bool, data: dict) -> None:
    _CHILD_TELEMETRY.emit(RawEvent({"phase": phase, "ok": ok, "data": data}))


def _init_backend():
    """The child's first backend touch. Anything but a TPU is refused: this
    benchmark's numbers are device numbers, and a CPU run under their names
    is worse than no run. Then the persistent compile cache is placed
    (``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.xla_cache``)
    so a second run in the same machine compiles warm."""
    import jax

    from network_distributed_pytorch_tpu import hostenv

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {first.platform!r}"
            f" ({first.device_kind!r}); bench.py measures the chip and does"
            " not fall back to the CPU"
        )
    hostenv.configure_compile_cache()
    return devices


class _CacheProbe:
    """Persistent-compilation-cache accounting around one phase.

    Construct before the phase's compiles, ``report()`` after: a compile
    served from the cache writes no new entry, so ``new_entries == 0``
    reads as "hit" and ``> 0`` as "miss" (fresh compiles persisted)."""

    def __init__(self):
        from network_distributed_pytorch_tpu import hostenv

        self._hostenv = hostenv
        self.dir = hostenv.configure_compile_cache()  # placed at init: a no-op
        self.before = hostenv.compile_cache_entries(self.dir)

    def report(self) -> dict:
        after = self._hostenv.compile_cache_entries(self.dir)
        new = after - self.before
        return {
            "status": "miss" if new > 0 else "hit",
            "new_entries": new,
            "entries_total": after,
        }


def _peak_flops(device) -> float:
    """Peak bf16 FLOP/s for ``device``. The table lives in ``observe.mfu``
    — one provenance for the numbers both the bench MFU and the run
    report's roofline use — and a TPU kind it lacks raises there."""
    from network_distributed_pytorch_tpu.observe.mfu import peak_flops

    return peak_flops(device.device_kind, device.platform)


def _small_preset() -> bool:
    """``BENCH_PRESET=small`` runs the shallow models (a quick check that
    every phase still starts on the chip); the default is the full preset."""
    return os.environ.get("BENCH_PRESET", "").lower() == "small"


def _make_model(dtype, small: bool):
    from network_distributed_pytorch_tpu.models import resnet18, resnet50

    if small:
        return resnet18(num_classes=10, norm="batch", stem="cifar", width=8, dtype=dtype)
    return resnet50(num_classes=10, norm="batch", stem="imagenet", dtype=dtype)


def _cifar_batch(batch_size: int):
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.data import synthetic_cifar10

    images, labels = synthetic_cifar10(batch_size, seed=0)
    return (jnp.asarray(images), jnp.asarray(labels))


def _phase_probe() -> dict:
    import jax

    d = jax.devices()[0]
    # runtime attestation: jaxlib pins the compiled XLA the numbers came
    # from — a perf delta across rounds with different jaxlibs is a
    # toolchain change, not a repo regression (gate.py's device-provenance
    # guard reads the platform field; the version rides along for humans)
    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", None)
    except Exception:  # noqa: BLE001 — attestation is best-effort
        jaxlib_version = None
    return {
        "device": getattr(d, "device_kind", d.platform),
        "platform": d.platform,
        "n_devices": jax.device_count(),
        "jaxlib_version": jaxlib_version,
    }


def _median(xs):
    import statistics

    return statistics.median(xs)


def _scanned_cifar_setup(dtype):
    """Build + AOT-compile the CHUNK-scanned CIFAR train step — ONE scaffold
    shared by the flagship (bf16) and fp32 decomposition arms, so the pair
    differs in nothing but dtype and the comparison isolates exactly that.
    Returns ``(scanned, state, chunk_batch, compiled, batch_size, small,
    compile_stats)`` where ``compile_stats`` splits the AOT cost into its
    tracing (``lower_ms``) and XLA-compile (``compile_ms``) components —
    the compile component is what a warm persistent cache replays."""
    import jax
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.experiments.common import image_classifier_loss
    from network_distributed_pytorch_tpu.parallel import ExactReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import make_scanned_train_fn

    small = _small_preset()
    batch_size = 32 if small else 256  # reference global batch — ddp_init.py:49
    model = _make_model(dtype, small)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=True)
    loss_fn = image_classifier_loss(model, has_batch_stats=True)
    scanned = make_scanned_train_fn(
        loss_fn, ExactReducer(), variables["params"], learning_rate=0.001,
        momentum=0.9, algorithm="sgd", mesh=make_mesh(), donate_state=True,
    )
    state = scanned.init_state(
        variables["params"], model_state={"batch_stats": variables["batch_stats"]}
    )
    batch = _cifar_batch(batch_size)
    chunk_batch = (
        jnp.broadcast_to(batch[0][None], (CHUNK,) + batch[0].shape),
        jnp.broadcast_to(batch[1][None], (CHUNK,) + batch[1].shape),
    )
    t0 = time.perf_counter()
    lowered = scanned.fn.lower(state, chunk_batch)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    compile_stats = {
        "lower_ms": round(1000.0 * (t1 - t0), 2),
        "compile_ms": round(1000.0 * (t2 - t1), 2),
    }
    return scanned, state, chunk_batch, compiled, batch_size, small, compile_stats


def _reps(env_var: str, default: str) -> int:
    """Rep count for a timing phase (``env_var`` overrides)."""
    return max(1, int(os.environ.get(env_var, default)))


def _timed_dispatches(compiled, state, chunk_batch, reps):
    """Warmup + ``reps`` fetch-to-observe timed CHUNK-step dispatches.
    Returns ``(state, times_s, first_execute_s)`` in MEASUREMENT order —
    ``first_execute_s`` is the warmup dispatch timed separately: against an
    AOT executable it contains NO compile (that is ``compile_stats``), only
    first-run costs (program load, donation setup, allocator warmup), so
    publishing it apart from the steady-state reps keeps both honest
    (two identical one-shot July runs differed by 54% — 22.8k vs 35.0k
    imgs/sec; every published rate needs median + spread, and the published
    sequence must keep its time order so a drift across reps — warm-up, a
    draining abandoned compile — stays visible; callers sort a local copy
    for min/median/max)."""
    from network_distributed_pytorch_tpu.utils.timing import wait_result

    t0 = time.perf_counter()
    state, losses = compiled(state, chunk_batch)  # warmup / first execute
    wait_result(losses)
    first_execute_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state, losses = compiled(state, chunk_batch)
        wait_result(losses)  # fetch-to-observe-completion, utils.timing
        times.append(time.perf_counter() - t0)
    return state, times, first_execute_s


def _flops_band(ratio: float, chunk: int):
    """Classify the FLOPs cross-check ratio ``flops_chunk / flops_1`` as
    ``"trip"`` (trip-multiplied, ratio ~chunk), ``"once"`` (count-once,
    ratio ~1), or ``None`` (matches neither — caller withholds MFU).

    The original two ±2x windows — [chunk/2, 2*chunk] and [0.5, 2] —
    OVERLAP once chunk <= 4 (at chunk=2, ratio 1.5 sits in both, and the
    trip-multiplied branch won by ``if`` ordering, silently dividing a
    count-once flops figure by chunk). Inside the overlap the nearer band
    center in log space decides; outside it the windows are disjoint and
    the behavior is unchanged (identical to the old code for chunk >= 8).
    At chunk == 1 the bands coincide and the tie resolves to ``"trip"`` —
    harmless, since dividing by 1 equals counting once."""
    if ratio <= 0 or chunk < 1:
        return None
    in_trip = 0.5 * chunk <= ratio <= 2.0 * chunk
    in_once = 0.5 <= ratio <= 2.0
    if in_trip and in_once:
        return (
            "trip"
            if abs(math.log(ratio / chunk)) <= abs(math.log(ratio))
            else "once"
        )
    if in_trip:
        return "trip"
    if in_once:
        return "once"
    return None


def _phase_flagship() -> dict:
    """bf16 MXU compute + scanned epoch runner, AOT-compiled so the MFU
    numerator is the cost analysis of the EXACT executable timed."""
    import jax
    import jax.numpy as jnp

    t_phase0 = time.perf_counter()
    scanned, state, chunk_batch, compiled, batch_size, small, compile_stats = (
        _scanned_cifar_setup(jnp.bfloat16)
    )
    flops_chunk = 0.0
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        flops_chunk = float(ca.get("flops", 0.0))
    except Exception:  # cost analysis is best-effort; MFU just goes unreported
        pass
    reps = _reps("BENCH_FLAGSHIP_REPS", "5")
    state, times, first_exec = _timed_dispatches(compiled, state, chunk_batch, reps)
    ranked = sorted(times)
    dt = _median(times)
    out = {
        "preset": "small" if small else "full",
        # the one-time costs, split: AOT trace + XLA compile (what the
        # persistent cache can replay) vs the first executable dispatch
        # (program load / donation setup — never cacheable). The old record
        # lumped all three into an invisible warmup.
        "lower_ms": compile_stats["lower_ms"],
        "compile_ms": compile_stats["compile_ms"],
        "first_execute_ms": round(1000.0 * first_exec, 2),
        "flagship_imgs_per_sec": round(batch_size * CHUNK / dt, 2),
        "step_time_ms": round(1000.0 * dt / CHUNK, 4),
        "flagship_reps": reps,
        # min dispatch time -> max rate and vice versa
        "flagship_imgs_per_sec_max": round(batch_size * CHUNK / ranked[0], 2),
        "flagship_imgs_per_sec_min": round(batch_size * CHUNK / ranked[-1], 2),
        # measurement order, NOT sorted: a monotone drift across reps
        # (warm-up, an abandoned compile draining) must stay visible in the
        # published sequence
        "dispatch_times_ms": [round(1000.0 * t, 2) for t in times],
    }
    # published floor for this preset, emitted even when mfu itself is
    # withheld (failed cross-check) — the target is policy, not
    # measurement, and gate.py needs it either way
    out["mfu_target"] = _mfu_target(out["preset"])
    # flops_chunk ÷ CHUNK is only valid where the compiler's cost analysis
    # multiplies the scan body by its trip count. The TPU toolchain does
    # (measured: chip runs report flops_per_step = 10.39 GF for this
    # program at CHUNK=10 — exactly one step's conv work, so flops_chunk
    # was 10×); XLA:CPU counts the body ONCE regardless of trip count
    # (measured: identical flops at chunk 1/2/8) — hence the cross-check.
    peak = _peak_flops(jax.devices()[0])
    if flops_chunk > 0:
        # advisor r4: don't trust the trip-count-multiplied semantic as a
        # toolchain invariant — cross-check against a chunk-1 lowering of
        # the SAME program each run (compile-only; cached after the first
        # run). Ratio ~CHUNK confirms multiplied semantics; ~1 means the
        # toolchain switched to count-once (then flops_chunk IS one step);
        # anything else withholds MFU rather than publishing a number
        # known to be wrong by up to CHUNK x.
        per_step = None
        # the cross-check costs one extra (cacheable) compile AFTER the
        # timing is already measured — it must never cost the phase its
        # headline number. Bound it by the REAL budget this phase has left
        # (same clock as child_main: static budget minus 45, capped by the
        # global deadline), run the compile in a daemon thread, and on
        # timeout abandon it into _ABANDONED_THREADS (the child drains
        # those before exit) and publish with the historically-validated
        # division instead.
        elapsed = time.perf_counter() - t_phase0
        budget_left = PHASE_BUDGET_S.get("flagship", 330) - 45.0 - elapsed
        deadline_unix = float(os.environ.get("BENCH_DEADLINE_UNIX", "0"))
        if deadline_unix:
            budget_left = min(budget_left, deadline_unix - time.time() - 45.0)
        xcheck_s = min(
            budget_left - 20.0,
            float(os.environ.get("BENCH_CROSSCHECK_SOFT_S", "150")),
        )
        if xcheck_s < 20.0:
            per_step = flops_chunk / CHUNK
            out["flops_method"] = (
                "hlo scan-trip-multiplied (cross-check skipped: "
                f"{int(max(0, budget_left))}s of phase budget left)"
            )
            out["mfu"] = round(per_step / (dt / CHUNK) / peak, 4)
            out["flops_per_step"] = per_step
            return out
        try:
            one_batch = (
                chunk_batch[0][:1],
                chunk_batch[1][:1],
            )
            xbox: dict = {}

            def _xcheck():
                try:
                    ca1 = scanned.fn.lower(state, one_batch).compile()
                    xbox["ca"] = ca1.cost_analysis()
                except BaseException as e:  # noqa: BLE001 — relayed
                    xbox["error"] = e

            xt = threading.Thread(
                target=_xcheck, daemon=True, name="flagship-crosscheck"
            )
            xt.start()
            xt.join(xcheck_s)
            if xt.is_alive():
                _ABANDONED_THREADS["flagship_crosscheck"] = xt
                raise TimeoutError(f"chunk-1 compile exceeded {int(xcheck_s)}s")
            if "error" in xbox:
                raise xbox["error"]
            ca1 = xbox["ca"]
            ca1 = ca1[0] if isinstance(ca1, (list, tuple)) else ca1
            flops_1 = float(ca1.get("flops", 0.0))
            if flops_1 <= 0:
                # the chunk-1 analysis returned no flops — the cross-check
                # is UNAVAILABLE, not a mismatch (same best-effort caveat
                # as the except path below)
                raise ValueError("chunk-1 cost analysis returned no flops")
            ratio = flops_chunk / flops_1
            out["flops_chunk_ratio"] = round(ratio, 2)
            band = _flops_band(ratio, CHUNK)
            if band == "trip":
                per_step = flops_chunk / CHUNK
                out["flops_method"] = "hlo scan-trip-multiplied (chunk-1 cross-checked)"
            elif band == "once":
                per_step = flops_chunk
                out["flops_method"] = "hlo count-once (chunk-1 cross-checked)"
        except Exception as e:  # noqa: BLE001 — cross-check is best-effort;
            # an uncross-checked number keeps the historically-validated
            # division but says so
            per_step = flops_chunk / CHUNK
            out["flops_method"] = (
                "hlo scan-trip-multiplied (cross-check unavailable: "
                f"{type(e).__name__}: {e})"[:160]
            )
        if per_step is not None:
            out["mfu"] = round(per_step / (dt / CHUNK) / peak, 4)
            out["flops_per_step"] = per_step
        else:
            out["mfu_withheld"] = (
                f"flops_chunk/flops_1 ratio {out.get('flops_chunk_ratio')} "
                f"matches neither ~{CHUNK} (trip-multiplied) nor ~1 (count-once)"
            )
    return out


def _phase_baseline() -> dict:
    """The literal-translation arm: fp32, one host dispatch per step."""
    import jax
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.experiments.common import image_classifier_loss
    from network_distributed_pytorch_tpu.parallel import ExactReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import make_train_step
    from network_distributed_pytorch_tpu.utils.timing import wait_result

    small = _small_preset()
    batch_size = 32 if small else 256
    model = _make_model(jnp.float32, small)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=True)
    loss_fn = image_classifier_loss(model, has_batch_stats=True)
    step = make_train_step(
        loss_fn, ExactReducer(), variables["params"], learning_rate=0.001,
        momentum=0.9, algorithm="sgd", mesh=make_mesh(), donate_state=True,
    )
    state = step.init_state(
        variables["params"], model_state={"batch_stats": variables["batch_stats"]}
    )
    batch = _cifar_batch(batch_size)
    t0 = time.perf_counter()
    state, loss = step(state, batch)  # compile + warmup
    wait_result(loss)
    # jit path: trace, compile, and first execute are ONE opaque call —
    # unlike the AOT arms there is no seam to time them apart, so the
    # field says so instead of pretending to be a pure compile time
    first_call_ms = round(1000.0 * (time.perf_counter() - t0), 2)
    # three independent timed passes (round-4 verdict weak #5: vs_baseline
    # rested on a single unreplicated pair; with two passes the median IS
    # an endpoint, so three is the floor at which median and spread are
    # distinct); each pass pays the host round trip every step by design —
    # that is this arm's whole point
    passes = max(1, int(os.environ.get("BENCH_BASELINE_PASSES", "3")))
    rates = []
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(BASELINE_REPS):
            state, loss = step(state, batch)
        wait_result(loss)  # fetch-to-observe-completion, utils.timing
        rates.append(batch_size * BASELINE_REPS / (time.perf_counter() - t0))
    med = _median(rates)
    return {
        "baseline_imgs_per_sec": round(med, 2),
        "baseline_first_call_ms": first_call_ms,
        "baseline_first_call_note": "jit compile + first execute, unsplittable",
        "baseline_step_time_ms": round(1000.0 * batch_size / med, 4),
        # spread endpoints ride the record like the flagship's — the
        # vs_baseline ratio's denominator needs error bars too
        "baseline_imgs_per_sec_min": round(min(rates), 2),
        "baseline_imgs_per_sec_max": round(max(rates), 2),
        "baseline_passes": [round(r, 2) for r in sorted(rates)],
    }


def _phase_fp32arm() -> dict:
    """fp32 + scanned dispatch: the decomposition arm (round-4 verdict weak
    #5). The flagship/baseline pair differs in BOTH dtype (bf16 vs fp32) and
    dispatch structure (one scanned CHUNK-step dispatch vs one host dispatch
    per step); this arm holds the scanned dispatch fixed and swaps only the
    dtype, so  fp32arm/baseline  isolates dispatch amortization and
    flagship/fp32arm  isolates bf16-on-MXU. Identical protocol to the
    flagship by construction (``_scanned_cifar_setup``/``_timed_dispatches``
    are the same code)."""
    import jax.numpy as jnp

    _, state, chunk_batch, compiled, batch_size, small, compile_stats = (
        _scanned_cifar_setup(jnp.float32)
    )
    reps = _reps("BENCH_FP32ARM_REPS", "3")
    state, times, first_exec = _timed_dispatches(compiled, state, chunk_batch, reps)
    ranked = sorted(times)
    dt = _median(times)
    return {
        # same tier-labeling contract as the flagship: a small-preset rate
        # must never be readable as the full ResNet-50/batch-256 number
        "preset": "small" if small else "full",
        # same one-time-cost split as the flagship's
        "fp32_lower_ms": compile_stats["lower_ms"],
        "fp32_compile_ms": compile_stats["compile_ms"],
        "fp32_first_execute_ms": round(1000.0 * first_exec, 2),
        "fp32_scanned_imgs_per_sec": round(batch_size * CHUNK / dt, 2),
        "fp32_scanned_step_time_ms": round(1000.0 * dt / CHUNK, 4),
        "fp32_scanned_reps": reps,
        "fp32_scanned_imgs_per_sec_max": round(batch_size * CHUNK / ranked[0], 2),
        "fp32_scanned_imgs_per_sec_min": round(batch_size * CHUNK / ranked[-1], 2),
        # measurement order — same contract as the flagship's
        # dispatch_times_ms
        "fp32_dispatch_times_ms": [round(1000.0 * t, 2) for t in times],
    }


def _phase_gpt() -> dict:
    """GPT-2-small (124M) training-step throughput + MFU — the compute-dense
    workload where MFU is meaningful (CIFAR's 32×32 convs genuinely bound
    MXU utilization, so the flagship CIFAR MFU reads low by construction).
    Full shape: seq 1024, vocab 50257, bf16 — measured by the
    ``utils.benchmarks`` scaffold (AOT executable, cost analysis of the
    exact program timed, fetch-to-observe timing). The decoder stack runs
    scanned (``GPTConfig.scan_layers``): bit-identical math, ~5.6x smaller
    lowered HLO — the unrolled 124M step never finished compiling inside a
    phase budget in July (>855 s abandoned; 300 s timeout in r3)."""
    import jax

    from network_distributed_pytorch_tpu.utils.benchmarks import time_gpt_train_step

    small = _small_preset()
    gpt = time_gpt_train_step(
        small=small,
        seq_len=64 if small else 1024,
        batch=8,
        vocab=128 if small else 50257,
        scan_layers=True,
        reps=2 if small else 10,
    )
    flops = gpt.get("flops_per_step")
    peak = _peak_flops(jax.devices()[0])
    if flops:
        gpt["mfu"] = round(flops / (gpt["step_time_ms"] / 1000.0) / peak, 4)
    return {"gpt": gpt}


def _phase_overlap() -> dict:
    """Comm/compute schedule evidence for the PowerSGD step, from the
    scheduled v5e executable (SURVEY §5 set 'assert via profile' as the bar
    for replacing the reference's async-handle overlap,
    ``reducer.py:131-168``). Two findings from the post-optimization HLO:
    (a) async ``*-start``/``*-done`` collective windows and the compute
    scheduled inside them
    (``utils.overlap``); (b) what the all-reduce combiner did to the 4
    logical collectives (P, rank-1, Q, loss) — on v5e it MERGES the rank-1
    payload into the Q all-reduce, i.e. the separate collective the
    reference could only *hide* is eliminated outright. Claim discipline
    (VERDICT r3 #6): ``combiner_merged`` is the measured claim;
    ``n_async_collectives`` is reported as observed and has been 0 — we do
    NOT claim collectives overlap compute. Unless already on a ≥2-chip
    mesh, the step is compiled against an 8-chip v5e topology AOT — the
    schedule IS the evidence, no execution needed."""
    import jax
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.experiments.common import image_classifier_loss
    from network_distributed_pytorch_tpu.parallel import PowerSGDReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import make_train_step
    from network_distributed_pytorch_tpu.utils.hlo_audit import (
        collective_summary,
        hlo_text_of_compiled,
    )
    from network_distributed_pytorch_tpu.utils.overlap import overlap_report

    small = _small_preset()
    mesh = make_mesh()
    target_mesh = mesh
    if mesh.size < 2:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x4")
        target_mesh = make_mesh(devices=topo.devices)

    model = _make_model(jnp.bfloat16, small)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=True)
    loss_fn = image_classifier_loss(model, has_batch_stats=True)
    step = make_train_step(
        loss_fn,
        PowerSGDReducer(random_seed=714, compression_rank=4, matricize="last"),
        variables["params"], learning_rate=0.001, momentum=0.9,
        algorithm="ef_momentum", mesh=target_mesh, donate_state=False,
    )
    state_abs = jax.eval_shape(
        lambda p, bs: step.init_state(p, model_state={"batch_stats": bs}),
        variables["params"], variables["batch_stats"],
    )
    batch_abs = (
        jax.ShapeDtypeStruct((8 * target_mesh.size, 32, 32, 3), jnp.float32),
        jax.ShapeDtypeStruct((8 * target_mesh.size,), jnp.int32),
    )
    # ask for ASYNC collectives + the latency-hiding scheduler so any
    # *-start/*-done windows the compiler is willing to open appear in the
    # scheduled HLO; option sets are tried most-specific first, and an
    # executable with no async windows still yields the combiner evidence
    lowered = step.fn.lower(state_abs, batch_abs)
    compiled_exe, last_opt_err = None, None
    for opts in (
        {
            "xla_tpu_enable_latency_hiding_scheduler": "true",
            "xla_tpu_enable_async_collective_fusion": "true",
            "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": "true",
        },
        {"xla_tpu_enable_latency_hiding_scheduler": "true"},
        None,
    ):
        try:
            compiled_exe = (
                lowered.compile(compiler_options=opts) if opts else lowered.compile()
            )
            break
        except Exception as opt_err:  # noqa: BLE001 — try the next set
            last_opt_err = opt_err
    if compiled_exe is None:
        raise last_opt_err

    hlo = hlo_text_of_compiled(compiled_exe)
    rep = overlap_report(hlo)
    aud = collective_summary(hlo)
    summary = {
        "n_async_collectives": rep["n_async_collectives"],
        "n_overlapped": rep["n_overlapped"],
        "compiled_collectives": aud["count"],
        # P, rank-1, Q, loss — reducer.py:126-147 + the loss pmean
        "combiner_merged": aud["count"] < 4,
    }
    return {"overlap": summary}


def _phase_loader() -> dict:
    """Loader-isolation arm: host-side batch assembly throughput with the
    training step taken out of the loop, so a data-plane regression can't
    hide behind (or be blamed on) compute. Three numbers:

    - ``loader_python_samples_per_s``: the literal per-batch numpy
      assemble (gather + u8→f32 normalize), the pre-native hot path.
    - ``loader_samples_per_s``: ``NativeBatchLoader`` on the same dataset,
      order, and batch size — the fused multithreaded C++ pipeline
      (acceptance: ≥ 2× the Python arm where the native lib builds;
      falls back to the Python number, labeled, where it can't).
    - ``data_load_share``: fraction of a short overlapped train loop
      (double-buffered ``device_prefetch`` + a jitted reduction step)
      spent BLOCKED on data — the metric the flagship tier gates below
      5%. Measured here on a synthetic step, so it bounds the loader's
      own overhead, not any one model's arithmetic intensity."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from network_distributed_pytorch_tpu.data import device_prefetch
    from network_distributed_pytorch_tpu.native import NativeBatchLoader
    from network_distributed_pytorch_tpu.native.build import native_available

    small = _small_preset()
    n = 4096 if small else 16384
    batch = 64 if small else 256
    rng = np.random.RandomState(0)
    x = rng.randint(0, 256, size=(n, 32, 32, 3)).astype(np.uint8)
    y = rng.randint(0, 10, size=(n,)).astype(np.int32)
    loader = NativeBatchLoader(x, y, batch, seed=0)
    order = loader._order(0)

    def python_pass() -> int:
        cnt = 0
        for start in range(0, len(order), batch):
            sel = order[start : start + batch]
            _bx = ((x[sel].astype(np.float32) / 255.0) - 0.5) / 0.5
            _by = y[sel]
            cnt += len(sel)
        return cnt

    python_pass()  # warm caches so both arms measure steady state
    t0 = time.perf_counter()
    n_py = python_pass()
    py_rate = n_py / (time.perf_counter() - t0)

    out = {
        "loader_python_samples_per_s": round(py_rate, 1),
        "loader_native": bool(native_available()),
        "loader_dataset_n": n,
        "loader_batch": batch,
    }
    if out["loader_native"]:
        for _ in loader.epoch(0):  # warmup pass (thread spawn, faults)
            pass
        t0 = time.perf_counter()
        cnt = 0
        for bx, _by in loader.epoch(0):
            cnt += len(bx)
        native_rate = cnt / (time.perf_counter() - t0)
        out["loader_samples_per_s"] = round(native_rate, 1)
        out["loader_native_speedup"] = round(native_rate / py_rate, 2)
        out["loader_consumer_wait_s"] = round(
            loader.last_stats["consumer_wait_s"], 4
        )
    else:
        # the numpy tier fed this run (loader_native says so): the gate
        # metric still exists and compares against a same-tier baseline
        out["loader_samples_per_s"] = round(py_rate, 1)

    # the overlapped loop's step must carry REAL arithmetic — against a
    # trivial reduction nothing can hide and every loop reads ~100%
    # data-bound; two dense layers give the prefetcher a flagship-like
    # compute window to stage under
    feat = int(np.prod(x.shape[1:]))
    w1 = jnp.asarray(rng.randn(feat, 512).astype(np.float32) * 0.01)
    w2 = jnp.asarray(rng.randn(512, feat).astype(np.float32) * 0.01)

    @jax.jit
    def step(a, b, w1, w2):
        h = jnp.tanh(a.reshape(a.shape[0], -1) @ w1)
        return jnp.sum((h @ w2) ** 2) + jnp.sum(b)

    it = device_prefetch(loader.epoch(1), depth=2, label="bench_loader")
    wait_s = 0.0
    t_loop = time.perf_counter()
    steps = 0
    while True:
        t1 = time.perf_counter()
        try:
            bx, by = next(it)
        except StopIteration:
            break
        wait_s += time.perf_counter() - t1
        step(bx, by, w1, w2).block_until_ready()
        steps += 1
    total = time.perf_counter() - t_loop
    if steps and total > 0:
        out["data_load_share"] = round(wait_s / total, 4)
        out["data_load_share_target"] = DATA_LOAD_SHARE_TARGET
    return out


def _phase_serving() -> dict:
    """Paged-KV serving arm (PR 19): dense slot cache vs block-pool paged
    cache on the SAME model, workload, and KV device bytes. Three claims,
    each measured here rather than asserted:

    - ``kv_capacity_ratio``: peak concurrently-admitted requests, paged
      over dense, at equal KV HBM (the paged pool is sized to the dense
      cache's bytes plus one permanent garbage block). Requests are much
      shorter than ``max_len``, so the dense engine pins a full
      ``max_len`` row per request while the pool hands out only the
      blocks each request can actually reach — the acceptance bound is
      >= 2x (``KV_CAPACITY_RATIO_TARGET``).
    - ``serving_tokens_per_s_per_chip`` / ``p99_decode_ms_per_token``:
      throughput and tail latency of the PAGED arm — the engine the gate
      protects from here on.
    - ``serving_paged_bitwise_equal``: per-request token streams from the
      paged arm compared bit-for-bit against the dense arm's (the
      guarantee class that makes the capacity win free).

    A speculative arm (self-drafting target, ``spec_k=4``) rides along:
    same bitwise check, plus accept rate and target decode steps — on
    real hardware fewer target dispatches per token is the win; the
    accept accounting is what this tier can verify.
    """
    import jax

    from network_distributed_pytorch_tpu.models.gpt import gpt_tiny
    from network_distributed_pytorch_tpu.serving import (
        WorkloadConfig,
        poisson_workload,
        replay,
        slo_summary,
    )
    from network_distributed_pytorch_tpu.serving.engine import (
        PagedEngine,
        SlotEngine,
    )

    small = _small_preset()
    n_requests = 32 if small else 64
    dense_slots = 4
    max_len, block_len = 64, 8
    # budget <= 16 tokens/request -> <= 2 blocks of 8, against a dense
    # engine pinning all 64 positions per admission: the capacity gap the
    # ratio measures. rate_rps is effectively "all queued at t=0" so both
    # engines run at their admission ceiling, not the arrival rate's.
    workload = WorkloadConfig(
        n_requests=n_requests,
        rate_rps=2000.0,
        prompt_len=(4, 8),
        max_new_tokens=(2, 8),
        vocab=64,
        seed=0,
    )
    model = gpt_tiny(vocab_size=64, max_position_embeddings=max_len)
    params = model.init(
        jax.random.PRNGKey(0), jnp_zeros_tokens(max_len)
    )["params"]

    def arm(make_engine):
        eng = make_engine()
        t0 = time.perf_counter()
        finished = replay(eng, poisson_workload(workload), max_wall_s=120.0)
        wall = time.perf_counter() - t0
        tokens = {r.request_id: list(r.tokens) for r in finished}
        return eng, slo_summary(finished), tokens, wall

    dense, dense_slo, dense_tokens, dense_wall = arm(
        lambda: SlotEngine(
            model.config, params, n_slots=dense_slots, max_len=max_len
        )
    )
    # equal-HBM paged arm: pool = the dense cache's block-equivalents
    # (+ garbage block 0); n_slots raised so the BLOCK POOL is the
    # admission limit being measured, not the table count. Prefix sharing
    # off — random prompts never share, and a pinned index entry would
    # muddy the capacity count.
    n_blocks = dense_slots * (max_len // block_len) + 1
    paged, paged_slo, paged_tokens, paged_wall = arm(
        lambda: PagedEngine(
            model.config, params, n_slots=4 * dense_slots, max_len=max_len,
            block_len=block_len, n_blocks=n_blocks, prefix_sharing=False,
        )
    )
    spec, spec_slo, spec_tokens, spec_wall = arm(
        lambda: PagedEngine(
            model.config, params, n_slots=4 * dense_slots, max_len=max_len,
            block_len=block_len, n_blocks=n_blocks, prefix_sharing=False,
            draft_config=model.config, draft_params=params, spec_k=4,
        )
    )

    n_chips = 1  # single-device engines; the per-chip label is the contract
    ratio = (
        paged.peak_active / dense.peak_active if dense.peak_active else 0.0
    )
    total_tokens = sum(len(t) for t in paged_tokens.values())
    out = {
        "serving_requests": n_requests,
        "serving_dense_slots": dense_slots,
        "serving_block_len": block_len,
        "serving_n_blocks": n_blocks,
        # the equal-HBM attestation: pool bytes over dense cache bytes
        # (slightly > 1.0 — the garbage block is the only extra)
        "serving_hbm_parity": round(paged.pool_bytes / dense.cache_bytes, 4),
        "serving_dense_peak_active": dense.peak_active,
        "serving_paged_peak_active": paged.peak_active,
        "kv_capacity_ratio": round(ratio, 2),
        "kv_capacity_ratio_target": KV_CAPACITY_RATIO_TARGET,
        "serving_paged_bitwise_equal": paged_tokens == dense_tokens,
        "serving_spec_bitwise_equal": spec_tokens == dense_tokens,
        "serving_tokens_per_s_per_chip": round(
            total_tokens / paged_wall / n_chips, 2
        ),
        "serving_dense_tokens_per_s_per_chip": round(
            sum(len(t) for t in dense_tokens.values()) / dense_wall / n_chips,
            2,
        ),
        "p99_decode_ms_per_token": round(
            paged_slo["p99_decode_ms_per_token"], 3
        ),
        "serving_dense_p99_decode_ms_per_token": round(
            dense_slo["p99_decode_ms_per_token"], 3
        ),
        # speculative arm: accept accounting + the dispatch win (target
        # decode steps per generated token, lower is better — CPU wall
        # clock is draft-dominated at this model size, so the STEP ratio
        # is the portable evidence)
        "serving_spec_accept_rate": round(
            spec.stats().get("spec_accept_rate", 0.0), 4
        ),
        "serving_spec_decode_steps": spec.decode_steps,
        "serving_paged_decode_steps": paged.decode_steps,
        "serving_spec_p99_decode_ms_per_token": round(
            spec_slo["p99_decode_ms_per_token"], 3
        ),
        "serving_spec_wall_s": round(spec_wall, 3),
    }
    if not out["serving_paged_bitwise_equal"]:
        raise RuntimeError("paged serving arm diverged bitwise from dense")
    if not out["serving_spec_bitwise_equal"]:
        raise RuntimeError("speculative serving arm diverged bitwise from dense")
    return out


def jnp_zeros_tokens(max_len: int):
    """Tiny helper so _phase_serving's jax import stays phase-local."""
    import jax.numpy as jnp

    return jnp.zeros((1, max_len), jnp.int32)


_PHASE_FNS = {
    "probe": _phase_probe,
    "flagship": _phase_flagship,
    "baseline": _phase_baseline,
    "gpt": _phase_gpt,
    "fp32arm": _phase_fp32arm,
    "overlap": _phase_overlap,
    "loader": _phase_loader,
    "serving": _phase_serving,
}


class _PhaseAbandoned(TimeoutError):
    """A phase blew its child-side deadline; its daemon thread may still be
    draining on the device (relevant to later phases' timing honesty)."""


# threads of abandoned phases, by phase name — the child tries to DRAIN
# these before exiting (daemon threads die with the process, mid-compile or
# mid-execution), and labels every later phase that shared the chip with one
_ABANDONED_THREADS: dict = {}


def _run_with_deadline(name: str, fn, deadline_s: float) -> dict:
    """Run one phase in a daemon thread; on deadline, raise instead of
    letting the parent SIGKILL the child mid-compile.

    A killed child takes the initialised backend with it, and the respawned
    one re-pays process start and backend init before the next phase. A
    child-side timeout instead reports the phase as an error marker and
    keeps the SAME process (and the chip it holds) for the remaining
    phases. The abandoned thread stays alive as a daemon; jax dispatch is
    thread-safe, so the next phase can proceed while it drains.

    The parent's per-event budget remains the backstop for true C-level
    hangs that stall this thread's join return.
    """
    box: dict = {}

    def worker():
        try:
            box["out"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to main thread
            box["error"] = e

    t = threading.Thread(target=worker, daemon=True, name=f"phase-{name}")
    t.start()
    t.join(deadline_s)
    if t.is_alive():
        _ABANDONED_THREADS[name] = t
        raise _PhaseAbandoned(
            f"phase {name} exceeded its child-side deadline of"
            f" {int(deadline_s)}s (abandoned, child continues)"
        )
    if "error" in box:
        e = box["error"]
        raise e if isinstance(e, Exception) else RuntimeError(repr(e))
    return box["out"]


def child_main(phase_list: list) -> int:
    try:
        _init_backend()
    except Exception as e:  # noqa: BLE001 — reported, then exit non-zero
        _child_emit("__init__", False, {"error": f"{type(e).__name__}: {e}"[:400]})
        return 1
    # the parent's ABSOLUTE deadline (unix seconds): the child must finish —
    # or abandon — each phase before the parent's own budget math
    # (min(phase budget, global remaining)) would SIGKILL it mid-compile.
    # Static phase budgets alone are not enough: near the end of the global
    # window the parent's cap is the SMALLER `left() - 15`, so the child's
    # deadline must track the same clock.
    deadline_unix = float(os.environ.get("BENCH_DEADLINE_UNIX", "0")) or None
    failed = False
    for name in phase_list:
        try:
            budget = float(PHASE_BUDGET_S.get(name, 240)) - 45.0
            if deadline_unix is not None:
                budget = min(budget, deadline_unix - time.time() - 30.0)
            # under 30 s of real budget: skip rather than floor. A floor
            # (an earlier revision used max(30, budget)) can push the
            # child's self-deadline PAST the parent's `left() - 15` kill
            # time, re-introducing the SIGKILL mid-compile the
            # self-deadline exists to prevent. Applies to the probe
            # too: it runs unwrapped (near-instant after init), but not
            # when the global window is already spent.
            if budget <= (0 if name == "probe" else 30.0):
                raise TimeoutError(
                    f"phase {name} skipped: under 30s of budget left "
                    "(global deadline near, or a static BENCH_*_BUDGET_S "
                    "under 75s)"
                )
            # an earlier abandoned thread — a whole phase's, or an intra-
            # phase one like the flagship FLOPs cross-check compile — may
            # still be compiling/executing on the device while THIS phase
            # runs: its timed numbers shared the chip with that drain; say
            # so. _ABANDONED_THREADS (filtered to alive at phase START) is
            # the one registry both kinds land in; the liveness filter
            # keeps threads that finished draining before this phase — and
            # a phase's own late-abandoned helper, which never overlapped
            # its timing — off the label.
            live = sorted(
                n for n, t in _ABANDONED_THREADS.items() if t.is_alive()
            )
            if name == "probe":
                data = _PHASE_FNS[name]()
            else:
                # persistent-compilation-cache accounting brackets the
                # phase: zero new entries after its compiles = served from
                # cache ("hit")
                cache = _CacheProbe()
                data = _run_with_deadline(name, _PHASE_FNS[name], budget)
                data["compilation_cache"] = cache.report()
            if live:
                data["concurrent_abandoned"] = live
            _child_emit(name, True, data)
        except Exception as e:  # noqa: BLE001 — a phase crash must not
            # take down the phases behind it; it does fail the run
            failed = True
            _child_emit(name, False, {"error": f"{type(e).__name__}: {e}"[:400]})
    if _ABANDONED_THREADS:
        # drain abandoned compiles before exiting (daemon threads die with
        # the process): spend whatever remains of the global window on the
        # join and report what drained
        grace_until = (
            deadline_unix - 10.0
            if deadline_unix is not None
            else time.time() + float(os.environ.get("BENCH_DRAIN_GRACE_S", "120"))
        )
        drained, still_alive = [], []
        for name, t in _ABANDONED_THREADS.items():
            t.join(max(0.0, grace_until - time.time()))
            (still_alive if t.is_alive() else drained).append(name)
        _child_emit(
            "__drain__", True, {"drained": drained, "still_alive": still_alive}
        )
    return 1 if failed else 0


# ---------------------------------------------------------------------------
# parent: orchestration
# ---------------------------------------------------------------------------


def _artifact_pointers(out: dict) -> None:
    """Compact pointer to the committed accuracy study so the bench line
    names the fuller record. (Old chip records are NOT re-exported onto new
    lines: a line carries what this run measured on the device it names.)"""
    try:
        with open(os.path.join(HERE, "artifacts", "ACCURACY_STUDY.json")) as f:
            st = json.load(f)
    except (OSError, ValueError):  # pointer only
        return
    out["accuracy_study"] = {
        t: {
            "accuracy_delta_pts": st[t].get("accuracy_delta_pts"),
            "gradient_bytes_ratio": st[t].get("gradient_bytes_ratio"),
        }
        for t in ("cifar", "imdb", "imdb_wide")
        if t in st
    }


class _ChildProc:
    """One measurement child with a line-streaming stdout reader."""

    def __init__(self, phases: list):
        import queue

        self.queue = queue.Queue()
        env = dict(os.environ)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phases", ",".join(phases)],
            stdout=subprocess.PIPE, stderr=None, env=env, text=True,
            cwd=HERE,
        )
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            if line.startswith(MARKER):
                try:
                    self.queue.put(json.loads(line[len(MARKER):]))
                except ValueError:
                    pass
        self.queue.put(None)  # EOF

    def next_event(self, timeout_s: float):
        """The next phase result, None on EOF, or raises queue.Empty."""
        return self.queue.get(timeout=max(0.1, timeout_s))

    def kill(self):
        try:
            self.proc.kill()
            self.proc.wait(timeout=10)
        except Exception:  # noqa: BLE001 — already gone
            pass


def _merge(out: dict, phase: str, ok: bool, data: dict, status: dict) -> None:
    if not ok:
        status[phase] = "error: " + str(data.get("error", "?"))[:200]
        return
    status[phase] = "ok"
    if phase == "probe":
        out["device"] = data["device"]
        out["platform"] = data["platform"]
        out["n_devices"] = data["n_devices"]
        out["jaxlib_version"] = data.get("jaxlib_version")
    else:
        out.update(data)
    flag = out.get("flagship_imgs_per_sec")
    base = out.get("baseline_imgs_per_sec")
    if flag:
        out["value"] = flag
    if flag and base:
        out["vs_baseline"] = round(flag / base, 3)


def _await_child_exit(child, out: dict, left) -> None:
    """After every phase has reported, wait (within the global window) for
    the child to drain abandoned compiles and exit by itself, recording its
    ``__drain__`` report if one arrives."""
    import queue

    while True:
        budget = min(left() - 10.0, 300.0)
        if budget <= 0:
            return  # window truly spent — the backstop kill may fire
        try:
            ev = child.next_event(budget)
        except queue.Empty:  # a POLL timeout, not the window: keep waiting
            # until left() runs out (returning here would kill mid-drain
            # with window remaining — the wedge)
            continue
        except Exception:  # noqa: BLE001 — advisor r4: a persistent
            # non-Empty error (broken queue after reader-thread death)
            # means the child is effectively gone; looping on it would
            # burn the whole remaining window before the backstop kill
            return
        if ev is None:  # child exited cleanly
            return
        if ev.get("phase") == "__drain__":
            out["abandoned_drain"] = ev.get("data")
            _emit(out)


# serialized byte budget for the final summary line. The driver reads a
# fixed-size tail of stdout (~2,000 chars); 1,200 leaves headroom for the
# newline plus a partially-truncated previous line sharing the tail.
_SUMMARY_LIMIT = 1200
# headline keys in keep-priority order — when the serialized summary
# overflows _SUMMARY_LIMIT, keys drop from the BOTTOM of this list first
_SUMMARY_PRIORITY = (
    "metric", "value", "unit", "vs_baseline", "device", "platform",
    "n_devices", "jaxlib_version", "preset", "wall_s", "partial",
    "flagship_imgs_per_sec", "flagship_imgs_per_sec_min",
    "flagship_imgs_per_sec_max", "baseline_imgs_per_sec",
    "baseline_imgs_per_sec_min", "baseline_imgs_per_sec_max", "mfu",
    "mfu_target", "fp32_scanned_imgs_per_sec", "tpu_error", "init_retries",
    "orchestrator_error", "flops_chunk_ratio",
)


def _compact_summary(out: dict, status: dict) -> dict:
    """A bounded digest of the cumulative record, emitted as the round's
    VERY LAST stdout line: the driver parses a fixed-size tail, and the
    full record can outgrow it (per-dispatch time lists, artifact pointers,
    400-char error strings) — then the tail's only complete line would be
    truncated garbage. Serialized size is guaranteed <= _SUMMARY_LIMIT:
    every string is clipped, and whole keys drop in reverse priority order
    until the line fits."""

    def _clip(v):
        return v[:120] if isinstance(v, str) else v

    summary = {"summary": True}
    for k in _SUMMARY_PRIORITY:
        if out.get(k) is not None:
            summary[k] = _clip(out[k])
    # per-phase status strings, clipped hard: error statuses carry up to
    # 200 chars each and six phases of those would eat half the budget
    summary["phases"] = {k: _clip(str(v))[:60] for k, v in status.items()}
    gpt = out.get("gpt")
    if isinstance(gpt, dict):
        summary["gpt"] = {
            k: _clip(gpt[k])
            for k in ("model", "seq_len", "mfu", "tokens_per_sec")
            if gpt.get(k) is not None
        }
    while len(json.dumps(summary)) > _SUMMARY_LIMIT and len(summary) > 1:
        summary.pop(next(reversed(summary)))
    return summary


def orchestrate() -> int:
    """Run every phase under its deadline; 0 only when a TPU was found and
    every phase came back ok."""
    t_start = time.time()
    # a statically configured BENCH_*_BUDGET_S below 75 s means the
    # child-side skip rule (budget - 45 <= 30) suppresses that phase on
    # EVERY run — surface the misconfiguration instead of letting it read
    # as a mysterious per-run timeout
    for _name, _b in PHASE_BUDGET_S.items():
        # child-side skip: budget-45 must EXCEED 30, so 75 itself skips
        if _name != "probe" and _b <= 75:
            print(
                f"# bench: WARNING: {_name} budget {_b}s <= 75s implies a "
                "permanent skip (child-side rule: budget-45 must exceed "
                "30s); raise BENCH_" + _name.upper() + "_BUDGET_S",
                file=sys.stderr, flush=True,
            )
    # children self-deadline against the SAME absolute clock the parent
    # kills by, so near the end of the window the child still reports (and
    # survives) before the parent's `left() - 15` cap would SIGKILL it
    # mid-compile (_run_with_deadline)
    os.environ["BENCH_DEADLINE_UNIX"] = str(t_start + TOTAL_DEADLINE_S)

    def left() -> float:
        return TOTAL_DEADLINE_S - (time.time() - t_start)

    out = {
        "metric": "cifar10_resnet50_train_imgs_per_sec",
        "value": 0.0,
        "unit": "imgs/sec",
        "vs_baseline": 0.0,
        "partial": True,
    }
    _artifact_pointers(out)
    _emit(out)  # a valid line exists before the first backend touch

    status = {}
    out["phases"] = status
    pending = list(PHASES)
    init_failures = 0
    crashed = None  # orchestrator-level exception, re-raised AFTER the
    # bounded summary line lands (a phase raising must never leave the
    # round's stdout tail without a valid standalone summary)
    try:
        while pending and left() > 45:
            child = _ChildProc(pending)
            child_events = 0
            gave_up = False  # parent-side timeout: the child is WEDGED — the
            # kill backstop must fire immediately, not after a drain wait
            window_spent = False  # global window ran out with phases pending:
            # the child may be mid-drain; give it the last few seconds
            try:
                while pending:
                    budget = min(
                        PHASE_BUDGET_S.get(pending[0], 240)
                        + (INIT_GRACE_S if child_events == 0 else 0),
                        left() - 15,
                    )
                    if budget <= 0:
                        window_spent = True
                        break
                    try:
                        ev = child.next_event(budget)
                    except Exception:  # queue.Empty — child wedged (compile hang)
                        status[pending[0]] = f"timeout after {int(budget)}s"
                        pending.pop(0)
                        gave_up = True
                        break
                    if ev is None:  # child exited
                        if child_events == 0:
                            # died before ANY marker line — a native crash
                            # inside backend init (segfault/OOM in the PJRT
                            # client emits no Python exception, so the child
                            # can't report __init__ itself): an init failure
                            init_failures += 1
                            if init_failures < 2:
                                out["init_retries"] = (
                                    out.get("init_retries", 0) + 1
                                )
                            out.setdefault(
                                "tpu_error", "child process died during backend init"
                            )
                        elif pending:
                            status.setdefault(pending[0], "child exited early")
                            pending.pop(0)
                        break
                    child_events += 1
                    if ev["phase"] == "__init__":
                        # every init failure gets exactly one more attempt
                        # (a chip another process has not released yet);
                        # the second ends the run — there is no other tier
                        init_failures += 1
                        if init_failures < 2:
                            out["init_retries"] = out.get("init_retries", 0) + 1
                        out["tpu_error"] = str(ev["data"].get("error", "?"))[:300]
                        break
                    if ev["phase"] == "__drain__":
                        # the child's end-of-run report on abandoned-compile
                        # drains — informational, not a measurement phase
                        out["abandoned_drain"] = ev["data"]
                        _emit(out)
                        continue
                    init_failures = 0
                    if ev["phase"] in pending:
                        pending.remove(ev["phase"])
                    _merge(out, ev["phase"], ev["ok"], ev["data"], status)
                    _emit(out)
            finally:
                if (not pending and not gave_up) or window_spent:
                    # normal completion (or window exhaustion with the child
                    # possibly mid-drain): let the child drain + exit on its
                    # own; the kill below is then a no-op or a backstop. On
                    # window exhaustion _await_child_exit self-bounds to the
                    # last ~left()-10 seconds.
                    _await_child_exit(child, out, left)
                child.kill()
            if init_failures >= 2:
                break
    except BaseException as exc:  # noqa: B036 — even SystemExit must
        # not skip the summary emission; re-raised below
        crashed = exc
    if crashed is not None:
        reason = "skipped: orchestrator error"
    elif init_failures >= 2:
        reason = "skipped: no TPU"
    else:
        reason = "skipped: out of budget"
    for p in pending:
        status.setdefault(p, reason)
    out["partial"] = crashed is not None
    if crashed is not None:
        out["orchestrator_error"] = (
            f"{type(crashed).__name__}: {crashed}"[:300]
        )
    out["wall_s"] = round(time.time() - t_start, 1)
    if crashed is None and out.get("platform") == "tpu":
        _run_perf_gate(out, status)
        _record_gate_baseline(out, status)
    _emit(out)
    # the full record above stays the authoritative line; the bounded
    # summary AFTER it is what a fixed-size tail is guaranteed to hold
    # — and it must land even on a crash: round 5's driver record ended
    # in a front-truncated full record and "parsed": null because the
    # exception path skipped this line entirely
    _emit(_compact_summary(out, status))
    if crashed is not None:
        raise crashed
    measured = out.get("platform") == "tpu" and all(
        status.get(p) == "ok" for p in PHASES
    )
    return 0 if measured else 1


def _run_perf_gate(out: dict, status: dict) -> None:
    """Gate the round's freshest run report against the PREVIOUS round's
    recorded baseline, before ``_record_gate_baseline`` overwrites it.

    Only a TPU round gets here, and it runs ``scripts/gate.py
    --strict-device``: a ``device=cpu`` report must FAIL against a chip
    baseline instead of silently satisfying it — cross-hardware ratios are
    not regressions, they are provenance errors. The verdict rides the
    published record (``gate`` in phases)."""
    report_path = os.path.join(HERE, "artifacts", "run_report.json")
    baseline_path = os.path.join(HERE, "artifacts", "GATE_BASELINE.json")
    if not (os.path.exists(report_path) and os.path.exists(baseline_path)):
        status["gate"] = "skipped: no report/baseline pair"
        return
    argv = [
        sys.executable, os.path.join(HERE, "scripts", "gate.py"),
        "--report", report_path, "--root", HERE, "--strict-device",
    ]
    try:
        rc = subprocess.run(argv, timeout=120).returncode
    except (OSError, subprocess.TimeoutExpired) as exc:
        status["gate"] = f"error: {type(exc).__name__}"[:60]
        return
    status["gate"] = "ok" if rc == 0 else f"regressed (exit {rc})"


def _record_gate_baseline(out: dict, status: dict) -> None:
    """Record the round's headline throughput as the perf-gate baseline
    (artifacts/GATE_BASELINE.json, read by scripts/gate.py). Any TPU round
    with an ok flagship qualifies."""
    if status.get("flagship") != "ok" or not out.get("flagship_imgs_per_sec"):
        return
    rec = {
        "schema": 1,
        "source": "bench.py",
        "recorded_unix": int(time.time()),
        # runtime attestation, so gate.py's device-provenance guard (and a
        # human reading the baseline) knows exactly what produced these
        # numbers: a CPU report gating against this on a chip baseline is
        # flagged, not silently compared
        "platform": out.get("platform"),
        "jaxlib_version": out.get("jaxlib_version"),
        "n_devices": out.get("n_devices"),
        "init_retries": int(out.get("init_retries", 0) or 0),
        "preset": out.get("preset"),
        "flagship_imgs_per_sec": out.get("flagship_imgs_per_sec"),
        "value": out.get("value"),
        "vs_baseline": out.get("vs_baseline"),
        "phases": {k: str(v)[:60] for k, v in status.items()},
    }
    # flagship MFU (when the round derived one) rides along so gate.py can
    # compare a run report's mfu_headline like-for-like (ROADMAP item 2:
    # gate on MFU, not just imgs/sec)
    mfu = out.get("mfu")
    if isinstance(mfu, (int, float)) and mfu > 0:
        rec["mfu"] = float(mfu)
    # the tier's published MFU floor rides along unconditionally: gate.py
    # uses it as an ABSOLUTE target for the mfu metric (drift alone can
    # ratchet a slow regression past a relative-only gate)
    mfu_target = out.get("mfu_target")
    if isinstance(mfu_target, (int, float)) and mfu_target > 0:
        rec["mfu_target"] = float(mfu_target)
    # live-plane alert count from the newest probe run report (when one
    # exists): rides along so gate.py's lower-is-better alerts_fired
    # metric has a recorded reference. Zero is the healthy value and is
    # recorded as such — a later round that starts firing MORE alerts than
    # this baseline regresses the health envelope
    try:
        with open(os.path.join(HERE, "artifacts", "run_report.json")) as f:
            doc = json.load(f)
        fired = (doc.get("alerts") or {}).get("fired")
        if isinstance(fired, (int, float)) and fired >= 0:
            rec["alerts_fired"] = float(fired)
        # cross-rank critical-path comm share (observe.critpath) rides
        # along from the same report: zero (compute-bound path) is the
        # healthy value and records as such, so a later round whose steps
        # start gating on collective-wait regresses against it
        share = (doc.get("critpath") or {}).get("comm_share")
        if isinstance(share, (int, float)) and share >= 0:
            rec["critpath_comm_share"] = float(share)
        # peak device memory from the memory observatory: measured when
        # the sampler ran, else the compile-time predicted peak
        # (memory_summary picks and labels the source). Lower-is-better
        # in gate.py — a model/step change that doubles the footprint
        # regresses against this baseline before it OOMs in production
        hbm = (doc.get("memory") or {}).get("hbm_peak_bytes")
        if isinstance(hbm, (int, float)) and hbm > 0:
            rec["hbm_peak_bytes"] = float(hbm)
        # gradient-fidelity scalar (observe.fidelity via report.py): the
        # worst shape-group's mean relative compression error. Zero
        # (exact reducers) is the healthy value and records as such, so
        # a later round whose compressed wire quietly degrades what it
        # delivers regresses against this reference
        fid = (doc.get("fidelity") or {}).get("rel_error")
        if isinstance(fid, (int, float)) and fid >= 0:
            rec["fidelity_rel_error"] = float(fid)
    except (OSError, ValueError):
        pass
    # loader-isolation arm (PR 12): native assembly samples/s is a
    # higher-is-better gate metric, data_load_share a lower-is-better one
    # with an absolute ceiling (DATA_LOAD_SHARE_TARGET), mirroring the
    # mfu/mfu_target pair. Only recorded when the loader phase ran ok —
    # a skipped phase must not erase the previous baseline's fields.
    if str(status.get("loader", "")).startswith("ok"):
        for key in ("loader_samples_per_s", "data_load_share"):
            v = out.get(key)
            if isinstance(v, (int, float)) and v >= 0:
                rec[key] = float(v)
        if "data_load_share" in rec:
            rec["data_load_share_target"] = DATA_LOAD_SHARE_TARGET
    # paged-serving arm (PR 19): throughput and tail latency of the paged
    # engine are relative gate metrics; the capacity ratio also carries its
    # absolute >= 2x floor, same contract as data_load_share's ceiling.
    # Phase-gated like the loader's so a skipped arm keeps the previous
    # baseline's serving fields alive.
    if str(status.get("serving", "")).startswith("ok"):
        for key in (
            "serving_tokens_per_s_per_chip",
            "p99_decode_ms_per_token",
            "kv_capacity_ratio",
        ):
            v = out.get(key)
            if isinstance(v, (int, float)) and v > 0:
                rec[key] = float(v)
        if "kv_capacity_ratio" in rec:
            rec["kv_capacity_ratio_target"] = KV_CAPACITY_RATIO_TARGET
    # disaster-recovery MTTR from the newest game-day report (run_probe
    # phase 5 — the plain probe report has no replans): rides along so
    # gate.py's lower-is-better recovery_time_s metric has a recorded
    # reference for the quorum-replan game day
    try:
        with open(os.path.join(HERE, "artifacts", "gameday_report.json")) as f:
            mttr = json.load(f).get("recovery_time_s")
        if isinstance(mttr, (int, float)) and mttr > 0:
            rec["recovery_time_s"] = float(mttr)
    except (OSError, ValueError):
        pass
    # fleet goodput from the newest multi-job game day (run_probe
    # phase 10): higher-is-better weighted work per chip-second, so a
    # later round whose scheduler burns more chips for the same work —
    # or strands jobs unfinished — regresses against this reference
    try:
        with open(os.path.join(HERE, "artifacts", "fleet_report.json")) as f:
            goodput = json.load(f).get("fleet_goodput")
        if isinstance(goodput, (int, float)) and goodput > 0:
            rec["fleet_goodput"] = float(goodput)
    except (OSError, ValueError):
        pass
    # cost-model observatory (run_probe phase 7): the planner replay
    # reports carry predicted-vs-realized step time; record the WORST
    # fabric's error (the bound the model must hold everywhere) plus the
    # matching ms pair, so gate.py's lower-is-better costmodel_error and
    # its absolute 25% ceiling both have a recorded reference
    worst = None
    for name in sorted(glob.glob(
        os.path.join(HERE, "artifacts", "plan_replay_*_report.json")
    )):
        try:
            with open(name) as f:
                cm = json.load(f).get("costmodel") or {}
        except (OSError, ValueError):
            continue
        err = cm.get("error")
        if isinstance(err, (int, float)) and err >= 0 and (
            worst is None or err > worst.get("error", -1.0)
        ):
            worst = cm
    if worst is not None:
        rec["costmodel_error"] = float(worst["error"])
        rec["costmodel_error_target"] = COSTMODEL_ERROR_TARGET
        for src, dst in (
            ("predicted_step_s", "predicted_step_ms"),
            ("realized_step_s", "realized_step_ms"),
        ):
            v = worst.get(src)
            if isinstance(v, (int, float)) and v > 0:
                rec[dst] = float(v) * 1e3
    path = os.path.join(HERE, "artifacts", "GATE_BASELINE.json")
    try:
        os.makedirs(os.path.join(HERE, "artifacts"), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f, indent=1)
        os.replace(tmp, path)
    except OSError:  # best-effort: the line already printed
        pass


def main() -> int:
    if "--phases" in sys.argv:
        phases = sys.argv[sys.argv.index("--phases") + 1].split(",")
        return child_main([p for p in phases if p])
    return orchestrate()


if __name__ == "__main__":
    sys.exit(main())
