#!/bin/sh
# Test runner: force the CPU backend with 8 virtual devices
# (JAX_PLATFORMS=cpu, --xla_force_host_platform_device_count=8, nothing
# else). The chip is reached through `python chip_smoke.py`, not from here.
#
# The collective-rendezvous deadlines (XLA:CPU default 20 s/40 s — low
# enough that a heavy multi-device program's SERIALIZED per-device computes
# on a 1-core host abort the whole pytest process, observed at
# test_exact_cifar10_fsdp_strategy) are raised by tests/conftest.py via
# hostenv.force_cpu_devices(collective_timeout_s=120), which strips and
# re-appends those flags before jax init — setting them here would be dead
# configuration.
set -e
cd "$(dirname "$0")"

# observability lint: no bare print() outside the observe stdout sink —
# every human banner must flow through telemetry so the console and the
# structured JSONL log cannot drift apart. The same script enforces the
# observe/ clock discipline (time.monotonic() for durations), covering
# observe/fidelity.py with no carve-outs: fidelity stats are keyed by
# step index and joined to the wire ledger by tag, never by timestamp.
python scripts/lint_no_print.py

# donation lint: every hot jax.jit in experiments//parallel//serving/ must
# donate its carry or carry a justified '# lint: no-donate' opt-out — an
# un-donated train step doubles peak params+optimizer memory
python scripts/lint_donation.py

# jax-free lint: the fleet control plane (scheduler, supervisor, serving
# frontend, live health plane) must import and run without jax — a wedged
# PJRT client must never be able to stall the process that kills and
# reschedules workers. Runs before any jax import so the transitive
# (import-time) check is meaningful.
python scripts/lint_jax_free.py

mkdir -p artifacts

# tests/ includes the resilience chaos suite (tests/test_chaos.py,
# tests/test_supervisor.py): the fault-primitive and supervisor-mechanics
# tests run in the fast tier (-m "not slow" compatible); the full chaos
# matrix on a real training loop and the SIGKILL-and-resume determinism
# test are @slow like the other end-to-end drives.
set +e
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest tests/ --junitxml=artifacts/junit.xml "$@"
rc=$?
set -e

# Observability probe + perf gate: record a tiny supervised run so every
# CI pass leaves a fresh artifacts/run_report.json (with per-phase MFU +
# roofline) and artifacts/toy_trace.json (Perfetto timeline, checked
# well-formed with spans from every rank), then run the gate advisory
# against the recorded baseline (bench.py's artifacts/GATE_BASELINE.json
# or the newest BENCH_r*.json) — all inside run_probe. The probe's fifth
# phase is the disaster game day: a correlated zone outage mid-epoch that
# the supervisor must survive by replanning the mesh, with the measured
# MTTR gated as recovery_time_s. The sixth phase is the data plane: the
# loader-throughput smoke with the native pipeline forced off, plus a
# chaos loader_slow_shard that must surface as a straggler verdict in
# the merged report. The seventh phase is the what-if planner: simulated-
# fabric toy runs calibrate scripts/plan.py's offline cost model, the
# predicted-best config must beat the measured default when replayed, and
# the gate reads the model's own costmodel_error against its 25% ceiling.
# The ninth phase is the memory game day: a headroom precursor alert must
# fire before a chaos oom, the rank's post-mortem must name the top
# buffer class in artifacts/oom_report.json, and a doubled-footprint
# rerun must trip the hbm_peak_bytes gate.
# The twelfth phase is the serving storm game day: a 10x Poisson burst
# against one paged toy worker must push the live p99 past the SLO, the
# telemetry-driven autoscaler must scale the pool up (typed autoscale
# events, chips leased from the fleet scheduler), the post-scale trickle
# must land back inside the SLO, every request must finish (zero lost),
# and the drained pool must scale back down with every lease returned.
# The thirteenth phase is the gradient-fidelity game day: a chaos
# fidelity_degrade latches a x1000 compression error onto ONE wire-ledger
# bucket, which must be blamed at the exact shape-group by live alert,
# report fidelity table, and an alert-triggered controller ascend
# independently (the fidelity page landing before any loss plateau); the
# rung switch splits artifacts/fidelity_frontier.json into >= 2
# accuracy-per-byte segments, and the advisory gate at the end reads the
# new fidelity_rel_error metric off the recorded report.
# Advisory because shared CI boxes have
# noisy step times; run gate.py without --advisory on dedicated perf
# hardware to make it blocking.
python scripts/run_probe.py || true

exit $rc
