"""The harness end to end: refusal without a TPU, and the rehearsal (tiny
presets on the CPU) on one and on four virtual devices."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells

ROOT = cells.CHECKOUT
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_METRICS = {
    "samples_per_s", "step_ms", "peak_hbm_gb", "setup_s", "compile_s", "data_wait_pct",
    "dispatch_ms", "grads_ms", "mfu_pct", "reduce_ms", "collective_exposed_ms",
    "flash_fwd_roofline", "orthogonalize_ms", "device_idle_pct", "step_temp_gb",
}


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900,
    )


def test_refuses_to_run_without_a_tpu():
    done = run("--workload", "imdb_psgd16_b16", "--seed", "0", "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())  # no result line


def test_refuses_an_unknown_cell():
    done = run("--workload", "no_such_cell", "--seed", "0", "--seconds", "1", "--trace", "0", "--rehearsal")
    assert done.returncode != 0 and "no cell named" in done.stderr


@pytest.mark.parametrize("cell,trace", [
    ("imdb_psgd16_b16", "0"), ("imdb_psgd16_b16_x4", "1"), ("cifar_psgd4_b128", "0"),
])
def test_rehearsal_walks_the_whole_path(cell, trace):
    done = run("--workload", cell, "--seed", "5", "--seconds", "1", "--trace", trace, "--rehearsal")
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(last) == CONTRACT_KEYS  # no breakdown either: nothing was traced
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    chips = cells.cell(cell)["entry"]["chips"]
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": chips, "memory_peak_bytes": 0}
    # counts only: no time, rate, utilisation or idle share under a device metric's name
    assert not set(last["metrics"]) & DEVICE_METRICS
    if trace == "0":
        assert last["metrics"]["wire_bytes_per_step"]["value"] > 0
    detail = cells.load_json(os.path.join(
        ROOT, "chiprun_out", "benchmark", cell, f"seed5_trace{trace}_rehearsal.json"))
    assert all(detail["checks"].values()), detail["checks"]
    assert not any(k in detail for k in ("setup_s", "step_times_s", "step_p95_ms", "span_totals_s"))
    if chips > 1:
        assert detail["wire_audit"]["exact"] and detail["wire_audit"]["hlo_collective_count"] >= 2
