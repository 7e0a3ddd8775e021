"""Everything is found by name: every file ``BENCHMARK.json`` names loads and
resolves, and a cell, a configuration, a builder and a per-layer metric added
as new files plus one ``BENCHMARK.json`` entry each are found and run, with
no file of the benchmark edited."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import cells

ROOT = cells.CHECKOUT
NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


def test_manifest_meets_the_contract():
    import re

    bench = cells.manifest()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"] and isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    for entry in bench["configs"] + bench["workloads"] + bench["end_to_end"] + bench["per_layer"]:
        assert re.match(NAME, entry["name"]), entry["name"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])


@pytest.mark.parametrize("name", [w["name"] for w in cells.manifest()["workloads"]])
def test_every_cell_resolves_by_name(name):
    cell = cells.cell(name)
    builder = cell["config"]["builder"]
    for kind in ("builders", "flops", "reference"):
        assert cells.module(kind, builder)
    assert cell["config"]["reduced"] == cells.manifest()["configs"][
        [c["name"] for c in cells.manifest()["configs"]].index(cell["entry"]["config"])
    ]["reduced"]
    assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
    assert len(cell["end_to_end"]) >= 2 and len(cell["per_layer"]) >= 1
    for kind in ("end_to_end", "per_layer"):
        for metric in cell[kind]:
            assert callable(cells.module(cells.READERS[kind], metric["name"]).read)
    flops = cells.module("flops", builder).flops_per_sample(cell["config"])
    assert flops > 1e9
    # a per-layer metric is reported only where the metric it moves is
    assert {m["moves"] for m in cell["per_layer"]} <= {m["name"] for m in cell["end_to_end"]}


def test_a_metric_stays_out_of_a_cell_it_does_not_list(tmp_path):
    """A cell left out of an end-to-end metric's ``workloads`` reports neither
    it nor the per-layer metrics that move it, whatever those list."""
    bench = cells.manifest()
    cell = bench["workloads"][0]["name"]
    others = [w["name"] for w in bench["workloads"][1:]]
    for m in bench["end_to_end"]:
        if m["name"] == "step_ms":
            m["workloads"] = others
    for m in bench["per_layer"]:
        if m["moves"] == "step_ms":
            m.pop("workloads", None)  # lists every cell
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(os.path.join(ROOT, "benchmark", "workloads"), tmp_path / "benchmark" / "workloads")
    shutil.copytree(os.path.join(ROOT, "benchmark", "configs"), tmp_path / "benchmark" / "configs")
    got = cells.cell(cell, root=str(tmp_path))
    assert "step_ms" not in {m["name"] for m in got["end_to_end"]}
    assert got["per_layer"] and "step_ms" not in {m["moves"] for m in got["per_layer"]}
    assert "step_ms" in {m["moves"] for m in cells.cell(others[0], root=str(tmp_path))["per_layer"]}


def test_an_unknown_device_kind_has_no_peak():
    assert cells.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        cells.peaks("TPU v9 imaginary")


def test_a_cell_added_as_files_only_is_found_and_runs(tmp_path):
    """Copy the benchmark, ADD a workload, a configuration, a builder (with
    its flops and reference) and a per-layer metric, each a new file, plus one
    entry each in BENCHMARK.json, and rehearse the new cell."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".xla_cache", "fixtures"))
    bench = cells.manifest()
    b = root / "benchmark"
    config = cells.load_json(os.path.join(ROOT, "benchmark", "configs", "distilbert-base-imdb.json"))
    config.update(name="added-config", builder="added_builder")
    (b / "configs" / "added-config.json").write_text(json.dumps(config))
    workload = cells.load_json(os.path.join(ROOT, "benchmark", "workloads", "imdb_psgd16_b16.json"))
    workload.update(config="added-config")
    (b / "workloads" / "added_cell.json").write_text(json.dumps(workload))
    for kind in ("builders", "flops", "reference"):
        (b / kind / "added_builder.py").write_text(f"from .distilbert_imdb import *  # noqa: F401,F403\n")
    (b / "layer_metrics" / "added_steps.py").write_text(
        "COUNT = True\n\n\ndef read(run):\n    return float(len(run.steps))\n"
    )
    bench["configs"].append({"name": "added-config", "source": "test", "file": "benchmark/configs/added-config.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "added_cell", "config": "added-config", "traffic": "added",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "added_steps", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "host loop", "moves": "samples_per_s",
                               "workloads": ["added_cell"]})
    # the one edit to an entry that is there: a metric that lists its cells gains the new cell's name
    for metric in bench["end_to_end"]:
        if "workloads" in metric:
            metric["workloads"].append("added_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(root), ROOT]), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "added_cell", "--seed", "3",
         "--seconds", "0.5", "--trace", "1", "--rehearsal"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"]["added_steps"]["value"] == last["attempted"] > 0
    assert set(last["metrics"]) == {"added_steps"}  # no device metric from a CPU run
