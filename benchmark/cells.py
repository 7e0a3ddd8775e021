"""Finding a cell's files by the names in ``BENCHMARK.json``.

Nothing here lists a cell, a configuration, a builder or a metric: a later
PR adds files and one entry to ``BENCHMARK.json``, and edits no file.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
# where the readers of each list of metrics in BENCHMARK.json live
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
CHECKOUT = os.path.dirname(HERE)


def load_json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def manifest(root: str = CHECKOUT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = CHECKOUT) -> Dict:
    """The cell ``name``: its ``BENCHMARK.json`` entry, its workload file,
    its configuration file, and the metrics it reports."""
    bench = manifest(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"benchmark: no cell named {name!r} in BENCHMARK.json ({known})")
    entry = entries[0]
    config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    workload = load_json(os.path.join(root, bench["paths"][0], "workloads", f"{name}.json"))
    config = load_json(os.path.join(root, config_entry["file"]))
    for key in ("config", "chips"):
        if workload[key] != entry[key]:
            raise SystemExit(
                f"benchmark: {name}: workload file says {key}={workload[key]!r},"
                f" BENCHMARK.json says {entry[key]!r}"
            )

    def mine(metric: Dict) -> bool:
        return name in metric.get("workloads", [name])

    end_to_end = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in end_to_end}
    return {
        "name": name,
        "entry": entry,
        "workload": workload,
        "config": config,
        "end_to_end": end_to_end,
        # a per-layer metric is reported only where the metric it moves is
        "per_layer": [m for m in bench["per_layer"] if mine(m) and m["moves"] in reported],
    }


def module(kind: str, name: str):
    """``benchmark.<kind>.<name>``: a builder, a flops function, a plain
    reference or a per-layer metric reader, found by name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(HERE, "peaks.json"))["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: device kind {device_kind!r} is not in benchmark/peaks.json"
            f" ({', '.join(table)}): no peak, no run"
        )
    return table[device_kind]

