#!/usr/bin/env python
"""What-if planner: price untried comm configs from one run's artifacts.

Front end of :mod:`observe.costmodel`. Calibrates the analytic cost model
from a machine-readable run report (``scripts/report.py --run-dir`` /
``artifacts/run_report.json``, or directly from a ``--run-dir``), searches
the comm-config space (fallback-ladder rungs plus rank/bucket variants)
across the requested fabrics, and writes:

- ``--out`` (default ``artifacts/plan.json``): the tuned per-fabric plan —
  ranked predictions, per-fabric best pick, and the rung-name ladder
  ordering. ``launch.py --plan`` applies the best pick's knobs directly;
  ``resilience.controller.ladder_from_plan`` reorders the fallback ladder
  from the same file.
- ``--events-out`` (default ``artifacts/predictions.jsonl``): every
  prediction as a typed ``PredictionEvent`` record — the calibration
  observatory's write side. When a predicted config is later executed,
  ``scripts/report.py --plan`` joins predicted-vs-realized and
  ``scripts/gate.py`` gates the model's own ``costmodel_error``.

stdlib + observe only — jax-free, runs on a laptop against copied
artifacts.

Usage::

    python scripts/plan.py --report artifacts/run_report.json
    python scripts/plan.py --run-dir runs/r7 --fabrics 1GbE,100GbE
    python scripts/plan.py --report r.json --source-fabric ICI(v5e) --top 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _costmodel():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from network_distributed_pytorch_tpu.observe import costmodel

    return costmodel


def _say(msg: str) -> None:
    sys.stderr.write(f"# plan: {msg}\n")


def _load_report(args) -> dict:
    if args.run_dir:
        # build the report in-process off the run dir (same loaders the
        # report CLI uses), without clobbering any existing run_report.json
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import report as report_mod

        _, report = report_mod.run_report(args.run_dir)
        return report
    with open(args.report) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{args.report} is not a report dict")
    return doc


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--report",
        default=os.path.join("artifacts", "run_report.json"),
        help="machine-readable run report to calibrate from",
    )
    parser.add_argument(
        "--run-dir", default=None,
        help="calibrate straight from a run directory instead of --report",
    )
    parser.add_argument(
        "--out", default=os.path.join("artifacts", "plan.json"),
        help="tuned per-fabric plan file (launch.py --plan consumes it)",
    )
    parser.add_argument(
        "--events-out",
        default=os.path.join("artifacts", "predictions.jsonl"),
        help="PredictionEvent JSONL (one record per plan entry)",
    )
    parser.add_argument(
        "--fabrics", default=None,
        help="comma-separated FABRICS_BYTES_PER_S keys (default: all)",
    )
    parser.add_argument(
        "--source-fabric", default=None,
        help="fabric the measured run executed on — subtracts its modeled"
             " exposed comm from the compute calibration (needed when the"
             " step/compute span encloses the collectives)",
    )
    parser.add_argument(
        "--fabric-matrix", default=None,
        help="measured per-edge fabric matrix (scripts/report.py writes"
             " artifacts/fabric_matrix.json) — prices every ring term"
             " against the slowest measured edge instead of the named"
             " fabric's scalar",
    )
    parser.add_argument(
        "--hierarchical", action="store_true",
        help="extend the search with the two-level hierarchical grid"
             " (sync period H x outer rank x sync/async) — the geo"
             " placement question priced against the matrix's slow edge",
    )
    parser.add_argument(
        "--sites", type=int, default=0,
        help="site count for the hierarchical grid (0 = model default)",
    )
    parser.add_argument(
        "--top", type=int, default=3,
        help="per-fabric predictions to summarize on stderr (default 3)",
    )
    args = parser.parse_args(argv)

    costmodel = _costmodel()
    try:
        report = _load_report(args)
    except (OSError, ValueError) as e:
        _say(f"no usable report ({e}); nothing to plan")
        return 1
    try:
        calib = costmodel.calibrate(report, source_fabric=args.source_fabric)
    except ValueError as e:
        _say(f"calibration failed: {e}")
        return 1

    fabrics = (
        [f.strip() for f in args.fabrics.split(",") if f.strip()]
        if args.fabrics else None
    )
    matrix = None
    if args.fabric_matrix:
        from network_distributed_pytorch_tpu.observe import fabric as fabric_mod

        matrix = fabric_mod.load_matrix(args.fabric_matrix)
        if matrix is None:
            _say(f"no usable fabric matrix at {args.fabric_matrix};"
                 " falling back to scalar fabric tables")
        else:
            bn = matrix.get("bottleneck") or {}
            _say(
                f"per-edge matrix: {len(matrix.get('edges', []))} edge(s),"
                f" bottleneck {bn.get('src')}->{bn.get('dst')}"
            )
    configs = None
    if args.hierarchical:
        configs = costmodel.default_configs(calib) + costmodel.hierarchical_configs(
            calib, sites=args.sites
        )
        _say(f"hierarchical grid: +{len(configs) - len(costmodel.default_configs(calib))}"
             " two-level config(s)")
    plan = costmodel.build_plan(
        calib, fabrics=fabrics, configs=configs, matrix=matrix
    )

    for path in (args.out, args.events_out):
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(plan, f, indent=1)
    events = costmodel.prediction_events(plan)
    with open(args.events_out, "w") as f:
        for ev in events:
            f.write(json.dumps(ev.record(), default=str) + "\n")

    _say(
        f"calibrated from {calib.source_run or args.report}: step "
        f"{calib.step_time_s * 1e3:.2f} ms (compute {calib.compute_s * 1e3:.2f}"
        f" ms), {calib.dense_bytes:.0f} dense B/step, W={calib.n_workers},"
        f" exposed {calib.exposed_fraction:.2f}"
    )
    for fabric, slot in plan["fabrics"].items():
        ranked = slot["ranked"][: max(1, args.top)]
        picks = "; ".join(
            f"{p['config']['name'] or p['config_key']}"
            f" {p['predicted_step_s'] * 1e3:.2f} ms"
            for p in ranked
        )
        _say(f"{fabric}: {picks}")
    _say(f"wrote {args.out} and {len(events)} prediction(s) -> {args.events_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
