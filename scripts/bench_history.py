#!/usr/bin/env python
"""Consolidate the driver's ``BENCH_r*.json`` round records into one
trend document, ``artifacts/bench_history.json``.

Each round record is a driver artifact: ``{"n": round, "cmd", "rc",
"tail": <the round's final stdout, JSONL>}`` whose tail ends in the
compact bench summary line. This script re-parses every round with the
SAME extraction the perf gate uses (``scripts/gate.py
extract_metrics``), so the history and the gate can never disagree about
what a round scored, and emits:

- per-round rows: round number, source file, exit code, device
  provenance (platform / jaxlib / device count — the attestation
  ``gate.py``'s ``device_mismatch`` guard reads), and every comparable
  gate metric the round recorded;
- per-metric trend lines: the (round, value) series plus an EWMA over
  all but the newest value, and a drift warning when the newest value
  sits beyond ``--drift-tolerance`` (relative) on the WRONG side of that
  EWMA for its gate direction — the slow ratchet a single
  round-over-round comparison cannot see;
- a ``warnings`` list, also echoed to stderr, covering metric drift and
  provenance breaks (a round whose platform differs from the previous
  round's — the cross-hardware jumps that make raw trend lines lie).

stdlib-only and jax-free, like every script here. Machine output goes to
stdout (one JSON summary line); human commentary goes to stderr — this
script is NOT in the no-print lint's allowlist and must stay that way.

Usage::

    python scripts/bench_history.py [--root DIR] [--out FILE] \
        [--drift-tolerance 0.15]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

if HERE not in sys.path:
    sys.path.insert(0, HERE)

import gate  # noqa: E402  (the shared metric extraction / directions)

#: EWMA smoothing for the trend baseline: ~last 5 rounds dominate.
EWMA_ALPHA = 0.3

#: Minimum points before a drift verdict means anything: the EWMA needs a
#: history to deviate FROM.
MIN_TREND_POINTS = 3


def _say(msg: str) -> None:
    sys.stderr.write(f"# bench-history: {msg}\n")


def _round_number(path: str) -> Optional[int]:
    m = re.search(r"BENCH_r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else None


def _platform_of(doc: Dict) -> Optional[str]:
    """Device provenance of a round's summary: gate.py's resolution order
    and nothing else — a record that does not name the device it ran on has
    none (an older chip record stapled to the line is not provenance)."""
    return gate._platform_of(doc)


def load_round(path: str) -> Optional[Dict]:
    """One BENCH_r*.json -> a history row, or None when the record is
    unreadable. A round that crashed before emitting a summary still
    rows (rc + empty metrics) — a vanished round is itself a trend."""
    try:
        with open(path) as f:
            raw = f.read()
    except OSError:
        return None
    rec = None
    try:
        rec = json.loads(raw)
    except ValueError:
        pass
    doc: Optional[Dict] = None
    rc = None
    if isinstance(rec, dict):
        rc = rec.get("rc")
        parsed = rec.get("parsed")
        if isinstance(parsed, dict) and gate.extract_metrics(parsed):
            doc = parsed
        elif isinstance(rec.get("tail"), str):
            doc = gate._summary_from_lines(rec["tail"].splitlines())
        elif gate.extract_metrics(rec):
            doc = rec
    else:  # plain JSONL history
        doc = gate._summary_from_lines(raw.splitlines())
    doc = doc or {}
    row = {
        "round": _round_number(path),
        "file": os.path.basename(path),
        "rc": rc,
        "platform": _platform_of(doc),
        "jaxlib_version": doc.get("jaxlib_version"),
        "n_devices": doc.get("n_devices"),
        "preset": doc.get("preset"),
        "metrics": gate.extract_metrics(doc),
    }
    return row


def ewma(values: List[float], alpha: float = EWMA_ALPHA) -> float:
    acc = values[0]
    for v in values[1:]:
        acc = alpha * v + (1.0 - alpha) * acc
    return acc


def trend_lines(
    rows: List[Dict], drift_tolerance: float
) -> Tuple[Dict[str, Dict], List[str]]:
    """Per-metric (round, value) series + EWMA drift verdicts."""
    series: Dict[str, List[Tuple[Optional[int], float]]] = {}
    for row in rows:
        for name, v in row["metrics"].items():
            series.setdefault(name, []).append((row["round"], v))
    trends: Dict[str, Dict] = {}
    warnings: List[str] = []
    for name in sorted(series):
        pts = series[name]
        values = [v for _, v in pts]
        direction = gate.METRICS.get(name, "lower")
        trend = {
            "direction": direction,
            "points": [{"round": r, "value": v} for r, v in pts],
            "latest": values[-1],
            "ewma": ewma(values[:-1]) if len(values) > 1 else values[-1],
            "drift_warning": False,
        }
        if len(values) >= MIN_TREND_POINTS:
            base = trend["ewma"]
            latest = values[-1]
            if base:
                rel = (latest - base) / abs(base)
                bad = rel > drift_tolerance if direction == "lower" \
                    else rel < -drift_tolerance
                trend["drift_rel"] = rel
                if bad:
                    trend["drift_warning"] = True
                    warnings.append(
                        f"{name}: latest {latest:.6g} drifted {rel:+.1%}"
                        f" against its EWMA {base:.6g}"
                        f" ({direction} is better)"
                    )
        trends[name] = trend
    return trends, warnings


def provenance_breaks(rows: List[Dict]) -> List[str]:
    """Rounds whose attested platform differs from the previous attested
    round — the cross-hardware jumps that make raw trends lie (and the
    context gate.py's device_mismatch advisories point here for)."""
    warnings: List[str] = []
    prev: Optional[Tuple[Optional[int], str]] = None
    for row in rows:
        p = row.get("platform")
        if not p:
            continue
        if prev is not None and p != prev[1]:
            warnings.append(
                f"round {row['round']}: platform changed"
                f" '{prev[1]}' (round {prev[0]}) -> '{p}'"
                " — trend values cross hardware here"
            )
        prev = (row["round"], p)
    return warnings


def build_history(root: str, drift_tolerance: float) -> Dict:
    paths = sorted(
        glob.glob(os.path.join(root, "BENCH_r*.json")),
        key=lambda p: (
            _round_number(p) is None,
            _round_number(p) or 0,
            p,
        ),
    )
    rows = [r for p in paths if (r := load_round(p)) is not None]
    trends, warnings = trend_lines(rows, drift_tolerance)
    warnings.extend(provenance_breaks(rows))
    return {
        "schema": 1,
        "source": "scripts/bench_history.py",
        "n_rounds": len(rows),
        "rounds": rows,
        "trends": trends,
        "drift_tolerance": drift_tolerance,
        "warnings": warnings,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--root", default=REPO,
        help="directory holding the BENCH_r*.json round records",
    )
    parser.add_argument(
        "--out", default=None,
        help="where to write the history document"
             " (default <root>/artifacts/bench_history.json)",
    )
    parser.add_argument(
        "--drift-tolerance", type=float, default=0.15,
        help="relative EWMA deviation (in the bad direction for the"
             " metric) that flags a drift warning (default 0.15)",
    )
    args = parser.parse_args(argv)

    history = build_history(args.root, args.drift_tolerance)
    out = args.out or os.path.join(args.root, "artifacts", "bench_history.json")
    parent = os.path.dirname(out)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(out, "w") as f:
        json.dump(history, f, indent=1)
    _say(
        f"consolidated {history['n_rounds']} round(s),"
        f" {len(history['trends'])} metric trend(s) -> {out}"
    )
    for w in history["warnings"]:
        _say(f"warning: {w}")
    sys.stdout.write(
        json.dumps(
            {
                "out": out,
                "n_rounds": history["n_rounds"],
                "n_metrics": len(history["trends"]),
                "warnings": len(history["warnings"]),
            }
        )
        + "\n"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
