"""Cross-rank critical-path analysis over the merged span timeline.

The span plane is strictly per-rank; the wire ledger says every rank
synchronizes at each step's collectives. Stitching the two gives a
per-step causal graph: each rank runs its local chain of spans, each
charged its self time (data_load -> compute -> collective-wait -> ...),
and the step's
collective is a synchronization edge joining all participants — no rank's
step completes before the slowest rank reaches the join. The longest
weighted path through that graph therefore runs entirely along ONE rank's
timeline (the rank with the largest summed span self time), which makes
the critical path computable in closed form per step, and the interesting
output is the BLAME: which rank gated the step, which of its phases
carried the gap, and — when the gating phase is collective-wait — which
ring edge the wait sat on.

Blame discipline: the gating phase is the phase with the largest EXCESS
over the cross-rank median of that phase, not the largest absolute
duration — a throttled link must blame collective-wait even when compute
is absolutely larger on every rank. The per-edge charge follows the ring
topology (``utils.bandwidth.ring_neighbors``): rank r's exposed comm wait
sits on its outgoing edge (r, (r+1) mod W).

All cross-rank timings here are stitched on the run-log clock model and
inherit its skew tolerance (``MergedRun.clock_skew_bound_s``) — they are
merge-tolerant estimates, never bitwise facts. jax-free, stdlib + observe
only.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .analytics import _load_utils_module, percentile
from .events import CritPathEvent

PHASE_DATA = "data_load"
PHASE_COMPUTE = "compute"
PHASE_COMM = "collective-wait"
PHASES = (PHASE_DATA, PHASE_COMPUTE, PHASE_COMM)


def phase_of(span_name: str) -> str:
    """Map a span name onto the three-way phase taxonomy: anything
    carrying ``data_load`` is the input pipeline, anything carrying
    ``comm`` is exposed collective wait, and the rest (compute,
    checkpoint, eval) charges the compute lane."""
    name = str(span_name)
    if PHASE_DATA in name:
        return PHASE_DATA
    if "comm" in name:
        return PHASE_COMM
    return PHASE_COMPUTE


def _self_spans_by_step_rank(
    events: List[Dict],
) -> Dict[int, Dict[int, List[Dict]]]:
    """{step: {rank: [span records]}}, each charged its SELF time:
    ``dur_s`` less the spans of the same (step, rank) group that name it
    as parent. A leaf keeps its whole duration; a container keeps what no
    child covers (the loop's ``data_load`` less its assemble / to_device /
    stage children is the wait for the producer), so nested trees neither
    double-charge their children nor drop the time between them."""
    grouped: Dict[Tuple[int, int], List[Dict]] = {}
    for e in events:
        if e.get("event") != "span":
            continue
        step, rank, dur = e.get("step"), e.get("rank"), e.get("dur_s")
        if step is None or rank is None:
            continue
        if not isinstance(dur, (int, float)) or dur < 0:
            continue
        grouped.setdefault((int(step), int(rank)), []).append(e)
    out: Dict[int, Dict[int, List[Dict]]] = {}
    for (step, rank), spans in grouped.items():
        covered: Dict = {}
        for s in spans:
            parent = s.get("parent_id")
            if parent is not None:
                covered[parent] = covered.get(parent, 0.0) + float(s["dur_s"])
        out.setdefault(step, {})[rank] = [
            dict(s, dur_s=max(float(s["dur_s"]) - covered.get(s.get("span_id"), 0.0), 0.0))
            for s in spans
        ]
    return out


def _phase_split(spans: List[Dict]) -> Dict[str, float]:
    split = {p: 0.0 for p in PHASES}
    for s in spans:
        split[phase_of(s.get("name") or "")] += float(s["dur_s"])
    return split


def step_blame(
    per_rank: Dict[int, Dict[str, float]], world_size: int, step: int
) -> Optional[CritPathEvent]:
    """One step's blame verdict from its per-rank phase splits. None when
    no rank reported spans."""
    if not per_rank:
        return None
    totals = {r: sum(split.values()) for r, split in per_rank.items()}
    crit = max(sorted(totals), key=lambda r: totals[r])
    split = per_rank[crit]
    # excess over the cross-rank median per phase: what THIS rank spent
    # beyond what a typical rank spent there
    excess = {}
    for p in PHASES:
        med = percentile([per_rank[r][p] for r in per_rank], 50) or 0.0
        excess[p] = split[p] - med
    phase = max(PHASES, key=lambda p: excess[p])
    if excess[phase] <= 0:
        # no rank stands out (or a single-rank world): fall back to the
        # critical rank's absolutely largest phase
        phase = max(PHASES, key=lambda p: split[p])
    edge_src = edge_dst = None
    if phase == PHASE_COMM and world_size > 1:
        edge_src, edge_dst = crit, (crit + 1) % world_size
    return CritPathEvent(
        step=step,
        rank=crit,
        phase=phase,
        path_s=totals[crit],
        edge_src=edge_src,
        edge_dst=edge_dst,
        data_s=split[PHASE_DATA],
        compute_s=split[PHASE_COMPUTE],
        comm_s=split[PHASE_COMM],
    )


def analyze(events: List[Dict], world_size: int) -> Optional[Dict]:
    """The run-level critical-path report off a merged event list.

    Returns None when the run carries no stepped, ranked spans (the
    single-log report mode, or a spanless worker). Otherwise a dict with
    the per-step ``CritPathEvent`` records, path-seconds-weighted blame
    shares by rank and by phase, the top gating edge, and the gate's
    scalar ``comm_share`` — the share of summed critical-path seconds the
    gating ranks spent in collective-wait (lower is better)."""
    by_step = _self_spans_by_step_rank(events)
    verdicts: List[CritPathEvent] = []
    for step in sorted(by_step):
        per_rank = {
            r: _phase_split(spans) for r, spans in by_step[step].items()
        }
        ev = step_blame(per_rank, world_size, step)
        if ev is not None:
            verdicts.append(ev)
    if not verdicts:
        return None
    total_path = sum(v.path_s for v in verdicts)
    blame_rank: Dict[int, float] = {}
    blame_phase: Dict[str, float] = {p: 0.0 for p in PHASES}
    edge_steps: Dict[Tuple[int, int], int] = {}
    for v in verdicts:
        blame_rank[v.rank] = blame_rank.get(v.rank, 0.0) + v.path_s
        blame_phase[v.phase] += v.path_s
        if v.edge_src is not None:
            edge = (v.edge_src, v.edge_dst)
            edge_steps[edge] = edge_steps.get(edge, 0) + 1
    top_edge = None
    if edge_steps:
        (src, dst), n = max(
            sorted(edge_steps.items()), key=lambda kv: kv[1]
        )
        top_edge = {"src": src, "dst": dst, "blamed_steps": n}
    comm_s = sum(v.comm_s for v in verdicts)
    return {
        "schema": 1,
        "n_steps": len(verdicts),
        "world_size": world_size,
        "total_path_s": total_path,
        # the gate's scalar: collective-wait seconds on the gating ranks
        # over total critical-path seconds (lower = less network-gated)
        "comm_share": comm_s / total_path if total_path > 0 else 0.0,
        "blame_by_rank": {
            str(r): s / total_path if total_path > 0 else 0.0
            for r, s in sorted(blame_rank.items())
        },
        "blame_by_phase": {
            p: s / total_path if total_path > 0 else 0.0
            for p, s in blame_phase.items()
        },
        "top_edge": top_edge,
        "events": [v.record() for v in verdicts],
    }


def comm_waits_by_edge(
    events: List[Dict], world_size: int
) -> Dict[Tuple[int, int], List[float]]:
    """Per-ring-edge exposed-wait samples: rank r's collective-wait
    spans charged to its outgoing edge. The live plane's per-edge detector
    and the fabric matrix share this charging rule."""
    bw = _load_utils_module("bandwidth")
    edges = {src: (src, dst) for src, dst in bw.ring_neighbors(world_size)}
    out: Dict[Tuple[int, int], List[float]] = {}
    for step_group in _self_spans_by_step_rank(events).values():
        for rank, spans in step_group.items():
            if rank not in edges:
                continue
            wait = sum(
                float(s["dur_s"])
                for s in spans
                if phase_of(s.get("name") or "") == PHASE_COMM
            )
            if wait > 0:
                out.setdefault(edges[rank], []).append(wait)
    return out
