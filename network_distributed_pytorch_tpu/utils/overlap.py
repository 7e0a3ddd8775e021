"""Comm/compute overlap evidence from the scheduled HLO.

The reference's one concurrency trick is launching the rank-1 allreduce
async and joining it after the Gram-Schmidt orthogonalization
(``reducer.py:131-137, 166-168``). The TPU-native claim (DESIGN.md) is that
XLA's latency-hiding scheduler reproduces this without handles: collectives
compile to ``*-start``/``*-done`` pairs and the scheduler moves compute
between them. SURVEY §5 set the bar "assert via profile" — this module
asserts it from the *scheduled executable itself*: the post-optimization
HLO module is scheduled (``is_scheduled=true``), so the textual instruction
order of the entry computation IS the execution order, and any instruction
between a collective's ``-start`` and its ``-done`` runs inside the
communication window.

What the v5e schedule ACTUALLY shows (measured on the chip, round 5): the
all-reduces compile as synchronous HLO ops whose async-ness lives inside
the TPU collective emitter (``backend_config``'s
``RotatedPincerShortEmitter/StrategyRing`` — the op IS a pipelined ICI
ring transfer), while the schedule's visible latency hiding is the
``copy-start``/``copy-done`` DMA prefetch windows with compute inside
them — both are extracted here. Generic ``async-start`` wrappers (the
async-collective-fusion form) are recognized too, classified by the
wrapped collective. On CPU the backend emits synchronous collectives and
no DMA windows, so the report honestly zeroes those fields.

The report also attributes evidence to SPECIFIC collectives: every async
window carries the ``name`` of its start op, and synchronous collectives
(the CPU backend, and any TPU op the emitter keeps synchronous) are listed
in schedule order with the compute ops scheduled between each and the
next (``n_sync_gaps_with_compute``).
"""

from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict, List

_START_RE = re.compile(
    r"%(?P<name>[\w.\-]+) = [^=]*?"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"-start\("
)
# XLA also emits the GENERIC async wrapper form — `%x = ... async-start`,
# whose called computation (named e.g. "%async_computation.N" or carrying
# calls=%...all-reduce...) holds the wrapped op. The async-collective-fusion
# pass produces exactly this shape, so matching only `<kind>-start` would
# report n_async_collectives=0 on a schedule that IS overlapping.
_GENERIC_START_RE = re.compile(
    r"%(?P<name>[\w.\-]+) = [^=]*?\basync-start\("
)
_ASYNC_KIND_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
)
# the TPU memory scheduler's async DMA windows (`copy-start`/`copy-done`):
# on v5e the collectives themselves compile SYNCHRONOUS (their async-ness
# lives inside the collective emitter, see _EMITTER_RE), and the visible
# latency hiding in the schedule is these prefetch copies
_COPY_START_RE = re.compile(r"%(?P<name>[\w.\-]+) = [^=]*?\bcopy-start\(")
# the collective's backend_config names the TPU emitter/strategy that runs
# it on the ICI fabric — extracted as evidence the wire path is the ring
_EMITTER_RE = re.compile(r'"emitter":"(\w+)","strategy":"(\w+)"')
# ops that do real work while a collective is in flight; fusions are where
# XLA puts elementwise/reduction compute, dot/conv are the MXU ops
_COMPUTE_RE = re.compile(r"= [^=]*?(?:fusion|dot|convolution)\(")
# a SYNCHRONOUS collective: the kind immediately followed by its operand
# paren (the -start/-done forms have a suffix there, so they can't match)
_SYNC_RE = re.compile(
    r"%(?P<name>[\w.\-]+) = [^=]*?\b"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)\("
)


@dataclass
class AsyncCollective:
    kind: str
    start_line: int
    done_line: int
    ops_between: int
    compute_ops_between: int
    name: str = ""  # HLO name of the start op — ties evidence to a chunk

    @property
    def overlapped(self) -> bool:
        return self.compute_ops_between > 0


def _entry_mask(lines: List[str]) -> List[bool]:
    """True for lines inside an ``ENTRY`` computation (the scheduled body;
    collectives inside async-wrapper sub-computations must not be counted
    twice). Multiple modules may be concatenated, so there may be several
    entry blocks."""
    mask = [False] * len(lines)
    inside = False
    for i, line in enumerate(lines):
        if line.lstrip().startswith("ENTRY"):
            inside = True
            continue
        if inside and line.rstrip() == "}":
            inside = False
            continue
        mask[i] = inside
    return mask


def overlap_report(hlo_text: str) -> Dict[str, object]:
    """Scan the scheduled entry computation for ``-start``/``-done`` pairs
    and count the (compute) instructions scheduled inside each window."""
    lines = hlo_text.splitlines()
    entry = _entry_mask(lines)
    pending: Dict[str, tuple] = {}  # %name -> (kind, line_no)
    collectives: List[AsyncCollective] = []
    sync: List[Dict[str, object]] = []  # schedule-ordered sync collectives
    n_copy_windows = 0
    n_copy_windows_with_compute = 0
    for i, line in enumerate(lines):
        m = _START_RE.search(line)
        if m:
            pending[m.group("name")] = (m.group("kind"), i)
            continue
        gm = _GENERIC_START_RE.search(line)
        if gm:
            # classify the wrapped op from the same line (the async-start's
            # operand list / calls= annotation names the inner collective);
            # plain compute async wrappers are labeled as such
            km = _ASYNC_KIND_RE.search(line)
            pending[gm.group("name")] = (
                km.group(1) if km else "async-compute", i,
            )
            continue
        cm = _COPY_START_RE.search(line)
        if cm:
            pending[cm.group("name")] = ("copy", i)
            continue
        dm = re.search(r"-done\(%?([\w.\-]+)", line)
        if dm and dm.group(1) in pending:
            name = dm.group(1)
            kind, start = pending.pop(name)
            if kind == "async-compute":
                continue  # generic async wrapper around non-collective work
            window = lines[start + 1 : i]
            if kind == "copy":
                # DMA prefetch window — counted, not listed per-op (there
                # are hundreds; the counts are the latency-hiding evidence)
                n_copy_windows += 1
                if any(_COMPUTE_RE.search(w) for w in window):
                    n_copy_windows_with_compute += 1
                continue
            collectives.append(
                AsyncCollective(
                    kind=kind,
                    start_line=start,
                    done_line=i,
                    ops_between=sum(1 for w in window if " = " in w),
                    compute_ops_between=sum(
                        1 for w in window if _COMPUTE_RE.search(w)
                    ),
                    name=name,
                )
            )
            continue
        if entry[i]:
            sm = _SYNC_RE.search(line)
            if sm:
                sync.append(
                    {"name": sm.group("name"), "kind": sm.group("kind"), "line": i}
                )
    # attribute in-schedule compute to the sync collective it follows: the
    # ops between collective j and j+1 are what the backend can run while
    # j's successor chunk has not yet been launched — on sync backends this
    # textual interleaving IS the decomposed-pipeline evidence
    for j, op in enumerate(sync):
        end = sync[j + 1]["line"] if j + 1 < len(sync) else len(lines)
        gap = lines[op["line"] + 1 : end]
        op["compute_ops_after"] = sum(1 for w in gap if _COMPUTE_RE.search(w))
    interior_gaps_with_compute = sum(
        1 for op in sync[:-1] if op["compute_ops_after"] > 0
    )
    overlapped = [c for c in collectives if c.overlapped]
    return {
        "scheduled": "is_scheduled=true" in hlo_text,
        "n_async_collectives": len(collectives),
        "n_overlapped": len(overlapped),
        "all_overlap": bool(collectives) and len(overlapped) == len(collectives),
        "collectives": [asdict(c) for c in collectives],
        # the TPU schedule's visible latency hiding: async DMA windows and
        # how many have real compute scheduled inside them
        "n_async_copy_windows": n_copy_windows,
        "n_copy_windows_with_compute": n_copy_windows_with_compute,
        # synchronous collectives in schedule order, each with the compute
        # scheduled between it and the next collective; gaps-with-compute
        # counts the INTERIOR gaps only (compute after the last collective
        # proves nothing about interleaving)
        "n_sync_collectives": len(sync),
        "sync_collectives": sync,
        "n_sync_gaps_with_compute": interior_gaps_with_compute,
        "sync_interleaved": len(sync) >= 2 and interior_gaps_with_compute > 0,
        # which TPU collective emitter/strategy runs the (synchronous-in-
        # HLO) collectives — e.g. RotatedPincerShortEmitter / StrategyRing:
        # the op's async-ness lives in the emitter on the ICI ring, not in
        # start/done pairs
        "collective_emitters": sorted(
            {f"{e}/{s}" for e, s in _EMITTER_RE.findall(hlo_text)}
        ),
    }


def comm_attribution(overlap: Dict) -> Dict[str, float]:
    """Count-weighted comm-time attribution from an overlap extract (the
    full :func:`overlap_report` dict, or the subset a ``CompileEvent``
    carries): how many of the step's collectives have compute scheduled
    inside/behind their window (``hidden``) vs serialized on the critical
    path (``exposed``).

    Async collectives are hidden when compute sits between ``-start`` and
    ``-done``; synchronous chunk collectives are hidden when the INTERIOR
    gap after them holds compute (the pipelined-chunk evidence; the last
    collective of a sync chain has no successor to hide behind, so it is
    always exposed). The fractions are count-weighted — the schedule
    proves WHICH collectives overlap, not for how long — which makes
    ``exposed_fraction × step_time`` an upper bound on the step's exposed
    communication time, the honest budget ``observe.analytics`` divides
    measured bytes by."""
    n_async = int(overlap.get("n_async_collectives") or 0)
    n_over = int(overlap.get("n_overlapped") or 0)
    n_sync = int(overlap.get("n_sync_collectives") or 0)
    interior = max(0, n_sync - 1)
    gaps = min(int(overlap.get("n_sync_gaps_with_compute") or 0), interior)
    total = n_async + n_sync
    hidden = min(n_over, n_async) + gaps
    hidden_fraction = hidden / total if total else 0.0
    return {
        "n_collectives": total,
        "n_hidden": hidden,
        "hidden_fraction": hidden_fraction,
        "exposed_fraction": 1.0 - hidden_fraction,
    }
