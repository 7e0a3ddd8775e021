"""From a profiler trace (``*.xplane.pb``) to numbers.

What one trace of this runtime holds (looked at by hand, PR 22): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
program execution), ``XLA Ops`` (one event per HLO instruction executed; a
``while`` spans its body's events) and ``Async XLA Ops`` (start-to-done spans
of asynchronous copies and collectives); a plane ``/host:CPU`` with one line
per thread, where the program's spans arrive as ``TraceAnnotation`` events
under their own names. All on one clock, in nanoseconds.

An op event's name is the instruction's whole HLO text (``%fusion.12 = ...``)
and carries no scope. The scope (``step.grads``, ``reduce.powersgd`` ...) is in
the compiled module's text, ``metadata={op_name="jit(..)/step.grads/.."}``, so
the reduction takes that text beside the trace and joins the two by
instruction name. An instruction without metadata takes the scope of the
computation it calls (a fusion's body), else of its first operand.

The reduction:

- the traced window, per chip: from the start of the second program execution
  to the start of the last, so it holds whole periods, no cut step, and not
  the period the profiler's own start stalls;
- busy time: the union of the op intervals inside the window; idle share is
  one minus busy over window;
- per op, its self time (its interval minus the ops it spans), summed by
  scope and by ``scope/opcode:primitive``;
- collective time, and the part of it during which no other op ran;
- the longest idle gaps, each named by the host span open across it, after
  the host plane has been moved onto the device's clock (``clock_shift``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
# the host loop's leaf spans: what the host can be doing while the chip idles
HOST_SPANS = ("step/loss_sync", "step/compute", "data_load", "epoch_hook")
_SCOPE = re.compile(r"^[a-z_]+\.[a-z_]+$")  # the program's named scopes: step.grads, reduce.powersgd
_OPCODE = re.compile(r"(?<=[\s)])([a-z][a-z0-9_\-]*)\(")
_INSTR = re.compile(r"^\s+(?:ROOT )?(%[^\s]+) = (.*)$")
_OPNAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|body)=(%[^\s,)}]+)")
_OPERAND = re.compile(r"%[A-Za-z0-9_.\-]+")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s]+) \(")


# ---- intervals ---------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def measure(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` (merged) that no interval of ``b`` (merged) covers."""
    out, j = [], 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def self_times(events: Sequence[Tuple[float, float]]) -> List[float]:
    """For events as (start, end), each one's time not covered by the events
    it spans (a ``while`` and its body's ops sit on one line)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    out = [0.0] * len(events)
    stack: List[int] = []
    for i in order:
        start, end = events[i]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        out[i] = end - start
        if stack:
            out[stack[-1]] -= min(end, events[stack[-1]][1]) - start
        stack.append(i)
    return [max(x, 0.0) for x in out]


# ---- HLO text: instruction name -> op_name -----------------------------------


class HloNames:
    """``%instruction`` -> the ``op_name`` its metadata carries, resolved
    through called computations and operands where it carries none."""

    def __init__(self, hlo_text: Optional[str]) -> None:
        self.own: Dict[str, str] = {}
        self.calls: Dict[str, str] = {}
        self.operand: Dict[str, str] = {}
        self.members: Dict[str, List[str]] = defaultdict(list)
        self._resolved: Dict[str, str] = {}
        computation = ""
        for line in (hlo_text or "").splitlines():
            head = _COMPUTATION.match(line)
            if head:
                computation = head.group(1)
                continue
            m = _INSTR.match(line)
            if not m:
                continue
            name, rest = m.groups()
            self.members[computation].append(name)
            op_name = _OPNAME.search(rest)
            if op_name:
                self.own[name] = op_name.group(1)
                continue
            called = _CALLS.search(rest)
            if called:
                self.calls[name] = called.group(1)
            # the first %name after the opcode's "(" is the first operand
            opcode = _OPCODE.search(" " + rest)
            operand = _OPERAND.search(rest, max(opcode.end() - 1, 0) if opcode else 0)
            if operand:
                self.operand[name] = operand.group(0)

    def op_name(self, name: str, depth: int = 0) -> str:
        if name in self._resolved:
            return self._resolved[name]
        found = self.own.get(name, "")
        if not found and name in self.calls:
            inside = [self.own[n] for n in self.members.get(self.calls[name], []) if n in self.own]
            by_scope: Dict[str, List[str]] = defaultdict(list)
            for op in inside:
                by_scope[scope_of(op)].append(op)
            if by_scope:
                found = max(by_scope.values(), key=len)[0]
        if not found and name in self.operand and depth < 8:
            found = self.op_name(self.operand[name], depth + 1)
        self._resolved[name] = found
        return found


def scopes_of(op_name: str) -> List[str]:
    """Every named scope on the path, outermost first."""
    return [part for part in op_name.split("/") if _SCOPE.match(part)]


def scope_of(op_name: str) -> str:
    found = scopes_of(op_name)
    return found[0] if found else "unscoped"


def parse_event_name(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an op event's name."""
    name, _, rest = text.partition(" = ")
    opcode = _OPCODE.search(" " + rest)
    return name.strip(), opcode.group(1) if opcode else "?"


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


# ---- the reduced trace -------------------------------------------------------


class Op:
    __slots__ = ("name", "opcode", "op_name", "scopes", "start", "end", "self_s")

    def __init__(self, name, opcode, op_name, scopes, start, end):
        self.name, self.opcode, self.op_name, self.scopes = name, opcode, op_name, scopes
        self.start, self.end, self.self_s = start, end, 0.0

    @property
    def scope(self) -> str:
        return self.scopes[0] if self.scopes else "unscoped"

    @property
    def kind(self) -> str:
        """``scope/opcode:what``: a kernel by its own name, anything else by
        the jax primitive its metadata ends in."""
        if self.opcode == "custom-call":
            what = re.sub(r"\.\d+$", "", self.name.lstrip("%"))
        else:
            what = self.op_name.rsplit("/", 1)[-1] if self.op_name else ""
        return f"{self.scope}/{self.opcode}" + (f":{what}" if what else "")


class Chip:
    def __init__(self, name: str, ops: List[Op], async_spans: List[Tuple[str, float, float]],
                 modules: List[Interval]) -> None:
        self.name = name
        self.modules = modules
        if len(modules) >= 3:
            # the first period is left out: the profiler's own start stalls it
            self.lo, self.hi, self.steps = modules[1][0], modules[-1][0], len(modules) - 2
        elif len(modules) == 2:
            self.lo, self.hi, self.steps = modules[0][0], modules[1][0], 1
        else:
            self.lo = min((o.start for o in ops), default=0.0)
            self.hi = max((o.end for o in ops), default=0.0)
            self.steps = max(len(modules), 1)
        self.ops = [o for o in ops if o.start >= self.lo and o.start < self.hi]
        for op, s in zip(self.ops, self_times([(o.start, o.end) for o in self.ops])):
            op.self_s = s
        self.busy = clip(union((o.start, o.end) for o in self.ops), self.lo, self.hi)
        collective = [(o.start, o.end) for o in self.ops if is_collective(o.opcode)]
        collective += [(s, e) for n, s, e in async_spans
                       if is_collective(parse_event_name(n)[1]) and self.lo <= s < self.hi]
        self.collective = clip(union(collective), self.lo, self.hi)
        others = union((o.start, o.end) for o in self.ops
                       if not is_collective(o.opcode) and o.opcode != "while")
        self.collective_exposed = subtract(self.collective, others)

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    @property
    def busy_s(self) -> float:
        return measure(self.busy)


class Reduced:
    def __init__(self, chips: List[Chip], host: List[Tuple[str, float, float]]) -> None:
        self.chips = chips
        self.host = host
        self.clock_shift_s = 0.0
        by_name: Dict[str, List[Interval]] = defaultdict(list)
        for name, start, end in host:
            by_name[name].append((start, end))
        self._host_index = {
            name: (sorted(spans), [s for s, _ in sorted(spans)]) for name, spans in by_name.items()
        }

    # -- what the contract's `device` wants
    @property
    def busy_s(self) -> float:
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    @property
    def window_s(self) -> float:
        return sum(c.window_s for c in self.chips) / len(self.chips)

    @property
    def steps(self) -> int:
        return min(c.steps for c in self.chips)

    # -- per step, mean over chips
    def per_step(self, pick) -> Optional[float]:
        """Seconds per step of the ops ``pick(op)`` accepts (self time)."""
        totals = [sum(o.self_s for o in c.ops if pick(o)) / c.steps for c in self.chips]
        return sum(totals) / len(totals) if any(t > 0 for t in totals) else None

    def calls(self, pick) -> List[float]:
        """Device seconds of every op ``pick`` accepts, over all chips."""
        return [o.end - o.start for c in self.chips for o in c.ops if pick(o)]

    def scope_s(self, scope: str) -> Optional[float]:
        return self.per_step(lambda o: scope in o.scopes)

    def collective_s(self) -> Optional[float]:
        if not any(c.collective for c in self.chips):
            return None
        return sum(measure(c.collective) / c.steps for c in self.chips) / len(self.chips)

    def collective_exposed_s(self) -> Optional[float]:
        if not any(c.collective for c in self.chips):
            return None
        return sum(measure(c.collective_exposed) / c.steps for c in self.chips) / len(self.chips)

    # -- where the time goes
    def by_kind(self) -> List[Tuple[str, float]]:
        totals: Dict[str, float] = defaultdict(float)
        for c in self.chips:
            for o in c.ops:
                totals[o.kind] += o.self_s / len(self.chips)
        return sorted(totals.items(), key=lambda kv: -kv[1])

    def by_scope(self) -> Dict[str, float]:
        totals: Dict[str, float] = defaultdict(float)
        for c in self.chips:
            for o in c.ops:
                totals[o.scope] += o.self_s / len(self.chips)
        return dict(totals)

    def _overlaps(self, lo: float, hi: float) -> Dict[str, float]:
        """Seconds of the gap [lo, hi) under each host span; the spans are
        leaves of one thread's nesting, so they never overlap each other."""
        out: Dict[str, float] = {}
        for name in HOST_SPANS:
            spans, starts = self._host_index.get(name, ((), ()))
            i = max(bisect.bisect_right(starts, lo) - 1, 0)
            overlap = 0.0
            while i < len(spans) and spans[i][0] < hi:
                overlap += max(min(spans[i][1], hi) - max(spans[i][0], lo), 0.0)
                i += 1
            if overlap > 0:
                out[name] = overlap
        return out

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """The first chip's idle time, split by what the host was doing
        meanwhile; "other" is idle time under none of the host's spans (the
        loop's own bookkeeping between them, or no host span at all)."""
        chip = self.chips[0]
        blamed: Dict[str, float] = defaultdict(float)
        for lo, hi in gaps(chip.busy, chip.lo, chip.hi):
            under = self._overlaps(lo, hi)
            for name, seconds in under.items():
                blamed[name] += seconds
            blamed["other"] += max(hi - lo - sum(under.values()), 0.0)
        return sorted(blamed.items(), key=lambda kv: -kv[1])

    def longest_gaps(self, n: int = 5) -> List[Tuple[str, float]]:
        """The longest gaps, each under the host span that covers most of it."""
        chip = self.chips[0]
        longest = sorted(gaps(chip.busy, chip.lo, chip.hi), key=lambda g: g[0] - g[1])[:n]
        out = []
        for lo, hi in longest:
            under = self._overlaps(lo, hi)
            out.append((max(under, key=under.get) if under else "other", hi - lo))
        return out

    def breakdown(self) -> Dict[str, List]:
        """The contract's ``breakdown``: the ten op kinds with most device
        time, and the five longest idle gaps by the host span open across
        each, then all idle time by host span; seconds over the traced window."""
        longest = [[f"longest:{k}", v] for k, v in self.longest_gaps(5)]
        totals = [[f"all:{k}", v] for k, v in self.idle_gaps()[:5]]
        return {
            "device_ops": [[k, v] for k, v in self.by_kind()[:10]],
            "idle_gaps": longest + totals,
        }

    def report(self) -> Dict:
        return {
            "chips": len(self.chips), "steps": self.steps, "clock_shift_s": self.clock_shift_s,
            "window_s": self.window_s, "busy_s": self.busy_s,
            "idle_share": 1.0 - self.busy_s / self.window_s if self.window_s else None,
            "per_chip_idle_share": [1.0 - c.busy_s / c.window_s if c.window_s else None for c in self.chips],
            "by_scope_s": self.by_scope(),
            "by_kind_s": self.by_kind()[:25],
            "idle_gaps_s": self.idle_gaps(),
            "longest_gaps_s": self.longest_gaps(),
            "collective_s_per_step": self.collective_s(),
            "collective_exposed_s_per_step": self.collective_exposed_s(),
            "ops_in_window": sum(len(c.ops) for c in self.chips),
        }


# ---- reading -----------------------------------------------------------------


def reduce_planes(planes: Iterable, hlo_text: Optional[str] = None) -> Reduced:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name`` and
    ``events`` carrying ``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` hands them out. Times come back in seconds."""
    names = HloNames(hlo_text)
    parsed: Dict[str, Tuple[str, str, str, Tuple[str, ...]]] = {}
    chips, host = [], []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            ops, spans, modules = [], [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        text = ev.name
                        if text not in parsed:
                            name, opcode = parse_event_name(text)
                            op_name = names.op_name(name)
                            parsed[text] = (name, opcode, op_name, tuple(scopes_of(op_name)))
                        start = ev.start_ns * 1e-9
                        ops.append(Op(*parsed[text], start, start + ev.duration_ns * 1e-9))
                elif line.name == "Async XLA Ops":
                    spans = [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                             for ev in line.events]
                elif line.name == "XLA Modules":
                    modules = step_modules(line.events)
            if ops:
                chips.append(Chip(plane.name, ops, spans, modules))
        elif plane.name == "/host:CPU":
            wanted = set(HOST_SPANS)
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host.append((ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    if not chips:
        raise ValueError("the trace holds no device plane with ops: nothing ran on a TPU while it was on")
    chips.sort(key=lambda c: c.name)
    shift = clock_shift(chips[0].modules, host)
    host = [(name, start - shift, end - shift) for name, start, end in host]
    reduced = Reduced(chips, host)
    reduced.clock_shift_s = shift
    return reduced


def step_modules(events: Iterable) -> List[Interval]:
    """The executions of the step program among a chip's module events: the
    program with most device time. Others run beside it (on four chips jax
    reshards every batch with a small ``_multi_slice`` program per array) and
    must not be counted as steps or cut the window."""
    by_program: Dict[str, List[Interval]] = defaultdict(list)
    for ev in events:
        start = ev.start_ns * 1e-9
        by_program[ev.name.split("(")[0]].append((start, start + ev.duration_ns * 1e-9))
    if not by_program:
        return []
    return sorted(max(by_program.values(), key=measure))


def clock_shift(modules: Sequence[Interval], host: Sequence[Tuple[str, float, float]]) -> float:
    """How far the host plane runs ahead of the device plane, in seconds.

    The two planes are stamped by different clocks, and in the traces read by
    hand they disagreed by up to 2 ms, enough to put a program's execution
    before its own dispatch. One event is seen by both: a step's program ends
    on the device, and the ``step/loss_sync`` span that waited for its loss
    ends on the host a moment later. The shift is the median distance between
    the two, paired from the last step back; the host spans are moved by it
    before any gap is blamed on them. What it cannot see, it hides: the
    delivery of the loss (some tenths of a millisecond) is counted to
    whatever the host did next."""
    device_ends = sorted(end for _, end in modules)
    host_ends = sorted(end for name, _, end in host if name == "step/loss_sync")
    n = min(len(device_ends), len(host_ends))
    if n == 0:
        return 0.0
    distances = sorted(h - d for h, d in zip(host_ends[-n:], device_ends[-n:]))
    return distances[n // 2]


def reduce_file(path: str, hlo_text: Optional[str] = None) -> Reduced:
    """One ``*.xplane.pb``, or one gzipped (the recorded fixture)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    return reduce_planes(data.planes, hlo_text)


def reduce_dir(trace_dir: str, hlo_text: Optional[str] = None) -> Reduced:
    """The one ``*.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return reduce_file(found[-1], hlo_text)
