"""Nested host-side spans — the time-attribution primitive.

The run-level timeline (PR 5) places *events* on a common clock but has no
notion of *phases*: a step record says how long a step took, not where the
time went. Spans close that gap: ``with span("step/compute"): ...`` times a
named region on the monotonic clock and emits one typed
:class:`observe.events.SpanEvent` at close, carrying its parent span id and
nesting depth, so the merged run log reconstructs the host-side flamegraph
(``scripts/report.py --trace-out`` renders it as a Perfetto timeline).

Design constraints, in order:

- **jax-free.** The bench parent orchestrator and the jax-free toy worker
  both emit spans. When jax IS already imported, each span additionally
  mirrors itself into a ``jax.profiler.TraceAnnotation`` so the host phases
  land inside device traces — resolved via ``sys.modules`` so this module
  never force-imports jax.
- **Thread-safe nesting.** The span stack is thread-local: the loader's
  prefetch thread and the training loop can both hold open spans without
  corrupting each other's parentage. Span ids are process-unique.
- **Zero plumbing for deep call sites.** The training loop (or worker
  entry point) installs its telemetry as the process *ambient* recorder
  (:func:`recording` / :func:`set_ambient`); leaf modules — the data
  loader, checkpointing — just call ``span(...)`` and emit through
  whatever recorder is ambient, or no-op when none is (the default, so
  un-instrumented programs pay one dict lookup per span).
- **Monotonic durations.** ``dur_s`` comes from ``time.monotonic()``; wall
  clock is only ever stamped by ``Telemetry.emit`` (the ``ts`` field at
  span CLOSE) — lint-enforced by ``scripts/lint_no_print.py``'s
  monotonic-clock rule.
- **One step, one identifier.** A span opened without ``step=`` takes the
  step of the span that encloses it, so ``data_load/assemble`` under
  ``data_load`` carries that step's number.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
import time
from typing import Iterator, Optional

from .events import SpanEvent
from .telemetry import Telemetry

_LOCAL = threading.local()
_IDS = itertools.count(1)  # itertools.count.__next__ is atomic (C level)
_AMBIENT: Optional[Telemetry] = None

# the supervisor's worker env contract (duplicated literally, like
# observe.runlog): a managed rank's spans self-tag with its rank
_ENV_RANK = "RESILIENCE_RANK"


def _stack() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def set_ambient(telemetry: Optional[Telemetry]) -> Optional[Telemetry]:
    """Install ``telemetry`` as the process-wide default span recorder;
    returns the previous one so callers can restore it."""
    global _AMBIENT
    previous = _AMBIENT
    _AMBIENT = telemetry
    return previous


def ambient() -> Optional[Telemetry]:
    return _AMBIENT


@contextlib.contextmanager
def recording(telemetry: Optional[Telemetry]) -> Iterator[None]:
    """Scope ``telemetry`` as the ambient span recorder (restores the prior
    recorder on exit — the training loop's standard wrapper)."""
    previous = set_ambient(telemetry)
    try:
        yield
    finally:
        set_ambient(previous)


def current_span_id() -> Optional[int]:
    """The innermost open span's id on this thread (None outside spans)."""
    stack = _stack()
    return stack[-1][0] if stack else None


def _default_rank() -> Optional[int]:
    try:
        return int(os.environ[_ENV_RANK])
    except (KeyError, TypeError, ValueError):
        return None


def _jax_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` when jax is already imported (so
    host spans land inside device traces), else None. Never imports jax."""
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:
        return None
    try:
        return jax_mod.profiler.TraceAnnotation(name)
    except Exception:  # profiler unavailable on this backend — span still works
        return None


class span:
    """Time a named region and emit a :class:`SpanEvent` at close.

    ``telemetry`` overrides the ambient recorder; with neither, the span
    still maintains the nesting stack (so an inner recorded span keeps
    correct parentage) but emits nothing. ``step`` defaults to the
    enclosing span's. A context manager written out as a class: the loop
    opens seven of these per step, and the generator form cost a
    microsecond more each.
    """

    __slots__ = (
        "name", "recorder", "step", "rank", "span_id", "parent_id", "depth",
        "stack", "annotation", "t0", "counters",
    )

    def __init__(
        self,
        name: str,
        telemetry: Optional[Telemetry] = None,
        step: Optional[int] = None,
        rank: Optional[int] = None,
    ) -> None:
        self.name = name
        self.recorder = telemetry
        self.step = step
        self.rank = rank
        # what the region counted, set by the body (``with span(..) as s:
        # s.counters = {..}``): JSON-ready, emitted with the span
        self.counters = None

    def __enter__(self) -> "span":
        if self.recorder is None:
            self.recorder = _AMBIENT
        stack = self.stack = _stack()
        self.span_id = next(_IDS)
        self.depth = len(stack)
        if stack:
            self.parent_id, _, parent_step = stack[-1]
            if self.step is None:
                self.step = parent_step
        else:
            self.parent_id = None
        stack.append((self.span_id, self.name, self.step))
        self.annotation = _jax_annotation(self.name)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        dur = time.monotonic() - self.t0
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)
        self.stack.pop()
        recorder = self.recorder
        if recorder is None:
            return
        # positional, in SpanEvent's field order: keywords cost 0.3 us more
        recorder.emit(
            SpanEvent(
                self.name, self.span_id, self.parent_id, self.depth, dur,
                self.step, self.rank if self.rank is not None else _default_rank(),
                self.counters,
            )
        )
