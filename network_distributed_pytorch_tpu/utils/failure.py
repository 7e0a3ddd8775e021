"""Failure detection — the aux subsystem the reference almost has.

The reference's only failure handling is a rendezvous timeout whose failure
path prints ``[Failure] Distributed Environment Failed`` and falls through
WITHOUT exiting (``ddp_guide_cifar10/ddp_init.py:98-99`` — the training then
crashes later). ``mesh.initialize_distributed`` already fixes that (raises
immediately). This module adds the detection machinery the reference lacks
(SURVEY §5: "rendezvous timeouts only — no retry, no elasticity"):

- :class:`StepWatchdog` — detects a hung training step (e.g. a peer died
  mid-collective, so the allreduce never completes) and runs a callback on
  the deadline. A hung XLA collective cannot be interrupted from Python, so
  the callback's job is to REPORT (structured banner, flight-recorder dump)
  and decide (e.g. ``os._exit`` for a supervisor restart).
- :func:`retry_transient` — bounded retry for transient runtime errors
  (preemption blips, runtime hiccups) with exponential backoff.
- :class:`HeartbeatMonitor` — file-based liveness over a shared filesystem,
  the same substrate as the reference's ``file://`` rendezvous
  (``ddp_guide/ddp_init.py:41``): each process beats its own file; any
  process can list peers whose heartbeat has gone stale.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
from typing import Callable, Dict, List, Optional


class StepWatchdog:
    """Deadline monitor for potentially-hanging calls.

    Usage::

        wd = StepWatchdog(timeout_seconds=300, on_timeout=report_and_exit)
        for batch in loader:
            with wd.watch(f"step {i}"):
                state, loss = step(state, batch)   # blocks on device

    ``on_timeout(label)`` runs on the (single, long-lived) monitor thread
    when a watched region exceeds the deadline; the watched call itself keeps
    blocking (XLA cannot be interrupted) — the callback reports and/or
    terminates the process. ``compile_grace`` skips monitoring the first N
    watched regions: step 1 includes XLA compilation, which can legitimately
    exceed a steady-state deadline (a spurious fire + supervisor restart
    there would recompile and fire again, forever).
    """

    def __init__(
        self,
        timeout_seconds: float,
        on_timeout: Optional[Callable[[str], None]] = None,
        compile_grace: int = 0,
    ):
        self.timeout_seconds = timeout_seconds
        self.on_timeout = on_timeout or self._default_report
        self.compile_grace = compile_grace
        self.fired: List[str] = []  # labels whose deadline passed
        self._watch_count = 0
        self._cond = threading.Condition()
        self._fired_lock = threading.Lock()  # fired is appended on the
        # monitor thread and read/cleared on the training thread
        self._deadline: Optional[float] = None
        self._label: Optional[str] = None
        self._thread: Optional[threading.Thread] = None

    def reset(self) -> None:
        """Re-arm for a fresh run: disarm any pending deadline, zero the
        watch count (so ``compile_grace`` applies again — a supervisor-
        restarted worker recompiles, which legitimately needs the grace),
        and clear the fired history. The monitor thread is reused."""
        with self._cond:
            self._deadline = None
            self._label = None
            self._watch_count = 0
            self._cond.notify()
        with self._fired_lock:
            self.fired.clear()

    @staticmethod
    def _default_report(label: str) -> None:
        # structured version of the reference's failure banner
        # (ddp_guide_cifar10/ddp_init.py:98) — but impossible to miss
        from ..observe import FailureEvent, default_telemetry

        default_telemetry().emit(FailureEvent(kind="watchdog_timeout", label=label))

    def _monitor(self) -> None:
        while True:
            with self._cond:
                while self._deadline is None:
                    self._cond.wait()
                remaining = self._deadline - time.monotonic()
                if remaining > 0:
                    self._cond.wait(remaining)
                    continue
                label = self._label
                self._deadline = None
                self._label = None
            with self._fired_lock:
                self.fired.append(label)
            self.on_timeout(label)

    def _arm(self, label: str) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._monitor, daemon=True)
            self._thread.start()
        with self._cond:
            self._deadline = time.monotonic() + self.timeout_seconds
            self._label = label
            self._cond.notify()

    def _disarm(self) -> None:
        with self._cond:
            self._deadline = None
            self._label = None
            self._cond.notify()

    class _Watch:
        def __init__(self, wd: "StepWatchdog", label: str):
            self.wd = wd
            self.label = label

        def __enter__(self):
            with self.wd._cond:
                self.wd._watch_count += 1
                self.armed = self.wd._watch_count > self.wd.compile_grace
            if self.armed:
                self.wd._arm(self.label)
            return self

        def __exit__(self, *exc):
            if self.armed:
                self.wd._disarm()
            return False

    def watch(self, label: str = "step") -> "_Watch":
        return self._Watch(self, label)


def retry_transient(
    fn: Callable,
    retries: int = 3,
    backoff_seconds: float = 1.0,
    exceptions=(RuntimeError,),
    on_retry: Optional[Callable[[int, BaseException], None]] = None,
    max_backoff_seconds: Optional[float] = None,
    jitter: float = 0.0,
    telemetry=None,
    label: str = "",
    rng: Optional[random.Random] = None,
):
    """Call ``fn()``; on a transient error retry up to ``retries`` times with
    exponential backoff. Re-raises the last error when exhausted. The
    reference has no retry anywhere (SURVEY §5).

    ``max_backoff_seconds`` caps the exponential growth;``jitter`` spreads
    each sleep uniformly over ``[backoff, backoff * (1 + jitter)]`` so a
    cohort of ranks retrying the same transient fault doesn't stampede the
    coordinator in lockstep (``rng`` makes the spread seedable for tests).
    Every attempt is emitted as a ``FailureEvent(kind="retry")`` through
    ``telemetry`` (the default stdout registry when None) — the structured
    log sees every retry, not just callers that passed ``on_retry``."""
    from ..observe import FailureEvent, default_telemetry

    emit_to = telemetry if telemetry is not None else default_telemetry()
    rng = rng if rng is not None else random
    attempt = 0
    while True:
        try:
            return fn()
        except exceptions as e:  # noqa: PERF203
            attempt += 1
            emit_to.emit(
                FailureEvent(
                    kind="retry",
                    label=label,
                    message=(
                        f"attempt {attempt}/{retries}:"
                        f" {type(e).__name__}: {e}"
                    ),
                )
            )
            if attempt > retries:
                raise
            if on_retry is not None:
                on_retry(attempt, e)
            delay = backoff_seconds * (2 ** (attempt - 1))
            if max_backoff_seconds is not None:
                delay = min(delay, max_backoff_seconds)
            if jitter > 0:
                delay *= 1.0 + jitter * rng.random()
            time.sleep(delay)


class HeartbeatMonitor:
    """Liveness via per-process heartbeat files on a shared filesystem.

    The multi-host analogue of the reference's ``file://`` rendezvous
    directory: process i touches ``<dir>/heartbeat_<i>.json`` when it beats;
    `stale_peers(threshold)` lists processes whose last beat is older than
    ``threshold`` seconds (or that never beat at all). ``min_interval_seconds``
    rate-limits beats so ``beat()`` can sit in a hot training loop without a
    filesystem write per step (beats within the interval are skipped).
    """

    def __init__(
        self,
        directory: str,
        process_id: int,
        num_processes: int,
        min_interval_seconds: float = 0.0,
        incarnation: int = 0,
        startup_grace_seconds: Optional[float] = None,
    ):
        self.directory = directory
        self.process_id = process_id
        self.num_processes = num_processes
        self.min_interval_seconds = min_interval_seconds
        # which life of this rank is beating: a supervisor-restarted worker
        # beats with incarnation+1, so a reader can tell the live replacement
        # apart from the stale file its dead predecessor left behind
        self.incarnation = incarnation
        # never-booted peers are not stale at t=0: they get this long to
        # produce a first beat before counting (None = use the reader's
        # threshold, so "never beat" and "beat then died" age out alike)
        self.startup_grace_seconds = startup_grace_seconds
        self._created_ts = time.time()
        self._last_beat = -float("inf")
        os.makedirs(directory, exist_ok=True)

    def _path(self, pid: int) -> str:
        return os.path.join(self.directory, f"heartbeat_{pid}.json")

    def beat(self, **extra) -> None:
        """Write this process's heartbeat (atomic rename); a no-op when the
        previous beat is newer than ``min_interval_seconds``."""
        now = time.monotonic()
        if now - self._last_beat < self.min_interval_seconds:
            return
        self._last_beat = now
        payload = {
            "process_id": self.process_id,
            "incarnation": self.incarnation,
            "ts": time.time(),
            **extra,
        }
        tmp = self._path(self.process_id) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, self._path(self.process_id))

    def peer_payloads(self) -> Dict[int, Optional[Dict]]:
        """Full latest beat payload per process (None = never beat)."""
        out: Dict[int, Optional[Dict]] = {}
        for pid in range(self.num_processes):
            try:
                with open(self._path(pid)) as f:
                    payload = json.load(f)
                out[pid] = payload if "ts" in payload else None
            except (OSError, ValueError):
                out[pid] = None
        return out

    def last_beats(self) -> Dict[int, Optional[float]]:
        """Timestamp of every process's latest beat (None = never beat)."""
        return {
            pid: (p["ts"] if p is not None else None)
            for pid, p in self.peer_payloads().items()
        }

    def stale_peers(self, threshold_seconds: float) -> List[int]:
        """Process ids (excluding self) not seen within the threshold.

        A peer that NEVER beat only counts once the startup grace has
        passed — at t=0 nobody has booted yet, and declaring the whole
        world stale there would make any grace-free monitor restart-storm
        on its first poll."""
        now = time.time()
        grace = (
            self.startup_grace_seconds
            if self.startup_grace_seconds is not None
            else threshold_seconds
        )
        booting = now - self._created_ts <= grace
        stale = []
        for pid, ts in self.last_beats().items():
            if pid == self.process_id:
                continue
            if ts is None:
                if not booting:
                    stale.append(pid)
            elif now - ts > threshold_seconds:
                stale.append(pid)
        return stale
