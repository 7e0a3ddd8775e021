"""Recovery guards: the detection/retry side of the failure paths the
chaos plan injects.

Two wrappers, both opt-in from ``resilient_train_loop``:

- :class:`GuardedStep` — retries a step whose execution raised a transient
  ``RuntimeError`` (preemption blip, runtime hiccup, injected
  ``ChaosTransientError``) and rejects a step whose loss came back
  non-finite (NaN gradient burst) WITHOUT advancing state, re-running it
  instead. Requires the wrapped step to have been built with
  ``donate_state=False`` — a donated input buffer cannot be replayed.
  A ``RESOURCE_EXHAUSTED`` error is the one ``RuntimeError`` it does NOT
  retry: replaying an allocation that just killed the allocator only
  reproduces the corpse. Instead the guard dumps the OOM post-mortem
  (``observe.memory.build_oom_report`` → ``artifacts/oom_report.json``:
  last live memory sample, compile-time footprint split, ranked
  buffer-class attribution) and re-raises as :class:`OutOfMemoryError`,
  which is deliberately not a ``RuntimeError`` so ``retry_transient``
  cannot swallow it.
- :func:`guarded_batches` — drops loader output that would poison the run:
  non-finite values or a leading dim that disagrees with the expected
  global batch (a short batch would either recompile or silently skew the
  global-batch accounting).

Plus the preemption-grace side of elastic recovery:

- :class:`PreemptionGuard` — a SIGTERM handler that converts a preemption
  notice into a request for an emergency COMMITTED checkpoint at the next
  step boundary (``resilient_train_loop`` polls it), so a supervisor's
  graceful SIGTERM-then-SIGKILL shutdown loses zero completed steps
  instead of everything since the last epoch boundary.

And the degraded-fabric side (DESIGN.md):

- :func:`derive_collective_deadline` — a per-collective time budget from
  the wire ledger's bytes and the ``FABRICS_BYTES_PER_S`` model, floored
  by the measured collective p50 × a slack factor.
- :class:`CollectiveWatchdog` — a fence hook (``parallel.comm``) arming a
  ``StepWatchdog``-style monitor-thread timer around every fenced collective;
  expiry emits ``FailureEvent(kind="comm_deadline")`` and marks the
  attempt, never kills the process itself.
- :class:`CommDeadlineGuard` — wraps the step OUTSIDE :class:`GuardedStep`
  (a deadline expiry is not a transient exception — the step returns,
  late); one in-place retry, then the step is marked degraded, and only K
  CONSECUTIVE degraded steps escalate (``CommEscalationError``, which is
  deliberately not a ``RuntimeError`` so the transient-retry machinery
  cannot swallow it) — a transient flap recovers with zero restarts.

And the geo-resilient (hierarchical outer loop) side:

- :func:`derive_outer_deadline` — the cross-site twin of
  :func:`derive_collective_deadline`: a time budget for the OUTER
  (slow-fabric) reduction of ``parallel.hierarchical``, modeled at the
  cross-site fabric's line rate over the site count.
- :class:`PartitionPolicy` — the host-side partition state machine: on an
  outer-deadline expiry or an injected ``comm_partition``, training
  degrades to site-local rounds (typed ``observe.PartitionEvent``), the
  site-local step count is charged against a ``max_local_steps``
  divergence budget, and when the edge heals the next completed sync is
  recorded as the rejoin. Budget exhaustion raises
  :class:`CommEscalationError` — the supervisor takes over only when the
  merge-tolerance story has genuinely run out.

Every recovery action is a ``FailureEvent`` through telemetry, so the run
log shows fault → detection → recovery with timestamps.
"""

from __future__ import annotations

import math
import signal
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, Optional


class NonFiniteLossError(RuntimeError):
    """A step reported a NaN/inf loss — treated as transient: the state
    that produced it is discarded and the step re-run on its inputs."""


class CommDeadlineError(RuntimeError):
    """A collective blew its derived deadline (transient-shaped: retryable)."""


class CommEscalationError(Exception):
    """K consecutive steps degraded by collective-deadline expiries: the
    fabric is persistently sick and the supervisor should take over.

    Deliberately NOT a ``RuntimeError``: :class:`GuardedStep` /
    ``retry_transient`` catch ``RuntimeError``, and an escalation must
    propagate past them to the worker's top level."""


class OutOfMemoryError(Exception):
    """The device allocator died (``RESOURCE_EXHAUSTED``) under the
    guarded step. Deliberately NOT a ``RuntimeError`` — jax surfaces its
    OOM as ``XlaRuntimeError`` (a ``RuntimeError``), which
    ``retry_transient`` would happily replay, and replaying an allocation
    that just exhausted the device reproduces the failure at best and
    corrupts the run's timeline at worst. :class:`GuardedStep` detects
    the OOM by message, writes the forensics report, then raises this so
    the failure propagates straight to the worker's top level."""


# the message shapes jax's allocator death arrives in — XlaRuntimeError
# carries the XLA status name; some backends spell the prose form only
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory")


def is_oom_error(exc: BaseException) -> bool:
    """Whether a raised exception is a device out-of-memory, by message:
    jax's ``XlaRuntimeError`` IS a ``RuntimeError`` (no dedicated type to
    ``isinstance`` against), so the status string is the only stable
    signal — and the injected ``ChaosOutOfMemoryError`` is shaped to
    match it exactly."""
    text = str(exc)
    return any(marker in text for marker in _OOM_MARKERS)


class CheckpointUnwritableError(OSError):
    """The checkpoint directory rejected writes past the save retry budget
    (filer read-only, permissions revoked, path shadowed). Restarting the
    worker cannot fix it — every restart would die at the same commit —
    so the worker exits with ``CKPT_UNWRITABLE_EXIT_CODE`` and the
    supervisor fails the run fast instead of burning its restart budget
    into a storm. An ``OSError`` subclass (it IS an I/O failure) but NOT a
    ``RuntimeError``, so no transient-retry wrapper can swallow it."""


def derive_collective_deadline(
    payload_bytes: int,
    n_workers: int,
    fabric: str = "ICI(v5e)",
    measured_p50_s: Optional[float] = None,
    slack: float = 4.0,
    floor_s: float = 0.05,
) -> float:
    """Per-collective deadline: ``max(modeled_time, measured_p50) × slack``,
    floored at ``floor_s``.

    The model is ``utils.bandwidth.allreduce_time_s`` (the ring lower
    bound at the fabric's ``FABRICS_BYTES_PER_S`` line rate) — optimistic
    by construction, hence the slack factor; the measured p50 of recent
    fenced collectives keeps the deadline honest on hardware slower than the
    model (CPU test meshes most of all); the floor keeps tiny payloads
    from deriving microsecond hair-trigger deadlines."""
    # path-load so the supervisor-parent import path stays jax-free (the
    # utils package __init__ pulls jax; the bandwidth module itself is
    # stdlib-only)
    from ..observe.analytics import _load_utils_module

    bw = _load_utils_module("bandwidth")
    modeled = bw.allreduce_time_s(int(payload_bytes), int(n_workers), fabric)
    budget = max(modeled, measured_p50_s or 0.0) * slack
    return max(budget, floor_s)


def derive_outer_deadline(
    outer_payload_bytes: int,
    n_sites: int,
    fabric: str = "1GbE",
    measured_p50_s: Optional[float] = None,
    slack: float = 6.0,
    floor_s: float = 0.25,
) -> float:
    """Deadline for ONE cross-site outer reduction of the hierarchical
    loop: :func:`derive_collective_deadline` re-parameterized for the slow
    fabric.

    ``outer_payload_bytes`` is the COMPRESSED outer payload (the
    hierarchical reducer's ``bits_by_fabric()['outer'] // 8``), ``n_sites``
    the outer-axis world, ``fabric`` the cross-site link class from the
    fabric matrix's bottleneck edge. The defaults are deliberately looser
    than the inner deadline's: a WAN edge has orders-of-magnitude more
    natural jitter than ICI, and the async overlap means a late outer sync
    costs nothing until the NEXT round needs its result — the deadline
    exists to declare the edge dead, not merely slow."""
    return derive_collective_deadline(
        outer_payload_bytes, n_sites, fabric,
        measured_p50_s=measured_p50_s, slack=slack, floor_s=floor_s,
    )


class PartitionPolicy:
    """The host-side partition state machine of the geo-resilient outer
    loop (``parallel.hierarchical`` / the toy game-day worker).

    Transitions, each a typed ``observe.PartitionEvent``:

    - :meth:`note_partition` — the cross-site edge was declared dead (an
      outer watchdog expiry, or ``CommFaultInjector.partitioned``):
      ``phase="partitioned"``. Idempotent while already partitioned.
    - :meth:`note_local_round` — one outer round ran site-local (inner
      steps only, no cross-site collective): ``phase="local"``, the
      round's inner steps charged against the ``max_local_steps``
      divergence budget and ``outer_staleness`` incremented. Raises
      :class:`CommEscalationError` when the budget is exhausted — the
      point where site-local drift exceeds what the EF-corrected catch-up
      reduction is documented to absorb, so the supervisor must decide.
    - :meth:`note_sync` — a cross-site sync COMPLETED: staleness resets;
      if it ends a partition it is the rejoin (``phase="rejoin"``, the
      catch-up reduction having folded the accumulated site-local deltas
      through error feedback).

    jax-free and clock-free: the policy counts steps and rounds, never
    reads a clock, so tests replay it exactly."""

    def __init__(
        self,
        max_local_steps: int,
        telemetry: Any = None,
        rank: int = 0,
        incarnation: int = 0,
    ):
        self.max_local_steps = int(max_local_steps)
        self._telemetry = telemetry
        self._rank = rank
        self._incarnation = incarnation
        self.partitioned = False
        self.edge: Optional[tuple] = None
        self.local_steps = 0
        self.outer_staleness = 0
        self.events: list = []  # every PartitionEvent, in order (tests/report)

    def _emit(self, phase: str, step: Optional[int], reason: str = ""):
        from ..observe import PartitionEvent

        ev = PartitionEvent(
            phase=phase,
            edge=list(self.edge) if self.edge is not None else None,
            local_steps=self.local_steps,
            max_local_steps=self.max_local_steps,
            outer_staleness=self.outer_staleness,
            reason=reason,
            rank=self._rank,
            step=step,
            incarnation=self._incarnation,
        )
        self.events.append(ev)
        if self._telemetry is not None:
            self._telemetry.emit(ev)
        return ev

    @property
    def remaining_budget(self) -> int:
        return max(0, self.max_local_steps - self.local_steps)

    def note_partition(
        self,
        edge: Optional[tuple] = None,
        step: Optional[int] = None,
        reason: str = "",
    ) -> None:
        """The cross-site edge is down. Safe to call every step while the
        fault holds — only the first call per partition emits."""
        if self.partitioned:
            return
        self.partitioned = True
        self.edge = tuple(edge) if edge is not None else None
        self.local_steps = 0
        self._emit("partitioned", step, reason or "cross-site edge declared dead")

    def note_local_round(
        self, inner_steps: int, step: Optional[int] = None
    ) -> None:
        """One outer round completed WITHOUT its cross-site sync. Charges
        the divergence budget; raises when it is exhausted."""
        self.local_steps += int(inner_steps)
        self.outer_staleness += 1
        self._emit("local", step)
        if self.local_steps > self.max_local_steps:
            raise CommEscalationError(
                f"partition divergence budget exhausted: {self.local_steps} "
                f"site-local steps > max_local_steps={self.max_local_steps}; "
                f"escalating to supervisor"
            )

    def note_sync(self, step: Optional[int] = None) -> None:
        """A cross-site outer sync completed. Ends an active partition
        (the rejoin) and resets the staleness counter either way."""
        if self.partitioned:
            self._emit(
                "rejoin", step,
                f"edge healed after {self.local_steps} site-local steps; "
                f"EF catch-up reduction merged",
            )
            self.partitioned = False
            self.edge = None
            self.local_steps = 0
        self.outer_staleness = 0


class OuterSyncDriver:
    """Per-round routing glue for the geo-resilient loop: decides, BEFORE
    each round is dispatched, whether the cross-site outer sync may run —
    composing the two partition signals (the chaos injector's
    ``partitioned`` flag, i.e. the fault is declared; and an outer
    :class:`CollectiveWatchdog` whose expiry on an ``outer.*`` tag declares
    the edge dead empirically) over a :class:`PartitionPolicy` that owns
    the state machine, the typed events, and the divergence budget.

    Usage, in a round loop::

        driver = OuterSyncDriver(policy, probes=[lambda: injector.partitioned],
                                 watchdog=outer_watchdog)
        if driver.should_sync(step=i):
            state, losses = compiled(state, batches)       # sync round
            driver.note_sync(step=i)
        else:
            state, losses = compiled.local_round(state, batches)
            driver.note_local(compiled.sync_every, step=i)  # may escalate

    jax-free; probes are zero-arg callables so the driver never imports
    the injector's module."""

    def __init__(
        self,
        policy: PartitionPolicy,
        probes: Any = (),
        watchdog: Any = None,
        edge_probe: Any = None,
    ):
        self.policy = policy
        self._probes = list(probes)
        self._watchdog = watchdog
        self._edge_probe = edge_probe

    def _partition_reason(self) -> Optional[str]:
        for probe in self._probes:
            if probe():
                return "partition fault active"
        wd = self._watchdog
        if wd is not None and wd.expired_this_attempt():
            return "outer sync deadline expired"
        return None

    def should_sync(self, step: Optional[int] = None) -> bool:
        """True → run the sync round; False → the edge is (still) down,
        run the collective-free local round."""
        reason = self._partition_reason()
        if reason is not None:
            edge = self._edge_probe() if self._edge_probe is not None else None
            self.policy.note_partition(edge=edge, step=step, reason=reason)
            return False
        return True

    def note_sync(self, step: Optional[int] = None) -> None:
        if self._watchdog is not None:
            self._watchdog.begin_attempt()
        self.policy.note_sync(step=step)

    def note_local(self, inner_steps: int, step: Optional[int] = None) -> None:
        """Charge one site-local round; raises ``CommEscalationError`` via
        the policy when the divergence budget is exhausted."""
        self.policy.note_local_round(inner_steps, step=step)


class CollectiveWatchdog:
    """A deadline timer around every fenced collective, driven as a
    ``parallel.comm`` fence hook.

    One monitor thread (the :class:`utils.failure.StepWatchdog` pattern:
    a ``Condition`` guarding a single monotonic deadline) watches the
    currently-armed collective. The hook arms on every ``launch`` with a
    deadline from :func:`derive_collective_deadline` (the payload's bytes;
    measured p50 over the last ``history`` collectives as the floor) and
    disarms on the next fence point — so the armed window brackets exactly
    one collective's wire time plus its retire compute. Expiry emits
    ``FailureEvent(kind="comm_deadline")`` from the monitor thread and
    flags the attempt; it never interrupts the step, which completes
    (late) on its own.

    Escalation policy lives here too: :meth:`note_step` tracks the
    CONSECUTIVE-degraded-step streak, :meth:`should_escalate` compares it
    against ``escalate_after`` (K), and :meth:`take_epoch` hands the
    per-epoch expiry/degraded counters to the fallback controller.

    Register this hook BEFORE any fault injector, so the timer is armed
    when an injected stall starts sleeping."""

    def __init__(
        self,
        n_workers: int = 1,
        fabric: str = "ICI(v5e)",
        slack: float = 4.0,
        floor_s: float = 0.05,
        escalate_after: int = 3,
        history: int = 64,
        telemetry: Any = None,
        rank: int = 0,
        label: str = "comm",
    ):
        self.n_workers = n_workers
        self.fabric = fabric
        self.slack = slack
        self.floor_s = floor_s
        self.escalate_after = escalate_after
        self._telemetry = telemetry
        self._rank = rank
        self._label = label
        self._cond = threading.Condition()
        self._deadline: Optional[float] = None
        self._armed: Optional[Dict[str, Any]] = None
        self._arm_t: Optional[float] = None
        self._durations: deque = deque(maxlen=history)
        self._stop = False
        self._expired_this_attempt = False
        self._degraded_streak = 0
        self._epoch_expiries = 0
        self._epoch_degraded = 0
        self.fired: list = []
        self._thread = threading.Thread(
            target=self._monitor, name=f"collective-watchdog-{label}",
            daemon=True,
        )
        self._thread.start()

    # -- the fence hook (io_callback thread) --------------------------------
    def __call__(self, info: Dict[str, Any]) -> None:
        if info.get("device_index") != self._rank:
            return
        now = time.monotonic()
        with self._cond:
            if self._arm_t is not None:
                self._durations.append(now - self._arm_t)
            if info.get("phase") == "launch":
                durs = sorted(self._durations)
                p50 = durs[len(durs) // 2] if durs else None
                budget = derive_collective_deadline(
                    info.get("payload_bytes", 0), self.n_workers,
                    self.fabric, measured_p50_s=p50, slack=self.slack,
                    floor_s=self.floor_s,
                )
                self._armed = {**info, "deadline_s": budget}
                self._arm_t = now
                self._deadline = now + budget
            else:  # retire: the pipeline's last result landed
                self._armed = None
                self._arm_t = None
                self._deadline = None
            self._cond.notify_all()

    # -- monitor thread -----------------------------------------------------
    def _monitor(self) -> None:
        while True:
            with self._cond:
                if self._stop:
                    return
                if self._deadline is None:
                    self._cond.wait()
                    continue
                now = time.monotonic()
                if now < self._deadline:
                    self._cond.wait(self._deadline - now)
                    continue
                info = self._armed or {}
                self._deadline = None
                self._armed = None
                self._arm_t = None
                self._expired_this_attempt = True
                self._epoch_expiries += 1
                self.fired.append(info)
            self._emit_deadline(info)

    def _emit_deadline(self, info: Dict[str, Any]) -> None:
        if self._telemetry is None:
            return
        from ..observe import FailureEvent

        self._telemetry.emit(
            FailureEvent(
                kind="comm_deadline",
                label=str(info.get("tag", "?")),
                message=(
                    f"collective exceeded deadline "
                    f"{info.get('deadline_s', 0.0):.3f}s "
                    f"({info.get('payload_bytes', 0)} B on {self.fabric})"
                ),
                rank=self._rank,
            )
        )

    # -- attempt / step / epoch bookkeeping (loop thread) -------------------
    def begin_attempt(self) -> None:
        with self._cond:
            self._expired_this_attempt = False

    @property
    def expired_this_attempt(self) -> bool:
        with self._cond:
            return self._expired_this_attempt

    def note_step(self, degraded: bool) -> None:
        with self._cond:
            if degraded:
                self._degraded_streak += 1
                self._epoch_degraded += 1
            else:
                self._degraded_streak = 0

    def should_escalate(self) -> bool:
        with self._cond:
            return self._degraded_streak >= self.escalate_after

    def take_epoch(self) -> Dict[str, int]:
        """Per-epoch counters for the fallback controller; resets them
        (the consecutive-degraded streak is NOT reset — escalation is
        about the fabric, not the calendar)."""
        with self._cond:
            out = {
                "deadline_expiries": self._epoch_expiries,
                "degraded_steps": self._epoch_degraded,
            }
            self._epoch_expiries = 0
            self._epoch_degraded = 0
            return out

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout=5.0)

    def __enter__(self) -> "CollectiveWatchdog":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


class CommDeadlineGuard:
    """Deadline-expiry policy around a step: one in-place retry, then mark
    degraded, escalate only on K consecutive degraded steps.

    Sits OUTSIDE :class:`GuardedStep` — an expired collective is not an
    exception (the step returns, late, with a VALID state), so the guard
    inspects the watchdog's attempt flag after each call. Requires
    ``donate_state=False`` on the underlying step, same as GuardedStep:
    the retry re-runs on the original inputs. Attribute access delegates
    to the wrapped step."""

    def __init__(
        self,
        step: Callable,
        watchdog: CollectiveWatchdog,
        telemetry: Any = None,
        label: str = "step",
        rank: int = 0,
    ):
        self._inner = step
        self._watchdog = watchdog
        self._telemetry = telemetry
        self._label = label
        self._rank = rank
        self._step_index = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _emit(self, kind: str, step: int, message: str) -> None:
        if self._telemetry is None:
            return
        from ..observe import FailureEvent

        self._telemetry.emit(
            FailureEvent(
                kind=kind, label=self._label, message=message,
                rank=self._rank, step=step,
            )
        )

    def __call__(self, state, batch):
        wd = self._watchdog
        i = self._step_index
        self._step_index += 1
        wd.begin_attempt()
        out = self._inner(state, batch)
        if not wd.expired_this_attempt:
            wd.note_step(False)
            return out
        # a collective blew its deadline: the returned state is usable but
        # the step is suspect — discard it and re-run once in place
        self._emit(
            "comm_step_retry", i,
            "collective deadline expired; retrying step in place",
        )
        wd.begin_attempt()
        out = self._inner(state, batch)
        if not wd.expired_this_attempt:
            wd.note_step(False)
            return out
        wd.note_step(True)
        self._emit(
            "comm_degraded", i,
            "collective deadline expired on retry; step marked degraded",
        )
        if wd.should_escalate():
            raise CommEscalationError(
                f"{self._label}: {wd.escalate_after} consecutive degraded "
                f"steps (collective deadlines); escalating to supervisor"
            )
        return out


class PreemptionGuard:
    """SIGTERM → "checkpoint at the next step boundary, then stop".

    Signal handlers cannot safely save a checkpoint (the step may be
    mid-execution, the state half-donated), so the handler only raises a
    flag; ``resilient_train_loop`` polls :attr:`requested` after every
    completed step and performs the emergency committed save itself, sets
    :attr:`checkpoint_saved`, and returns early. The worker process then
    exits with ``resilience.chaos.PREEMPT_EXIT_CODE`` so the supervisor
    can tell a graceful death from a hard one.

    Use as a context manager (or ``install()``/``uninstall()``) so the
    previous SIGTERM disposition is restored — important in test processes.
    """

    def __init__(self, telemetry: Any = None, rank: int = 0,
                 incarnation: int = 0, label: str = "train"):
        self._telemetry = telemetry
        self._rank = rank
        self._incarnation = incarnation
        self._label = label
        self._prev = None
        self._installed = False
        self._requested = False
        self.checkpoint_saved = False

    @property
    def requested(self) -> bool:
        return self._requested

    def request(self) -> None:
        """Raise the flag without a signal — the handler body, also usable
        directly (e.g. by a cloud preemption-notice poller)."""
        self._requested = True
        if self._telemetry is not None:
            from ..observe import FailureEvent

            self._telemetry.emit(
                FailureEvent(
                    kind="preempt_notice", label=self._label,
                    rank=self._rank, incarnation=self._incarnation,
                    message="SIGTERM received; emergency checkpoint at next"
                            " step boundary",
                )
            )

    def _handle(self, signum, frame) -> None:
        self.request()

    def install(self) -> "PreemptionGuard":
        self._prev = signal.signal(signal.SIGTERM, self._handle)
        self._installed = True
        return self

    def uninstall(self) -> None:
        if self._installed:
            signal.signal(signal.SIGTERM, self._prev or signal.SIG_DFL)
            self._installed = False

    def __enter__(self) -> "PreemptionGuard":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()


class GuardedStep:
    """Retry-on-transient + non-finite-loss rejection around a compiled
    step, plus the OOM forensics trap. Attribute access delegates to the
    wrapped step.

    The optional memory-observability hooks feed the post-mortem:
    ``memory_sampler`` (an ``observe.memory.MemorySampler``; its last
    sample becomes the report's live side), ``footprint`` (the
    compile-time split dict from ``observe.memory.memory_footprint_fields``),
    and ``buffers_fn`` (a zero-arg callable returning
    ``{buffer_class: bytes}`` — params / EF memory / serving slots — so
    the report names the top suspect). All default to None: the guard
    still detects the OOM and writes a minimal report without them."""

    def __init__(
        self,
        step: Callable,
        retries: int = 2,
        backoff_seconds: float = 0.05,
        max_backoff_seconds: float = 5.0,
        jitter: float = 0.1,
        telemetry: Any = None,
        label: str = "step",
        rank: int = 0,
        memory_sampler: Any = None,
        footprint: Optional[Dict] = None,
        buffers_fn: Optional[Callable[[], Dict[str, float]]] = None,
        oom_report_path: Optional[str] = None,
    ):
        self._inner = step
        self.retries = retries
        self.backoff_seconds = backoff_seconds
        self.max_backoff_seconds = max_backoff_seconds
        self.jitter = jitter
        self._telemetry = telemetry
        self._label = label
        self._rank = rank
        self.memory_sampler = memory_sampler
        self.footprint = footprint
        self._buffers_fn = buffers_fn
        self._oom_report_path = oom_report_path
        self._step_index = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _oom(self, exc: BaseException) -> "OutOfMemoryError":
        """Build + persist the post-mortem, emit the failure event, and
        return the non-retryable exception for the caller to raise. Every
        forensics step is best-effort — the process is dying either way,
        and a broken report path must not mask the real OOM."""
        from ..observe.memory import build_oom_report, write_oom_report

        last = getattr(self.memory_sampler, "last", None)
        buffers = None
        if self._buffers_fn is not None:
            try:
                buffers = self._buffers_fn()
            except Exception:
                buffers = None
        report = build_oom_report(
            error=str(exc),
            label=self._label,
            rank=self._rank,
            step=self._step_index,
            last_memory=last.record() if last is not None else None,
            footprint=self.footprint,
            buffers=buffers,
        )
        try:
            path = write_oom_report(report, self._oom_report_path)
        except OSError:
            path = None
        if self._telemetry is not None:
            from ..observe import FailureEvent

            self._telemetry.emit(
                FailureEvent(
                    kind="oom",
                    label=self._label,
                    rank=self._rank,
                    step=self._step_index,
                    message=(
                        f"device out of memory"
                        f" (top buffer: {report['top_buffer'] or 'unknown'};"
                        f" forensics: {path or 'unwritable'})"
                    ),
                )
            )
        return OutOfMemoryError(
            f"{self._label}: device out of memory at step "
            f"{self._step_index}; forensics at {path or '<unwritable>'}"
        )

    def __call__(self, state, batch):
        import jax

        # lazy: utils' package import pulls jax, which the supervisor
        # parent (importing this module via resilience/__init__) must avoid
        from ..utils.failure import retry_transient

        def attempt():
            try:
                new_state, loss = self._inner(state, batch)
                # forces the step to completion; a non-finite loss means
                # the update that produced it is poison — discard
                # new_state and let retry re-run from the (non-donated)
                # inputs. device_get is inside the try because async
                # dispatch surfaces allocator deaths here, not at launch
                host_loss = float(jax.device_get(loss))
            except RuntimeError as err:
                if is_oom_error(err):
                    raise self._oom(err) from err
                raise
            if not math.isfinite(host_loss):
                raise NonFiniteLossError(
                    f"{self._label}: non-finite loss {host_loss}"
                )
            return new_state, loss

        try:
            return retry_transient(
                attempt,
                retries=self.retries,
                backoff_seconds=self.backoff_seconds,
                max_backoff_seconds=self.max_backoff_seconds,
                jitter=self.jitter,
                exceptions=(RuntimeError,),
                telemetry=self._telemetry,
                label=self._label,
            )
        finally:
            self._step_index += 1


def guarded_batches(
    batches_for_epoch: Callable[[int], Iterator[Any]],
    expected_batch: Optional[int] = None,
    telemetry: Any = None,
    label: str = "loader",
) -> Callable[[int], Iterator[Any]]:
    """Wrap a per-epoch batch generator factory: malformed batches (wrong
    leading dim, non-finite floats) are dropped with a
    ``FailureEvent(kind="bad_batch_dropped")`` instead of reaching the
    compiled step, where they would recompile (shape) or poison the
    parameters (NaN)."""
    import numpy as np

    from ..observe import FailureEvent

    def problem(batch) -> Optional[str]:
        leaves = list(batch.values()) if isinstance(batch, dict) else list(batch)
        lead = {np.asarray(a).shape[0] for a in leaves}
        if len(lead) > 1:
            return f"ragged leading dims {sorted(lead)}"
        if expected_batch is not None and lead and lead != {expected_batch}:
            return f"leading dim {lead.pop()} != expected {expected_batch}"
        for a in leaves:
            arr = np.asarray(a)
            if np.issubdtype(arr.dtype, np.floating) and not np.all(
                np.isfinite(arr)
            ):
                return "non-finite values"
        return None

    def gen(epoch: int):
        for i, batch in enumerate(batches_for_epoch(epoch)):
            reason = problem(batch)
            if reason is not None:
                if telemetry is not None:
                    telemetry.emit(
                        FailureEvent(
                            kind="bad_batch_dropped",
                            label=label,
                            step=i,
                            message=f"epoch {epoch}: {reason}",
                        )
                    )
                continue
            yield batch

    return gen
