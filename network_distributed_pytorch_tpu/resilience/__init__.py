"""resilience — fault injection, recovery guards, and the supervising
launcher.

The reference's entire failure story is a rendezvous timeout that prints a
banner and falls through (``ddp_guide_cifar10/ddp_init.py:98-99``, SURVEY
§5: "no retry, no elasticity, no save/load anywhere") — on a 100-epoch run
over slow links, the paper's own flagship regime, that means any preemption
or peer death is a silent full restart. ``utils.failure`` and
``utils.checkpoint`` provide the primitives (watchdog, heartbeat, retry,
committed checkpoints); this package is the layer that exercises and
operates them:

- :mod:`resilience.chaos`      — deterministic, schedule-driven fault
  injection (``ChaosPlan``): every failure path in the repo becomes
  testable on CPU with no wall-clock randomness.
- :mod:`resilience.guards`     — the recovery side: a step wrapper that
  retries transient errors and rejects non-finite losses, a batch guard
  that drops malformed loader output, and the ``PreemptionGuard`` that
  turns SIGTERM into an emergency committed checkpoint at the next step
  boundary.
- :mod:`resilience.supervisor` — the restarting launcher: spawns per-rank
  workers, watches exit codes and heartbeats, restarts crashed/hung ranks
  with bounded backoff (SIGTERM-then-SIGKILL with a grace window, never a
  bare kill), resumes from the newest committed checkpoint, and degrades
  to a shrunk world when a rank is permanently gone.
- :mod:`resilience.controller` — the degraded-fabric policy loop: an
  ordered fallback ladder over the comm knobs (PowerSGD compression →
  widened sync period → two-level reduction) walked down on degraded
  epoch verdicts and back up, with hysteresis, when the fabric recovers —
  every move a typed ``PolicyEvent``.
- :mod:`resilience.reshard`    — what makes the degraded restart lossless:
  deterministic state resharding from a topology-tagged checkpoint across
  MESH shapes, not just world sizes (EF memories fold by summation — or
  zero-pad on a widening data axis — preserving the unsent-error sum
  bit-for-bit, TP-sharded params merge/re-split by pure byte movement,
  per-worker stats merge, partitions re-split from the fixed permutation,
  global batch preserved via accumulation rescale).

Disaster-recovery extensions (PR 11): correlated chaos faults
(``zone_outage``, ``host_flap``, ``ckpt_unwritable``), the supervisor's
quorum restart planner (``plan_mesh`` — classify deaths in a window as
correlated vs independent, restart the survivors at the largest viable
mesh), and the typed ``CheckpointUnwritableError`` fail-fast path.

Memory observatory extensions: the ``oom`` chaos fault
(``ChaosOutOfMemoryError``, shaped like the real ``RESOURCE_EXHAUSTED``),
and ``GuardedStep``'s OOM forensics trap — detect by message, dump the
ranked post-mortem to ``artifacts/oom_report.json`` via
``observe.memory``, and re-raise as the non-retryable
``OutOfMemoryError``.

The whole package is jax-free at import time (the supervisor parent
process never initializes a backend; workers do — reshard/guards import
jax lazily inside the functions that touch pytrees).
"""

from .chaos import (  # noqa: F401
    CHAOS_EXIT_CODE,
    CHECKPOINT_FAULTS,
    CKPT_UNWRITABLE_EXIT_CODE,
    COMM_FAULTS,
    CORRELATED_FAULTS,
    FAULT_KINDS,
    INJECTION_SITES,
    LOADER_FAULTS,
    MEMORY_FAULTS,
    PREEMPT_EXIT_CODE,
    PROCESS_FAULTS,
    STEP_FAULTS,
    ChaosOutOfMemoryError,
    ChaosPlan,
    ChaosStep,
    ChaosTransientError,
    CommFaultInjector,
    FaultSpec,
    apply_checkpoint_fault,
    chaos_batches,
    check_fault_registry,
    make_checkpoint_unwritable,
    restore_checkpoint_writable,
)
from .controller import (  # noqa: F401
    DEFAULT_LADDER,
    EpochHealth,
    FallbackController,
    PolicyDecision,
    Rung,
    ladder_from_plan,
)
from .guards import (  # noqa: F401
    CheckpointUnwritableError,
    CollectiveWatchdog,
    CommDeadlineError,
    CommDeadlineGuard,
    CommEscalationError,
    GuardedStep,
    NonFiniteLossError,
    OutOfMemoryError,
    PreemptionGuard,
    derive_collective_deadline,
    guarded_batches,
    is_oom_error,
)
from .reshard import (  # noqa: F401
    MESH_AXES,
    derive_rank_key,
    fold_groups,
    fold_memories,
    make_topology,
    memory_total,
    merge_model_state,
    merge_tp_leaf,
    mesh_world,
    normalize_mesh_axes,
    rescale_accum_steps,
    reshard_from_checkpoint,
    reshard_mesh_state,
    reshard_tp_params,
    reshard_train_state,
    split_tp_leaf,
    topology_mesh,
    widen_memories,
    widen_model_state,
    widen_template,
)
from .scheduler import (  # noqa: F401
    FleetConfig,
    FleetScheduler,
    JobManifest,
    JobSpool,
)
from .supervisor import (  # noqa: F401
    Supervisor,
    SupervisorConfig,
    SupervisorResult,
    incarnation_from_env,
    mesh_from_env,
    plan_mesh,
)
