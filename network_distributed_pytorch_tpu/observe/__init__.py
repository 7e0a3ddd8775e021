"""observe — the unified telemetry subsystem.

The reference's defining feature is bytes-on-wire accounting at every
collective, yet it never ships the reporting loop (SURVEY C9:
``bits_communicated`` accumulated but never printed). This package closes
that loop as a first-class subsystem instead of scattered fragments:

- :mod:`observe.events`    — ONE typed event model (``StepEvent``,
  ``CollectiveEvent``, ``CompileEvent``, ``EpochEvent``, ``FailureEvent``)
  shared by the trainer, the reducers, the experiment drivers, the failure
  machinery, and ``bench.py``.
- :mod:`observe.sinks`     — pluggable outputs: the stdout banner sink (the
  only sanctioned ``print`` site in the package, lint-enforced), a JSONL
  file sink for run logs, a raw-JSON stream sink for driver-facing
  contracts (bench/launch), and an in-memory sink for tests.
- :mod:`observe.telemetry` — the process-local registry events flow
  through; experiments build theirs from ``ExperimentConfig.event_log``.
- :mod:`observe.ledger`    — the per-collective **wire ledger**: every
  collective a compiled step issues, tagged with (layer, op, axis, dtype,
  payload bytes), reconciled byte-exactly against the compiled HLO via
  ``utils.hlo_audit`` at trainer-compile time.
- :mod:`observe.runlog`    — the RUN level: the manifest a supervised
  launch writes (run id, world size, shard layout, spawn records) and the
  merger that aligns per-rank shards into one supervisor-clock-ordered
  timeline (run-start-marker clock-offset correction, torn-tail
  tolerance).
- :mod:`observe.analytics` — straggler detection (typed
  ``StragglerEvent``) and the effective-bandwidth estimator joining
  ledger bytes, measured step times, and schedule overlap attribution.
- :mod:`observe.critpath`  — the cross-rank critical-path analyzer:
  per-step blame attribution (which rank, which phase, which ring edge
  gated the step) as typed ``CritPathEvent`` records, stitched from the
  merged span shards and the ledger's synchronization semantics.
- :mod:`observe.fabric`    — the measured per-edge fabric matrix
  (``artifacts/fabric_matrix.json``): effective bandwidth/latency per
  (src, dst) ring neighbor, consumed back through
  ``utils.bandwidth.fabric_model`` by the cost model and the live plane.
- :mod:`observe.spans`     — nested, thread-safe host-side spans
  (``with span("step/compute"): ...``) emitting typed ``SpanEvent``
  records through the ambient recorder and mirrored into
  ``jax.profiler.TraceAnnotation`` when jax is loaded.
- :mod:`observe.mfu`       — per-phase MFU accounting: peak-FLOPs/HBM
  device tables, the analytic-vs-``cost_analysis`` FLOPs join, and the
  roofline verdict (compute / hbm / comm-exposed) as typed ``MfuEvent``
  records.
- :mod:`observe.live`      — the LIVE plane: streaming metric registry,
  resumable shard tailing, the supervisor-side aggregator, and the
  Prometheus-text ``/metrics`` exposition server.
- :mod:`observe.health`    — EWMA streaming detectors (grad-norm spike,
  loss plateau, step-time drift, bandwidth collapse, serving SLO burn,
  HBM headroom) emitting typed ``AlertEvent`` records back into the
  control plane.
- :mod:`observe.fidelity`  — the gradient-fidelity plane: the
  host-side tracker turning health-probe per-group compression
  diagnostics into typed ``FidelityEvent`` records (EF growth, replica/
  anchor drift), the per-group report aggregation behind the gate's
  ``fidelity_rel_error``, and the accuracy-per-byte frontier
  (``artifacts/fidelity_frontier.json``) joining loss against cumulative
  ledger bytes per fallback-ladder rung.
- :mod:`observe.memory`    — the device-memory plane: the compile-time
  HBM footprint audit (``memory.compiled_memory`` joined onto
  ``CompileEvent``), the live ``device.memory_stats()`` sampler emitting
  typed ``MemoryEvent`` records, and the OOM post-mortem builder behind
  ``artifacts/oom_report.json``.

``scripts/report.py`` turns a JSONL run log back into a human report
(step-time percentiles, bytes/step by tag, compression ratio,
analytic-vs-HLO delta, overlap stats) — and with ``--run-dir``, a whole
run directory into the merged multi-rank report plus
``artifacts/run_report.json``, which ``scripts/gate.py`` compares against
the recorded baseline.

Everything imported here is jax-free, so the bench parent orchestrator
(which deliberately imports no jax) can use the same sinks.
"""

from . import (  # noqa: F401
    analytics,
    costmodel,
    critpath,
    fabric,
    fidelity,
    health,
    live,
    memory,
    mfu,
    runlog,
    spans,
)
from .events import (  # noqa: F401
    SCHEMA_VERSION,
    AlertEvent,
    AutoscaleEvent,
    CollectiveEvent,
    CompileEvent,
    CritPathEvent,
    DataDropEvent,
    EpochEvent,
    Event,
    FailureEvent,
    FidelityEvent,
    JobEvent,
    JobFailedEvent,
    KVPoolEvent,
    LoaderEvent,
    MarkerEvent,
    MemoryEvent,
    MfuEvent,
    NoteEvent,
    PartitionEvent,
    PolicyEvent,
    PredictionEvent,
    PreemptEvent,
    RawEvent,
    RequestEvent,
    ReshapeEvent,
    ScheduleEvent,
    SpanEvent,
    StepEvent,
    StragglerEvent,
    TrainHealthEvent,
)
from .ledger import LedgerEntry, WireLedger  # noqa: F401
from .spans import recording, set_ambient, span  # noqa: F401
from .sinks import (  # noqa: F401
    JsonlSink,
    MemorySink,
    Sink,
    StdoutSink,
    StreamJsonSink,
)
from .telemetry import (  # noqa: F401
    Telemetry,
    audit_from_config,
    default_telemetry,
    telemetry_for_run,
    telemetry_from_config,
)
