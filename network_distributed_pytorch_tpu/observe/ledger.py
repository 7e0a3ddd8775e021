"""The per-collective wire ledger.

The analytic bytes-on-wire model (the reference's ``n_bits`` convention,
``reducer.py:197-198``) lived as ONE opaque integer per step
(``bits_per_step``). The ledger itemizes it: every collective a compiled
step issues gets a line — (tag, originating layer, op, mesh axis, dtype,
payload bytes, count) — so a run report can say not just "4.2 MB/step" but
*which* subsystem moved the bytes (reducer P/Q factors vs rank-1 payload
vs trainer loss-sync vs FSDP gather/scatter vs pipeline activations).

``reconcile`` checks the itemized total against the post-optimization HLO
(``utils.hlo_audit``) — byte-exact by construction for every reducer in
the repo, and the delta is an explicit signed field when it isn't.
:func:`audit_compiled_step` runs that reconciliation at trainer-compile
time and emits the result through telemetry (``CollectiveEvent`` per line
+ one ``CompileEvent``).

Module top level is jax-free; jax / HLO helpers are imported inside the
functions that need a compiled executable.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from .events import CollectiveEvent, CompileEvent

# the trainer's scalar-loss pmean (trainer.LOSS_SYNC_BITS = 32 bits); a
# literal here because trainer imports this module
_LOSS_SYNC_BYTES = 4


@dataclass(frozen=True)
class LedgerEntry:
    """One ledger line. ``payload_bytes`` is the TOTAL across all ``count``
    collectives of the entry (per-collective payloads may differ within an
    unpacked per-tensor entry, so the total is the well-defined number)."""

    tag: str  # "grads", "powersgd.P", "loss-sync", "fsdp.param-gather", ...
    layer: str  # reducer | trainer | fsdp | pipeline
    op: str  # all-reduce | all-gather | reduce-scatter | ...
    axis: str  # mesh axis name ("data", "pipe", ...); "" = unattributed
    dtype: str
    payload_bytes: int
    count: int = 1


class WireLedger:
    """The itemization of a compiled step's ``bits_per_step``.

    ``dense_grad_bits`` (when known) is the uncompressed gradient size —
    the numerator of the compression ratio a run report shows."""

    def __init__(
        self,
        entries: Sequence[LedgerEntry] = (),
        dense_grad_bits: Optional[int] = None,
    ):
        self.entries: List[LedgerEntry] = list(entries)
        self.dense_grad_bits = dense_grad_bits

    def add(self, entry: LedgerEntry) -> LedgerEntry:
        self.entries.append(entry)
        return entry

    def total_bytes(self) -> int:
        return sum(e.payload_bytes for e in self.entries)

    def total_bits(self) -> int:
        return 8 * self.total_bytes()

    def by_tag(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.entries:
            out[e.tag] = out.get(e.tag, 0) + e.payload_bytes
        return out

    def by_layer(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.entries:
            out[e.layer] = out.get(e.layer, 0) + e.payload_bytes
        return out

    def layer_bytes(self, layer: str) -> int:
        return sum(e.payload_bytes for e in self.entries if e.layer == layer)

    def compression_ratio(self) -> Optional[float]:
        """dense gradient bytes / reducer-layer wire bytes (1.0 = exact DDP;
        None when either side is unknown/zero)."""
        reducer_bytes = self.layer_bytes("reducer")
        if not reducer_bytes or self.dense_grad_bits is None:
            return None
        return (self.dense_grad_bits / 8) / reducer_bytes

    def collective_events(self, label: str) -> List[CollectiveEvent]:
        return [
            CollectiveEvent(
                label=label,
                tag=e.tag,
                layer=e.layer,
                op=e.op,
                axis=e.axis,
                dtype=e.dtype,
                payload_bytes=e.payload_bytes,
                count=e.count,
            )
            for e in self.entries
        ]

    def reconcile(self, hlo_text: str) -> Dict:
        """Analytic total vs the compiled HLO's collective payloads
        (``utils.hlo_audit.collective_summary``). The delta is signed and
        always reported."""
        from ..utils.hlo_audit import collective_summary

        summary = collective_summary(hlo_text)
        analytic = self.total_bytes()
        hlo_bytes = int(summary["total_payload_bytes"])
        return {
            "analytic_bytes": analytic,
            "hlo_bytes": hlo_bytes,
            "delta_bytes": hlo_bytes - analytic,
            "exact": hlo_bytes == analytic,
            "hlo_by_kind": dict(summary["by_kind"]),
            "hlo_collective_count": int(summary["count"]),
        }


def loss_sync_entry(axis: str) -> LedgerEntry:
    """The trainer's one non-reducer collective: the scalar loss pmean for
    reporting (``trainer.LOSS_SYNC_BITS``)."""
    return LedgerEntry(
        tag="loss-sync",
        layer="trainer",
        op="all-reduce",
        axis=axis,
        dtype="float32",
        payload_bytes=_LOSS_SYNC_BYTES,
    )


def reducer_ledger_entries(
    reducer, params_template, axis: str, n_workers: int = 1
) -> List[LedgerEntry]:
    """Itemized entries for one reduction of ``params_template``. Reducers
    that know their structure implement ``ledger_entries`` (ExactReducer,
    PowerSGDReducer); anything else gets one opaque entry at its analytic
    ``bits_per_step`` so the ledger total still matches the step's."""
    if hasattr(reducer, "ledger_entries"):
        return list(
            reducer.ledger_entries(params_template, axis=axis, n_workers=n_workers)
        )
    import jax

    leaves = jax.tree_util.tree_leaves(params_template)
    if hasattr(reducer, "bits_per_step"):
        bits = reducer.bits_per_step(params_template, n_workers=n_workers)
    else:
        bits = sum(8 * int(l.size) * l.dtype.itemsize for l in leaves)
    dtypes = {str(l.dtype) for l in leaves}
    return [
        LedgerEntry(
            tag="reduction",
            layer="reducer",
            op="all-reduce",
            axis=axis,
            dtype=dtypes.pop() if len(dtypes) == 1 else "mixed",
            payload_bytes=bits // 8,
        )
    ]


def step_ledger(
    reducer,
    params_template,
    axis: str,
    n_workers: int,
    expected_bits: Optional[int] = None,
    include_loss_sync: bool = True,
) -> WireLedger:
    """The trainer's compile-time ledger: reducer entries + the loss-sync
    pmean (skipped for the single-process step, which has no mesh and no
    loss collective), with the dense gradient size recorded for the
    compression ratio. ``expected_bits`` (the step's ``bits_per_step``)
    pins the invariant that the ledger is an ITEMIZATION of the analytic
    model, not a second model that can drift."""
    import jax

    entries = reducer_ledger_entries(reducer, params_template, axis, n_workers)
    if include_loss_sync:
        entries.append(loss_sync_entry(axis))
    dense = sum(
        8 * int(l.size) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params_template)
    )
    ledger = WireLedger(entries, dense_grad_bits=dense)
    if expected_bits is not None and ledger.total_bits() != expected_bits:
        raise AssertionError(
            f"wire ledger itemizes {ledger.total_bits()} bits but the step's "
            f"analytic bits_per_step is {expected_bits} — the ledger must sum "
            f"to the model it itemizes (entries: {entries})"
        )
    return ledger


def ledger_from_hlo_summary(summary: Dict, layer: str, axis: str = "") -> WireLedger:
    """A ledger derived FROM a compiled HLO audit (the pipeline/sequence
    experiments, whose wire traffic is activation collectives the analytic
    model doesn't itemize): one entry per collective kind. Reconciling this
    ledger against the same HLO is exact by construction."""
    by_kind: Dict[str, Dict] = {}
    for op in summary["ops"]:
        slot = by_kind.setdefault(
            op.kind, {"payload": 0, "count": 0, "dtypes": set()}
        )
        slot["payload"] += op.payload_bytes
        slot["count"] += 1
        slot["dtypes"].add(op.dtype)
    entries = [
        LedgerEntry(
            tag=kind,
            layer=layer,
            op=kind,
            axis=axis,
            dtype=slot["dtypes"].pop() if len(slot["dtypes"]) == 1 else "mixed",
            payload_bytes=slot["payload"],
            count=slot["count"],
        )
        for kind, slot in sorted(by_kind.items())
    ]
    return WireLedger(entries)


def _overlap_extract(report: Dict) -> Dict:
    keys = (
        "scheduled",
        "n_async_collectives",
        "n_overlapped",
        "n_async_copy_windows",
        "n_copy_windows_with_compute",
        "n_sync_collectives",
        "n_sync_gaps_with_compute",
        "sync_interleaved",
        "collective_emitters",
    )
    return {k: report[k] for k in keys if k in report}


def compiled_cost(compiled) -> Optional[Dict[str, float]]:
    """XLA's cost model for a ``jax.stages.Compiled`` as a flat
    ``{metric: float}`` dict (``"flops"``, ``"bytes accessed"``, ...), or
    ``None`` when it reports no flops — callers then use the analytic
    count."""
    cost = compiled.cost_analysis()
    if not cost:
        return None
    out = {
        k: float(v) for k, v in cost.items() if isinstance(v, (int, float))
    }
    return out if out.get("flops") else None


def device_cost_fields(compiled, analytic_flops: Optional[float] = None) -> Dict:
    """The ``CompileEvent`` device-cost extension for an AOT executable:
    XLA's own per-execution cost model when it reports flops
    (:func:`compiled_cost`), else the caller's analytic FLOPs count, plus
    the device identity the peak-FLOPs table is keyed on. Returns kwargs
    for ``CompileEvent`` (just ``device_kind`` when neither source knows a
    FLOPs number)."""
    import jax

    from .mfu import peak_flops

    dev = jax.devices()[0]
    device_kind, platform = dev.device_kind, dev.platform
    cost = compiled_cost(compiled) if compiled is not None else None
    if cost is not None:
        flops, source = cost["flops"], "cost_analysis"
        bytes_accessed = cost.get("bytes accessed")
    elif analytic_flops and analytic_flops > 0:
        flops, source, bytes_accessed = float(analytic_flops), "analytic", None
    else:
        return {"device_kind": device_kind}
    peak = peak_flops(device_kind, platform)
    return {
        "flops_per_step": flops,
        "bytes_accessed_per_step": bytes_accessed,
        "flops_source": source,
        "device_kind": device_kind,
        "peak_flops_per_s": peak if peak > 0 else None,
    }


def audit_compiled_step(step, *args, label: str = "train_step", telemetry=None) -> CompileEvent:
    """AOT-compile ``step.fn(*args)``, reconcile the step's wire ledger
    against the executable's HLO, extract the overlap evidence and the
    device-cost fields (``observe.mfu``'s FLOPs join inputs), and emit
    the result (one ``CollectiveEvent`` per ledger line + a
    ``CompileEvent``) through ``telemetry``.

    This pays one extra XLA compile (the AOT lowering does not populate the
    jit call cache), which is why experiment drivers gate it behind the
    config's audit flag."""
    from ..utils.hlo_audit import hlo_text_of_compiled
    from ..utils.overlap import overlap_report
    from .memory import memory_footprint_fields
    from .spans import span

    ledger = getattr(step, "ledger", None)
    if ledger is None:
        # steps without an itemized ledger still get the honesty check
        # against their one-number analytic model
        ledger = WireLedger(
            [
                LedgerEntry(
                    tag="step",
                    layer="trainer",
                    op="all-reduce",
                    axis="",
                    dtype="unknown",
                    payload_bytes=getattr(step, "bits_per_step", 0) // 8,
                )
            ]
        )
    with span("audit/compile"):
        compiled = step.fn.lower(*args).compile()
        hlo_text = hlo_text_of_compiled(compiled)
    rec = ledger.reconcile(hlo_text)
    event = CompileEvent(
        label=label,
        analytic_bytes=rec["analytic_bytes"],
        hlo_bytes=rec["hlo_bytes"],
        delta_bytes=rec["delta_bytes"],
        exact=rec["exact"],
        hlo_collective_count=rec["hlo_collective_count"],
        hlo_by_kind=rec["hlo_by_kind"],
        dense_grad_bytes=(
            ledger.dense_grad_bits // 8 if ledger.dense_grad_bits else None
        ),
        compression_ratio=ledger.compression_ratio(),
        overlap=_overlap_extract(overlap_report(hlo_text)),
        # which comm config this step compiled with (parallel.trainer
        # stamps it on CompiledStep) — the offline cost model's join key
        comm_config=dict(getattr(step, "comm_config", None) or {}),
        **device_cost_fields(
            compiled, getattr(step, "flops_per_step", None)
        ),
        # the compile-time HBM footprint split (observe.memory) — empty
        # kwargs on backends without memory_analysis, so the predicted
        # side of the memory join degrades to absent, never crashes
        **memory_footprint_fields(compiled),
    )
    if telemetry is not None:
        for ce in ledger.collective_events(label):
            telemetry.emit(ce)
        telemetry.emit(event)
    return event
