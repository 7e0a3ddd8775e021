"""Device-memory observability: the HBM footprint plane.

Time and bytes-on-wire are measured exhaustively elsewhere (spans/MFU,
the wire ledger + fabric matrix); this module covers the third axis that
kills runs — device memory — with the same predicted-vs-measured
discipline the cost model uses:

- **Compile-time footprint audit** (:func:`memory_footprint_fields`):
  XLA's per-executable buffer-assignment split
  (argument/output/temp/generated-code bytes) via
  :func:`compiled_memory`, attached to the
  :class:`observe.events.CompileEvent` next to the FLOPs fields so every
  jitted step publishes its predicted peak. This side is EXACT per
  executable (see DESIGN.md guarantee classes).
- **Live telemetry** (:class:`MemorySampler`): ``device.memory_stats()``
  sampled every ``--health-every`` steps into typed
  :class:`observe.events.MemoryEvent` records — allocator-level numbers,
  merge-tolerance across ranks, never bitwise. On backends without
  ``memory_stats`` (CPU) the sampler degrades to a one-way no-op: it
  checks once, disables itself, and never logs — no per-step spam.
- **OOM forensics** (:func:`build_oom_report` /
  :func:`write_oom_report`): the ranked per-buffer post-mortem the
  guarded step dumps to ``artifacts/oom_report.json`` on
  ``RESOURCE_EXHAUSTED``, joining the last live sample, the compile-time
  split, and the caller's buffer-class attribution (params / EF memory /
  serving slots) so the report names the top buffer class instead of
  just the corpse.

Import contract: this module is imported by the jax-free ``observe``
package ``__init__`` — jax is only ever imported lazily inside the
functions that genuinely need a device handle. Clock discipline: the
module reads NO clock at all; event timestamps come from the telemetry's
``ts``/``ts_mono`` stamping like every other event source
(``scripts/lint_no_print.py``'s monotonic-clock lint covers this file —
``observe/memory.py`` is deliberately NOT in its ``MONO_ALLOWED`` set).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from .events import MemoryEvent

OOM_REPORT_NAME = "oom_report.json"

# the memory_stats() keys the sampler carries into MemoryEvent (allocator
# vocabulary shared by the TPU and GPU jax backends)
_STAT_FIELDS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")

# the compile-time split fields, in the order the report renders them
FOOTPRINT_FIELDS = (
    "argument_bytes",
    "output_bytes",
    "temp_bytes",
    "generated_code_bytes",
)


def compiled_memory(compiled) -> Optional[Dict[str, float]]:
    """XLA's buffer-assignment split for a ``jax.stages.Compiled``:
    ``{"argument_bytes", "output_bytes", "temp_bytes",
    "generated_code_bytes"}`` floats read off ``memory_analysis()``'s
    ``*_size_in_bytes`` attributes, or ``None`` when there is no
    executable or the backend returns no analysis."""
    if compiled is None:
        return None
    mem = compiled.memory_analysis()
    if mem is None:
        return None
    out = {}
    for field in FOOTPRINT_FIELDS:
        value = getattr(mem, field.replace("_bytes", "_size_in_bytes"), None)
        if isinstance(value, (int, float)):
            out[field] = float(value)
    return out or None


def memory_footprint_fields(compiled) -> Dict:
    """CompileEvent kwargs for the compile-time HBM footprint of a
    ``jax.stages.Compiled`` — the predicted side of the memory join.

    ``peak_hbm_bytes`` is the split's sum: XLA's buffer assignment
    accounts arguments, outputs, temps, and generated code separately,
    and their total is the executable's device-memory high water.
    Empty dict (NOT None) when there is no analysis, so callers can
    always ``**`` it.
    """
    out = compiled_memory(compiled)
    if not out:
        return {}
    out["peak_hbm_bytes"] = sum(out.values())
    return out


def device_memory_stats(device=None) -> Optional[Dict]:
    """The allocator's view of one device's memory, normalized to
    ``{"bytes_in_use", "peak_bytes_in_use", "bytes_limit"}`` floats (a
    key the backend omits is absent). None when the backend has no
    ``memory_stats`` (CPU returns None, older backends raise) — the
    caller treats that as "this plane does not exist here", silently.
    """
    try:
        import jax

        if device is None:
            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    if not isinstance(stats, dict):
        return None
    out = {
        name: float(stats[name])
        for name in _STAT_FIELDS
        if isinstance(stats.get(name), (int, float))
    }
    return out or None


def all_device_memory_stats() -> List[Dict]:
    """:func:`device_memory_stats` for EVERY local device, each row tagged
    with its device ``id`` — the sampler and the OOM report read device 0
    only, which cannot show whether a multi-chip run put anything on the
    other chips. Empty where the backend has no ``memory_stats`` (CPU)."""
    import jax

    rows = []
    for device in jax.local_devices():
        stats = device_memory_stats(device)
        if stats:
            rows.append({"id": device.id, **stats})
    return rows


class MemorySampler:
    """Periodic device-memory probe riding the ``--health-every`` cadence.

    ``sample(step)`` reads :func:`device_memory_stats` and emits one
    :class:`MemoryEvent` through the telemetry. The first read that
    comes back empty disables the sampler permanently (``enabled`` goes
    False): a CPU run probes exactly once and then no-ops with zero
    events and zero log lines, per the graceful-degradation contract.
    """

    def __init__(self, telemetry, label: str = "", rank: Optional[int] = None,
                 device=None):
        self._telemetry = telemetry
        self._label = label
        self._rank = rank
        self._device = device
        self._device_kind = ""
        self.enabled = True

    def _resolve_device(self):
        if self._device is None:
            try:
                import jax

                self._device = jax.local_devices()[0]
            except Exception:
                return None
        if not self._device_kind:
            self._device_kind = str(
                getattr(self._device, "device_kind", "") or ""
            )
        return self._device

    def sample(self, step: int) -> Optional[MemoryEvent]:
        if not self.enabled:
            return None
        stats = device_memory_stats(self._resolve_device())
        if not stats:
            self.enabled = False
            return None
        event = MemoryEvent(
            step=int(step),
            bytes_in_use=stats.get("bytes_in_use"),
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            bytes_limit=stats.get("bytes_limit"),
            device_kind=self._device_kind,
            rank=self._rank,
            label=self._label,
        )
        self.last = event
        if self._telemetry is not None:
            self._telemetry.emit(event)
        return event


def tree_bytes(tree) -> int:
    """Device bytes held by a jax pytree's array leaves (params, EF
    memories, KV caches) — the buffer-class attribution input of the OOM
    report. 0 for None/empty trees; non-array leaves count nothing."""
    try:
        import jax
    except Exception:
        return 0
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if isinstance(size, int) and isinstance(itemsize, int):
            total += size * itemsize
    return total


def build_oom_report(
    error: str = "",
    label: str = "",
    rank: Optional[int] = None,
    step: Optional[int] = None,
    last_memory: Optional[Dict] = None,
    footprint: Optional[Dict] = None,
    buffers: Optional[Dict[str, float]] = None,
) -> Dict:
    """The OOM post-mortem document: buffer classes ranked by bytes
    (largest first — ``top_buffer`` names the leading suspect), the last
    live :class:`MemoryEvent` record, and the compile-time footprint
    split. Pure dict assembly, jax-free — the toy worker builds the same
    document for the chaos game day."""
    ranked: List[Dict] = sorted(
        (
            {"name": str(name), "bytes": float(b)}
            for name, b in (buffers or {}).items()
            if isinstance(b, (int, float)) and b >= 0
        ),
        key=lambda row: -row["bytes"],
    )
    return {
        "schema": 1,
        "kind": "oom",
        "label": label,
        "rank": rank,
        "step": step,
        "error": str(error)[:2000],
        "last_memory": dict(last_memory) if last_memory else None,
        "footprint": dict(footprint) if footprint else None,
        "buffers": ranked,
        "top_buffer": ranked[0]["name"] if ranked else None,
    }


def write_oom_report(report: Dict, path: Optional[str] = None) -> str:
    """Persist the post-mortem (default ``artifacts/oom_report.json``),
    atomically — the process is about to die and a torn forensics file
    would be worse than none."""
    if path is None:
        path = os.path.join("artifacts", OOM_REPORT_NAME)
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, default=str)
    os.replace(tmp, path)
    return path
