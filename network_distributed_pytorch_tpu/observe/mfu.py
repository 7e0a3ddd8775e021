"""Per-phase MFU accounting and roofline classification.

The round-5 chip bench recorded 22.8k imgs/sec but MFU 0.0047 on TPU v5
lite — the hardware was ~99% idle and nothing could say *why*. This module
is the measurement layer that answers it, as three jax-free pieces:

**The device tables.** ``PEAK_BF16_FLOPS`` (peak dense bf16 FLOP/s per
chip) and ``HBM_BYTES_PER_S`` (per-chip HBM bandwidth), both keyed by
``device_kind`` substring from public spec sheets — longest match wins
("v5 lite" before "v5"). ``bench.py`` delegates its peak lookup here, so
there is exactly one provenance for the numbers the gate compares.

**The FLOPs join.** At compile time the trainer records per-step FLOPs on
its :class:`observe.events.CompileEvent` — XLA's own
``compiled.cost_analysis()`` when the backend provides it
(``observe.ledger.compiled_cost``), the analytic model count otherwise, the
``flops_source`` field says which. At report time
:func:`mfu_from_compile_records` joins those recorded counts with the
measured steady-state step time: ``MFU = flops_per_step / step_time /
peak`` — computed from the run log alone, on a machine with no jax.

**The roofline verdict.** :func:`classify_roofline` names the limiter:

- ``comm-exposed`` — the schedule's count-weighted exposed-communication
  fraction (``utils.overlap.comm_attribution``, the same budget the
  effective-bandwidth estimator charges) is ≥ ``COMM_EXPOSED_THRESHOLD``:
  collectives sit on the critical path, so neither FLOPs nor HBM is the
  binding resource.
- ``hbm`` — arithmetic intensity (FLOPs / bytes accessed, from the cost
  model) is below the device's ridge point (peak FLOP/s ÷ HBM bytes/s).
- ``compute`` — above the ridge (or bytes unknown): the MXU is the limit.
- ``unknown`` — no peak for the device (the CPU smoke tier must not
  publish a verdict it cannot ground).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from .events import MfuEvent

# Peak dense bf16 FLOP/s per chip by device_kind substring (public spec
# sheets). Longest match wins ("v5 lite" before "v5").
PEAK_BF16_FLOPS: Dict[str, float] = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
    "v6": 918e12,
}

# Per-chip HBM bandwidth, bytes/s (public spec sheets; same keying rules).
# The ridge point peak/HBM is what separates compute-bound from HBM-bound.
HBM_BYTES_PER_S: Dict[str, float] = {
    "v2": 700e9,
    "v3": 900e9,
    "v4": 1228e9,
    "v5 lite": 819e9,
    "v5litepod": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v5": 2765e9,
    "v6 lite": 1640e9,
    "v6e": 1640e9,
    "v6": 1640e9,
}

# exposed-comm fraction at or above which the window is classified
# comm-exposed (count-weighted upper bound — see utils.overlap)
COMM_EXPOSED_THRESHOLD = 0.5

STEADY_STATE = "steady-state"


def _table_lookup(
    table: Dict[str, float], device_kind: str, platform: Optional[str]
) -> float:
    kind = (device_kind or "").lower()
    if platform is None:
        # report time: a run log records the kind only, and every TPU's
        # device_kind says "TPU" (the toy probe's "toy-sim" does not)
        platform = "tpu" if "tpu" in kind else ""
    if platform != "tpu":
        return 0.0
    for key in sorted(table, key=len, reverse=True):
        if key in kind:
            return table[key]
    raise ValueError(
        f"TPU device_kind {device_kind!r} is not in the observe.mfu peak"
        " tables; add its published peak there (a device that is not in"
        " the table is an error, not a default)"
    )


def peak_flops(device_kind: str, platform: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s for a TPU device kind; 0.0 off-TPU (a CPU has no
    entry and gets no MFU); raises for a TPU kind the table lacks.
    ``platform`` is jax's ``device.platform`` where a device is at hand;
    None reads it off the kind."""
    return _table_lookup(PEAK_BF16_FLOPS, device_kind, platform)


def hbm_bandwidth(device_kind: str, platform: Optional[str] = None) -> float:
    """HBM bytes/s for a TPU device kind; same contract as
    :func:`peak_flops`."""
    return _table_lookup(HBM_BYTES_PER_S, device_kind, platform)


def classify_roofline(
    flops_per_step: float,
    bytes_accessed_per_step: Optional[float],
    peak_flops_per_s: float,
    hbm_bytes_per_s: Optional[float],
    exposed_comm_fraction: Optional[float] = None,
) -> Dict[str, Optional[float]]:
    """The roofline verdict plus the numbers it was derived from (see the
    module docstring for the decision order)."""
    out: Dict[str, Optional[float]] = {
        "bound": "unknown",
        "arithmetic_intensity": None,
        "ridge_flops_per_byte": None,
    }
    if (
        bytes_accessed_per_step
        and bytes_accessed_per_step > 0
        and flops_per_step > 0
    ):
        out["arithmetic_intensity"] = flops_per_step / bytes_accessed_per_step
    if peak_flops_per_s > 0 and hbm_bytes_per_s and hbm_bytes_per_s > 0:
        out["ridge_flops_per_byte"] = peak_flops_per_s / hbm_bytes_per_s
    if not peak_flops_per_s > 0:
        return out
    if (
        exposed_comm_fraction is not None
        and exposed_comm_fraction >= COMM_EXPOSED_THRESHOLD
    ):
        out["bound"] = "comm-exposed"
    elif (
        out["arithmetic_intensity"] is not None
        and out["ridge_flops_per_byte"] is not None
        and out["arithmetic_intensity"] < out["ridge_flops_per_byte"]
    ):
        out["bound"] = "hbm"
    else:
        out["bound"] = "compute"
    return out


def _exposed_fraction(overlap: Optional[Dict]) -> Optional[float]:
    """Count-weighted exposed-comm fraction from a CompileEvent's overlap
    extract — None when the schedule carries no collective evidence."""
    if not overlap:
        return None
    from .analytics import _load_utils_module

    attribution = _load_utils_module("overlap").comm_attribution(overlap)
    if not attribution["n_collectives"]:
        return None
    return attribution["exposed_fraction"]


def mfu_event(
    label: str,
    step_time_s: float,
    flops_per_step: float,
    n_steps: int = 0,
    flops_source: str = "analytic",
    device_kind: str = "",
    platform: Optional[str] = None,
    peak_flops_per_s: Optional[float] = None,
    bytes_accessed_per_step: Optional[float] = None,
    hbm_bytes_per_s_: Optional[float] = None,
    exposed_comm_fraction: Optional[float] = None,
    window: str = STEADY_STATE,
) -> MfuEvent:
    """Build the typed MFU verdict for one measured window. ``peak`` and
    HBM bandwidth default to the device tables; pass them explicitly when
    the record itself carries authoritative values (the toy probe, a chip
    whose kind the tables do not know yet)."""
    peak = (
        peak_flops_per_s
        if peak_flops_per_s is not None
        else peak_flops(device_kind, platform)
    )
    hbm = (
        hbm_bytes_per_s_
        if hbm_bytes_per_s_ is not None
        else hbm_bandwidth(device_kind, platform)
    )
    roofline = classify_roofline(
        flops_per_step, bytes_accessed_per_step, peak, hbm,
        exposed_comm_fraction,
    )
    mfu = (
        flops_per_step / step_time_s / peak
        if peak > 0 and step_time_s > 0
        else None
    )
    return MfuEvent(
        label=label,
        window=window,
        n_steps=n_steps,
        step_time_s=step_time_s,
        flops_per_step=flops_per_step,
        flops_source=flops_source,
        peak_flops_per_s=peak,
        mfu=mfu,
        bound=str(roofline["bound"]),
        device_kind=device_kind,
        bytes_accessed_per_step=bytes_accessed_per_step,
        arithmetic_intensity=roofline["arithmetic_intensity"],
        ridge_flops_per_byte=roofline["ridge_flops_per_byte"],
        hbm_bytes_per_s=hbm if hbm > 0 else None,
        exposed_comm_fraction=exposed_comm_fraction,
    )


def mfu_from_compile_records(
    compile_records: Sequence[Dict],
    step_time_s: Optional[float],
    n_steps: int = 0,
    window: str = STEADY_STATE,
) -> List[MfuEvent]:
    """The report-time join: one MFU verdict per compile record that
    recorded a FLOPs count (deduped by label — every rank and incarnation
    re-emits the same compile-time record), against the run's measured
    steady-state step time."""
    if not isinstance(step_time_s, (int, float)) or not step_time_s > 0:
        return []
    out: List[MfuEvent] = []
    seen = set()
    for rec in compile_records:
        label = rec.get("label", "")
        flops = rec.get("flops_per_step")
        if label in seen or not isinstance(flops, (int, float)) or flops <= 0:
            continue
        seen.add(label)
        peak = rec.get("peak_flops_per_s")
        out.append(
            mfu_event(
                label=label,
                step_time_s=float(step_time_s),
                flops_per_step=float(flops),
                n_steps=n_steps,
                flops_source=str(rec.get("flops_source") or "analytic"),
                device_kind=str(rec.get("device_kind") or ""),
                peak_flops_per_s=(
                    float(peak) if isinstance(peak, (int, float)) else None
                ),
                bytes_accessed_per_step=rec.get("bytes_accessed_per_step"),
                exposed_comm_fraction=_exposed_fraction(rec.get("overlap")),
                window=window,
            )
        )
    return out
