"""Metrics: per-step loss, wall-clock, and bytes-on-wire reporting.

This finishes what the reference started and never shipped (SURVEY C9): it
accumulates ``bits_communicated`` per step
(``ddp_powersgd_guide_cifar10/ddp_init.py:123,161``) but never prints or
persists it, and it imports ``time`` without ever measuring anything
(``ddp_guide/ddp_init.py:4``). Here every step logs loss / step-time /
cumulative bits, epochs emit the reference's per-epoch mean-loss banner
(``ddp_init.py:183``), and everything flows through the ``observe``
telemetry — the stdout banners and the structured JSONL log are two sinks
on the same events, so they cannot drift apart.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..observe import EpochEvent, StepEvent, Telemetry, default_telemetry


@dataclass
class StepRecord:
    step: int
    epoch: int
    loss: float
    step_time_s: float
    bits_cumulative: int
    # False = end_step without a matching start_step: there is no timing
    # origin, so step_time_s is meaningless. Persisted (not silently ~0 s)
    # so downstream percentiles can exclude it.
    valid: bool = True


@dataclass
class MetricsLogger:
    """Host-side accumulator; bits/step is static so the Python-int tally is
    exact (no device traffic). Events are emitted through ``telemetry``
    (default: the process-wide stdout-banner registry)."""

    bits_per_step: int = 0
    log_every: int = 0  # 0 = silent per-step
    records: List[StepRecord] = field(default_factory=list)
    telemetry: Optional[Telemetry] = None
    # device ids holding shards of the first batch the loop fed (set by
    # experiments.common.train_loop; reported under the summary's placement)
    batch_devices: Optional[List[int]] = None
    _epoch_losses: List[float] = field(default_factory=list)
    _step: int = 0
    _bits: int = 0
    _t_last: Optional[float] = None

    def _telemetry(self) -> Telemetry:
        return self.telemetry if self.telemetry is not None else default_telemetry()

    def start_step(self) -> None:
        self._t_last = time.perf_counter()

    def end_step(
        self, epoch: int, loss: float, bits: Optional[int] = None
    ) -> StepRecord:
        if self._t_last is None:
            valid, dt = False, 0.0
        else:
            valid, dt = True, time.perf_counter() - self._t_last
        # one timing origin per step: a second end_step without a new
        # start_step must not silently reuse (or double-count) the old one
        self._t_last = None
        # `bits` overrides the static per-step cost for callers whose steps
        # have varying wire cost (e.g. streaming DiLoCo's per-fragment phases)
        self._bits += self.bits_per_step if bits is None else bits
        rec = StepRecord(self._step, epoch, float(loss), dt, self._bits, valid)
        self.records.append(rec)
        self._epoch_losses.append(float(loss))
        self._step += 1
        self._telemetry().emit(
            StepEvent(
                step=rec.step,
                epoch=rec.epoch,
                loss=rec.loss,
                step_time_s=rec.step_time_s,
                bits_cumulative=rec.bits_cumulative,
                valid=rec.valid,
                verbose=bool(self.log_every) and self._step % self.log_every == 0,
            )
        )
        return rec

    def end_epoch(self, epoch: int, rank: int = 0) -> float:
        """Per-epoch mean loss, emitted in the reference's banner style
        (``ddp_powersgd_guide_cifar10/ddp_init.py:183``)."""
        mean = sum(self._epoch_losses) / max(len(self._epoch_losses), 1)
        self._telemetry().emit(
            EpochEvent(
                epoch=epoch,
                rank=rank,
                mean_loss=mean,
                bits_cumulative=self._bits,
            )
        )
        self._epoch_losses = []
        return mean

    @property
    def bits_communicated(self) -> int:
        return self._bits

    def summary(self) -> Dict:
        # steady-state step time: drop the compile step and untimed records
        times = [r.step_time_s for r in self.records[1:] if r.valid]
        return {
            "steps": len(self.records),
            "first_loss": self.records[0].loss if self.records else None,
            "final_loss": self.records[-1].loss if self.records else None,
            "mean_step_time_s": sum(times) / len(times) if times else None,
            "bits_per_step": self.bits_per_step,
            "bits_communicated": self._bits,
            "bytes_communicated": self._bits // 8,
        }

    def dump_jsonl(self, path: str, append: bool = False) -> None:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "a" if append else "w") as f:
            for r in self.records:
                f.write(json.dumps(r.__dict__) + "\n")
