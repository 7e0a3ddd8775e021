"""The Mamba-2 scan's share of its roofline: the least time the chip could
take for the forward and backward scans of all Mamba layers of one step (per
layer the larger of required operations over the bf16 peak and required
bytes over the HBM peak, ``benchmark/flops/nemotron_h.py::ssd_cost``; the
bytes bound it at these shapes) over the device time spent under
``mamba.ssd``. Recomputation is time spent, not work required."""

from ..flops import nemotron_h
from .scoped import scope_seconds


def read(run):
    cfg = run.cfg
    seconds = scope_seconds(run, "mamba.ssd")
    if not seconds or "mamba_num_heads" not in cfg:
        return None
    flops, moved = nemotron_h.ssd_cost(cfg, cfg["per_chip_batch"] * cfg["seq_len"])
    least = max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * cfg["hybrid_override_pattern"].count("M") * least / seconds
