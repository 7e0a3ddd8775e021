"""The sliding-window attention kernels' share of their roofline: the least
time the chip could take for what the flash kernels are asked for in every
sliding layer of one step (per layer the larger of required operations over
the bf16 peak and required bytes over the HBM peak,
``benchmark/flops/afmoe.py::window_attention_cost``: the visible pairs of the
band only; the MXU binds at these shapes) over the device time spent under
``attn.window``. Tiles the kernels walk beyond the band are time spent, not
work required."""

from ..flops import afmoe
from .scoped import scope_seconds


def read(run):
    cfg = run.cfg
    seconds = scope_seconds(run, "attn.window")
    if not seconds or "sliding_window" not in cfg or "layer_types" not in cfg:
        return None
    flops, moved = afmoe.window_attention_cost(cfg, cfg["per_chip_batch"] * cfg["seq_len"])
    least = max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * cfg["layer_types"].count("sliding_attention") * least / seconds
