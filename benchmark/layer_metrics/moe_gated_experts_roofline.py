"""The gated routed experts' share of their roofline, as
``moe_experts_roofline`` reads the ungated ones': the least time the chip
could take for the three grouped products of every expert layer of one step,
forward and backward, for the assignments that really landed on the held
experts (the step's own counters, median over steps; per layer the larger of
required operations over the bf16 peak and required bytes over the HBM peak,
``benchmark/flops/afmoe.py::experts_cost``) over the device time spent under
``moe.experts``, which also holds the gather, the combine and the
recomputation: time spent, not work required. Nothing to read where the
configuration's experts are not gated (no ``layer_types``)."""

from ..flops import afmoe
from .scoped import median, scope_seconds, step_counters


def read(run):
    seconds = scope_seconds(run, "moe.experts")
    steps = step_counters(run)
    if not seconds or not steps or "layer_types" not in run.cfg:
        return None
    least = 0.0
    for layer in steps[0]:
        landed = median([sum(step[layer]["held"]) for step in steps])
        flops, moved = afmoe.experts_cost(run.cfg, landed)
        least += max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
