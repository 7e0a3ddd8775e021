"""The gated delta rule's share of its roofline: the least time the chip
could take for the rule of all linear-attention layers of one step, forward
and backward (per layer the larger of required operations over the bf16 peak
and required bytes over the HBM peak,
``benchmark/flops/qwen3_next.py::gated_delta_cost``: the chunked form's
products at the configuration's chunk, and q, k, v, g, beta, o and their
cotangents moved once) over the device time spent under ``gdn.rule``.
Recomputation is time spent, not work required. Nothing to read where the
configuration has no linear-attention layer."""

from ..flops import qwen3_next
from .scoped import scope_seconds


def read(run):
    cfg = run.cfg
    seconds = scope_seconds(run, "gdn.rule")
    if not seconds or "linear_num_value_heads" not in cfg:
        return None
    flops, moved = qwen3_next.gated_delta_cost(cfg, cfg["per_chip_batch"] * cfg["seq_len"])
    least = max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * cfg["layer_types"].count("linear_attention") * least / seconds
