"""The block-wise flash calls' share of their roofline: the least time the
chip could take for what the kernels are asked for in every layer of one step
(per layer the larger of required operations over the bf16 peak and required
bytes over the HBM peak, ``benchmark/flops/sdar.py::blockwise_attention_cost``:
the ``L^2 + L B`` visible pairs a head only, 18 D a pair under ``remat``; the
MXU binds) over the device time spent under ``attn.blockwise``. The tiles on
the three diagonals are computed whole and mostly hidden, and the kernels'
exp, max and rescaling ride no MXU: time spent, not work required. Nothing to
read where the program has no such scope or the configuration no blocks."""

from ..flops import sdar
from .scoped import scope_seconds


def read(run):
    cfg = run.cfg
    seconds = scope_seconds(run, "attn.blockwise")
    if not seconds or "block_length" not in cfg:
        return None
    flops, moved = sdar.blockwise_attention_cost(cfg, cfg["per_chip_batch"] * cfg["text_len"])
    least = max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * cfg["num_hidden_layers"] * least / seconds
