"""The selective scan's share of its roofline: the least time the chip could
take for the forward and backward scans of every Mamba layer of one step (per
layer the larger of required operations over the bf16 peak and required bytes
over the HBM peak, ``benchmark/flops/phi4flash.py::s6_cost``: x, delta and y
at (T, C), B and C at (T, N), forward and backward; the bytes bind, about a
millisecond a layer) over the device time spent under ``mamba.scan``.
Recomputation is time spent, not work required.

``peaks.json`` has no vector-unit peak, and the scan has no matmul form: its
7 operations a (token, channel, index) run on the VPU and its ``exp`` on the
EUP, neither at the MXU's 197e12 a second. So the share's ceiling is not 100:
a kernel that held the state in VMEM and did nothing but the arithmetic would
read about 10% here. Read it against that, and against its own past."""

from ..flops import phi4flash
from .scoped import scope_seconds


def read(run):
    cfg = run.cfg
    seconds = scope_seconds(run, "mamba.scan")
    if not seconds or "mamba_d_state" not in cfg:
        return None
    flops, moved = phi4flash.s6_cost(cfg, cfg["per_chip_batch"] * cfg["seq_len"])
    least = max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * phi4flash.kinds(cfg).count(phi4flash.MAMBA) * least / seconds
