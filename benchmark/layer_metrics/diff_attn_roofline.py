"""Differential attention's flash calls' share of their roofline: the least
time the chip could take for what the kernels are asked for in every sliding,
full and cross layer of one step (per layer the larger of required operations
over the bf16 peak and required bytes over the HBM peak,
``benchmark/flops/phi4flash.py::diff_attention_cost``: the visible pairs only,
at 1.5 times a plain layer's; the MXU binds) over the device time spent under
``attn.window`` + ``attn.full`` + ``attn.cross``. The softmax the program
computes twice a pair of heads (once a value half), the tiles walked beyond
the band and the fold's relayouts are time spent, not work required."""

from ..flops import phi4flash


def read(run):
    cfg = run.cfg
    if not run.trace or "layer_indices" not in cfg:
        return None
    scopes = ("attn.window", "attn.full", "attn.cross")
    seconds = run.trace.per_step(lambda o: any(scope in o.op_name for scope in scopes))
    if not seconds:
        return None
    tokens, least = cfg["per_chip_batch"] * cfg["seq_len"], 0.0
    for kind in phi4flash.kinds(cfg):
        if kind in (phi4flash.SLIDING, phi4flash.FULL, phi4flash.CROSS):
            flops, moved = phi4flash.diff_attention_cost(cfg, tokens, kind)
            least += max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
