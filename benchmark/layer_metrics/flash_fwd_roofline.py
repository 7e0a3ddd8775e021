"""The flash attention forward kernel's share of its roofline: the least
time the chip could take for one call (the larger of operations over peak
FLOP/s and bytes of q, k, v, o over peak bytes/s; at DistilBERT's shapes the
operations bound it) over the kernel's mean device time per call. The kernel
is the ``tpu_custom_call`` whose metadata sits under ``jit(flash_attention)``."""

from ..flops import flash_attention


def read(run):
    cfg = run.cfg
    if not run.trace or "n_heads" not in cfg:
        return None
    calls = run.trace.calls(
        lambda o: o.opcode == "custom-call" and "jit(flash_attention)" in o.op_name
    )
    if not calls:
        return None
    bytes_per_element = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    flops, moved = flash_attention.forward_cost(
        cfg["per_chip_batch"], cfg["n_heads"], cfg["seq_len"],
        cfg["dim"] // cfg["n_heads"], bytes_per_element,
    )
    least = max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(calls) / len(calls))
