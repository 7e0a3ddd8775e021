"""Model FLOP/s utilisation: the operations the forward and backward passes
require per sample (``benchmark/flops/<builder>.py``, from shapes) times the
samples per second of the steps before the profiler's slice, over chips times
the chip's bf16 peak (``benchmark/peaks.json``)."""


def read(run):
    steps, period = run.clean_period()
    if not steps or period <= 0 or not run.peaks:
        return None
    rate = steps * run.samples_per_step / period
    peak = run.device["count"] * run.peaks["bf16_flops_per_s"]
    return 100.0 * run.flops_per_sample * rate / peak
