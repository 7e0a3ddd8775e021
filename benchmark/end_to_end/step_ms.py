"""Median over the window's steps of the host time from dispatch to the loss
arriving: the program's own ``step`` span, which ends in ``device_get``."""


def read(run):
    times = sorted(s["step_s"] for s in run.steps)
    return 1e3 * times[len(times) // 2] if times else None
