"""Median host time to enqueue one step: the ``step/compute`` span, which
returns when the program is dispatched, not when it is done."""


def read(run):
    times = sorted(r["dur_s"] for r in run.clean_spans("step/compute"))
    return 1e3 * times[len(times) // 2] if times else None
