"""Share of the traced window in which no op ran on the chip (mean over the
chips): one minus the union of the op intervals over the window."""


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
