"""Share of the untraced steps' time the loop spent waiting for its next
batch: the ``data_load`` spans over the same steps' whole period."""


def read(run):
    steps, period = run.clean_period()
    waits = run.clean_spans("data_load")
    if not steps or period <= 0 or not waits:
        return None
    return 100.0 * sum(r["dur_s"] for r in waits if r["step"] > 0) / period
