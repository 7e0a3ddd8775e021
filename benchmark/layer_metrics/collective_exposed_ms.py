"""Per step, the time collective ops ran on a chip while no other op ran on
it; mean over the chips. Absent where the trace holds no collective (one
chip: XLA drops them)."""


def read(run):
    seconds = run.trace.collective_exposed_s() if run.trace else None
    return None if seconds is None else 1e3 * seconds
