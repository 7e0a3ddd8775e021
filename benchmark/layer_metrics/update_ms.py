"""Device time per step of the ops under the scope ``step.update``: the clip
and the parameter and momentum update. Absent where the program has no such
scope."""


def read(run):
    seconds = run.trace.scope_s("step.update") if run.trace else None
    return None if seconds is None else 1e3 * seconds
