"""Device time per step of the ops under the scope ``step.grads``."""


def read(run):
    seconds = run.trace.scope_s("step.grads") if run.trace else None
    return None if seconds is None else 1e3 * seconds
