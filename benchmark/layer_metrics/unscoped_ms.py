"""Device time per step of the ops that carry none of the program's scopes:
what the compiler adds on its own (copies and slices of the arguments
between memory spaces) and whatever the program has not named yet."""


def read(run):
    seconds = run.trace.per_step(lambda o: not o.scopes) if run.trace else None
    return None if seconds is None else 1e3 * seconds
