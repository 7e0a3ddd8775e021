"""Device time per step of the ops under ``reduce.powersgd`` or
``reduce.exact``: the reducer's kernels, sweeps and collectives."""


def read(run):
    if not run.trace:
        return None
    found = [run.trace.scope_s(s) for s in ("reduce.powersgd", "reduce.exact")]
    found = [x for x in found if x is not None]
    return 1e3 * sum(found) if found else None
