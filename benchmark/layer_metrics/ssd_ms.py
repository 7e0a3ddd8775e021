"""Device self time per step under the scope ``mamba.ssd``, forward,
recomputation and backward (see ``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "mamba.ssd")
    return None if seconds is None else 1e3 * seconds
