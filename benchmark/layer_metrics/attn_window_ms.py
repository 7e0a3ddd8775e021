"""Device self time per step under the scope ``attn.window``: the flash
kernels of every sliding-window attention layer, forward, recomputation and
backward (see ``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "attn.window")
    return None if seconds is None else 1e3 * seconds
