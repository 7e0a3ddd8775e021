"""Device self time per step under the scope ``gmu.mix``: every Gated Memory Unit
whole (its two projections and ``memory * silu(.)`` between them, and the
memory's cotangent), forward, recomputation and backward (see ``scoped.py``),
in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "gmu.mix")
    return None if seconds is None else 1e3 * seconds
