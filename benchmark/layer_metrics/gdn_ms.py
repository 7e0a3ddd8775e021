"""Device self time per step under the scope ``gdn.rule``: the chunked gated
delta rule of every linear-attention layer (``ops/gated_delta.py``), forward,
recomputation and backward (see ``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "gdn.rule")
    return None if seconds is None else 1e3 * seconds
