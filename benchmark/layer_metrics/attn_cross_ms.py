"""Device self time per step under the scope ``attn.cross``: the flash
kernels of every cross-attention layer (queries of its own, keys and values
another layer's cache) and the fold's relayouts round them, forward,
recomputation and backward (see ``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "attn.cross")
    return None if seconds is None else 1e3 * seconds
