"""Device self time per step under the scope ``attn.full``: the flash kernels
of every full (causal, no window) attention layer of a model that also has
windowed ones, forward, recomputation and backward (see ``scoped.py``), in
milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "attn.full")
    return None if seconds is None else 1e3 * seconds
