"""Device self time per step under the scope ``mamba.scan``: the selective scan
alone (``ops/selective_scan.py``: the chunks' ``lax.scan``, the associative
scan inside a chunk, the sum over the state index), forward, recomputation
and backward (see ``scoped.py``), in milliseconds. Not the projections that
make ``delta``, B and C nor the gate: those are ``mamba_frame_ms``'s."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "mamba.scan")
    return None if seconds is None else 1e3 * seconds
