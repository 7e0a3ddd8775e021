"""Device self time per step under the leaf scope ``moe.count`` of
``parallel/moe.py::held_experts_moe``: the counting scatter-add of the T*k
slots into the held experts' bins, their prefix sum and ``landed``; forward,
recomputation and backward (see ``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "moe.count")
    return None if seconds is None else 1e3 * seconds
