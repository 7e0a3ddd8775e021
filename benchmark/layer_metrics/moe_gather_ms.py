"""Device self time per step under the leaf scope ``moe.gather`` of
``parallel/moe.py::held_experts_moe``: a chunk's token indices and the gather
of its rows from the tokens (backward: the (rows, D) -> (T, D) scatter-add);
forward, recomputation and backward (see ``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "moe.gather")
    return None if seconds is None else 1e3 * seconds
