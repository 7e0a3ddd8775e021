"""Device self time per step under the scope ``moe.overflow`` of
``parallel/moe.py::held_experts_moe``: everything the second and later
chunks of an expert layer cost, forward, recomputation and backward (see
``scoped.py``), in milliseconds. Its ops are inside ``moe.experts`` and carry
a chunk's leaf besides (``moe_combine_ms`` says which do not). Where no layer
of the slice held more than T assignments it is what the ``cond`` costs
unentered: the zeros of the other branch and the add of them."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "moe.overflow")
    return None if seconds is None else 1e3 * seconds
