"""Device self time per step under the leaf scope ``moe.products`` of
``parallel/moe.py::held_experts_moe``: the grouped products (the Pallas
kernels and their cotangents), the activation between them, each chunk's group
sizes, the weights' casts to the compute dtype; forward, recomputation and
backward (see ``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "moe.products")
    return None if seconds is None else 1e3 * seconds
