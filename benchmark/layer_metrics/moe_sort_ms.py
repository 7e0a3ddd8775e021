"""Device self time per step under the leaf scope ``moe.sort`` of
``parallel/moe.py::held_experts_moe``: the lookup of each chosen expert's slot
here and the stable argsort of the T*k slots; forward, recomputation and
backward (see ``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "moe.sort")
    return None if seconds is None else 1e3 * seconds
