"""Device self time per step under the leaf scope ``moe.layout`` of
``parallel/moe.py::held_experts_moe``: what the expert layer does inside
neither ``moe.route`` nor ``moe.experts`` (so in neither ``moe_route_ms`` nor
``moe_experts_ms``): the sorted tokens, the weights gathered into sorted order
and that gather's cotangent, their pads, the step's counters, the output's
cast; forward, recomputation and backward (see ``scoped.py``), in
milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "moe.layout")
    return None if seconds is None else 1e3 * seconds
