"""Device self time per step under the leaf scope ``moe.score`` of
``parallel/moe.py::held_experts_moe``: the router's fp32 product, the sigmoid
or softmax, ``top_k``, the picked scores and their renormalised weights
(backward: the scatter into (T, E)); forward, recomputation and backward (see
``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "moe.score")
    return None if seconds is None else 1e3 * seconds
