"""Device self time per step of the backward pass: the ops under the scope
``step.grads`` whose path ``passes.pass_of`` reads as ``bwd``, in
milliseconds. ``fwd_ms + remat_ms + bwd_ms = grads_ms``."""

from .passes import pass_seconds


def read(run):
    seconds = pass_seconds(run, "bwd")
    return None if seconds is None else 1e3 * seconds
