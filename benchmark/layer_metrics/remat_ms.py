"""Device self time per step of the recomputed forward: the ops under the
scope ``step.grads`` whose path ``passes.pass_of`` reads as ``remat`` (jax
writes ``rematted_computation`` on them), in milliseconds. Nothing where the
model keeps its activations (``remat`` false). ``fwd_ms + remat_ms + bwd_ms =
grads_ms``.

Eleven numbers do not say which scope's recomputation is dear, so this
reader also prints the whole table, one line on stdout before the run's last
line: ``benchmark: passes {scope: [fwd, remat, bwd]}`` in ms a step, every op
under ``step.grads`` counted once, by the innermost scope on its path."""

import json

from .passes import pass_seconds, table


def read(run):
    seconds = pass_seconds(run, "remat")
    if seconds is None:
        return None
    rows = {scope: [round(1e3 * s, 4) for s in row] for scope, row in table(run).items()}
    print("benchmark: passes " + json.dumps(rows), flush=True)
    return 1e3 * seconds
