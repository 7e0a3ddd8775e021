"""What the readers of the Nemotron-H layers share: device time under one of
the program's named scopes wherever the scope sits on an op's path, and the
counters the program put on its ``step/loss_sync`` spans.

``trace.scope_s`` takes a scope only where it stands alone between slashes.
jax writes a scope so in the backward pass and in a recomputed forward
(``.../checkpoint/rematted_computation/mamba.ssd/dot_general``) but wraps it
in the forward pass proper (``.../jvp(mamba.ssd)/dot_general``), so these
readers look for the scope's name anywhere in the op's path: forward,
recomputation and backward together.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def scope_seconds(run, scope: str) -> Optional[float]:
    """Device self time per step of every op whose path names ``scope``."""
    if not run.trace:
        return None
    return run.trace.per_step(lambda o: scope in o.op_name)


def step_counters(run) -> List[Dict]:
    """One entry per step outside the profiler's slice that carried
    counters: ``{layer: {"held": [per expert], "absent": n, "dropped": n}}``,
    summed over the workers' leading axis."""
    out = []
    for record in run.clean_spans("step/loss_sync"):
        counters = record.get("counters")
        if not counters:
            continue
        out.append({
            layer: {
                "held": [sum(worker[i] for worker in c["held"]) for i in range(len(c["held"][0]))],
                "absent": sum(c["absent"]),
                "dropped": sum(c["dropped"]),
            }
            for layer, c in counters.items()
        })
    return out


def median(values: List[float]) -> Optional[float]:
    values = sorted(values)
    return values[len(values) // 2] if values else None
