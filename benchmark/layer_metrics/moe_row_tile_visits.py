"""The row tiles one grouped product of an expert layer visits: the
``row_tiles`` counter ``held_experts_moe`` returns (the row extent of its
kernels' grid over the first chunk: one visit for every (row tile, held
expert) pair with rows in common, so work that follows how the load falls on
the tiles), summed over workers; the worst expert layer of a step, the median
over the steps outside the profiler's slice. From the counters the timed step
itself writes on its ``step/loss_sync`` spans; nothing to read where the
program writes no such counter."""

from .scoped import median


def read(run):
    worst = []
    for record in run.clean_spans("step/loss_sync"):
        layers = [c["row_tiles"] for c in (record.get("counters") or {}).values() if "row_tiles" in c]
        if layers:
            worst.append(max(sum(per_worker) for per_worker in layers))
    return median(worst)
