"""How near the load runs to the edge of an expert layer's first chunk: the
assignments that landed on the held experts (the ``held`` counter) over the
rows of one chunk (``parallel/moe.py::chunk_rows`` of the configuration's
shapes), in percent, the fullest worker, the worst expert layer of a step, the
median over the steps outside the profiler's slice. Under 100 the first chunk
held the load; the expected load reads 100 / 1.5 = 67 where the rule's 3/2
sets the chunk, less where T rows do. From the counters the timed step itself
writes on its ``step/loss_sync`` spans; nothing to read where the program
writes no such counter or has no such rule."""

from .moe_chunks import chunk_rows_of, landed_per_step
from .scoped import median

COUNT = True


def read(run):
    rows, landed = chunk_rows_of(run), landed_per_step(run)
    if rows is None or not landed:
        return None
    return 100.0 * median(landed) / rows
