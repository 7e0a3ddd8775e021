"""How unevenly the router loads the experts held here: per step and expert
layer, the busiest held expert's assignments over the mean of the held
experts'; the worst layer of a step, the median over the steps outside the
profiler's slice. From the counters the timed step itself writes
(``step/loss_sync`` spans). 1.0 is an even load. It also prints the
counters' totals, the dropped assignments among them (always 0)."""

from .scoped import median, step_counters

COUNT = True


def read(run):
    steps = step_counters(run)
    if not steps:
        return None
    landed = sum(sum(c["held"]) for step in steps for c in step.values())
    absent = sum(c["absent"] for step in steps for c in step.values())
    dropped = sum(c["dropped"] for step in steps for c in step.values())
    print(
        f"benchmark: expert counters over {len(steps)} steps: {landed} assignments on held experts,"
        f" {absent} on absent experts, {dropped} dropped", flush=True,
    )
    worst = [
        max(max(c["held"]) * len(c["held"]) / max(sum(c["held"]), 1) for c in step.values())
        for step in steps
    ]
    return median(worst)
