"""How many chunks of an expert layer held live rows (1 + the later chunks
``held_experts_moe`` entered): ``max(ceil(landed / rows), 1)`` from the
``held`` counter and the rows of one chunk, which the program's own rule
(``parallel/moe.py::chunk_rows``) makes of the configuration's shapes; the
largest over workers, the worst expert layer of a step, the MAXIMUM over the
steps outside the profiler's slice: 1 where the first chunk held every step's
load, which is what the rule sizes it for; above 1, some step paid
``moe.overflow``'s later chunks (~24 ms a layer entered, PERF.md section 6,
PR 39) and ``step_ms`` has a second level. From the counters the timed step
itself writes on its ``step/loss_sync`` spans; nothing to read where the
program writes no such counter or has no such rule."""

COUNT = True


def chunk_rows_of(run):
    """The rows of one chunk of this run's expert layers; None where the
    program has no rule for it or the configuration no expert layer."""
    try:
        from network_distributed_pytorch_tpu.parallel.moe import chunk_rows
    except ImportError:
        return None
    cfg = run.cfg
    if not cfg.get("held_experts"):
        return None
    tokens = cfg["per_chip_batch"] * cfg["seq_len"]  # what one worker's layer sees in a call
    return chunk_rows(tokens, cfg["num_experts_per_tok"], len(cfg["held_experts"]), cfg["router_width"])


def landed_per_step(run):
    """A step outside the slice -> the assignments that landed on the held
    experts of its fullest (worker, expert layer)."""
    out = []
    for record in run.clean_spans("step/loss_sync"):
        layers = [c["held"] for c in (record.get("counters") or {}).values() if "held" in c]
        if layers:
            out.append(max(sum(worker) for per_worker in layers for worker in per_worker))
    return out


def read(run):
    rows, landed = chunk_rows_of(run), landed_per_step(run)
    if rows is None or not landed:
        return None
    return max(-(-max(landed) // rows), 1)
