"""The share of a sample's positions that carried loss: the ``masked`` counter
the masked-token loss writes beside every expert layer's (how many positions
of the step's noised copies were replaced by ``[MASK]``, summed over workers)
over the step's text tokens, in percent, the median over the steps outside the
profiler's slice. ``t_b ~ U(eps, 1)`` a block puts it near 50; it moves with
the pool's draw, and with it the rows a layer routes as one token id. From the
counters the timed step itself writes on its ``step/loss_sync`` spans; nothing
to read where the program writes no such counter."""

from .scoped import median

COUNT = True


def read(run):
    tokens = run.samples_per_step * run.cfg.get("text_len", 0)
    shares = []
    for record in run.clean_spans("step/loss_sync"):
        masked = [c["masked"] for c in (record.get("counters") or {}).values() if "masked" in c]
        if masked and tokens:
            shares.append(100.0 * sum(masked[0]) / tokens)  # every layer carries the same count
    return median(shares)
