"""Device self time per step under the leaf scope ``moe.combine`` of
``parallel/moe.py::held_experts_moe``: a chunk's rows weighted, the zeros
(T, D) and the scatter-add of the rows into their tokens (backward: a
gather); forward, recomputation and backward (see ``scoped.py``), in
milliseconds.

With it, what ``moe.overflow`` runs outside any chunk: the ``cond`` and the
``scan`` themselves, their predicates and counters, the zeros of the branch
not taken and the adds of the carry. Those are instructions jax names from
the call, so no scope inside the call can reach them; they add chunks'
outputs up, which is combining. Counted here, ``moe_gather_ms +
moe_products_ms + moe_combine_ms = moe_experts_ms`` to the last digit."""

CHUNK = ("moe.gather", "moe.products", "moe.combine")


def read(run):
    if not run.trace:
        return None
    seconds = run.trace.per_step(
        lambda o: "moe.combine" in o.op_name
        or ("moe.overflow" in o.op_name and not any(leaf in o.op_name for leaf in CHUNK))
    )
    return None if seconds is None else 1e3 * seconds
