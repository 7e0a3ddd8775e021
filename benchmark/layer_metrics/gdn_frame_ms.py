"""Device self time per step of what frames the gated delta rule in its
mixer: the scope ``gdn.conv`` (the causal depthwise conv and its silu) and
the scope ``gdn.frame`` (beta, g, the l2 norms of q and k before the rule,
the gated RMSNorm after it), forward, recomputation and backward (see
``scoped.py``), in milliseconds."""


def read(run):
    if not run.trace:
        return None
    seconds = run.trace.per_step(lambda o: "gdn.conv" in o.op_name or "gdn.frame" in o.op_name)
    return None if seconds is None else 1e3 * seconds
