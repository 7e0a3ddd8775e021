"""Device self time per step of what frames the selective scan in its mixer:
the scope ``mamba.conv`` (the causal depthwise conv, its bias and its silu)
and the scope ``mamba.frame`` (``x_proj``, ``dt_proj``, the softplus, the gate
``y * silu(z)``), forward, recomputation and backward (see ``scoped.py``), in
milliseconds. Not ``in_proj`` and ``out_proj``, which carry no scope of their
own, and not the scan (``s6_ms``)."""


def read(run):
    if not run.trace:
        return None
    seconds = run.trace.per_step(lambda o: "mamba.conv" in o.op_name or "mamba.frame" in o.op_name)
    return None if seconds is None else 1e3 * seconds
