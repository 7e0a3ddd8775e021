"""Device time per step summed over every call of the Pallas Gram-Schmidt
kernel: the ``tpu_custom_call``s whose metadata sits under
``jit(orthogonalize_pallas)``. Absent where the reducer resolved to XLA."""


def read(run):
    if not run.trace:
        return None
    seconds = run.trace.per_step(
        lambda o: o.opcode == "custom-call" and "jit(orthogonalize_pallas)" in o.op_name
    )
    return None if seconds is None else 1e3 * seconds
