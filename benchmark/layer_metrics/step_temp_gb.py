"""The step executable's temporaries in GB (1e9 bytes): activations kept for
the backward pass and the reducer's scratch, from the ``memory_analysis()`` of
the executable that ran. It is the part of ``peak_hbm_gb`` the allocator's
own peak leaves out, and the part a batch or a rematerialisation moves."""


def read(run):
    return run.step_temp_bytes / 1e9 if run.step_temp_bytes else None
