"""Peak device memory on the fullest chip, in GB (1e9 bytes): what
``device.memory_peak_bytes`` on the result line says."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
