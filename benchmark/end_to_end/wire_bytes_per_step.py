"""Bytes one worker puts on the wire per step: the step's own ledger
(``bits_per_step / 8``). A count; the same on one chip and on four."""

COUNT = True  # a count: a rehearsal on the CPU may print it


def read(run):
    return float(run.wire_bytes_per_step) if run.wire_bytes_per_step else None
