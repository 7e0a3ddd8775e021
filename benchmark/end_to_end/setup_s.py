"""Process start to the first measured step: interpreter, imports, data,
weights, trace, compile or cache read, warm-up."""


def read(run):
    return run.setup_s or None
