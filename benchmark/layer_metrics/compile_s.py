"""Host clock around the step's first call (trace, lower, compile or cache
read, one execution) and, where it runs, the wire audit's AOT compile."""


def read(run):
    return run.compile_s or None
