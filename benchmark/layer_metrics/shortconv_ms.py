"""Device self time per step under the scope ``shortconv.mix``: the two gates
and the three-tap depthwise conv of every short-convolution mixer
(``models/lfm2.py``; not its two projections), forward, recomputation and
backward (see ``scoped.py``), in milliseconds: what XLA leaves of the mix as
ops of its own. What it fuses into the projections' fusions carries their
name and is theirs (on the v5e most of the forward: PERF.md section 6, PR
41), which is why the mix has no roofline share. Nothing to read where the
program has no such scope."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "shortconv.mix")
    return None if seconds is None else 1e3 * seconds
