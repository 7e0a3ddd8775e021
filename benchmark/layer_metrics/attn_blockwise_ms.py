"""Device self time per step under the scope ``attn.blockwise``: the flash
kernels over the 2L rows ``[noised ; clean]`` of every layer under the
block-wise rule (``ops/flash_attention.py``, ``blockwise=(L, B)``: the key
tiles the rule hides skipped by loop bounds), forward, recomputation and
backward (see ``scoped.py``), in milliseconds. Nothing to read where the
program has no such scope."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "attn.blockwise")
    return 1e3 * seconds if seconds else None
