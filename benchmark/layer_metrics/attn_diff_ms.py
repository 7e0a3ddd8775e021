"""Device self time per step under the scope ``attn.diff``: what differential
attention adds round its flash calls — the pairing of adjacent heads, the
stacking of ``(q1, q1, q2, q2)`` over ``(k1, k1, k2, k2)`` and ``(v1, v2, v1,
v2)``, lambda, the difference ``O1 - lambda O2`` and the subln — in every
sliding, full and cross layer, forward, recomputation and backward (see
``scoped.py``), in milliseconds."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "attn.diff")
    return None if seconds is None else 1e3 * seconds
