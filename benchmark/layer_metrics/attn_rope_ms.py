"""Device self time per step under the scope ``attn.rope``: the per-head
RMSNorm of q and k and the rotary turn of every attention layer that has them
(frequencies, angles, cos, sin and the turn itself in fp32; YaRN's blend and
factor where a layer kind has them), forward, recomputation and backward (see
``scoped.py``), in milliseconds: elementwise passes over q and k that XLA
fuses as it can. Nothing to read where the program has no such scope."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "attn.rope")
    return None if seconds is None else 1e3 * seconds
