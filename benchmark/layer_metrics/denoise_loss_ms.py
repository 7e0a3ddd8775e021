"""Device self time per step under the scope ``denoise.loss``: the final norm
and the head on the L noised rows and the weighted cross-entropy of the
replaced positions (``models/sdar.py``, ``models.layers.masked_token_loss``),
forward, recomputation and backward (see ``scoped.py``), in milliseconds.
Nothing to read where the program has no such scope."""

from .scoped import scope_seconds


def read(run):
    seconds = scope_seconds(run, "denoise.loss")
    return 1e3 * seconds if seconds else None
