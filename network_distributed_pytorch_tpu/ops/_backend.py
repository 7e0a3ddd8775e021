"""What the backend decides for every Pallas kernel — a leaf module, so the
kernels and the package ``__init__`` that re-exports them can both import
it."""

import jax


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in the interpreter here: Mosaic compiles
    them on TPU only, so every other backend (the CPU test path) interprets.
    The one place the backend decides a kernel's mode and what ``"auto"``
    selects; experiments report the outcome in their summary
    (``experiments.common.device_fields``) so a run that found no chip
    cannot be read as one that compiled the kernels."""
    return jax.default_backend() != "tpu"
