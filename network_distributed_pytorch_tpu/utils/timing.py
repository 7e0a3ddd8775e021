"""The one timing primitive: a timed region ends by fetching a small result.

JAX dispatch is asynchronous, so a host timing that does not wait for a
result measures the enqueue. On the installed runtime (jax 0.9.0, libtpu
0.0.34, TPU v5e) ``block_until_ready`` and ``jax.device_get`` of a small
result agree on when a program finished — ``chip_smoke.py`` times one
~47 ms program both ways on every run (46.6 ms against 47.1 ms, dispatch
alone 0.2 ms, PR 21) and fails if they ever disagree. Every timed region in
this repo ends in :func:`wait_result`, which also hands the caller the value
it usually wants next (a loss, sampled ids).
"""

from __future__ import annotations

import time
from typing import Callable

import jax


def wait_result(x):
    """Fetch ``x`` to host; returns once the computation that produced it
    has completed. Use a SMALL output (a loss scalar, sampled ids) so the
    transfer itself is negligible."""
    return jax.device_get(x)


def time_amortized(fn: Callable[[], object], repeats: int = 3) -> float:
    """Mean seconds per call of ``fn`` over ``repeats`` calls, EACH fetched
    via :func:`wait_result` before the next dispatch. Fetch-per-call is
    deliberate: the calls are data-independent, so fetching only the last
    one would let earlier executions overlap and understate per-call time.
    The cost is that each call's figure includes one host round-trip —
    biased high, never low (averaging over ``repeats`` smooths jitter).
    The caller warms up (compiles) before handing ``fn`` over."""
    wait_result(fn())  # settle any pending work outside the timed region
    t0 = time.perf_counter()
    for _ in range(repeats):
        wait_result(fn())
    return (time.perf_counter() - t0) / repeats
