"""L2 — communication primitives with bits-on-wire accounting.

The reference wraps ``torch.distributed`` collectives in free functions that
no-op when ``world_size <= 1`` (``reducer.py:193-195``,
``tensor_buffer.py:59-69``) and counts every payload with
``n_bits(t) = 8 * nelement * element_size`` (``reducer.py:197-198``).

TPU-native design: collectives are ``jax.lax`` ops *inside* a traced
``shard_map`` region, addressed by mesh axis name; XLA lowers them to ICI/DCN
collectives. The single-process fallback is the same shape here: when
``axis_name is None`` the wrappers are identity (no mesh axis → no wire).

Bits accounting is **static** — computed from shapes/dtypes at trace time, so
it composes with ``jit`` at zero runtime cost (the reference computes the same
number at runtime from tensor metadata). Like the reference, bits are counted
per logical collective payload regardless of world size
(``reducer.py:127,133,146`` increment unconditionally).

Every reducer payload reaches the wire through ONE entry,
:func:`tagged_all_reduce_mean`: a ``pmean`` that carries the payload's ledger
tag and, when host fence hooks are registered at trace time, is bracketed by
their ``launch`` / ``retire`` callbacks (comm fault injection, collective
deadline watchdogs). With no hook registered it traces to the bare ``pmean``.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Trace-time tag-prefix stack (hierarchical reduction levels): reducers
# hardcode their payload tags ("grads", "powersgd.P", ...) because they are
# topology-blind; the hierarchical reducer runs the SAME reducer code per
# fabric level and needs the level visible in every fence-hook info dict
# and ledger line. ``tag_scope("outer")`` prefixes every tag that
# :func:`tagged_all_reduce_mean` burns into its callbacks while the scope
# is active — at TRACE time, like the hook-presence gate, so the compiled
# program carries "outer.powersgd.P" etc. and watchdogs/chaos injectors can
# filter by level without the reducer knowing it was nested.
_TAG_SCOPE: List[str] = []


@contextlib.contextmanager
def tag_scope(prefix: str):
    """Prefix every collective tag traced inside the ``with`` body with
    ``prefix + "."`` (nestable; prefixes compose outermost-first)."""
    _TAG_SCOPE.append(str(prefix))
    try:
        yield
    finally:
        _TAG_SCOPE.pop()


def scoped_tag(tag: str) -> str:
    """``tag`` under the currently active :func:`tag_scope` prefixes."""
    if not _TAG_SCOPE:
        return tag
    return ".".join(_TAG_SCOPE + [tag])

# Host-side fence hooks (degraded-fabric survival, DESIGN.md): a hook is a
# plain Python callable invoked ON THE HOST on either side of every
# :func:`tagged_all_reduce_mean` — once per device per execution, with an
# info dict {tag, payload_bytes, phase, device_index} where phase is
# "launch" (the payload is about to ride its collective) or "retire" (the
# reduced result is available). The insertion is an ``io_callback`` whose
# token is fenced into the dataflow, so a sleeping hook genuinely delays
# the collective (comm fault injection) and a timing hook genuinely
# brackets it (collective deadline watchdogs) — while the callback itself
# issues NO collectives, leaving the wire ledger byte-exact. Hooks are
# consulted at TRACE time: with no hook registered the compiled graph is
# bit-for-bit the pre-hook graph; registered hooks are late-bound (the host
# shim reads the registry at call time), so the active hook set may change
# between executions without recompiling.
_FENCE_HOOKS: List[Callable[[Dict], None]] = []


def add_fence_hook(fn: Callable[[Dict], None]) -> None:
    """Register a host-side fence hook (see module note). Hooks run
    in registration order — register watchdogs BEFORE injectors so the
    deadline timer is armed when an injected stall starts sleeping."""
    _FENCE_HOOKS.append(fn)


def remove_fence_hook(fn: Callable[[Dict], None]) -> None:
    """Unregister a fence hook (no-op when absent)."""
    try:
        _FENCE_HOOKS.remove(fn)
    except ValueError:
        pass


def fence_hooks_active() -> bool:
    """True when at least one fence hook is registered — the trace-time
    gate for inserting the host callbacks at all."""
    return bool(_FENCE_HOOKS)


def _run_fence_hooks(device_index, *, tag: str, payload_bytes: int, phase: str):
    info = {
        "tag": tag,
        "payload_bytes": payload_bytes,
        "phase": phase,
        "device_index": int(device_index),
    }
    for hook in list(_FENCE_HOOKS):
        hook(info)
    return np.int32(0)


def _fence_callback(
    carry: jax.Array, *, tag: str, payload_bytes: int, phase: str,
    axis_name: Optional[str]
) -> jax.Array:
    """Fence a host callback into ``carry``'s dataflow on one side of a
    collective: the callback's token and the carried value pass through one
    ``optimization_barrier``, so XLA can neither hoist the collective above
    the callback nor sink the callback past the result.

    ``ordered=False`` deliberately: ordering comes from DATAFLOW, not the
    global token chain — each callback's token is fenced into its own
    payload (launch) or result (retire), so per-device callback order
    follows the collectives' data dependencies. (``ordered=True`` also trips
    an XLA sharding-propagation check on jaxlib 0.4.37 when the enclosing
    jit carries explicit shardings: the ordering token becomes an extra
    entry parameter the propagation vector doesn't cover.)"""
    from jax.experimental import io_callback

    shim = functools.partial(
        _run_fence_hooks, tag=tag, payload_bytes=payload_bytes, phase=phase,
    )
    token = io_callback(
        shim,
        jax.ShapeDtypeStruct((), jnp.int32),
        jnp.asarray(axis_index(axis_name), jnp.int32),
        ordered=False,
    )
    carry, _ = fence(carry, token)
    return carry


def n_bits(x: jax.Array | jax.ShapeDtypeStruct) -> int:
    """Payload size in bits: ``8 * nelement * element_size`` (reference
    ``reducer.py:197-198``). Static — usable inside jit (returns a Python int)."""
    return 8 * int(x.size) * x.dtype.itemsize


def all_reduce_sum(x: jax.Array, axis_name: Optional[str]) -> jax.Array:
    """``dist.all_reduce(SUM)`` analogue (``ddp_guide_cifar10/ddp_init.py:61``).

    Identity when ``axis_name`` is None — the reference's single-process no-op
    (``reducer.py:193-195``).
    """
    if axis_name is None:
        return x
    return jax.lax.psum(x, axis_name)


def all_reduce_mean(x: jax.Array, axis_name: Optional[str]) -> jax.Array:
    """allreduce-then-divide-by-world-size, fused (reference does
    ``all_reduce(buf); buf /= n_workers`` — ``reducer.py:126-128``)."""
    if axis_name is None:
        return x
    return jax.lax.pmean(x, axis_name)


def all_gather(x: jax.Array, axis_name: Optional[str]) -> jax.Array:
    """``dist.all_gather`` analogue (``tensor_buffer.py:50-57``): returns the
    per-worker values stacked on a new leading axis. Single-process fallback
    returns ``x[None]`` — the reference's one-element copy
    (``tensor_buffer.py:64-69``)."""
    if axis_name is None:
        return x[None]
    return jax.lax.all_gather(x, axis_name)


def all_gather_replicated(x: jax.Array, axis_name: Optional[str]) -> jax.Array:
    """``all_gather`` whose output is typed **replicated** (invariant) over the
    mesh axis, not varying.

    The gathered value is mathematically identical on every worker either way;
    this variant tells shard_map's replication checker so, which lets reducers
    built on gathers (top-k / sign / int8 payload exchange) feed the trainer's
    replicated ``params``/``momenta`` out_specs without a spurious
    re-synchronizing psum. Wire cost is identical to ``all_gather``.
    """
    if axis_name is None:
        return x[None]
    try:
        from jax.lax import all_gather_invariant  # newer jax exports it
    except ImportError:
        try:
            from jax._src.lax.parallel import all_gather_invariant
        except ImportError:
            # pre-varying-types jax has no invariant gather; without
            # replication tracking (check_rep=False) plain all_gather is
            # the identical op — same wire cost, same stacked result
            all_gather_invariant = jax.lax.all_gather
    return all_gather_invariant(x, axis_name)


def bucket_assignments(
    sizes_bytes: List[int], bucket_bytes: int
) -> List[List[int]]:
    """Assign leaf indices to size-targeted buckets in REVERSE index order.

    Backward-order bucketing (the DDP gradient-bucket strategy): autodiff
    produces gradients roughly in reverse parameter order — the loss-side
    layers' grads materialize first — so walking the leaves last-to-first
    and closing a bucket once it reaches ``bucket_bytes`` yields buckets in
    gradient *production* order. Bucket 0's collective depends only on the
    last few leaves and can launch while the front of the backward pass is
    still computing; each later bucket is fenced behind its predecessor's
    result (see ``ExactReducer``), which pins the DDP launch order into the
    schedule.

    Pure Python over static sizes — usable at trace time and in
    ledger/bits bookkeeping alike. Every bucket
    is non-empty; indices *within* a bucket stay in ascending order so the
    per-bucket packer layout is deterministic. ``bucket_bytes`` clamps to
    >= 1 byte; a target at or above the total yields one bucket.
    """
    target = max(1, int(bucket_bytes))
    buckets: List[List[int]] = []
    cur: List[int] = []
    acc = 0
    for i in reversed(range(len(sizes_bytes))):
        cur.append(i)
        acc += int(sizes_bytes[i])
        if acc >= target:
            buckets.append(sorted(cur))
            cur, acc = [], 0
    if cur:
        buckets.append(sorted(cur))
    return buckets


def fence(*values):
    """``lax.optimization_barrier`` over one or more pytrees: the returned
    values are identical but XLA may neither reorder computations across the
    barrier nor fuse ops on opposite sides of it. This is the pin that keeps
    ``ExactReducer``'s bucket chain in launch order and a fence hook's
    callback on its side of the collective."""
    if not values:
        return values
    out = jax.lax.optimization_barrier(values)
    return out[0] if len(values) == 1 else out


def tagged_all_reduce_mean(
    flat: jax.Array, axis_name: Optional[str], tag: str = "payload"
) -> jax.Array:
    """Allreduce-mean of one payload under its ledger ``tag`` (prefixed by
    the active :func:`tag_scope`) — the single entry every reducer payload
    takes to the wire.

    When fence hooks are registered at trace time (see
    :func:`add_fence_hook`), the collective is bracketed by a ``launch`` and
    a ``retire`` host callback fenced into the dataflow, so comm faults and
    deadline watchdogs bite on every collective. With none registered this
    is exactly :func:`all_reduce_mean`.
    """
    if not fence_hooks_active():
        return all_reduce_mean(flat, axis_name)
    callback = functools.partial(
        _fence_callback, tag=scoped_tag(tag),
        payload_bytes=int(flat.size) * flat.dtype.itemsize,
        axis_name=axis_name,
    )
    out = all_reduce_mean(callback(flat, phase="launch"), axis_name)
    return callback(out, phase="retire")


def axis_size(axis_name: Optional[str]) -> int:
    """World size along the collective axis; 1 outside any mesh (the
    reference's ``n_workers=1`` fallback, ``reducer.py:13-18``). Static."""
    if axis_name is None:
        return 1
    return jax.lax.axis_size(axis_name)


def axis_index(axis_name: Optional[str]) -> jax.Array | int:
    """Rank along the collective axis (``dist.get_rank()`` analogue)."""
    if axis_name is None:
        return 0
    return jax.lax.axis_index(axis_name)
