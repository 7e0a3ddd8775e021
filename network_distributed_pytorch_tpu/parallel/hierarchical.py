"""Hierarchical (fabric-aware) gradient reduction: exact within the fast
fabric, compressed only across the slow one — plus the geo-resilient
two-level training loop built on it.

The reference's whole subject is DDP over slow inter-node links
(README.md:1-2 — "Internel / 1Gb / 10Gb / 100Gb"), but its compression is
all-or-nothing: PowerSGD compresses across EVERY pair of workers, including
ones connected by fast in-node links where compression only adds
approximation error (``reducer.py:43-170`` has no topology awareness).

On TPU the topology is explicit in the mesh: chips within a slice talk over
ICI (~hundreds of GB/s), hosts talk over DCN (~GbE-class — exactly the
reference's regime). This module exploits that at two levels:

**Per-step** (:class:`HierarchicalReducer`):

1. **exact** packed all-reduce of the send buffer over the ``inner`` (ICI)
   axis — full fidelity where bandwidth is free;
2. any compressing reducer (PowerSGD, top-k, sign, int8, or exact) over the
   ``outer`` (DCN) axis only — compression loss is paid solely where it buys
   wire time.

Semantics: the compressed quantity is the *group mean* gradient, and the
error-feedback memory tracks the outer compression residual (identical on
every chip of a host group, since their input is the group mean). With
``ExactReducer`` as the outer reducer this is exactly equivalent to a flat
all-reduce (mean of group means over equal groups = global mean) — the
equivalence test pins it.

**Per-round** (:func:`make_hierarchical_train_fn`): the cross-site sync is
taken off the per-step critical path entirely — DiLoCo-style. Each round
runs ``sync_every`` inner steps whose gradients are exactly all-reduced
over the FAST axis only (DDP within a site), then the round's parameter
displacement Δ = anchor − θ_H rides ONE compressed, error-feedback-carried
outer reduction across the slow edges. With ``outer_async=True`` the outer
update lands one round late (``inflight`` slot in the carry), modeling an
outer collective that overlaps the next round's inner steps: the step cadence
is the fast-fabric cadence, and the slow fabric only has to deliver one
compressed delta per ``sync_every`` steps. The survival story — degrading
to :meth:`CompiledHierarchical.local_round` when the slow edge partitions
and rejoining via the anchor-relative delta (which telescopes over any
number of skipped syncs) — is driven from the host by
``resilience.guards.PartitionPolicy``/``OuterSyncDriver``.

Wire accounting (byte-exact vs the compiled HLO, like everything else): the
inner exact payload + the outer reducer's payload + nothing hidden. Every
collective is tagged with its level (``inner.*`` / ``outer.*`` via
``comm.tag_scope``), so fence hooks (chaos, watchdogs) and the per-level
ledger can tell the fabrics apart. The interesting number for the
reference's study is the outer (slow-fabric) share — reported separately
via :meth:`bits_by_fabric`.

Use the per-step reducer with the stock trainer by passing the 2-D mesh and
the axis tuple::

    mesh = make_mesh(axis_sizes=(n_hosts, chips_per_host),
                     axis_names=("dcn", "ici"))
    reducer = HierarchicalReducer(PowerSGDReducer(...), mesh,
                                  inner_axis="ici", outer_axis="dcn")
    step = make_train_step(loss_fn, reducer, params, ...,
                           mesh=mesh, axis_name=("dcn", "ici"))

(jax collectives accept axis-name tuples, so the trainer's pcast/pmean/
sharding specs work unchanged over both axes.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from .comm import n_bits, tag_scope, tagged_all_reduce_mean
from .packing import TensorPacker

PyTree = Any
AxisName = Union[str, Tuple[str, ...], None]


def _packed_exact_mean(tree: PyTree, axis_name: str, tag: str) -> PyTree:
    """Exact allreduce-mean of a whole pytree as ONE packed collective
    (``TensorBuffer`` style — many tiny leaves cost one wire payload),
    routed through :func:`~.comm.tagged_all_reduce_mean` so fence hooks
    (chaos faults, deadline watchdogs) and tag scoping apply. Bitwise
    identical to per-leaf ``pmean`` (an all-reduce is elementwise; packing
    is a permutation). Mixed-dtype trees fall back to one collective per
    dtype group, preserving every leaf's dtype and the byte total."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    out = list(leaves)
    multi = len(groups) > 1
    for gi, (dtype, idx) in enumerate(sorted(groups.items(), key=lambda kv: str(kv[0]))):
        group = [leaves[i] for i in idx]
        packer = TensorPacker.for_arrays(group)
        flat = packer.pack(group)
        gtag = f"{tag}.d{gi}" if multi else tag
        reduced = tagged_all_reduce_mean(flat, axis_name, tag=gtag)
        for i, r in zip(idx, packer.unpack(reduced)):
            out[i] = r
    return jax.tree_util.tree_unflatten(treedef, out)


class HierarchicalReducer:
    """Exact mean over ``inner_axis``; ``outer`` reducer over ``outer_axis``."""

    def __init__(
        self,
        outer,
        mesh,
        inner_axis: str = "ici",
        outer_axis: str = "dcn",
    ):
        self.outer = outer
        self.inner_axis = inner_axis
        self.outer_axis = outer_axis
        # static axis sizes for the (outside-trace) bits model
        self.inner_world = int(mesh.shape[inner_axis])
        self.outer_world = int(mesh.shape[outer_axis])

    def init(self, grads_template: PyTree):
        return self.outer.init(grads_template)

    def reduce(
        self, state, send: PyTree, axis_name: AxisName
    ) -> Tuple[Any, PyTree, PyTree, int]:
        if axis_name is None:
            # single-process fallback, reference reducer.py:13-18
            return self.outer.reduce(state, send, None)
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        assert set(axes) == {self.inner_axis, self.outer_axis}, (
            f"trainer axes {axes} != reducer axes "
            f"({self.inner_axis!r}, {self.outer_axis!r})"
        )
        # phase 1: exact group mean over the fast fabric — packed into one
        # tagged collective so fence hooks see "inner.grads" per execution
        with tag_scope("inner"):
            send = _packed_exact_mean(send, self.inner_axis, tag="grads")
        inner_bits = sum(
            n_bits(l) for l in jax.tree_util.tree_leaves(send)
        )
        # phase 2: compressed reduction across the slow fabric only; the
        # outer reducer's hardcoded tags pick up the "outer." level prefix
        with tag_scope("outer"):
            state, out, memory, outer_bits = self.outer.reduce(
                state, send, self.outer_axis
            )
        return state, out, memory, inner_bits + outer_bits

    def compression_error(
        self, state, send: PyTree, axis_name: AxisName = None
    ) -> jax.Array:
        """Relative compression error of the OUTER reducer — the only lossy
        stage (the inner exact mean is bitwise). Delegates to the outer
        reducer's own collective-free probe, so a hierarchical rung reports
        its slow-fabric distortion rather than silently reporting zero (or,
        worse, an inner-stage number that is zero by construction)."""
        del axis_name  # the probe is collective-free on either fabric
        if hasattr(self.outer, "compression_error"):
            return self.outer.compression_error(state, send, None)
        return jnp.zeros((), jnp.float32)

    # ---- fidelity --------------------------------------------------------

    def _inner_groups(self, grads_template: PyTree):
        """(group, tag) pairs for the exact inner payload — mirrors the
        dtype grouping :meth:`ledger_entries` prices (``inner.grads`` /
        ``inner.grads.d{gi}``) so the fidelity↔ledger join stays exact."""
        leaves = jax.tree_util.tree_leaves(grads_template)
        groups: dict = {}
        for i, leaf in enumerate(leaves):
            groups.setdefault(str(jnp.dtype(leaf.dtype)), []).append(i)
        multi = len(groups) > 1
        return [
            (f"inner.grads.d{gi}" if multi else "inner.grads", idx)
            for gi, (_, idx) in enumerate(sorted(groups.items()))
        ]

    def fidelity_group_tags(self, grads_template: PyTree) -> dict:
        """Static ``fidelity group -> wire-ledger tag`` map: the exact inner
        payload (group == tag, zero error by construction) plus the outer
        reducer's own groups re-keyed under ``outer.`` — matching the
        ``outer.{tag}`` re-tagging :meth:`ledger_entries` applies."""
        tags = {name: name for name, _ in self._inner_groups(grads_template)}
        if hasattr(self.outer, "fidelity_group_tags"):
            for g, t in self.outer.fidelity_group_tags(grads_template).items():
                tags[f"outer.{g}"] = f"outer.{t}"
        return tags

    def fidelity_stats(
        self,
        state,
        send: PyTree,
        memories: Optional[PyTree] = None,
        axis_name: AxisName = None,
    ) -> dict:
        """Per-group fidelity diagnostics (health-probe shape, one entry per
        :meth:`fidelity_group_tags` key): the inner exact groups are zeros /
        ones by construction; the outer groups are the outer reducer's OWN
        collective-free diagnostics re-keyed under ``outer.``."""
        del axis_name
        stats: dict = {
            name: {
                "rel_error": jnp.zeros((), jnp.float32),
                "cosine_sim": jnp.ones((), jnp.float32),
                "ef_norm": jnp.zeros((), jnp.float32),
                "quantized_share": jnp.zeros((), jnp.float32),
            }
            for name, _ in self._inner_groups(send)
        }
        if hasattr(self.outer, "fidelity_stats"):
            outer = self.outer.fidelity_stats(state, send, memories, None)
            for g, v in outer.items():
                stats[f"outer.{g}"] = v
        return stats

    # ---- analytics -------------------------------------------------------

    def bits_by_fabric(self, grads_template: PyTree) -> dict:
        """{'inner': exact ICI bits, 'outer': compressed DCN bits} — the
        outer number is the one the reference's slow-network study cares
        about."""
        leaves = jax.tree_util.tree_leaves(grads_template)
        return {
            "inner": sum(n_bits(l) for l in leaves),
            "outer": self._outer_bits(grads_template),
        }

    def _outer_bits(self, grads_template: PyTree) -> int:
        if hasattr(self.outer, "bits_per_step"):
            return self.outer.bits_per_step(
                grads_template, n_workers=self.outer_world
            )
        return sum(n_bits(l) for l in jax.tree_util.tree_leaves(grads_template))

    def bits_per_step(self, grads_template: PyTree, n_workers: int = 1) -> int:
        b = self.bits_by_fabric(grads_template)
        return b["inner"] + b["outer"]

    def ledger_entries(self, params_template, axis: str = "", n_workers: int = 1):
        """Per-level itemization: the packed exact inner payload (tag
        ``inner.grads``, on the fast axis) plus the outer reducer's own
        entries re-tagged under ``outer.`` (on the slow axis). Sums to
        :meth:`bits_per_step` — the trainer's ledger invariant."""
        from ..observe.ledger import LedgerEntry, reducer_ledger_entries

        leaves = jax.tree_util.tree_leaves(params_template)
        entries = []
        groups: dict = {}
        for leaf in leaves:
            key = str(jnp.dtype(leaf.dtype))
            groups[key] = groups.get(key, 0) + n_bits(leaf) // 8
        multi = len(groups) > 1
        for gi, (dtype, payload) in enumerate(sorted(groups.items())):
            entries.append(
                LedgerEntry(
                    tag=f"inner.grads.d{gi}" if multi else "inner.grads",
                    layer="reducer",
                    op="all-reduce",
                    axis=self.inner_axis,
                    dtype=dtype,
                    payload_bytes=payload,
                )
            )
        for e in reducer_ledger_entries(
            self.outer, params_template, axis=self.outer_axis,
            n_workers=self.outer_world,
        ):
            entries.append(
                dataclasses.replace(e, tag=f"outer.{e.tag}", axis=self.outer_axis)
            )
        return entries


def replica_drift_stats(params: PyTree, anchors: Optional[PyTree] = None) -> dict:
    """Replica/anchor drift for the fidelity plane, from a per-worker
    parameter tree (leading ``num_devices`` axis, the
    :class:`HierarchicalState.params` / ``LocalSGDState.params`` layout):

    - ``replica_drift``: RMS divergence of the per-worker copies from their
      mean, relative to the mean's norm — how far sites/replicas have walked
      apart since the last sync (identically zero for exact data-parallel
      states, where every copy is the same buffer broadcast).
    - ``anchor_drift``: distance of the mean params from ``anchors`` (the
      last applied outer update), relative to the anchor norm — how much
      displacement the next outer sync must carry. Zero when ``anchors`` is
      ``None`` (no outer loop to drift from).

    Pure local math over replicated/host-visible trees — collective-free,
    jit-safe, scalars only."""
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves:
        return {
            "replica_drift": jnp.zeros((), jnp.float32),
            "anchor_drift": jnp.zeros((), jnp.float32),
        }
    eps = jnp.float32(1e-30)
    dev_sq = jnp.zeros((), jnp.float32)
    mean_sq = jnp.zeros((), jnp.float32)
    means = []
    for leaf in leaves:
        f = leaf.astype(jnp.float32)
        mu = jnp.mean(f, axis=0)
        means.append(mu)
        dev_sq = dev_sq + jnp.sum(jnp.square(f - mu[None])) / f.shape[0]
        mean_sq = mean_sq + jnp.sum(jnp.square(mu))
    replica = jnp.sqrt(dev_sq) / jnp.maximum(jnp.sqrt(mean_sq), eps)
    if anchors is None:
        anchor = jnp.zeros((), jnp.float32)
    else:
        a_leaves = jax.tree_util.tree_leaves(anchors)
        diff_sq = jnp.zeros((), jnp.float32)
        a_sq = jnp.zeros((), jnp.float32)
        for mu, a in zip(means, a_leaves):
            af = a.astype(jnp.float32)
            diff_sq = diff_sq + jnp.sum(jnp.square(mu - af))
            a_sq = a_sq + jnp.sum(jnp.square(af))
        anchor = jnp.sqrt(diff_sq) / jnp.maximum(jnp.sqrt(a_sq), eps)
    return {"replica_drift": replica, "anchor_drift": anchor}


# ---------------------------------------------------------------------------
# The geo-resilient round loop: inner DDP at fast-fabric cadence, one async
# compressed outer sync per round, a collective-free local round for
# partition survival
# ---------------------------------------------------------------------------


class HierarchicalState(NamedTuple):
    """Round carry for :func:`make_hierarchical_train_fn`.

    ``params``/``inner_opt``/``memories``/``model_state`` are per-worker
    (leading ``num_devices`` axis — params diverge across SITES during a
    partition, and the inner optimizer moments are local by design);
    ``anchors`` (the global params at the last APPLIED outer update — the
    reference point every outer delta is measured from), ``outer_momenta``,
    ``reducer_state`` and ``inflight`` (the async slot: the outer update
    computed last round, landing this round) are replicated."""

    params: PyTree
    anchors: PyTree
    outer_momenta: PyTree
    inner_opt: PyTree
    memories: PyTree
    reducer_state: Any
    inflight: PyTree
    model_state: PyTree


class CompiledHierarchical(NamedTuple):
    """Two compiled round programs over the 2-D (outer × inner) mesh.

    ``sync_fn(state, batches, weights) -> (state, site_losses)`` runs
    ``sync_every`` inner-DDP steps (exact packed grad all-reduce on the
    fast axis, tag ``inner.step_grads``) then ONE hierarchical outer
    reduction of the anchor-relative delta (tags ``inner.grads`` +
    ``outer.*``) and applies an outer Nesterov update — the update lands
    immediately (``outer_async=False``) or one round late through the
    ``inflight`` carry slot (``outer_async=True``, modeling the outer
    collective overlapping the next round's inner steps).

    ``local_fn`` is the same round with NO outer-axis collective at all —
    the partition-survival program. Because the sync delta is measured
    against the replicated ``anchors`` (not the round's own start), local
    rounds need no extra bookkeeping: the next sync's delta telescopes over
    every skipped round, and the EF memories carry the compression residual
    across the gap (the rejoin catch-up reduction).

    ``site_losses`` has shape ``(outer_world, sync_every)`` — per-SITE loss
    trajectories (inner-axis mean only), which is what partition forensics
    needs; sites legitimately diverge between syncs.

    ``bits_per_round`` is the sync round's full wire cost;
    ``local_bits_per_round`` the collective-free round's (inner-axis bytes
    only). Scan-body caveat as :class:`~.localsgd.CompiledLocalSGD`: a
    text-level HLO audit sees the per-step collectives once."""

    sync_fn: Callable
    local_fn: Callable
    bits_per_round: int
    local_bits_per_round: int
    inner_bits_per_round: int
    outer_bits_per_round: int
    sync_every: int
    mesh: Mesh
    inner_axis: str
    outer_axis: str
    reducer: HierarchicalReducer
    outer_async: bool
    ledger: Any
    inner_optimizer: Any = None

    def __call__(self, state, batches, weights=None, *, local: bool = False):
        if weights is None:
            weights = jnp.ones((self.sync_every,), jnp.float32)
        fn = self.local_fn if local else self.sync_fn
        return fn(state, batches, weights)

    def local_round(self, state, batches, weights=None):
        return self(state, batches, weights, local=True)

    @property
    def bits_per_step(self) -> float:
        return self.bits_per_round / self.sync_every

    @property
    def outer_bits_per_step(self) -> float:
        """Slow-fabric bytes amortized per inner step — the number the
        cross-site shrink claim is about."""
        return self.outer_bits_per_round / self.sync_every

    @property
    def axis_name(self) -> Tuple[str, str]:
        return (self.outer_axis, self.inner_axis)

    def init_state(self, params: PyTree, model_state: PyTree = None) -> HierarchicalState:
        from .trainer import tile_per_worker

        n = self.mesh.size
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        inner = (
            self.inner_optimizer.init(params)
            if self.inner_optimizer is not None
            else zeros
        )
        return HierarchicalState(
            params=tile_per_worker(params, n),
            # a COPY: the state is donated on the first round, and handing
            # the caller's own buffers to the donor would delete them out
            # from under any later init_state/eval use
            anchors=jax.tree_util.tree_map(
                lambda p: jnp.array(p, copy=True), params
            ),
            outer_momenta=zeros,
            inner_opt=tile_per_worker(inner, n),
            memories=tile_per_worker(zeros, n),
            reducer_state=self.reducer.init(params),
            # fresh buffers — aliasing outer_momenta would donate the same
            # buffer twice under donate_argnums=(0,)
            inflight=jax.tree_util.tree_map(jnp.zeros_like, params),
            model_state=tile_per_worker(
                {} if model_state is None else model_state, n
            ),
        )

    def eval_params(self, state: HierarchicalState) -> PyTree:
        """Mean over the per-worker copies: at a steady sync point every
        copy equals the anchor (mean = identity); mid-partition it is the
        standard local-SGD eval convention."""
        return jax.tree_util.tree_map(lambda p: jnp.mean(p, axis=0), state.params)

    def eval_model_state(self, state: HierarchicalState, reduce: str = "mean") -> PyTree:
        from .trainer import collapse_per_worker

        return collapse_per_worker(state.model_state, reduce)


def make_hierarchical_train_fn(
    loss_fn,
    params_template: PyTree,
    inner_learning_rate: Optional[float] = None,
    outer_learning_rate: float = 0.7,
    outer_momentum: float = 0.9,
    outer_nesterov: bool = True,
    inner_momentum: float = 0.9,
    sync_every: int = 8,
    inner_algorithm: str = "sgd",
    outer_reducer=None,
    mesh: Optional[Mesh] = None,
    inner_axis: str = "ici",
    outer_axis: str = "dcn",
    outer_async: bool = True,
    donate_state: bool = True,
    inner_optimizer=None,
) -> CompiledHierarchical:
    """Compile the geo-resilient two-level round (see
    :class:`CompiledHierarchical`).

    Within a round, every inner step is EXACT DDP over ``inner_axis``
    (packed grad all-reduce — the fast fabric is free); across rounds the
    slow ``outer_axis`` carries one DiLoCo-style compressed outer update of
    the anchor-relative delta, with error feedback in per-worker
    ``memories``. ``outer_async=True`` (the default, and the point) folds
    the update in one round late via the ``inflight`` slot: the outer
    collective is off the step critical path, so the run steps at
    fast-fabric speed while the slow edge streams last round's delta.

    Equivalences pinned by test: ``outer_async=False`` +
    ``ExactReducer`` outer + ``outer_learning_rate=1, outer_momentum=0``
    is plain hierarchical parameter averaging; sites never diverge at sync
    points; a run of ``local_round`` s followed by one sync lands within
    the EF-bounded divergence budget of the never-partitioned oracle.

    Stability note: the defaults are the DiLoCo *sync* recipe. With
    ``outer_async=True`` every outer update lands one round stale —
    classic delayed-gradient dynamics, which roughly HALVES the stable
    outer step and punishes momentum stacking (an inner momentum of 0.9
    already overshoots the round delta). Async runs want
    ``outer_learning_rate≈0.5, outer_momentum≤0.5, outer_nesterov=False``
    and a plain (or lightly damped) inner optimizer; the async-vs-sync
    equivalence test pins that recipe converging at sync-mode quality.
    """
    from .localsgd import _mask_step
    from .reducers import ExactReducer
    from .trainer import (
        LOSS_SYNC_BITS,
        pad_leading,
        sgd_momentum_update,
        strip_leading,
    )

    assert mesh is not None, "hierarchical training is inherently multi-device"
    assert inner_algorithm in ("sgd", "sgd_plain", "optax")
    assert (inner_algorithm == "optax") == (inner_optimizer is not None)
    if inner_algorithm == "optax":
        if inner_learning_rate is not None:
            raise ValueError(
                "inner_learning_rate is unused with inner_algorithm='optax'"
                " — the optax inner_optimizer carries its own learning rate"
            )
    elif inner_learning_rate is None:
        raise ValueError(
            f"inner_algorithm={inner_algorithm!r} needs inner_learning_rate"
        )
    assert sync_every >= 1
    if outer_reducer is None:
        outer_reducer = ExactReducer()
    hier = HierarchicalReducer(
        outer_reducer, mesh, inner_axis=inner_axis, outer_axis=outer_axis
    )
    axes = (outer_axis, inner_axis)

    def inner_step(carry, batch):
        params, opt, model_state = carry
        (loss, model_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, model_state, batch
        )
        # exact DDP over the fast fabric ONLY — the inner path issues no
        # outer-axis collective
        with tag_scope("inner"):
            grads = _packed_exact_mean(grads, inner_axis, tag="step_grads")
        if inner_algorithm == "optax":
            import optax

            updates, opt = inner_optimizer.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
        elif inner_algorithm == "sgd":
            params, opt = sgd_momentum_update(
                params, opt, grads, inner_learning_rate, inner_momentum
            )
        else:
            params = jax.tree_util.tree_map(
                lambda p, g: p - inner_learning_rate * g, params, grads
            )
        loss = jax.lax.pmean(loss, inner_axis)
        return (params, opt, model_state), loss

    def run_inner(state: HierarchicalState, batches, weights):
        (params, inner_opt, model_state), losses = jax.lax.scan(
            _mask_step(inner_step),
            (
                strip_leading(state.params),
                strip_leading(state.inner_opt),
                strip_leading(state.model_state),
            ),
            (batches, weights),
        )
        # per-SITE loss trajectory: (1, H) per worker, invariant over the
        # inner axis, sharded over the outer axis in out_specs
        return params, inner_opt, model_state, losses[None, :]

    def sync_round(state: HierarchicalState, batches, weights):
        params, inner_opt, model_state, losses = run_inner(state, batches, weights)
        # outer gradient: displacement from the last APPLIED global anchor
        # (telescopes over any local rounds in between), plus the residual
        # the compressor dropped last sync (EF catch-up)
        anchors_v = jax.tree_util.tree_map(
            lambda p: jax.lax.pcast(p, axes, to="varying"), state.anchors
        )
        send = jax.tree_util.tree_map(
            lambda a, p, m: a - p + m,
            anchors_v, params, strip_leading(state.memories),
        )
        reducer_state, dbar, memories, _ = hier.reduce(
            state.reducer_state, send, axes
        )
        if outer_momentum > 0.0:
            outer_m = jax.tree_util.tree_map(
                lambda m, d: outer_momentum * m + d, state.outer_momenta, dbar
            )
            update = (
                jax.tree_util.tree_map(
                    lambda d, m: d + outer_momentum * m, dbar, outer_m
                )
                if outer_nesterov
                else outer_m
            )
        else:
            outer_m = state.outer_momenta
            update = dbar
        # async: THIS round's update goes into the inflight slot (it is
        # "on the wire" while the next round's inner steps run) and the
        # PREVIOUS round's lands now; sync mode applies immediately
        applied = state.inflight if outer_async else update
        inflight = update if outer_async else state.inflight
        new_anchor = jax.tree_util.tree_map(
            lambda a, u: a - outer_learning_rate * u, state.anchors, applied
        )
        new_params = jax.tree_util.tree_map(
            lambda p: jax.lax.pcast(p, axes, to="varying"), new_anchor
        )
        return (
            HierarchicalState(
                params=pad_leading(new_params),
                anchors=new_anchor,
                outer_momenta=outer_m,
                inner_opt=pad_leading(inner_opt),
                memories=pad_leading(memories),
                reducer_state=reducer_state,
                inflight=inflight,
                model_state=pad_leading(model_state),
            ),
            losses,
        )

    def local_round(state: HierarchicalState, batches, weights):
        params, inner_opt, model_state, losses = run_inner(state, batches, weights)
        # partition survival: keep stepping at fast-fabric speed, touch
        # nothing replicated — the anchor-relative delta at the next sync
        # absorbs everything that happened here
        return (
            HierarchicalState(
                params=pad_leading(params),
                anchors=state.anchors,
                outer_momenta=state.outer_momenta,
                inner_opt=pad_leading(inner_opt),
                memories=state.memories,
                reducer_state=state.reducer_state,
                inflight=state.inflight,
                model_state=pad_leading(model_state),
            ),
            losses,
        )

    state_specs = HierarchicalState(
        params=PartitionSpec(axes),
        anchors=PartitionSpec(),
        outer_momenta=PartitionSpec(),
        inner_opt=PartitionSpec(axes),
        memories=PartitionSpec(axes),
        reducer_state=PartitionSpec(),
        inflight=PartitionSpec(),
        model_state=PartitionSpec(axes),
    )
    in_specs = (state_specs, PartitionSpec(None, axes), PartitionSpec())
    out_specs = (state_specs, PartitionSpec(outer_axis))

    def compile_round(body):
        return jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=in_specs, out_specs=out_specs
            ),
            donate_argnums=(0,) if donate_state else (),
        )

    sync_fn = compile_round(sync_round)
    local_fn = compile_round(local_round)

    # ---- wire model + per-level ledger ----------------------------------
    from ..observe.ledger import LedgerEntry, WireLedger

    leaves = jax.tree_util.tree_leaves(params_template)
    dense_bits = sum(n_bits(l) for l in leaves)
    dtypes = {str(l.dtype) for l in leaves}
    by_fabric = hier.bits_by_fabric(params_template)
    inner_bits_per_round = sync_every * (dense_bits + LOSS_SYNC_BITS) + by_fabric["inner"]
    outer_bits_per_round = by_fabric["outer"]
    local_bits_per_round = sync_every * (dense_bits + LOSS_SYNC_BITS)
    bits_per_round = inner_bits_per_round + outer_bits_per_round
    entries = [
        LedgerEntry(
            tag="inner.step_grads",
            layer="reducer",
            op="all-reduce",
            axis=inner_axis,
            dtype=dtypes.copy().pop() if len(dtypes) == 1 else "mixed",
            payload_bytes=sync_every * dense_bits // 8,
            count=sync_every,
        ),
        LedgerEntry(
            tag="inner.loss-sync",
            layer="trainer",
            op="all-reduce",
            axis=inner_axis,
            dtype="float32",
            payload_bytes=sync_every * LOSS_SYNC_BITS // 8,
            count=sync_every,
        ),
    ]
    entries.extend(hier.ledger_entries(params_template))
    ledger = WireLedger(entries, dense_grad_bits=dense_bits)
    assert ledger.total_bits() == bits_per_round, (
        f"hierarchical ledger itemizes {ledger.total_bits()} bits but the "
        f"round's analytic model says {bits_per_round}"
    )
    return CompiledHierarchical(
        sync_fn=sync_fn,
        local_fn=local_fn,
        bits_per_round=bits_per_round,
        local_bits_per_round=local_bits_per_round,
        inner_bits_per_round=inner_bits_per_round,
        outer_bits_per_round=outer_bits_per_round,
        sync_every=sync_every,
        mesh=mesh,
        inner_axis=inner_axis,
        outer_axis=outer_axis,
        reducer=hier,
        outer_async=outer_async,
        ledger=ledger,
        inner_optimizer=inner_optimizer,
    )
