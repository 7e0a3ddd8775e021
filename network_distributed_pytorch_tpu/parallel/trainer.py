"""L4 — the trainer: one jitted, mesh-parallel training step.

Two reference training loops are reproduced as pure step functions:

- **Exact DDP** (``ddp_guide_cifar10/ddp_init.py:114-127``): forward → backward
  → allreduce-mean gradients → torch-style SGD with momentum
  (``v ← μ·v + g; p ← p − lr·v``).
- **Error-feedback SGD with momentum** (PowerSGD Algorithm 2,
  ``ddp_powersgd_guide_cifar10/ddp_init.py:125-181``): ``send ← g + e`` →
  ``reducer.reduce`` (compress/allreduce/decompress, e updated) →
  ``m ← λ·m + Δ`` → ``p ← p − lr·(Δ + m)``. The reference's first-step
  ``momentum = Δ.clone()`` special case (``ddp_init.py:166-172``) is exactly
  equivalent to zero-initialized momenta (λ·0 + Δ = Δ), so no step-0 branch
  is needed — the whole step is branch-free and jit-pure.

TPU-native design: the entire step — forward, backward, compression,
collectives, optimizer — is ONE ``shard_map`` region over ``Mesh(['data'])``,
traced once and compiled by XLA. Gradient synchronization is **hand-rolled
through the reducer**, NOT left to automatic SPMD psum insertion: that is the
reference's load-bearing design decision (it never uses torch DDP either,
SURVEY §2.3) — it is exactly what makes compression pluggable.

Bytes-on-wire are static per step, so they are returned as a Python int on
the compiled step object and accumulated host-side — closing the reference's
unfinished ``bits_communicated`` loop (SURVEY C9: collected, never reported).
"""

from __future__ import annotations


from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .comm import all_reduce_mean
from .mesh import DATA_AXIS

PyTree = Any
# (params, model_state, batch) -> (scalar loss, new_model_state).
# model_state carries non-gradient model variables (e.g. BatchNorm running
# stats — the reference's torchvision ResNets have them; torch DDP keeps them
# per-rank-local and UNSYNCED, and so does this trainer: in the distributed
# step model_state carries a per-worker leading axis, costs zero wire bytes
# per step, and is collapsed only at eval time
# (``CompiledStep.eval_model_state``). Stateless models pass {} through.
LossFn = Callable[[PyTree, PyTree, Any], Tuple[jax.Array, PyTree]]

# The one key of ``model_state`` that holds COUNTERS of the step just run
# rather than state it carries on: small integer arrays the loss function
# writes anew every step (an expert layer's assignments per expert, its
# drops). They ride the carry like batch-norm statistics, per worker and at
# no wire cost, and ``experiments.common.train_loop`` fetches them with the
# loss and puts them on that step's ``step/loss_sync`` span. A model_state
# without the key is untouched by all of this.
STEP_COUNTERS = "step_counters"

# The one non-reducer collective in the distributed step: the scalar loss is
# pmean'd for reporting (f32[] all-reduce = 4 bytes = 32 bits). Included in
# ``bits_per_step`` so the analytic model reconciles byte-exactly with the
# compiled HLO (utils.hlo_audit) — the honesty bar the reference's
# ``n_bits`` convention (reducer.py:197-198) never met.
LOSS_SYNC_BITS = 32


class TrainState(NamedTuple):
    """The full per-step carry, a pytree (mirrors the buffers the reference
    allocates up front, ``ddp_powersgd_guide_cifar10/ddp_init.py:130-135``).

    Replication structure (what is per-worker vs identical-everywhere) follows
    the reference exactly: params, momenta and reducer state are identical on
    every rank (their updates flow only through allreduced values), while the
    **error-feedback memories are genuinely per-worker state** (each rank
    stores its own residual ``send - decompressed``, ``reducer.py:163``) and
    so is ``model_state`` (torch DDP never syncs BatchNorm running stats —
    each rank keeps the stats of the batches it saw). In the distributed
    step, ``memories`` and ``model_state`` therefore carry a leading
    ``num_devices`` axis sharded over the data axis; everything else is
    replicated.
    """

    params: PyTree
    momenta: PyTree   # momenta  (zeros ≡ the reference's first-step clone-init)
    memories: PyTree  # error-feedback memories e (Algo 2 line 4: zeros); per-worker
    reducer_state: Any
    model_state: PyTree  # e.g. {'batch_stats': ...}; {} for stateless models


def init_train_state(
    params: PyTree,
    reducer,
    model_state: PyTree = None,
    num_devices: Optional[int] = None,
    optimizer=None,
) -> TrainState:
    """Zero-init the carry. ``num_devices`` adds the per-worker leading axis on
    the error memories for the distributed step (None → single-process).
    With an optax ``optimizer`` (algorithm="optax"), the ``momenta`` slot
    holds the optax opt_state instead of raw momentum buffers."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    model_state = {} if model_state is None else model_state
    if num_devices is None:
        memories = zeros
    else:
        memories = jax.tree_util.tree_map(
            lambda p: jnp.zeros((num_devices,) + p.shape, p.dtype), params
        )
        # per-worker model_state starts identical everywhere (same init),
        # then each worker's local batches evolve its own copy
        model_state = tile_per_worker(model_state, num_devices)
    return TrainState(
        params=params,
        momenta=optimizer.init(params) if optimizer is not None else zeros,
        memories=memories,
        reducer_state=reducer.init(params),
        model_state=model_state,
    )


def tile_per_worker(tree: PyTree, num_devices: int) -> PyTree:
    """Broadcast every leaf to a leading ``num_devices`` axis — the layout
    of genuinely per-worker carried state (error memories, local momenta,
    BN stats) before ``shard_map`` strips it back to one worker's copy."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (num_devices,) + jnp.shape(x)), tree
    )


def strip_leading(tree: PyTree) -> PyTree:
    """Per-worker global ``(num_devices, *shape)`` leaves → this device's
    ``(*shape)`` slice (inside shard_map, after the leading axis is sharded)."""
    return jax.tree_util.tree_map(lambda m: m[0], tree)


def pad_leading(tree: PyTree) -> PyTree:
    """Inverse of :func:`strip_leading`: re-add the length-1 leading axis so
    the out_specs concatenation rebuilds the global per-worker array."""
    return jax.tree_util.tree_map(lambda m: m[None], tree)


def sgd_momentum_update(
    params: PyTree, momenta: PyTree, delta: PyTree, lr: float, mu: float
) -> Tuple[PyTree, PyTree]:
    """torch ``optim.SGD`` with momentum: ``v ← μ·v + Δ; p ← p − lr·v``
    (the exact-DDP trainer's rule, ``ddp_guide_cifar10/ddp_init.py:110``).
    Shared by ``make_step_fn`` and the hand-rolled experiment steps."""
    momenta = jax.tree_util.tree_map(lambda m, d: mu * m + d, momenta, delta)
    params = jax.tree_util.tree_map(
        lambda p, m: p - lr * m, params, momenta
    )
    return params, momenta


def ef_momentum_update(
    params: PyTree, momenta: PyTree, delta: PyTree, lr: float, mu: float
) -> Tuple[PyTree, PyTree]:
    """PowerSGD Algorithm 2 lines 12-13: ``m ← λ·m + Δ; p ← p − lr·(Δ + m)``
    (``ddp_powersgd_guide_cifar10/ddp_init.py:166-178``)."""
    momenta = jax.tree_util.tree_map(lambda m, d: mu * m + d, momenta, delta)
    params = jax.tree_util.tree_map(
        lambda p, d, m: p - lr * (d + m), params, delta, momenta
    )
    return params, momenta


def collapse_per_worker(model_state: PyTree, reduce: str = "mean") -> PyTree:
    """Collapse a per-worker model_state (leading ``num_devices`` axis of
    local BN running stats — the reference's unsynced-BN torch-DDP semantics)
    into one copy for evaluation: ``"mean"`` averages the workers' stats
    (each saw a disjoint data shard, so the mean is the best single
    estimate); ``"first"`` takes worker 0's (what a torch rank-0 eval sees).
    Shared by the DDP and FSDP steps' ``eval_model_state``.

    Fetches to host before reducing (returns numpy leaves). An eager
    reduction over device-sharded leaves compiles a FRESH auto-partitioned
    multi-device program, and on hosts with fewer cores than devices its
    collective rendezvous can genuinely deadlock and abort the process
    (reproduced thrice at ``test_exact_cifar10_fsdp_strategy`` under CPU
    contention, surviving even a 600 s terminate deadline). BN stats are a
    few KB and eval prep is not a hot path, so the host round trip is the
    robust choice on every backend.

    Size assumption: every caller's per-worker model_state today is BN
    running stats (KBs). A future LARGE per-worker state (e.g. EMA params)
    would pay a full device->host transfer per eval through this path —
    at that point add a device-side reduction escape hatch rather than
    growing this function; the host round trip is deliberate for the
    deadlock reason above, not a perf choice."""
    model_state = jax.device_get(model_state)
    if reduce == "first":
        return jax.tree_util.tree_map(lambda x: x[0], model_state)
    assert reduce == "mean", reduce
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x).mean(axis=0), model_state
    )


def stateless_loss(fn: Callable[[PyTree, Any], jax.Array]) -> LossFn:
    """Adapt a ``(params, batch) -> loss`` function to the trainer signature."""

    def wrapped(params, model_state, batch):
        return fn(params, batch), model_state

    return wrapped


def make_step_fn(
    loss_fn: LossFn,
    reducer,
    learning_rate: float,
    momentum: float = 0.9,
    algorithm: str = "ef_momentum",
    axis_name: Optional[str] = DATA_AXIS,
    optimizer=None,
    accum_steps: int = 1,
    max_grad_norm: Optional[float] = None,
) -> Callable[[TrainState, Any], Tuple[TrainState, jax.Array]]:
    """Build the per-device step body: ``(state, local_batch) -> (state, loss)``.

    ``algorithm``:
      - ``"ef_momentum"`` — PowerSGD Algorithm 2 (the reference's hand-rolled
        update, ``ddp_init.py:156-178``); pair with any reducer.
      - ``"sgd"``         — torch-style SGD+momentum (``optim.SGD`` semantics
        used by the exact-DDP trainer, ``ddp_guide_cifar10/ddp_init.py:110``).
      - ``"sgd_nesterov"``— torch SGD with nesterov momentum (the reference's
        single-node IMDb baseline, ``IMDb_distillBERT_example.py:57``).
      - ``"sgd_plain"``   — SGD without momentum.
      - ``"optax"``       — any optax GradientTransformation applied to the
        reduced gradient (used for the reference's AdamW IMDb baseline,
        ``IMDb_dataset_distributer.py:55-66``); pass ``optimizer=``.

    The returned callable is pure; use it directly on one device
    (``axis_name=None``) or inside ``shard_map`` (see ``make_train_step``).

    ``accum_steps > 1`` enables gradient accumulation: batch leaves carry a
    leading ``accum_steps`` axis and the step scans the microbatches with a
    summed-gradient carry — device memory holds ONE microbatch's activations
    at a time (effective batch beyond HBM), while the reducer still runs
    once per step, so the wire cost is unchanged. The accumulated gradient
    is the mean over microbatches, identical (for mean losses over
    equal-size microbatches) to one big-batch gradient — pinned by test.
    """
    assert algorithm in ("ef_momentum", "sgd", "sgd_nesterov", "sgd_plain", "optax")
    assert (algorithm == "optax") == (optimizer is not None)
    assert accum_steps >= 1

    def clip_by_global_norm(delta: PyTree) -> PyTree:
        # torch.nn.utils.clip_grad_norm_ semantics, applied to the REDUCED
        # update on every worker (identical values, so no extra collective);
        # a beyond-reference extension — the reference never clips
        if max_grad_norm is None:
            return delta
        leaves = jax.tree_util.tree_leaves(delta)
        norm = jnp.sqrt(
            sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
        )
        scale = jnp.minimum(1.0, max_grad_norm / (norm + 1e-6))
        return jax.tree_util.tree_map(
            lambda l: (l * scale).astype(l.dtype), delta
        )

    def grads_of(diff_params, model_state, batch):
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(
                diff_params, model_state, batch
            )

        def microbatch(carry, mb):
            mstate, gsum, lsum = carry
            (loss, mstate), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                diff_params, mstate, mb
            )
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            return (mstate, gsum, lsum + loss), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, diff_params)
        lsum0 = jnp.zeros((), jnp.float32)
        if axis_name is not None:
            # fresh constants are device-invariant; the scan carry must match
            # the (varying) per-microbatch loss/grads under shard_map's
            # varying-manual-axes tracking
            lsum0 = jax.lax.pcast(lsum0, axis_name, to="varying")
        (model_state, gsum, lsum), _ = jax.lax.scan(
            microbatch, (model_state, zeros, lsum0), batch
        )
        mean = lambda t: jax.tree_util.tree_map(lambda x: x / accum_steps, t)
        return (lsum / accum_steps, model_state), mean(gsum)

    def step(state: TrainState, batch) -> Tuple[TrainState, jax.Array]:
        # (Algo 2 line 6) local stochastic gradient. Params enter the shard_map
        # region replicated; they must be cast to device-varying BEFORE
        # differentiation, otherwise jax's replication-tracking transpose
        # inserts an automatic psum and the reducer would see pre-synchronized
        # gradients — defeating the hand-rolled (compress-then-communicate)
        # sync that is the whole point of the reference design.
        diff_params = state.params
        if axis_name is not None:
            diff_params = jax.tree_util.tree_map(
                lambda p: jax.lax.pcast(p, axis_name, to="varying"), state.params
            )
        # named_scope: label the HLO so device traces (and span-mirrored
        # host annotations) attribute op time to grads / reduce / update
        with jax.named_scope("step.grads"):
            (loss, model_state), grads = grads_of(
                diff_params, state.model_state, batch
            )
        # non-gradient state (BN running stats) stays PER-WORKER, exactly
        # like torch DDP (the reference never syncs running stats); it is
        # collapsed only at eval time via CompiledStep.eval_model_state.
        # Keeping it local removes a per-step collective whose bytes the
        # analytic wire model would otherwise have to carry (round-1 verdict:
        # ~230KB/step of unaccounted BN traffic on ResNet-152).

        if algorithm == "ef_momentum":
            # (Algo 2 line 7) send = g + e  (ddp_init.py:156-157), via the
            # reducer's error-feedback entry point when it has one, so the
            # add is traced under the reducer's own scope. Reducers without
            # reduce_ef (the gather-family compressors) keep the explicit add.
            # (Algo 2 lines 8-11) compress → allreduce → decompress; e updated
            if hasattr(reducer, "reduce_ef"):
                reducer_state, delta, memories, _ = reducer.reduce_ef(
                    state.reducer_state, grads, state.memories, axis_name
                )
            else:
                send = jax.tree_util.tree_map(jnp.add, grads, state.memories)
                reducer_state, delta, memories, _ = reducer.reduce(
                    state.reducer_state, send, axis_name
                )
        else:
            # exact-DDP and optax paths: allreduce-mean the raw gradients
            reducer_state, delta, memories, _ = reducer.reduce(
                state.reducer_state, grads, axis_name
            )

        # the optimizer, every branch of it under one scope: the clip and the
        # parameter/momentum update (the third of the per-layer split of a
        # training step: forward/backward, exchange, optimizer)
        with jax.named_scope("step.update"):
            delta = clip_by_global_norm(delta)
            if algorithm == "ef_momentum":
                # (Algo 2 lines 12-13)
                params, momenta = ef_momentum_update(
                    state.params, state.momenta, delta, learning_rate, momentum
                )
            elif algorithm == "optax":
                import optax

                updates, momenta = optimizer.update(
                    delta, state.momenta, state.params
                )
                params = optax.apply_updates(state.params, updates)
            elif algorithm == "sgd":
                params, momenta = sgd_momentum_update(
                    state.params, state.momenta, delta, learning_rate, momentum
                )
            else:
                if algorithm == "sgd_nesterov":
                    # torch SGD nesterov: v ← μ·v + g; p ← p − lr·(g + μ·v)
                    momenta = jax.tree_util.tree_map(
                        lambda m, d: momentum * m + d, state.momenta, delta
                    )
                    update = jax.tree_util.tree_map(
                        lambda d, m: d + momentum * m, delta, momenta
                    )
                else:
                    momenta = state.momenta
                    update = delta
                params = jax.tree_util.tree_map(
                    lambda p, u: p - learning_rate * u, state.params, update
                )

        # report the globally-averaged loss (the reference prints per-rank
        # epoch means, ddp_init.py:183; global mean is strictly more useful)
        with jax.named_scope("step.loss_sync"):
            loss = all_reduce_mean(loss, axis_name)
        return TrainState(params, momenta, memories, reducer_state, model_state), loss

    return step


class CompiledStep(NamedTuple):
    """A jitted distributed step plus its static per-step wire cost.

    ``ledger`` is the itemization of ``bits_per_step``: one
    ``observe.ledger.LedgerEntry`` per collective the step issues, built at
    construction time with the guarantee that ``ledger.total_bits() ==
    bits_per_step`` (asserted in ``observe.ledger.step_ledger``).

    ``health_fn`` is the OFF-hot-path training-health probe
    (:func:`make_health_fn`): ``health_fn(state, batch) -> {grad_norm,
    ef_memory_norm, powersgd_rel_error, loss}``, a separately jitted
    dispatch the loop calls every ``health_every`` steps — never traced
    into ``fn``, never touching its donation or its ledger. None when the
    builder could not construct one (hand-rolled steps)."""

    fn: Callable[[TrainState, Any], Tuple[TrainState, jax.Array]]
    bits_per_step: int
    mesh: Optional[Mesh]
    reducer: Any
    optimizer: Any = None
    ledger: Any = None
    health_fn: Optional[Callable[[TrainState, Any], Any]] = None
    # the comm knobs this step compiled with (reducer_comm_config) —
    # stamped into the audit's CompileEvent so the offline cost model
    # (observe.costmodel) can identify WHICH config a run executed
    comm_config: Optional[Dict] = None
    # the carry's NamedShardings over ``mesh`` (one per TrainState field) —
    # ``fn``'s out_shardings, so also how ``init_state`` places it
    state_shardings: Any = None

    def __call__(self, state, batch):
        return self.fn(state, batch)

    @property
    def num_devices(self) -> Optional[int]:
        return self.mesh.size if self.mesh is not None else None

    def init_state(self, params: PyTree, model_state: PyTree = None) -> TrainState:
        """Build a correctly-shaped TrainState for this step (adds the
        per-worker leading axis on error memories and model_state in the
        distributed case), placed over the mesh with the step's own
        shardings: the first call then starts where every later call does —
        no host-to-device copy of the carry inside the first step, one
        dispatch signature from the first call on.

        Over a mesh that spans other processes the state stays on the host:
        placing it there is ``data.multihost.global_state_from_host``'s job
        (each process materialises only its own shards, no cross-process
        traffic), and that seam takes host values."""
        state = init_train_state(
            params, self.reducer, model_state, self.num_devices, self.optimizer
        )
        if self.state_shardings is None or self.mesh.is_multi_process:
            return state
        # one sharding per TrainState field: a pytree prefix of the state
        return jax.device_put(state, self.state_shardings)

    def eval_model_state(self, state: TrainState, reduce: str = "mean") -> PyTree:
        """Eval-ready model_state: the single-process step carries it plain;
        the distributed step collapses the per-worker copies
        (:func:`collapse_per_worker`)."""
        if self.mesh is None:
            return state.model_state
        return collapse_per_worker(state.model_state, reduce)


def _jit_step(
    sharded, mesh: Mesh, state_specs: TrainState, batch_spec: PartitionSpec,
    donate_state: bool,
):
    """jit a shard_mapped ``(state, batch) -> (state, out)`` step with its
    shardings pinned to the shard_map's own specs over ``mesh``; returns
    ``(fn, state_shardings)``. Left to infer them, jit keys the program on
    however each caller happened to place the carry — a host-built or
    restored state compiled a second program on the second call — and hands
    a ``P(axis)`` output back as ``P()`` when the axis has one device, so on
    one chip the carry never came back as it went in. Pinned, there is one
    program, what goes in is what comes out, and an abstract ``lower`` of
    unplaced shapes is the program that runs."""
    state_shardings = TrainState(*(NamedSharding(mesh, s) for s in state_specs))
    fn = jax.jit(
        sharded,
        donate_argnums=(0,) if donate_state else (),
        in_shardings=(state_shardings, NamedSharding(mesh, batch_spec)),
        out_shardings=(state_shardings, NamedSharding(mesh, PartitionSpec())),
    )
    return fn, state_shardings


def make_scanned_train_fn(
    loss_fn: LossFn,
    reducer,
    params_template: PyTree,
    learning_rate: float,
    momentum: float = 0.9,
    algorithm: str = "ef_momentum",
    mesh: Optional[Mesh] = None,
    axis_name: str = DATA_AXIS,
    donate_state: bool = True,
    optimizer=None,
    accum_steps: int = 1,
    max_grad_norm: Optional[float] = None,
) -> "CompiledStep":
    """Multi-step variant: ``fn(state, stacked_batches) -> (state, losses)``
    where each batch leaf has a leading ``num_steps`` axis and the step loop
    is a ``lax.scan`` INSIDE the compiled program.

    TPU-first rationale: the per-step host round-trip (dispatch + metric
    fetch) that the reference's Python loop pays on every batch disappears —
    one dispatch runs a whole epoch (or chunk) on device, with the same
    collectives. ``bits_per_step`` still refers to ONE step; multiply by the
    chunk length when accounting. With ``accum_steps > 1`` batch leaves are
    ``(num_steps, accum_steps, batch, ...)``.
    """
    body = make_step_fn(
        loss_fn, reducer, learning_rate, momentum, algorithm,
        axis_name=axis_name if mesh is not None else None, optimizer=optimizer,
        accum_steps=accum_steps, max_grad_norm=max_grad_norm,
    )

    def scan_steps(state: TrainState, batches):
        def f(st, batch):
            st, loss = body(st, batch)
            return st, loss

        return jax.lax.scan(f, state, batches)

    if mesh is None:
        fn = jax.jit(scan_steps, donate_argnums=(0,) if donate_state else ())
        bits = _reducer_bits(reducer, params_template)
        return CompiledStep(
            fn, bits, None, reducer, optimizer,
            _step_ledger(reducer, params_template, None, axis_name, bits),
        )

    def sharded_body(state: TrainState, batches):
        local = state._replace(
            memories=strip_leading(state.memories),
            model_state=strip_leading(state.model_state),
        )
        new_state, losses = scan_steps(local, batches)
        return (
            new_state._replace(
                memories=pad_leading(new_state.memories),
                model_state=pad_leading(new_state.model_state),
            ),
            losses,
        )

    state_specs = TrainState(
        params=PartitionSpec(),
        momenta=PartitionSpec(),
        memories=PartitionSpec(axis_name),
        reducer_state=PartitionSpec(),
        model_state=PartitionSpec(axis_name),
    )
    batch_spec = (
        PartitionSpec(None, axis_name)
        if accum_steps == 1
        else PartitionSpec(None, None, axis_name)
    )
    sharded = jax.shard_map(
        sharded_body,
        mesh=mesh,
        # batches: (num_steps[, accum], global_batch, ...) — sharded on the
        # batch dim
        in_specs=(state_specs, batch_spec),
        out_specs=(state_specs, PartitionSpec()),
    )
    fn, state_shardings = _jit_step(
        sharded, mesh, state_specs, batch_spec, donate_state
    )
    bits = _reducer_bits(reducer, params_template, mesh.size) + LOSS_SYNC_BITS
    return CompiledStep(
        fn,
        bits,
        mesh,
        reducer,
        optimizer,
        _step_ledger(reducer, params_template, mesh, axis_name, bits),
        health_fn=make_health_fn(
            loss_fn, reducer, mesh, axis_name, accum_steps
        ),
        comm_config=reducer_comm_config(reducer),
        state_shardings=state_shardings,
    )


def _tree_sq_norm(tree: PyTree) -> jax.Array:
    """Sum of squared elements over a pytree, accumulated in f32."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)


def make_health_fn(
    loss_fn: LossFn,
    reducer,
    mesh: Optional[Mesh] = None,
    axis_name: str = DATA_AXIS,
    accum_steps: int = 1,
) -> Callable[[TrainState, Any], Any]:
    """The training-health probe behind ``TrainHealthEvent``: a separately
    jitted ``(state, batch) -> {grad_norm, ef_memory_norm,
    powersgd_rel_error, loss}`` dispatch, called every ``health_every``
    steps by the training loops — OFF the hot path. Reducers exposing
    ``fidelity_stats`` add a nested ``"fidelity"`` sub-dict — per
    shape-group/bucket ``{rel_error, cosine_sim, ef_norm,
    quantized_share}`` scalars with static group keys that join the wire
    ledger's tags (``FidelityEvent``, :mod:`..observe.fidelity`); the flat
    legacy keys are unchanged.

    Sampling cost (documented in DESIGN.md): one extra forward+backward on
    the probe batch (the gradient is recomputed — the compiled step's
    gradients never leave the device, and widening its signature would
    break donation and every wrapper contract), plus one COLLECTIVE-FREE
    diagnostic compression round (``reducer.compression_error`` with
    ``axis_name=None``) for the relative error ``‖M − P̂Qᵀ‖/‖M‖``, plus
    four scalar all-reduces to average the stats across workers. With
    ``accum_steps > 1`` the probe samples microbatch 0 only — a health
    sample, not a training step. State is read, never mutated."""
    ax = axis_name if mesh is not None else None

    def health_body(state: TrainState, batch):
        if accum_steps > 1:
            batch = jax.tree_util.tree_map(lambda l: l[0], batch)
        diff_params = state.params
        if ax is not None:
            # same pcast-before-grad rule as the step: the probe must see
            # this worker's LOCAL gradient, not an auto-psum'd one
            diff_params = jax.tree_util.tree_map(
                lambda p: jax.lax.pcast(p, ax, to="varying"), state.params
            )
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            diff_params, state.model_state, batch
        )
        send = jax.tree_util.tree_map(jnp.add, grads, state.memories)
        gn2 = _tree_sq_norm(grads)
        en2 = _tree_sq_norm(state.memories)
        if hasattr(reducer, "compression_error"):
            rel = reducer.compression_error(state.reducer_state, send, None)
        else:
            rel = jnp.zeros((), jnp.float32)
        out = {
            "grad_norm": jnp.sqrt(all_reduce_mean(gn2, ax)),
            "ef_memory_norm": jnp.sqrt(all_reduce_mean(en2, ax)),
            "powersgd_rel_error": all_reduce_mean(rel, ax),
            "loss": all_reduce_mean(loss, ax),
        }
        # per-group fidelity diagnostics (observe.fidelity): same
        # collective-free diagnostic round, broken out per shape-group /
        # bucket with static keys, each scalar averaged across workers —
        # nested so the flat keys above keep their exact legacy meaning
        if hasattr(reducer, "fidelity_stats"):
            fid = reducer.fidelity_stats(
                state.reducer_state, send, state.memories, None
            )
            out["fidelity"] = {
                group: {k: all_reduce_mean(v, ax) for k, v in vals.items()}
                for group, vals in fid.items()
            }
        return out

    if mesh is None:
        # lint: no-donate — diagnostic probe reads the LIVE training state
        # the loop keeps stepping; donating it would free buffers in use
        return jax.jit(health_body)

    def sharded_health(state: TrainState, batch):
        local = state._replace(
            memories=strip_leading(state.memories),
            model_state=strip_leading(state.model_state),
        )
        return health_body(local, batch)

    state_specs = TrainState(
        params=PartitionSpec(),
        momenta=PartitionSpec(),
        memories=PartitionSpec(axis_name),
        reducer_state=PartitionSpec(),
        model_state=PartitionSpec(axis_name),
    )
    batch_spec = (
        PartitionSpec(axis_name)
        if accum_steps == 1
        else PartitionSpec(None, axis_name)
    )
    # lint: no-donate — same: the probe must not consume the state/batch
    # buffers the hot step is about to reuse
    return jax.jit(
        jax.shard_map(
            sharded_health,
            mesh=mesh,
            in_specs=(state_specs, batch_spec),
            out_specs=PartitionSpec(),
        )
    )


def _reducer_bits(reducer, params_template: PyTree, n_workers: int = 1) -> int:
    """Static bits-on-wire for one reduction of ``params_template``.
    ``n_workers`` matters for gather-family reducers (their gathered-result
    payload scales with W, ``parallel.compression``); allreduce payloads
    ignore it."""
    if hasattr(reducer, "bits_per_step"):
        return reducer.bits_per_step(params_template, n_workers=n_workers)
    leaves = jax.tree_util.tree_leaves(params_template)
    return sum(8 * int(l.size) * l.dtype.itemsize for l in leaves)


def _step_ledger(
    reducer,
    params_template: PyTree,
    mesh: Optional[Mesh],
    axis_name: str,
    bits_per_step: int,
):
    """Itemized wire ledger for a step with the given analytic cost; the
    single-process (mesh-less) step has no loss-sync collective."""
    from ..observe.ledger import step_ledger

    return step_ledger(
        reducer,
        params_template,
        axis=axis_name if mesh is not None else "",
        n_workers=mesh.size if mesh is not None else 1,
        expected_bits=bits_per_step,
        include_loss_sync=mesh is not None,
    )


def reducer_comm_config(reducer) -> Dict:
    """The comm knobs a reducer was constructed with, read back off the
    instance: what :mod:`observe.costmodel` joins plan predictions against
    (via the ``CompileEvent.comm_config`` plumbing). Knobs a reducer does
    not carry are simply absent — the cost model canonicalizes."""
    cfg: Dict = {"reducer": type(reducer).__name__.lower()}
    for attr, key in (
        ("compression_rank", "reducer_rank"),
        ("bucket_bytes", "bucket_bytes"),
    ):
        v = getattr(reducer, attr, None)
        if v is not None:
            cfg[key] = v
    return cfg


def make_train_step(
    loss_fn: LossFn,
    reducer,
    params_template: PyTree,
    learning_rate: float,
    momentum: float = 0.9,
    algorithm: str = "ef_momentum",
    mesh: Optional[Mesh] = None,
    axis_name: str = DATA_AXIS,
    donate_state: bool = True,
    optimizer=None,
    accum_steps: int = 1,
    max_grad_norm: Optional[float] = None,
) -> CompiledStep:
    """Compile the full distributed training step.

    With a mesh: params/momenta/reducer/model state are replicated, the batch
    and the per-worker error memories are sharded on their leading axis over
    ``axis_name``, and the step body runs under ``shard_map`` with the
    reducer's collectives riding the mesh (ICI on TPU). Without a mesh: the
    single-process fallback (reference ``reducer.py:13-18``) — same code, no
    collectives.

    ``accum_steps > 1``: gradient accumulation (see :func:`make_step_fn`);
    batch leaves then carry a leading ``accum_steps`` axis ahead of the
    sharded batch axis.
    """
    if mesh is None:
        body = make_step_fn(
            loss_fn, reducer, learning_rate, momentum, algorithm,
            axis_name=None, optimizer=optimizer, accum_steps=accum_steps,
            max_grad_norm=max_grad_norm,
        )
        fn = jax.jit(body, donate_argnums=(0,) if donate_state else ())
        bits = _reducer_bits(reducer, params_template)
        return CompiledStep(
            fn, bits, None, reducer, optimizer,
            _step_ledger(reducer, params_template, None, axis_name, bits),
            health_fn=make_health_fn(
                loss_fn, reducer, None, axis_name, accum_steps
            ),
            comm_config=reducer_comm_config(reducer),
        )

    body = make_step_fn(
        loss_fn, reducer, learning_rate, momentum, algorithm,
        axis_name=axis_name, optimizer=optimizer, accum_steps=accum_steps,
        max_grad_norm=max_grad_norm,
    )

    def sharded_body(state: TrainState, batch):
        local = state._replace(
            memories=strip_leading(state.memories),
            model_state=strip_leading(state.model_state),
        )
        new_state, loss = body(local, batch)
        return (
            new_state._replace(
                memories=pad_leading(new_state.memories),
                model_state=pad_leading(new_state.model_state),
            ),
            loss,
        )

    state_specs = TrainState(
        params=PartitionSpec(),
        momenta=PartitionSpec(),
        memories=PartitionSpec(axis_name),
        reducer_state=PartitionSpec(),
        model_state=PartitionSpec(axis_name),
    )
    batch_spec = (
        PartitionSpec(axis_name)
        if accum_steps == 1
        else PartitionSpec(None, axis_name)  # (accum, global_batch, ...)
    )
    sharded = jax.shard_map(
        sharded_body,
        mesh=mesh,
        in_specs=(state_specs, batch_spec),
        out_specs=(state_specs, PartitionSpec()),
    )
    fn, state_shardings = _jit_step(
        sharded, mesh, state_specs, batch_spec, donate_state
    )
    bits = _reducer_bits(reducer, params_template, mesh.size) + LOSS_SYNC_BITS
    return CompiledStep(
        fn,
        bits,
        mesh,
        reducer,
        optimizer,
        _step_ledger(reducer, params_template, mesh, axis_name, bits),
        health_fn=make_health_fn(
            loss_fn, reducer, mesh, axis_name, accum_steps
        ),
        comm_config=reducer_comm_config(reducer),
        state_shardings=state_shardings,
    )
