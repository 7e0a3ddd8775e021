"""Shared experiment machinery: the epoch/step loop with metrics.

The reference duplicates its ``setup()/run_task()/cleanup()`` lifecycle and
training loop in four directories (SURVEY §2.4); here it exists once. The
loop is host-side Python feeding a single compiled step — all math, including
the collectives, lives in the jitted ``shard_map`` step (trainer.py).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import flax.linen as nn
import jax

from ..parallel.trainer import STEP_COUNTERS, CompiledStep, TrainState
from ..utils.metrics import MetricsLogger


def accumulated_batches(
    arrays,
    config,
    max_steps_per_epoch: Optional[int] = None,
    keys: Optional[Tuple[str, ...]] = None,
) -> Callable[[int], Iterator[Any]]:
    """Per-epoch batch generator honoring ``config.accum_steps``: yields
    ``(global_batch, ...)`` leaves, or ``(accum, global_batch/accum, ...)``
    when accumulating (the trainer's batch contract, ``make_step_fn``).
    ``keys`` turns each batch into a dict (the HF-style IMDb batches)."""
    import jax.numpy as jnp

    from ..data import iterate_batches
    from ..observe.spans import span

    k = config.accum_steps
    if k < 1:
        raise ValueError(f"accum_steps must be >= 1, got {k}")
    if config.global_batch_size % k != 0:
        raise ValueError(
            f"global_batch_size {config.global_batch_size} is not divisible"
            f" by accum_steps {k}"
        )

    # the plain (image, label) epochs — every CIFAR experiment — run through
    # the C++ prefetch runtime: identical batch semantics to iterate_batches
    # (asserted in tests/test_native_loader.py) with assembly on a worker
    # thread one batch ahead of the training loop; dict/accumulated batches
    # keep the numpy path. Eligibility (dtypes, pair shape) lives with the
    # loader itself.
    native_loader = None
    if k == 1 and keys is None:
        from ..data import NativeBatchLoader

        native_loader = NativeBatchLoader.maybe_create(
            arrays, config.global_batch_size, seed=config.seed
        )

    def gen(epoch: int):
        it = (
            native_loader.epoch(epoch)
            if native_loader is not None
            else iterate_batches(
                arrays, config.global_batch_size, seed=config.seed, epoch=epoch
            )
        )
        for i, batch in enumerate(it):
            if max_steps_per_epoch is not None and i >= max_steps_per_epoch:
                return
            if k > 1:
                batch = tuple(
                    a.reshape((k, a.shape[0] // k) + a.shape[1:]) for a in batch
                )
            # the copy to device 0, on the consumer's thread: like the
            # loader's assemble it runs inside the loop's next(), so it
            # nests under data_load
            with span("data_load/to_device"):
                batch = tuple(jnp.asarray(a) for a in batch)
            yield dict(zip(keys, batch)) if keys else batch

    return gen


def exact_reducer_kwargs(config) -> Dict[str, Any]:
    """``ExactReducer`` constructor kwargs from config: the DDP-style
    backward-order bucket target (``config.bucket_bytes`` →
    ``bucket_bytes``)."""
    if getattr(config, "bucket_bytes", None) is None:
        return {}
    return {"bucket_bytes": config.bucket_bytes}


def powersgd_reducer_kwargs(config) -> Dict[str, Any]:
    """``PowerSGDReducer`` constructor kwargs from config: the Gram-Schmidt
    implementation override (``orthogonalize_impl`` — "auto" resolves to the
    Pallas kernel on TPU)."""
    return {"orthogonalize_impl": getattr(config, "orthogonalize_impl", "auto")}


def accum_batch_sharding(mesh, accum_steps: int):
    """Prefetch sharding for accumulated batches: the sharded batch dim sits
    BEHIND the accum axis. None for the unaccumulated default (train_loop
    derives it)."""
    if accum_steps <= 1:
        return None
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import DATA_AXIS

    return NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))


def train_loop(
    step: CompiledStep,
    state: TrainState,
    batches_for_epoch: Callable[[int], Iterator[Any]],
    epochs: int,
    rank: int = 0,
    log_every: int = 0,
    start_epoch: int = 0,
    watchdog: Any = None,
    heartbeat: Any = None,
    on_epoch_end: Optional[Callable[[int, TrainState], None]] = None,
    on_step_end: Optional[Callable[[int, int, TrainState], bool]] = None,
    prefetch: int = 2,
    batch_sharding: Any = None,
    telemetry: Any = None,
    trace_dir: Optional[str] = None,
    audit: bool = False,
    run_name: str = "train",
    health_every: int = 0,
) -> Tuple[TrainState, MetricsLogger]:
    """Run ``epochs`` passes, logging loss / step-time / cumulative bits
    (the reference's per-epoch banner + the bits it never reported).

    ``prefetch``: stage that many upcoming batches on device asynchronously
    (``data.device_prefetch``, placed with the step's batch sharding) so the
    host→device copy of batch N+1 overlaps the compute of batch N; 0
    disables.

    Observability (all default-off): events flow through ``telemetry`` (an
    ``observe.Telemetry``; None = the stdout-banner default); ``trace_dir``
    wraps the whole loop in a ``jax.profiler`` trace with a
    ``StepTraceAnnotation`` around every step (so Perfetto/XProf group ops
    per step); ``audit=True`` reconciles the step's wire ledger against the
    compiled HLO BEFORE the first execution (buffer donation invalidates
    the example args afterwards) and emits the per-collective ledger + the
    ``CompileEvent`` verdict.

    Optional hooks (all default-off; :func:`resilient_train_loop` wires
    them): a ``utils.failure.StepWatchdog`` around every step, a
    ``utils.failure.HeartbeatMonitor`` beat per step (rate-limited by the
    monitor itself), an ``on_epoch_end(epoch, state)`` callback (e.g.
    checkpointing), and an ``on_step_end(epoch, steps_done, state) ->
    stop?`` callback after every completed step — returning True ends the
    loop early with the current state (the preemption-grace shutdown path).

    ``health_every > 0`` (with a step carrying a ``health_fn`` and a
    telemetry): every N completed steps the loop dispatches the separately
    jitted health probe on the step's OWN batch and emits a
    ``TrainHealthEvent`` (grad norm, EF memory norm, PowerSGD relative
    compression error) — the live plane's NaN-precursor feed. Off the hot
    path by construction: a distinct dispatch that reads state, never
    mutates it; cost documented in DESIGN.md "health sampling".

    The same cadence drives an ``observe.memory.MemorySampler``: one
    ``device.memory_stats()`` read per health interval, emitted as a
    ``MemoryEvent`` (the live side of the memory observatory; needs no
    ``health_fn``). On CPU the sampler disables itself after the first
    empty read — zero events, zero log lines. If the step is a
    ``GuardedStep`` without a sampler of its own, the loop attaches this
    one so the OOM forensics report carries the last live sample.
    """
    import contextlib

    from ..data import device_prefetch
    from ..observe import FailureEvent, TrainHealthEvent
    from ..observe.fidelity import FidelityTracker
    from ..observe.spans import recording, span
    from ..parallel.mesh import DATA_AXIS, data_sharding
    from ..utils.profiling import step_annotation, trace

    # prefetch needs the step's batch sharding; on a mesh without the
    # standard 'data' axis (e.g. the hierarchical ('dcn','ici') layout) the
    # right spec isn't derivable here, so prefetch is skipped rather than
    # mis-placed (a default-device put would force a reshard copy anyway)
    mesh = getattr(step, "mesh", None)
    sharding = None
    if prefetch and mesh is not None:
        if batch_sharding is not None:
            sharding = batch_sharding
        elif DATA_AXIS in mesh.axis_names:
            sharding = data_sharding(mesh)
        else:
            prefetch = 0

    logger = MetricsLogger(
        bits_per_step=step.bits_per_step, log_every=log_every, telemetry=telemetry
    )
    has_counters = STEP_COUNTERS in (getattr(state, "model_state", None) or {})
    memory_sampler = None
    fidelity_tracker = None
    if health_every > 0 and telemetry is not None:
        from ..observe.memory import MemorySampler

        memory_sampler = MemorySampler(telemetry, label=run_name, rank=rank)
        if getattr(step, "memory_sampler", False) is None:
            # a GuardedStep (or compatible wrapper) constructed without a
            # sampler: share this one so OOM forensics see the live feed
            step.memory_sampler = memory_sampler
    audit_pending = audit
    trace_ctx = trace(trace_dir) if trace_dir else contextlib.nullcontext()
    # recording(telemetry) installs the ambient span recorder for the loop's
    # dynamic extent: the loader, checkpointing, and the audit path emit
    # spans with no telemetry plumbing of their own
    with trace_ctx, recording(telemetry):
        for epoch in range(start_epoch, epochs):
            batches = iter(batches_for_epoch(epoch))
            if prefetch:
                batches = device_prefetch(batches, sharding, depth=prefetch)
            steps_done = 0
            while True:
                # span the fetch itself: with prefetch on, a long data_load
                # span IS the "input pipeline can't keep up" verdict
                with span("data_load", step=logger._step):
                    batch = next(batches, None)
                if batch is None:
                    break
                if logger.batch_devices is None:
                    logger.batch_devices = _shard_devices(batch)
                if audit_pending:
                    # must precede the first execution: donate_argnums
                    # invalidates the state buffers the lowering would need
                    audit_pending = False
                    try:
                        from ..observe.ledger import audit_compiled_step

                        audit_compiled_step(
                            step, state, batch, label=run_name, telemetry=telemetry
                        )
                    except Exception as e:  # audit is advisory, never fatal
                        if telemetry is not None:
                            telemetry.emit(
                                FailureEvent(
                                    kind="audit_error",
                                    label=run_name,
                                    message=f"{type(e).__name__}: {e}",
                                )
                            )
                logger.start_step()
                ctx = (
                    watchdog.watch(f"epoch {epoch}")
                    if watchdog is not None
                    else contextlib.nullcontext()
                )
                with ctx, step_annotation(run_name, logger._step), span(
                    "step", step=logger._step
                ):
                    with span("step/compute", step=logger._step):
                        state, loss = step(state, batch)
                    # the device_get blocks until the step (and its
                    # collectives) retires: host-visible step tail
                    with span("step/loss_sync", step=logger._step) as sync:
                        if has_counters:
                            # the step's own counters ride the same fetch
                            loss, counted = jax.device_get(
                                (loss, state.model_state[STEP_COUNTERS])
                            )
                            sync.counters = jax.tree_util.tree_map(
                                lambda a: a.tolist(), counted
                            )
                        else:
                            loss = jax.device_get(loss)
                logger.end_step(epoch, loss)
                steps_done += 1
                if (
                    memory_sampler is not None
                    and memory_sampler.enabled
                    and logger._step % health_every == 0
                ):
                    # allocator read + one event emit; a backend without
                    # memory_stats turns this into a permanent no-op
                    with span("memory_probe", step=logger._step):
                        memory_sampler.sample(logger._step)
                health_fn = getattr(step, "health_fn", None)
                if (
                    health_every > 0
                    and health_fn is not None
                    and telemetry is not None
                    and logger._step % health_every == 0
                ):
                    # separately dispatched probe on the step's own batch —
                    # the batch is NOT donated, so its buffers are live; the
                    # probe reads the (new) state without mutating it
                    with span("health_probe", step=logger._step):
                        try:
                            stats = jax.device_get(health_fn(state, batch))
                            telemetry.emit(
                                TrainHealthEvent(
                                    step=logger._step,
                                    epoch=epoch,
                                    grad_norm=float(stats["grad_norm"]),
                                    ef_memory_norm=float(
                                        stats["ef_memory_norm"]
                                    ),
                                    powersgd_rel_error=float(
                                        stats["powersgd_rel_error"]
                                    ),
                                    loss=float(stats["loss"]),
                                    rank=rank,
                                    label=run_name,
                                )
                            )
                            # per-group fidelity plane: same probe sample,
                            # broken out per shape-group/bucket with the
                            # wire-ledger join tags (observe.fidelity)
                            fid = stats.get("fidelity")
                            if fid:
                                if fidelity_tracker is None:
                                    tags = {}
                                    r = getattr(step, "reducer", None)
                                    if hasattr(r, "fidelity_group_tags"):
                                        tags = r.fidelity_group_tags(
                                            state.params
                                        )
                                    fidelity_tracker = FidelityTracker(
                                        tags, rank=rank, label=run_name
                                    )
                                for ev in fidelity_tracker.events(
                                    logger._step, fid, epoch=epoch
                                ):
                                    telemetry.emit(ev)
                        except Exception as e:  # advisory, never fatal
                            telemetry.emit(
                                FailureEvent(
                                    kind="health_probe_error",
                                    label=run_name,
                                    message=f"{type(e).__name__}: {e}",
                                )
                            )
                if heartbeat is not None:
                    heartbeat.beat(epoch=epoch)
                if on_step_end is not None and on_step_end(
                    epoch, steps_done, state
                ):
                    return state, logger
            logger.end_epoch(epoch, rank=rank)
            if on_epoch_end is not None:
                with span("epoch_hook", step=epoch):
                    on_epoch_end(epoch, state)
    return state, logger


def audited_carry_loop(
    jitted,
    carry,
    batches_for_epoch: Callable[[int], Iterator[Any]],
    epochs: int,
    example_batch,
    rank: int = 0,
    log_every: int = 0,
    checkpoint_dir: Optional[str] = None,
    telemetry: Any = None,
    run_name: str = "carry_loop",
    ledger_layer: str = "pipeline",
) -> Tuple[Any, MetricsLogger, Dict]:
    """Shared driver for hand-rolled ``(carry, *batch) -> (carry, loss)``
    steps (the pipeline/sequence-parallel experiments, whose wire traffic is
    activation collectives rather than reducer payloads): AOT-compile ONCE,
    audit that same executable's HLO for honest bits-per-step, then run the
    epoch loop on it. The audit doubles as the wire ledger here — one
    ``CollectiveEvent`` per collective kind (attributed to ``ledger_layer``)
    plus the ``CompileEvent`` verdict flow through ``telemetry``. With
    ``checkpoint_dir``, the carry is saved at every epoch boundary and the
    newest checkpoint is resumed on entry (deterministic per-epoch batch
    streams ⇒ a crash-restart converges to the same state as an
    uninterrupted run, like ``resilient_train_loop``).
    Returns ``(carry, logger, audit_summary)``."""
    import jax as _jax

    from ..observe import CompileEvent
    from ..observe.ledger import device_cost_fields, ledger_from_hlo_summary
    from ..observe.spans import recording, span
    from ..utils.hlo_audit import collective_summary, hlo_text_of_compiled
    from ..utils.overlap import overlap_report

    start_epoch = 0
    if checkpoint_dir is not None:
        from ..utils.checkpoint import restore_latest

        resumed = restore_latest(
            checkpoint_dir, _jax.device_get(carry),
            telemetry=telemetry, label=run_name,
        )
        if resumed is not None:
            carry, resumed_epoch = resumed
            start_epoch = resumed_epoch + 1

    with span("audit/compile", telemetry=telemetry):
        compiled = jitted.lower(carry, *example_batch).compile()
        hlo_text = hlo_text_of_compiled(compiled)
    audit = collective_summary(hlo_text)
    if telemetry is not None:
        ledger = ledger_from_hlo_summary(audit, layer=ledger_layer)
        for ce in ledger.collective_events(run_name):
            telemetry.emit(ce)
        rec = ledger.reconcile(hlo_text)  # exact by construction
        ov = overlap_report(hlo_text)
        telemetry.emit(
            CompileEvent(
                label=run_name,
                analytic_bytes=rec["analytic_bytes"],
                hlo_bytes=rec["hlo_bytes"],
                delta_bytes=rec["delta_bytes"],
                exact=rec["exact"],
                hlo_collective_count=rec["hlo_collective_count"],
                hlo_by_kind=rec["hlo_by_kind"],
                overlap={
                    k: ov[k]
                    for k in (
                        "scheduled",
                        "n_async_collectives",
                        "n_overlapped",
                        "n_async_copy_windows",
                        "n_copy_windows_with_compute",
                        # the sync-interleave keys: what comm_attribution
                        # (and observe.analytics' bandwidth estimator)
                        # charges to the critical path
                        "n_sync_collectives",
                        "n_sync_gaps_with_compute",
                        "sync_interleaved",
                        "collective_emitters",
                    )
                    if k in ov
                },
                **device_cost_fields(compiled),
            )
        )
    logger = MetricsLogger(
        bits_per_step=8 * audit["total_payload_bytes"],
        log_every=log_every,
        telemetry=telemetry,
    )
    with recording(telemetry):
        for epoch in range(start_epoch, epochs):
            for batch in batches_for_epoch(epoch):
                logger.start_step()
                with span("step", step=logger._step):
                    with span("step/compute", step=logger._step):
                        carry, loss = compiled(carry, *batch)
                    with span("step/loss_sync", step=logger._step):
                        loss = float(_jax.device_get(loss))
                logger.end_step(epoch, loss)
            logger.end_epoch(epoch, rank=rank)
            if checkpoint_dir is not None:
                from ..utils.checkpoint import save_checkpoint

                save_checkpoint(checkpoint_dir, carry, step=epoch)
    return carry, logger, audit


def image_classifier_loss(model: nn.Module, has_batch_stats: bool):
    """Trainer loss_fn for NHWC image classifiers (CE loss, the reference's
    ``nn.CrossEntropyLoss()`` — ``ddp_guide_cifar10/ddp_init.py:110``)."""
    from ..utils.losses import cross_entropy_loss

    if not has_batch_stats:

        def loss_fn(params, model_state, batch):
            x, y = batch
            logits = model.apply({"params": params}, x, train=True)
            return cross_entropy_loss(logits, y), model_state

        return loss_fn

    def loss_fn(params, model_state, batch):
        x, y = batch
        logits, new_vars = model.apply(
            {"params": params, "batch_stats": model_state["batch_stats"]},
            x,
            train=True,
            mutable=["batch_stats"],
        )
        return cross_entropy_loss(logits, y), {"batch_stats": new_vars["batch_stats"]}

    return loss_fn


def evaluate_image_classifier(
    model, params, batch_stats, images, labels, batch_size: int = 256
) -> float:
    """Top-1 accuracy, eval mode (BN running stats). The reference never
    evaluates — convergence was eyeballed from loss prints (SURVEY §4); this
    provides the accuracy number its north-star targets actually need."""
    import jax.numpy as jnp

    from ..data import iterate_batches

    # lint: no-donate — eval predict has no carry; params are closed
    # over and re-used every batch
    @jax.jit
    def predict(x):
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=False
        )
        return jnp.argmax(logits, axis=-1)

    correct = total = 0
    # drop_last=False: evaluation must score EVERY example — the training
    # default (drop ragged tails for static shapes) would silently skip the
    # remainder, and with fewer examples than batch_size would score NOTHING
    # and report 0.0
    for x, y in iterate_batches(
        [images, labels], batch_size, shuffle=False, drop_last=False
    ):
        correct += int((predict(jnp.asarray(x)) == jnp.asarray(y)).sum())
        total += len(y)
    return correct / max(total, 1)


def evaluate_text_classifier(model, params, split, batch_size: int = 64) -> float:
    """Top-1 accuracy for the DistilBERT classifier on an encoded split."""
    import jax.numpy as jnp

    from ..data import iterate_batches

    # lint: no-donate — eval predict has no carry; params are closed
    # over and re-used every batch
    @jax.jit
    def predict(ids, mask):
        logits = model.apply({"params": params}, ids, mask, deterministic=True)
        return jnp.argmax(logits, axis=-1)

    arrays = [split["input_ids"], split["attention_mask"], split["labels"]]
    correct = total = 0
    # drop_last=False — score every example (see evaluate_image_classifier)
    for ids, mask, y in iterate_batches(
        arrays, batch_size, shuffle=False, drop_last=False
    ):
        correct += int((predict(jnp.asarray(ids), jnp.asarray(mask)) == jnp.asarray(y)).sum())
        total += len(y)
    return correct / max(total, 1)


def _shard_devices(tree: Any) -> list:
    """Ids of the devices holding addressable shards of ``tree``'s first
    array leaf (``[]`` for host arrays or an empty tree)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is not None:
            return sorted({shard.device.id for shard in shards})
    return []


def device_fields(reducer: Any = None, attn_impl: Optional[str] = None) -> Dict:
    """What this process actually ran on, for every run summary: the device
    as jax reports it, whether Pallas kernels compiled or were interpreted,
    the kernel choices ``"auto"`` resolved to against that backend
    (``attn_impl`` is the model's configured value — a deterministic
    forward runs what it resolves to; ``orthogonalize_impl`` is read back
    off the constructed reducer), and which host tier fed the data. With
    these on the record a run that found no chip cannot be read as one that
    used it."""
    from ..native.build import host_data_tier
    from ..observe.memory import all_device_memory_stats
    from ..ops import pallas_interpret
    from ..ops.flash_attention import resolve_attn_impl

    devices = jax.devices()
    out = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "pallas_interpret": pallas_interpret(),
        "host_data_tier": host_data_tier(),
        "device_memory": all_device_memory_stats(),
    }
    if attn_impl is not None:
        out["attn_impl"] = resolve_attn_impl(attn_impl)
    if getattr(reducer, "orthogonalize_impl", None) is not None:
        out["orthogonalize_impl"] = reducer.orthogonalize_impl
    return out


def summarize(
    name: str,
    logger: MetricsLogger,
    extra: Optional[Dict] = None,
    perplexity: bool = False,
    reducer: Any = None,
    attn_impl: Optional[str] = None,
    state: Optional[TrainState] = None,
) -> Dict:
    """Summary dict for an experiment run, always carrying
    :func:`device_fields`. ``perplexity=True`` (LM experiments) adds
    ``final_perplexity = exp(final_loss)``, None-safe for
    resumed-already-complete runs with zero recorded steps. ``state`` (the
    final TrainState) adds ``placement``: which devices hold shards of the
    params, the per-worker error memories and the batches the loop fed."""
    out = {"experiment": name, **logger.summary(), **device_fields(reducer, attn_impl)}
    if state is not None:
        out["placement"] = {
            "params": _shard_devices(state.params),
            "memories": _shard_devices(state.memories),
            "batch": logger.batch_devices,
        }
    if perplexity:
        import math

        fl = out.get("final_loss")
        out["final_perplexity"] = (
            math.exp(min(fl, 30.0)) if fl is not None else None
        )
    if extra:
        out.update(extra)
    return out


def adaptive_train_loop(
    step_factory: Callable[[Dict[str, Any]], CompiledStep],
    params: Any,
    model_state: Any,
    batches_for_epoch: Callable[[int], Iterator[Any]],
    epochs: int,
    controller: Any,
    injector: Any = None,
    telemetry: Any = None,
    rank: int = 0,
    log_every: int = 0,
    run_name: str = "train",
    fabric: str = "ICI(v5e)",
    deadline_slack: float = 4.0,
    deadline_floor_s: float = 0.05,
    escalate_after: int = 3,
    step_retries: int = 2,
    stragglers_for_epoch: Optional[Callable[[int], int]] = None,
    health_every: int = 0,
    alert_feed: Any = None,
) -> Tuple[TrainState, MetricsLogger, Any]:
    """The degraded-fabric survival loop: :func:`train_loop`'s epoch/step
    structure, driven by a rebuildable step and closed through the
    :class:`resilience.controller.FallbackController`.

    ``step_factory(overrides)`` builds a :class:`CompiledStep` for one
    fallback-ladder rung (overrides: ``reducer``, ``reducer_rank``,
    ``sync_every``); it MUST use
    ``donate_state=False`` — both guards replay steps on their inputs.
    Around every step: a :class:`resilience.guards.CollectiveWatchdog`
    fence hook arms per-collective deadlines (registered FIRST, so the timer is
    running when an injected stall sleeps), the optional
    :class:`resilience.chaos.CommFaultInjector` is advanced host-side and
    registered as the second fence hook, and the step runs inside
    ``CommDeadlineGuard(GuardedStep(step))`` — transient exceptions retry
    innermost; deadline expiries retry once, then mark the step degraded;
    K consecutive degraded steps raise
    :class:`resilience.guards.CommEscalationError` to the caller (the
    supervisor's restart path).

    At each epoch boundary the loop summarizes fabric health (host-side
    step-time p50; achieved wire bytes/s = ledger bytes-per-step over
    measured p50; the watchdog's expiry/degraded counters; optional
    ``stragglers_for_epoch(epoch)`` verdict count — cross-rank straggler
    detection lives in ``observe.analytics`` and needs the merged run log,
    so in-process callers inject it) and feeds it to
    ``controller.observe``. On a decision the step is rebuilt ONCE from
    the new rung's overrides and the training state carried across:
    ``params`` (and ``momenta`` — params-shaped and replicated under both
    reducers) transfer exactly; per-worker ``model_state`` is collapsed
    through ``eval_model_state`` and re-broadcast; error-feedback memories
    restart at zero (the unsent residual is forfeited — one step of
    compression error, the price of the switch; DESIGN.md). The decision
    lands in telemetry via ``controller.record`` with predicted (new
    rung's static ledger) vs realized (old rung, measured) bytes/step.

    Live-plane hooks (PR 10): ``health_every > 0`` emits a
    ``TrainHealthEvent`` every N steps via the step's ``health_fn`` probe
    (same contract as :func:`train_loop`) and a ``MemoryEvent`` from the
    shared ``observe.memory.MemorySampler`` on the same cadence — the
    sampler is also handed to the inner ``GuardedStep`` (with the carry's
    buffer-class sizes) so an OOM's post-mortem names its top suspect. ``alert_feed`` (an
    ``observe.live.AlertFeed`` tailing the run's ``alerts.jsonl``) is
    polled every step; each alert record is offered to
    ``controller.nudge`` — a critical or comm-shaped alert descends one
    rung IMMEDIATELY (mid-epoch rebuild, same single-recompile budget as a
    boundary decision, just paid early), other warns pre-charge the
    boundary hysteresis. The nudged epoch's boundary ``observe`` is a
    no-op (the controller self-enforces it).

    Returns ``(state, logger, controller)``.
    """
    import contextlib
    import statistics
    import time as _time

    from ..observe import FailureEvent, TrainHealthEvent
    from ..observe.fidelity import FidelityTracker
    from ..observe.spans import recording, span
    from ..parallel import comm
    from ..resilience.controller import EpochHealth
    from ..resilience.guards import (
        CollectiveWatchdog,
        CommDeadlineGuard,
        GuardedStep,
    )

    base = step_factory(controller.overrides)
    state = base.init_state(params, model_state)
    n_workers = getattr(base, "num_devices", None) or 1

    watchdog = CollectiveWatchdog(
        n_workers=n_workers, fabric=fabric, slack=deadline_slack,
        floor_s=deadline_floor_s, escalate_after=escalate_after,
        telemetry=telemetry, rank=rank, label=run_name,
    )

    memory_sampler = None
    fidelity_tracker = None
    if health_every > 0 and telemetry is not None:
        from ..observe.memory import MemorySampler

        memory_sampler = MemorySampler(telemetry, label=run_name, rank=rank)

    def _buffer_classes() -> Dict[str, float]:
        # leaf shapes are static across steps, so the current carry's
        # sizes ARE the live attribution — this runs only inside the OOM
        # post-mortem, never on the hot path
        from ..observe.memory import tree_bytes

        return {
            "params": float(tree_bytes(getattr(state, "params", None))),
            "momenta": float(tree_bytes(getattr(state, "momenta", None))),
            "ef_memory": float(tree_bytes(getattr(state, "memories", None))),
            "reducer_state": float(
                tree_bytes(getattr(state, "reducer_state", None))
            ),
            "model_state": float(
                tree_bytes(getattr(state, "model_state", None))
            ),
        }

    def _guard(inner: CompiledStep):
        from ..observe.memory import memory_footprint_fields

        return CommDeadlineGuard(
            GuardedStep(
                inner, retries=step_retries, telemetry=telemetry,
                label=run_name, rank=rank, memory_sampler=memory_sampler,
                footprint=memory_footprint_fields(
                    getattr(inner, "compiled", None)
                ) or None,
                buffers_fn=_buffer_classes,
            ),
            watchdog, telemetry=telemetry, label=run_name, rank=rank,
        )

    guard = _guard(base)
    logger = MetricsLogger(
        bits_per_step=base.bits_per_step, log_every=log_every,
        telemetry=telemetry,
    )

    # watchdog BEFORE injector: arm the deadline, then let the fault sleep
    comm.add_fence_hook(watchdog)
    if injector is not None:
        comm.add_fence_hook(injector)
    gstep = 0
    # compile grace for the health signal: the first steps after every
    # (re)build pay XLA compilation and cache warmup, which would poison
    # the epoch p50 the controller compares against — excluded from
    # step_times (still logged through the MetricsLogger)
    compile_grace = 2

    def _rebuild(decision) -> None:
        # ONE recompile per decision: rebuild at the new rung and carry
        # the training state across the switch. Shared by the boundary
        # observe and the mid-epoch alert nudge — the nudge spends the
        # same single-recompile budget, just before the epoch edge.
        nonlocal base, state, guard, compile_grace, fidelity_tracker
        # new rung => new reducer => new fidelity group keys; drop the
        # tracker so the next probe rebuilds it from the new layout
        fidelity_tracker = None
        realized = base.bits_per_step / 8
        new_base = step_factory(controller.overrides)
        carried_model = base.eval_model_state(state)
        new_state = new_base.init_state(state.params, carried_model)
        new_state = new_state._replace(momenta=state.momenta)
        base, state = new_base, new_state
        guard = _guard(base)
        compile_grace = 2
        controller.record(
            decision,
            predicted_bytes_per_step=base.bits_per_step / 8,
            realized_bytes_per_step=realized,
        )

    try:
        with recording(telemetry) if telemetry is not None else contextlib.nullcontext():
            for epoch in range(epochs):
                step_times = []
                for batch in batches_for_epoch(epoch):
                    if injector is not None:
                        injector.advance(gstep)
                    logger.start_step()
                    t0 = _time.monotonic()
                    with span("step", step=gstep):
                        with span("step/compute", step=gstep):
                            state, loss = guard(state, batch)
                        with span("step/loss_sync", step=gstep):
                            loss = jax.device_get(loss)
                    if compile_grace > 0:
                        compile_grace -= 1
                    else:
                        step_times.append(_time.monotonic() - t0)
                    logger.end_step(epoch, loss, bits=base.bits_per_step)
                    gstep += 1
                    if (
                        memory_sampler is not None
                        and memory_sampler.enabled
                        and gstep % health_every == 0
                    ):
                        with span("memory_probe", step=gstep):
                            memory_sampler.sample(gstep)
                    health_fn = getattr(base, "health_fn", None)
                    if (
                        health_every > 0
                        and health_fn is not None
                        and telemetry is not None
                        and gstep % health_every == 0
                    ):
                        with span("health_probe", step=gstep):
                            try:
                                stats = jax.device_get(
                                    health_fn(state, batch)
                                )
                                telemetry.emit(
                                    TrainHealthEvent(
                                        step=gstep,
                                        epoch=epoch,
                                        grad_norm=float(stats["grad_norm"]),
                                        ef_memory_norm=float(
                                            stats["ef_memory_norm"]
                                        ),
                                        powersgd_rel_error=float(
                                            stats["powersgd_rel_error"]
                                        ),
                                        loss=float(stats["loss"]),
                                        rank=rank,
                                        label=run_name,
                                    )
                                )
                                fid = stats.get("fidelity")
                                if fid:
                                    if fidelity_tracker is None:
                                        tags = {}
                                        r = getattr(base, "reducer", None)
                                        if hasattr(
                                            r, "fidelity_group_tags"
                                        ):
                                            tags = r.fidelity_group_tags(
                                                state.params
                                            )
                                        fidelity_tracker = FidelityTracker(
                                            tags, rank=rank, label=run_name
                                        )
                                    for ev in fidelity_tracker.events(
                                        gstep, fid, epoch=epoch
                                    ):
                                        telemetry.emit(ev)
                            except Exception as e:  # advisory, never fatal
                                telemetry.emit(
                                    FailureEvent(
                                        kind="health_probe_error",
                                        label=run_name,
                                        message=f"{type(e).__name__}: {e}",
                                    )
                                )
                    if alert_feed is not None:
                        # the live plane's feedback channel: alerts the
                        # supervisor-side detectors appended to
                        # alerts.jsonl reach the controller HERE, before
                        # the epoch boundary
                        for rec in alert_feed.poll():
                            d = controller.nudge(
                                rec.get("alert", ""),
                                epoch,
                                severity=rec.get("severity", "warn"),
                            )
                            if d is not None:
                                _rebuild(d)
                logger.end_epoch(epoch, rank=rank)
                if not step_times:
                    continue
                p50 = statistics.median(step_times)
                bytes_per_step = base.bits_per_step / 8
                counters = watchdog.take_epoch()
                health = EpochHealth(
                    epoch=epoch,
                    step_p50_s=p50,
                    achieved_bytes_per_s=(
                        bytes_per_step / p50 if p50 > 0 else 0.0
                    ),
                    deadline_expiries=counters["deadline_expiries"],
                    degraded_steps=counters["degraded_steps"],
                    stragglers=(
                        stragglers_for_epoch(epoch)
                        if stragglers_for_epoch is not None
                        else 0
                    ),
                )
                decision = controller.observe(health)
                if decision is None:
                    continue
                _rebuild(decision)
    finally:
        if injector is not None:
            comm.remove_fence_hook(injector)
        comm.remove_fence_hook(watchdog)
        watchdog.stop()
    return state, logger, controller


def resilient_train_loop(
    step: CompiledStep,
    init_state: TrainState,
    batches_for_epoch: Callable[[int], Iterator[Any]],
    epochs: int,
    checkpoint_dir: str,
    rank: int = 0,
    log_every: int = 0,
    watchdog_timeout_s: Optional[float] = None,
    heartbeat: Any = None,
    telemetry: Any = None,
    trace_dir: Optional[str] = None,
    audit: bool = False,
    run_name: str = "train",
    chaos_plan: Any = None,
    incarnation: int = 0,
    step_retries: int = 0,
    guard_batches: bool = False,
    expected_batch: Optional[int] = None,
    keep_last: Optional[int] = None,
    batch_sharding: Any = None,
    topology: Optional[Dict] = None,
    preemption_guard: Any = None,
    loader_state_fn: Optional[Callable[[int, int], Optional[Dict]]] = None,
) -> Tuple[TrainState, "MetricsLogger", int]:
    """:func:`train_loop` plus the survival kit the reference lacks entirely
    (SURVEY §5: no checkpointing, no retry; a failed init doesn't even exit):

    - on entry, resume from the newest COMMITTED checkpoint under
      ``checkpoint_dir`` that passes checksum verification — a torn or
      bit-flipped directory is skipped with a ``checkpoint_fallback`` event
      and the previous good step restored instead (full TrainState — the EF
      chain continues exactly);
    - every epoch, save one through the atomic commit protocol
      (``keep_last`` garbage-collects older steps);
    - optional :class:`utils.failure.StepWatchdog` around every step and
      :class:`utils.failure.HeartbeatMonitor` beat per step;
    - ``step_retries > 0`` wraps the step in
      :class:`resilience.guards.GuardedStep` (transient-error retry +
      non-finite-loss rejection; requires ``donate_state=False``), and
      ``guard_batches`` drops malformed loader batches;
    - ``chaos_plan`` (a :class:`resilience.chaos.ChaosPlan`) threads
      deterministic fault injection into all of the above — the chaos
      suite's entry point. ``incarnation`` is this worker's supervisor
      restart generation (``resilience.supervisor.incarnation_from_env``),
      matched against the plan so a restarted worker doesn't re-crash;
    - ``topology`` (a ``resilience.reshard.make_topology`` record for THIS
      run's world) tags every checkpoint with its world size and, on
      resume, routes a cross-world restore through the resharder: EF
      memories fold by summation, per-worker stats merge, and ``resumed``/
      ``resharded`` events plus an accounting ``note`` (old/new
      accumulation, recomputed ``bits_per_step``) land in telemetry;
    - ``preemption_guard`` (a ``resilience.guards.PreemptionGuard``) turns
      a SIGTERM into an emergency committed checkpoint at the next step
      boundary: the save records an ``epoch_cursor`` in the topology tag,
      the loop stops early, and the NEXT resume re-enters the same epoch
      skipping exactly the steps already accounted for.
    - ``loader_state_fn(epoch, batches_done)`` (optional) produces the
      data-plane loader-state dict (e.g.
      ``data.partition.ElasticIndexStream.state``) committed as
      ``_LOADER_STATE.json`` inside every checkpoint's atomic commit —
      epoch-boundary saves call it with ``(epoch + 1, 0)``, the
      preemption-grace save with the mid-epoch ``(epoch, batches_done)``.
      On resume, read it back via ``utils.checkpoint.read_loader_state(
      utils.checkpoint.latest_step_path(checkpoint_dir))`` BEFORE building
      ``batches_for_epoch``, so a resharded world re-enters the stream at
      the committed cursor (zero samples dropped or duplicated).

    Returns ``(state, logger, start_epoch)`` — ``start_epoch`` tells the
    caller how many epochs were skipped via resume.
    """
    import itertools
    import os

    from ..observe import FailureEvent, NoteEvent
    from ..utils.checkpoint import (
        read_topology,
        restore_latest,
        save_checkpoint,
    )
    from ..utils.failure import StepWatchdog

    state = init_state
    start_epoch = 0
    resume_skip = 0  # steps of start_epoch already in the restored state
    reshard_note: Dict[str, Any] = {}

    def _resharder(path, saved_topo):
        from ..resilience.reshard import reshard_from_checkpoint

        reshard_note["old"] = saved_topo or {}
        return reshard_from_checkpoint(
            path, init_state, saved_topology=saved_topo,
            mesh_axes=(topology or {}).get("mesh_axes"),
        )

    resumed = restore_latest(
        checkpoint_dir, init_state, telemetry=telemetry, label=run_name,
        resharder=_resharder if topology is not None else None,
    )
    if resumed is not None:
        state, resumed_epoch = resumed
        restored_topo = read_topology(
            os.path.join(os.path.abspath(checkpoint_dir), f"step_{resumed_epoch}")
        )
        cursor = (restored_topo or {}).get("epoch_cursor")
        if cursor and cursor.get("batches_done"):
            # a preemption-grace mid-epoch save: re-enter the SAME epoch,
            # skipping the steps already in the restored state (the
            # per-epoch batch stream is deterministic, so the skip is
            # exact even across a world change — steps/epoch is a function
            # of the preserved global batch, not the world size)
            start_epoch = int(cursor["epoch"])
            resume_skip = int(cursor["batches_done"])
        else:
            start_epoch = resumed_epoch + 1
        if telemetry is not None:
            mid = f" (+{resume_skip} steps)" if resume_skip else ""
            telemetry.emit(
                FailureEvent(
                    kind="resumed", label=run_name, rank=rank,
                    step=resumed_epoch, incarnation=incarnation,
                    message=f"resumed from step_{resumed_epoch},"
                            f" starting epoch {start_epoch}{mid}",
                )
            )
        if reshard_note and telemetry is not None:
            old, new = reshard_note["old"], topology or {}
            new_bits = new.get("bits_per_step")
            if new_bits is None:
                new_bits = getattr(step, "bits_per_step", None)
            mesh = ""
            if old.get("mesh_axes") or new.get("mesh_axes"):
                mesh = (
                    f" (mesh {old.get('mesh_axes')} ->"
                    f" {new.get('mesh_axes')})"
                )
            telemetry.emit(
                FailureEvent(
                    kind="resharded", label=run_name, rank=rank,
                    step=resumed_epoch, incarnation=incarnation,
                    message=f"world {old.get('world_size')} ->"
                            f" {new.get('world_size')}{mesh}: EF memories"
                            f" folded by summation, per-worker stats merged,"
                            f" partitions re-split from the fixed"
                            f" permutation",
                )
            )
            telemetry.emit(
                NoteEvent(
                    message=f"reshard accounting: global_batch"
                            f" {old.get('global_batch')} ->"
                            f" {new.get('global_batch')} (preserved),"
                            f" accum_steps {old.get('accum_steps')} ->"
                            f" {new.get('accum_steps')},"
                            f" bits_per_step {old.get('bits_per_step')} ->"
                            f" {new_bits}",
                )
            )

    if chaos_plan is not None:
        from ..resilience.chaos import ChaosStep, chaos_batches

        step = ChaosStep(
            step, chaos_plan, rank=rank, incarnation=incarnation,
            telemetry=telemetry,
        )
        batches_for_epoch = chaos_batches(
            batches_for_epoch, chaos_plan, rank=rank,
            incarnation=incarnation, telemetry=telemetry,
        )
    if step_retries > 0:
        from ..observe.memory import tree_bytes
        from ..resilience.guards import GuardedStep

        def _buffer_classes() -> Dict[str, float]:
            # the restored/initial carry: leaf shapes never change across
            # steps, so its sizes attribute the live state's bytes exactly
            # (runs only inside the OOM post-mortem, never per step)
            return {
                "params": float(tree_bytes(getattr(state, "params", None))),
                "momenta": float(tree_bytes(getattr(state, "momenta", None))),
                "ef_memory": float(
                    tree_bytes(getattr(state, "memories", None))
                ),
                "reducer_state": float(
                    tree_bytes(getattr(state, "reducer_state", None))
                ),
                "model_state": float(
                    tree_bytes(getattr(state, "model_state", None))
                ),
            }

        step = GuardedStep(
            step, retries=step_retries, telemetry=telemetry, label=run_name,
            rank=rank, buffers_fn=_buffer_classes,
        )
    if guard_batches:
        from ..resilience.guards import guarded_batches

        batches_for_epoch = guarded_batches(
            batches_for_epoch, expected_batch=expected_batch,
            telemetry=telemetry, label=run_name,
        )

    def _topo(cursor: Optional[Dict] = None) -> Optional[Dict]:
        if topology is None:
            return {"epoch_cursor": cursor} if cursor else None
        out = dict(topology)
        out["epoch_cursor"] = cursor
        return out

    def _loader_state(epoch: int, cursor: Optional[Dict]) -> Optional[Dict]:
        if loader_state_fn is None:
            return None
        if cursor is None:  # epoch-boundary save: the NEXT epoch starts clean
            return loader_state_fn(epoch + 1, 0)
        return loader_state_fn(int(cursor["epoch"]), int(cursor["batches_done"]))

    def _commit_save(st, epoch: int, cursor: Optional[Dict] = None) -> None:
        # small in-place retry budget for a transient write refusal, then
        # the typed fail-fast: emit the detection event and exit with the
        # sentinel code the supervisor converts into an immediate run
        # failure (restarting into a read-only checkpoint root is a
        # restart storm, not recovery)
        import time as _time

        from ..resilience.guards import CheckpointUnwritableError

        last = None
        for attempt in range(2):
            try:
                save_checkpoint(
                    checkpoint_dir, st, step=epoch, keep_last=keep_last,
                    topology=_topo(cursor),
                    loader_state=_loader_state(epoch, cursor),
                )
                return
            except CheckpointUnwritableError as e:
                last = e
                _time.sleep(0.05 * (attempt + 1))
        from ..resilience.chaos import CKPT_UNWRITABLE_EXIT_CODE

        if telemetry is not None:
            telemetry.emit(
                FailureEvent(
                    kind="checkpoint_unwritable", label=run_name, rank=rank,
                    step=epoch, incarnation=incarnation,
                    message=f"save retry budget exhausted: {last}",
                )
            )
        raise SystemExit(CKPT_UNWRITABLE_EXIT_CODE) from last

    def _save(epoch: int, st) -> None:
        _commit_save(st, epoch)
        if chaos_plan is not None:
            from ..resilience.chaos import apply_checkpoint_fault

            apply_checkpoint_fault(
                chaos_plan, checkpoint_dir, epoch, rank=rank,
                incarnation=incarnation, telemetry=telemetry,
            )

    def _on_step_end(epoch: int, steps_done: int, st) -> bool:
        if preemption_guard is None or not preemption_guard.requested:
            return False
        done = steps_done + (resume_skip if epoch == start_epoch else 0)
        _commit_save(st, epoch, cursor={"epoch": epoch, "batches_done": done})
        preemption_guard.checkpoint_saved = True
        if telemetry is not None:
            telemetry.emit(
                FailureEvent(
                    kind="preempt_checkpoint", label=run_name, rank=rank,
                    step=epoch, incarnation=incarnation,
                    message=f"emergency checkpoint committed at epoch"
                            f" {epoch} after {done} steps; stopping for"
                            f" preemption",
                )
            )
        return True

    if resume_skip:
        inner_batches, first_epoch, skip = batches_for_epoch, start_epoch, resume_skip

        def batches_for_epoch(epoch: int):  # noqa: F811
            it = inner_batches(epoch)
            return itertools.islice(it, skip, None) if epoch == first_epoch else it

    wd = (
        # grace on the first step: it includes XLA compilation, which may
        # legitimately exceed a steady-state deadline
        StepWatchdog(watchdog_timeout_s, compile_grace=1)
        if watchdog_timeout_s is not None
        else None
    )
    state, logger = train_loop(
        step, state, batches_for_epoch, epochs, rank=rank, log_every=log_every,
        start_epoch=start_epoch, watchdog=wd, heartbeat=heartbeat,
        on_epoch_end=_save,
        on_step_end=_on_step_end if preemption_guard is not None else None,
        batch_sharding=batch_sharding,
        telemetry=telemetry, trace_dir=trace_dir, audit=audit, run_name=run_name,
    )
    return state, logger, start_epoch
