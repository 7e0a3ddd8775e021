"""Entry point A — exact-allreduce DDP on CIFAR-10
(the reference's ``ddp_guide_cifar10``).

Reference configuration (``ddp_guide_cifar10/ddp_init.py``): pretrained
ResNet-50 (``:108``), global batch 256 (``:49``), SGD lr .001 momentum .9
(``:110``), CE loss, 100 epochs, gradients synchronized by exact
allreduce-mean after each backward (``:57-62``). Here the whole step —
forward, backward, ONE packed allreduce (vs the reference's ~161 per-param
collectives), SGD — is a single jitted ``shard_map`` over the data mesh.

``preset="small"`` is BASELINE.json's CPU-testable tier (ResNet-18, CIFAR
stem); ``preset="full"`` is the reference's exact configuration.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..data import load_cifar10_or_synthetic
from ..models import resnet18, resnet50
from ..parallel import ExactReducer, make_mesh
from ..parallel.trainer import make_train_step
from ..utils.config import ExperimentConfig
from .common import (
    accum_batch_sharding,
    accumulated_batches,
    image_classifier_loss,
    exact_reducer_kwargs,
    summarize,
    train_loop,
)


def build_model(preset: str, dtype=jnp.float32):
    if preset == "full":
        return resnet50(num_classes=10, norm="batch", stem="imagenet", dtype=dtype)
    return resnet18(num_classes=10, norm="batch", stem="cifar", width=16, dtype=dtype)


def run(
    config: Optional[ExperimentConfig] = None,
    preset: str = "small",
    data_dir: str = "./data",
    mesh=None,
    pretrained_variables=None,
    max_steps_per_epoch: Optional[int] = None,
    eval_after: bool = False,
    strategy: str = "ddp",
    checkpoint_dir: Optional[str] = None,
    keep_last: Optional[int] = None,
) -> Dict:
    """``strategy="ddp"`` is the reference's replicated-parameter exact DDP;
    ``strategy="fsdp"`` runs the SAME workload with params/grads/optimizer
    state ZeRO-3-sharded over the data axis (``parallel.fsdp`` — per-device
    model+optimizer memory drops by ~1/world; the training math is still
    exact data-parallel SGD).

    ``checkpoint_dir`` switches to :func:`common.resilient_train_loop`:
    per-epoch committed checkpoints, resume-on-entry, and (with
    ``config.chaos_plan``) deterministic fault injection healed by the
    recovery guards.

    ``config.adaptive_comm`` switches to :func:`common.adaptive_train_loop`
    instead: collective deadline watchdogs around every fenced collective and
    the :class:`resilience.controller.FallbackController` walking the
    reducer fallback ladder at epoch boundaries (``config.chaos_plan``
    then drives the comm-layer faults in-process — no supervisor needed,
    so checkpoint_dir is not required and not supported together)."""
    config = config or ExperimentConfig(
        training_epochs=1, global_batch_size=256, learning_rate=0.001
    )
    mesh = mesh or make_mesh()
    resilient = checkpoint_dir is not None
    adaptive = bool(config.adaptive_comm)
    if adaptive and resilient:
        raise ValueError(
            "adaptive_comm rebuilds the step per fallback-ladder rung;"
            " the checkpointed resilient loop carries one fixed step —"
            " pick one (checkpoint_dir or adaptive_comm)"
        )
    if config.chaos_plan and not (resilient or adaptive):
        raise ValueError(
            "config.chaos_plan requires checkpoint_dir or adaptive_comm"
        )

    images, labels, is_real = load_cifar10_or_synthetic(data_dir, train=True)
    model = build_model(preset, dtype=jnp.dtype(config.compute_dtype))

    if pretrained_variables is None:
        variables = model.init(
            jax.random.PRNGKey(config.seed), jnp.zeros((1, 32, 32, 3)), train=True
        )
    else:
        variables = pretrained_variables  # torchvision import, models.import_weights
    params = variables["params"]
    model_state = {"batch_stats": variables["batch_stats"]}

    loss_fn = image_classifier_loss(model, has_batch_stats=True)
    assert strategy in ("ddp", "fsdp"), strategy
    if adaptive and strategy != "ddp":
        raise ValueError(
            "adaptive_comm requires strategy='ddp' (the fallback ladder"
            " swaps reducers; the FSDP step has no reducer to swap)"
        )
    if strategy == "fsdp":
        from ..parallel.fsdp import make_fsdp_train_step

        if config.accum_steps > 1:
            raise ValueError("accum_steps is not supported with strategy='fsdp'")
        if config.max_grad_norm is not None:
            raise ValueError("max_grad_norm is not supported with strategy='fsdp'")
        if resilient:
            raise ValueError(
                "checkpoint_dir requires strategy='ddp' (the FSDP carry"
                " restores via restore_checkpoint_sharded, not this loop)"
            )
        step = make_fsdp_train_step(
            loss_fn,
            params,
            learning_rate=config.learning_rate,
            momentum=config.momentum,
            algorithm="sgd",
            mesh=mesh,
        )
    elif adaptive:
        from ..parallel import PowerSGDReducer

        def _build_step(overrides):
            # One fallback-ladder rung -> one compiled step. ``sync_every``
            # is accepted but ignored: this entry point is synchronous DDP
            # (every step reduces); the localsgd rung only widens anything
            # in entry point C. ``ef_momentum`` at EVERY rung (it equals
            # sgd-momentum under ExactReducer — memories stay zero) so the
            # momenta buffer carries exactly across a reducer switch.
            if overrides.get("reducer") == "powersgd":
                reducer = PowerSGDReducer(
                    random_seed=config.seed,
                    compression_rank=overrides.get(
                        "reducer_rank", config.reducer_rank
                    ),
                    reuse_query=config.reuse_query,
                )
            else:
                reducer = ExactReducer(
                    bucket_bytes=overrides.get(
                        "bucket_bytes", config.bucket_bytes
                    ),
                )
            return make_train_step(
                loss_fn,
                reducer,
                params,
                learning_rate=config.learning_rate,
                momentum=config.momentum,
                algorithm="ef_momentum",
                mesh=mesh,
                accum_steps=config.accum_steps,
                max_grad_norm=config.max_grad_norm,
                # the deadline guard replays a step on its inputs, which a
                # donated buffer cannot survive
                donate_state=False,
            )

        step = None  # built per-rung by adaptive_train_loop
    else:
        step = make_train_step(
            loss_fn,
            ExactReducer(**exact_reducer_kwargs(config)),
            params,
            learning_rate=config.learning_rate,
            momentum=config.momentum,
            algorithm="sgd",  # reference uses optim.SGD(lr, momentum=.9) — ddp_init.py:110
            mesh=mesh,
            accum_steps=config.accum_steps,
            max_grad_norm=config.max_grad_norm,
            # the retry guard re-runs a failed step on its inputs, which a
            # donated buffer cannot survive
            donate_state=not resilient,
        )
    if not adaptive:
        state = step.init_state(params, model_state=model_state)

    batches = accumulated_batches(
        [images, labels], config, max_steps_per_epoch=max_steps_per_epoch
    )
    from ..observe import audit_from_config, telemetry_from_config

    telemetry = telemetry_from_config(config)
    try:
        if resilient:
            from ..resilience import (
                PREEMPT_EXIT_CODE,
                ChaosPlan,
                PreemptionGuard,
                incarnation_from_env,
                make_topology,
            )
            from .common import resilient_train_loop

            plan = (
                ChaosPlan.load(config.chaos_plan)
                if config.chaos_plan else None
            )
            incarnation = incarnation_from_env()
            with PreemptionGuard(
                telemetry=telemetry, rank=config.process_id,
                incarnation=incarnation, label="exact_cifar10",
            ) as guard:
                state, logger, _ = resilient_train_loop(
                    step, state, batches, config.training_epochs,
                    checkpoint_dir=checkpoint_dir,
                    rank=config.process_id, log_every=config.log_every,
                    telemetry=telemetry, trace_dir=config.trace_dir,
                    audit=audit_from_config(config), run_name="exact_cifar10",
                    chaos_plan=plan, incarnation=incarnation,
                    step_retries=2 if plan is not None else 0,
                    guard_batches=plan is not None,
                    keep_last=keep_last,
                    batch_sharding=accum_batch_sharding(mesh, config.accum_steps),
                    # topology-tag every committed checkpoint so a restart
                    # on a shrunken mesh reshards instead of mis-resuming
                    topology=make_topology(
                        mesh.size,
                        global_batch=config.global_batch_size,
                        accum_steps=config.accum_steps,
                        data_seed=config.seed,
                        bits_per_step=step.bits_per_step,
                        rng_seed=config.seed,
                        incarnation=incarnation,
                    ),
                    preemption_guard=guard,
                )
            if guard.requested:
                # the emergency checkpoint is committed; die with the
                # graceful sentinel rather than report a half-run result
                # (the finally below still closes telemetry)
                raise SystemExit(PREEMPT_EXIT_CODE)
        elif adaptive:
            from ..resilience import (
                ChaosPlan,
                CommFaultInjector,
                FallbackController,
            )
            from .common import adaptive_train_loop

            plan = (
                ChaosPlan.load(config.chaos_plan)
                if config.chaos_plan else None
            )
            injector = (
                CommFaultInjector(
                    plan, rank=config.process_id, telemetry=telemetry,
                )
                if plan is not None else None
            )
            # with a tuned plan (launch.py --plan), walk the ladder in the
            # cost model's predicted-best-first order for this fabric —
            # same controller semantics, one recompile per decision, and a
            # stale/unreadable plan degrades to the static DEFAULT_LADDER
            ladder = None
            if config.plan_path:
                import json as _json

                from ..resilience import ladder_from_plan

                try:
                    with open(config.plan_path, "r", encoding="utf-8") as fh:
                        plan_doc = _json.load(fh)
                except (OSError, ValueError):
                    plan_doc = None
                if plan_doc is not None:
                    ladder = ladder_from_plan(plan_doc, config.comm_fabric)
            controller = FallbackController(
                ladder=ladder, telemetry=telemetry, rank=config.process_id,
            )
            # under a supervised run, tail the run's alerts.jsonl so the
            # live plane's detectors can nudge the controller mid-epoch
            import os as _os

            from ..observe import runlog as _runlog
            from ..observe.live import AlertFeed

            _run_dir = _os.environ.get(_runlog.ENV_RUN_DIR)
            feed = AlertFeed(_run_dir) if _run_dir else None
            state, logger, controller = adaptive_train_loop(
                _build_step, params, model_state, batches,
                config.training_epochs, controller,
                injector=injector, telemetry=telemetry,
                rank=config.process_id, log_every=config.log_every,
                run_name="exact_cifar10", fabric=config.comm_fabric,
                health_every=config.health_every, alert_feed=feed,
            )
        else:
            state, logger = train_loop(
                step, state, batches, config.training_epochs,
                rank=config.process_id, log_every=config.log_every,
                batch_sharding=accum_batch_sharding(mesh, config.accum_steps),
                telemetry=telemetry,
                trace_dir=config.trace_dir,
                audit=audit_from_config(config),
                run_name="exact_cifar10",
                health_every=config.health_every,
            )
    finally:
        telemetry.close()
    extra = {
        "preset": preset, "real_data": is_real, "strategy": strategy,
    }
    if adaptive:
        extra["final_rung"] = controller.rung.name
        extra["policy_decisions"] = len(controller.decisions)
    if eval_after:
        from .common import evaluate_image_classifier

        eval_params = step.unshard(state) if strategy == "fsdp" else state.params
        if adaptive:
            # the final rung's step object stayed inside the adaptive loop;
            # collapse the per-worker stats directly
            from ..parallel.trainer import collapse_per_worker

            eval_model_state = (
                collapse_per_worker(state.model_state)
                if mesh is not None else state.model_state
            )
        else:
            eval_model_state = step.eval_model_state(state)
        test_x, test_y, _ = load_cifar10_or_synthetic(data_dir, train=False)
        extra["eval_accuracy"] = evaluate_image_classifier(
            model, eval_params, eval_model_state["batch_stats"],
            test_x, test_y,
        )
    return summarize("exact_cifar10", logger, extra)
