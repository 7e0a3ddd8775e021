"""DiLoCo / local SGD on the reference's CIFAR workload (beyond parity):
the modern communication-AVOIDANCE answer to the slow-network problem the
reference attacks with compression, as a launcher entry point.

Same model/data scaffolding as ``powersgd_cifar10`` (ResNet on CIFAR-10,
synthetic fallback), but trained in sync rounds: each worker takes
``sync_every`` local SGD steps, then the round's parameter delta is
averaged and applied through an outer Nesterov step
(``parallel.localsgd.make_diloco_train_fn``). ``reducer="powersgd"``
compresses the outer delta under error feedback — avoidance × compression;
``fragments > 1`` switches to streaming DiLoCo (round-robin fragment sync,
K-fold lower peak bytes). Wire cost per round is the reducer pass over a
parameter-shaped tree instead of one gradient allreduce per step.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..data import load_cifar10_or_synthetic
from ..parallel import (
    ExactReducer,
    PowerSGDReducer,
    make_diloco_train_fn,
    make_mesh,
    make_streaming_diloco_train_fn,
)
from ..utils.config import ExperimentConfig
from ..utils.metrics import MetricsLogger
from .common import image_classifier_loss, summarize
from .powersgd_cifar10 import build_model


def run(
    config: Optional[ExperimentConfig] = None,
    preset: str = "small",
    data_dir: str = "./data",
    mesh=None,
    sync_every: int = 8,
    reducer: str = "exact",
    fragments: int = 1,
    inner_learning_rate: float = 0.05,
    outer_learning_rate: float = 0.7,
    outer_momentum: float = 0.9,
    max_steps_per_epoch: Optional[int] = None,
    eval_after: bool = False,
) -> Dict:
    """``inner_learning_rate`` is its own parameter (CLI ``--lr`` maps to
    it): local SGD needs a far hotter inner rate than the reference's DDP
    default lr, and ``config.learning_rate`` defaults to the latter."""
    config = config or ExperimentConfig(
        training_epochs=1, global_batch_size=512, reducer_rank=4,
    )
    mesh = mesh or make_mesh()
    assert reducer in ("exact", "powersgd"), reducer
    if max_steps_per_epoch is not None and max_steps_per_epoch < sync_every:
        raise ValueError(
            f"max_steps_per_epoch={max_steps_per_epoch} < sync_every="
            f"{sync_every}: not even one sync round would run"
        )

    images, labels, is_real = load_cifar10_or_synthetic(data_dir, train=True)
    model = build_model(preset, dtype=jnp.dtype(config.compute_dtype))
    variables = model.init(
        jax.random.PRNGKey(config.seed), jnp.zeros((1, 32, 32, 3)), train=True
    )
    loss_fn = image_classifier_loss(model, has_batch_stats=True)
    red = (
        PowerSGDReducer(
            random_seed=config.seed, compression_rank=config.reducer_rank,
            matricize="last",
        )
        if reducer == "powersgd"
        else ExactReducer()
    )
    common = dict(
        inner_learning_rate=inner_learning_rate,
        outer_learning_rate=outer_learning_rate,
        outer_momentum=outer_momentum,
        inner_momentum=config.momentum,
        sync_every=sync_every,
        reducer=red,
        mesh=mesh,
        # the round loop threads the carry strictly and eval reads only the
        # final state, so the donated round avoids a full params+momenta+
        # memories copy per sync
        donate_state=True,
    )
    if fragments > 1:
        diloco = make_streaming_diloco_train_fn(
            loss_fn, variables["params"], num_fragments=fragments, **common
        )
    else:
        diloco = make_diloco_train_fn(loss_fn, variables["params"], **common)
    state = diloco.init_state(
        variables["params"], model_state={"batch_stats": variables["batch_stats"]}
    )

    # rounds consume sync_every consecutive batches, stacked on a leading
    # axis — one compiled dispatch per round
    from ..data import iterate_batches

    # one logged "step" per ROUND. Plain DiLoCo has one fixed round cost;
    # streaming phases differ, so each round is charged ITS phase's exact
    # integer bits (keeping the logger's exact-tally contract)
    if fragments > 1:
        phase_bits = list(diloco.bits_per_phase)
        round_bits = max(phase_bits)  # reported peak; tally uses per-phase
    else:
        phase_bits = [diloco.bits_per_round]
        round_bits = diloco.bits_per_round
    from ..observe import DataDropEvent, telemetry_from_config

    telemetry = telemetry_from_config(config)
    logger = MetricsLogger(log_every=config.log_every, telemetry=telemetry)
    import numpy as np

    # inner-step cap honored exactly: only whole rounds run, so the cap
    # floors to full rounds (never overshoots it)
    max_rounds = (
        None if max_steps_per_epoch is None else max_steps_per_epoch // sync_every
    )
    total_rounds = 0
    for epoch in range(config.training_epochs):
        it = iterate_batches(
            [images, labels], config.global_batch_size, seed=config.seed,
            epoch=epoch,
        )
        pending = []
        rounds_done = 0
        for bx, by in it:
            if max_rounds is not None and rounds_done >= max_rounds:
                pending = []
                break
            if len(bx) != len(by) or len(by) == 0:
                # a genuinely malformed batch is the ONLY thing still
                # dropped (and tallied): partial ROUNDS are padded and
                # masked below, so the clean path's drop count is zero
                telemetry.emit(
                    DataDropEvent(
                        label="diloco_cifar10",
                        epoch=epoch,
                        dropped_batches=1,
                        dropped_samples=max(len(bx), len(by)),
                        reason=f"malformed batch: {len(bx)} images vs"
                               f" {len(by)} labels",
                        rank=config.process_id,
                    )
                )
                continue
            pending.append((bx, by))
            if len(pending) < sync_every:
                continue
            batches = tuple(
                jnp.asarray(np.stack([b[i] for b in pending]))
                for i in range(2)
            )
            pending = []
            logger.start_step()
            state, losses = diloco(state, batches)
            losses = np.asarray(jax.device_get(losses))
            # one logged "step" per ROUND; loss = round mean (the per-step
            # series is inside `losses`); the round is charged its phase's
            # exact wire bits
            logger.end_step(
                epoch, float(losses.mean()),
                bits=phase_bits[total_rounds % len(phase_bits)],
            )
            rounds_done += 1
            total_rounds += 1
        if pending:
            # pad-and-mask instead of dropping: the stack is padded to
            # sync_every with zero batches weighted 0.0, which the compiled
            # scan turns into carry no-ops (localsgd._mask_step) — every
            # sample still trains and syncs, at the same static shapes (no
            # recompile). Round loss averages over REAL steps only.
            n_real = len(pending)
            pad = sync_every - n_real
            zero = tuple(np.zeros_like(a) for a in pending[0])
            batches = tuple(
                jnp.asarray(np.stack([b[i] for b in pending] + [zero[i]] * pad))
                for i in range(2)
            )
            weights = jnp.asarray(
                [1.0] * n_real + [0.0] * pad, dtype=jnp.float32
            )
            pending = []
            logger.start_step()
            state, losses = diloco(state, batches, weights=weights)
            losses = np.asarray(jax.device_get(losses))
            logger.end_step(
                epoch, float(losses.sum() / n_real),
                bits=phase_bits[total_rounds % len(phase_bits)],
            )
            rounds_done += 1
            total_rounds += 1
        logger.end_epoch(epoch, rank=config.process_id)

    extra = {
        "preset": preset,
        "real_data": is_real,
        "sync_every": sync_every,
        "fragments": fragments,
        "reducer": reducer,
        "bits_per_round": round_bits,  # peak phase bits for streaming
    }
    if eval_after:
        from .common import evaluate_image_classifier

        test_x, test_y, _ = load_cifar10_or_synthetic(data_dir, train=False)
        params = diloco.eval_params(state)
        extra["eval_accuracy"] = evaluate_image_classifier(
            model, params,
            diloco.eval_model_state(state)["batch_stats"], test_x, test_y,
        )
    telemetry.close()
    return summarize("diloco_cifar10", logger, extra)
