"""Entry point B — PowerSGD-compressed DDP on CIFAR-10, the reference's
flagship (``ddp_powersgd_guide_cifar10``).

Reference configuration (``ddp_powersgd_guide_cifar10/ddp_init.py``):
pretrained ResNet-152 (``:111``), global batch 512 (``:52``), PowerSGD rank 4
(``:36,121``), error-feedback SGD with momentum λ=.9 hand-rolled outside the
optimizer (``:125-181``), lr .001, 100 epochs. The compressed reduction and
Algorithm-2 update run inside one jitted ``shard_map`` step; bytes-on-wire
are reported per epoch (the reference accumulated them silently,
``:123,161``).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..data import load_cifar10_or_synthetic
from ..models import resnet18, resnet152
from ..parallel import PowerSGDReducer, make_mesh
from ..parallel.trainer import make_train_step
from ..utils.config import ExperimentConfig
from .common import (
    accum_batch_sharding,
    accumulated_batches,
    image_classifier_loss,
    powersgd_reducer_kwargs,
    summarize,
    train_loop,
)


def build_model(preset: str, dtype=jnp.float32):
    if preset == "full":
        return resnet152(num_classes=10, norm="batch", stem="imagenet", dtype=dtype)
    return resnet18(num_classes=10, norm="batch", stem="cifar", width=16, dtype=dtype)


def run(
    config: Optional[ExperimentConfig] = None,
    preset: str = "small",
    data_dir: str = "./data",
    mesh=None,
    pretrained_variables=None,
    max_steps_per_epoch: Optional[int] = None,
    eval_after: bool = False,
) -> Dict:
    config = config or ExperimentConfig(
        training_epochs=1, global_batch_size=512, learning_rate=0.001, reducer_rank=4
    )
    mesh = mesh or make_mesh()

    images, labels, is_real = load_cifar10_or_synthetic(data_dir, train=True)
    model = build_model(preset, dtype=jnp.dtype(config.compute_dtype))

    if pretrained_variables is None:
        variables = model.init(
            jax.random.PRNGKey(config.seed), jnp.zeros((1, 32, 32, 3)), train=True
        )
    else:
        variables = pretrained_variables
    params = variables["params"]
    model_state = {"batch_stats": variables["batch_stats"]}

    reducer = PowerSGDReducer(
        random_seed=config.seed,  # reducer seeded with the config seed — ddp_init.py:121
        compression_rank=config.reducer_rank,
        reuse_query=config.reuse_query,
        matricize="last",  # flax HWIO/(in,out) layouts put output features last
        **powersgd_reducer_kwargs(config),
    )
    loss_fn = image_classifier_loss(model, has_batch_stats=True)
    step = make_train_step(
        loss_fn,
        reducer,
        params,
        learning_rate=config.learning_rate,
        momentum=config.momentum,  # λ in Algorithm 2 — ddp_init.py:32
        algorithm="ef_momentum",
        mesh=mesh,
        accum_steps=config.accum_steps,
        max_grad_norm=config.max_grad_norm,
    )
    state = step.init_state(params, model_state=model_state)

    batches = accumulated_batches(
        [images, labels], config, max_steps_per_epoch=max_steps_per_epoch
    )
    from ..observe import audit_from_config, telemetry_from_config

    telemetry = telemetry_from_config(config)
    try:
        state, logger = train_loop(
            step, state, batches, config.training_epochs,
            rank=config.process_id, log_every=config.log_every,
            batch_sharding=accum_batch_sharding(mesh, config.accum_steps),
            telemetry=telemetry,
            trace_dir=config.trace_dir,
            audit=audit_from_config(config),
            run_name="powersgd_cifar10",
            health_every=config.health_every,
        )
    finally:
        telemetry.close()
    extra = {
        "preset": preset,
        "real_data": is_real,
        "reducer_rank": config.reducer_rank,
    }
    if eval_after:
        from .common import evaluate_image_classifier

        test_x, test_y, _ = load_cifar10_or_synthetic(data_dir, train=False)
        extra["eval_accuracy"] = evaluate_image_classifier(
            model, state.params, step.eval_model_state(state)["batch_stats"], test_x, test_y
        )
    return summarize(
        "powersgd_cifar10", logger, extra, reducer=reducer, state=state
    )
