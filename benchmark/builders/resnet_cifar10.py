"""ResNet image classifier with per-worker batch norm under a gradient
reducer, composed as ``experiments/powersgd_cifar10.run`` composes it."""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from .. import compose, traffic


def model_of(cfg: Dict):
    from network_distributed_pytorch_tpu.models.resnet import BottleneckBlock, ResNet

    if cfg["block"] != "bottleneck":
        raise ValueError(f"resnet_cifar10 builds bottleneck ResNets, not {cfg['block']!r}")
    return ResNet(
        stage_sizes=list(cfg["stage_sizes"]), block_cls=BottleneckBlock,
        num_classes=cfg["num_classes"], width=cfg["width"], norm=cfg["norm"],
        stem=cfg["stem"], dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def init_fn_of(model, cfg: Dict):
    shape = tuple(cfg["image_shape"])
    return lambda key: model.init(key, jnp.zeros((1,) + shape), train=True)


def step_of(cfg: Dict, seed: int, mesh, params):
    """(experiment config, jitted step) for parameters or their shapes."""
    from network_distributed_pytorch_tpu.experiments.common import image_classifier_loss

    exp = compose.experiment_config(cfg, seed, mesh.size)
    step = compose.make_step(
        image_classifier_loss(model_of(cfg), has_batch_stats=True),
        compose.make_reducer(cfg, exp), params, cfg, mesh,
    )
    return exp, step


def abstract(cfg: Dict, seed: int, mesh):
    """The step with the shapes of its state and of one batch, nothing on a
    device: what an AOT compile for a described chip needs."""
    import jax

    variables = jax.eval_shape(init_fn_of(model_of(cfg), cfg), jax.random.PRNGKey(seed))
    exp, step = step_of(cfg, seed, mesh, variables["params"])
    state = jax.eval_shape(
        lambda p, b: step.init_state(p, model_state={"batch_stats": b}),
        variables["params"], variables["batch_stats"],
    )
    n = exp.global_batch_size
    batch = (
        jax.ShapeDtypeStruct((n,) + tuple(cfg["image_shape"]), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
    )
    return step, state, batch


def build(cfg: Dict, seed: int, mesh) -> compose.Built:
    from network_distributed_pytorch_tpu.experiments.common import accumulated_batches

    variables = compose.init_on_device(init_fn_of(model_of(cfg), cfg), seed)
    exp, step = step_of(cfg, seed, mesh, variables["params"])
    state = step.init_state(
        variables["params"], model_state={"batch_stats": variables["batch_stats"]}
    )
    pool = traffic.images(
        {**cfg["traffic"], "image_shape": cfg["image_shape"], "num_classes": cfg["num_classes"]},
        seed,
    )
    # (float32 images, int labels) is what the program's native batch loader takes
    batches = accumulated_batches(list(pool), exp)
    return compose.Built(step, state, compose.endless(batches), pool, exp.global_batch_size)
