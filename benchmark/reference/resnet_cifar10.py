"""Bottleneck ResNet (He et al. 2015; torchvision's v1.5 stride placement),
its loss and its gradients in plain ``jax.numpy``/``lax``: float32, every
convolution and product at precision "highest", batch norm in training mode
from the batch's own statistics (biased variance, eps 1e-5), no flax.

The whole per-worker batch goes through at once: batch norm sees it whole.
Departure: the running statistics are not advanced (nothing the check
compares depends on them in training mode); ``model_state`` passes through.

It reads the system's parameter tree (names as flax creates them in
``models/resnet.py``) and nothing else of the program.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, kernel, stride=1, pad=0):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=lax.Precision.HIGHEST,
    )


def _batch_norm(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_batch_norm(_conv(x, p["Conv_0"]["kernel"]), p["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(_conv(y, p["Conv_1"]["kernel"], stride, 1), p["BatchNorm_1"]))
    y = _batch_norm(_conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _batch_norm(_conv(x, p["conv_proj"]["kernel"], stride), p["norm_proj"])
    return jax.nn.relu(x + y)


def logits_of(params, x, stage_sizes, stem: str):
    if stem == "imagenet":
        x = jax.nn.relu(_batch_norm(_conv(x, params["conv_init"]["kernel"], 2, 3), params["norm_init"]))
        x = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)),
        )
    else:
        x = jax.nn.relu(_batch_norm(_conv(x, params["conv_init"]["kernel"], 1, 1), params["norm_init"]))
    index = 0
    for stage, blocks in enumerate(stage_sizes):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            x = _bottleneck(x, params[f"BottleneckBlock_{index}"], stride)
            index += 1
    x = jnp.mean(x, axis=(1, 2))
    return jnp.matmul(x, params["head"]["kernel"], precision=lax.Precision.HIGHEST) + params["head"]["bias"]


def _loss(params, batch, stage_sizes, stem):
    x, y = batch
    logp = jax.nn.log_softmax(logits_of(params, x.astype(jnp.float32), stage_sizes, stem), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def make_loss_and_grads(cfg: Dict):
    """``(params, model_state, worker_batch) -> (loss, grads, model_state)``."""
    stage_sizes, stem = tuple(cfg["stage_sizes"]), cfg["stem"]

    @jax.jit
    def one(params, batch):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(_loss)(params, batch, stage_sizes, stem)

    def loss_and_grads(params, model_state, batch):
        loss, grads = one(params, tuple(batch))
        return loss, grads, model_state

    return loss_and_grads
