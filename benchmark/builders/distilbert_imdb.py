"""DistilBERT sequence classifier under a gradient reducer, composed as
``experiments/powersgd_imdb.run`` composes it."""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from .. import compose, traffic


def model_of(cfg: Dict):
    from network_distributed_pytorch_tpu.models.distilbert import (
        DistilBertConfig,
        DistilBertForSequenceClassification,
    )

    return DistilBertForSequenceClassification(
        DistilBertConfig(
            vocab_size=cfg["vocab_size"],
            max_position_embeddings=cfg["max_position_embeddings"],
            dim=cfg["dim"], n_layers=cfg["n_layers"], n_heads=cfg["n_heads"],
            hidden_dim=cfg["hidden_dim"], num_labels=cfg["num_labels"],
            dtype=jnp.dtype(cfg["compute_dtype"]), attn_impl=cfg["attn_impl"],
        )
    )


def loss_fn_of(model):
    from network_distributed_pytorch_tpu.utils.losses import cross_entropy_loss

    def loss_fn(params, model_state, batch):
        logits = model.apply(
            {"params": params}, batch["input_ids"], batch["attention_mask"],
            deterministic=True,
        )
        return cross_entropy_loss(logits, batch["labels"]), model_state

    return loss_fn


def init_fn_of(model, cfg: Dict):
    t = cfg["seq_len"]
    return lambda key: model.init(
        key, jnp.zeros((1, t), jnp.int32), jnp.ones((1, t), jnp.int32)
    )["params"]


def step_of(cfg: Dict, seed: int, mesh, params):
    """(experiment config, jitted step) for parameters or their shapes."""
    exp = compose.experiment_config(cfg, seed, mesh.size)
    step = compose.make_step(
        loss_fn_of(model_of(cfg)), compose.make_reducer(cfg, exp), params, cfg, mesh
    )
    return exp, step


def abstract(cfg: Dict, seed: int, mesh):
    """The step with the shapes of its state and of one batch, nothing on a
    device: what an AOT compile for a described chip needs."""
    import jax

    params = jax.eval_shape(init_fn_of(model_of(cfg), cfg), jax.random.PRNGKey(seed))
    exp, step = step_of(cfg, seed, mesh, params)
    n, t = exp.global_batch_size, cfg["seq_len"]
    batch = {
        "input_ids": jax.ShapeDtypeStruct((n, t), jnp.int32),
        "attention_mask": jax.ShapeDtypeStruct((n, t), jnp.int32),
        "labels": jax.ShapeDtypeStruct((n,), jnp.int32),
    }
    return step, jax.eval_shape(step.init_state, params), batch


def build(cfg: Dict, seed: int, mesh) -> compose.Built:
    from network_distributed_pytorch_tpu.experiments.common import accumulated_batches

    params = compose.init_on_device(init_fn_of(model_of(cfg), cfg), seed)
    exp, step = step_of(cfg, seed, mesh, params)
    state = step.init_state(params)
    pool = traffic.token_sequences(
        {**cfg["traffic"], "seq_len": cfg["seq_len"]}, cfg["vocab_size"], seed
    )
    keys = ("input_ids", "attention_mask", "labels")
    batches = accumulated_batches([pool[k] for k in keys], exp, keys=keys)
    return compose.Built(step, state, compose.endless(batches), pool, exp.global_batch_size)
