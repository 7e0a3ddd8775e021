"""Entry point C — PowerSGD-compressed DistilBERT fine-tuning on IMDb
(the reference's ``ddp_powersgd_distillBERT_IMDb``).

Reference configuration (``ddp_powersgd_distillBERT_IMDb/ddp_init.py``):
DistilBERT-base sequence classifier (``:150``), IMDb with 80/20 split
(``:72``), tokenizer truncation+padding (``:74-77``), per-worker batch 16
(``:89``), PowerSGD rank 16 (``:38,163``), EF-SGD lr 5e-5 λ=.9, 5 epochs.
Same Algorithm-2 jitted step as the CIFAR flagship; batches are HF-style
dicts (input_ids / attention_mask / labels), like the reference's dict
batches (``:184-191``).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..data import prepare_imdb
from ..models.distilbert import distilbert_base, distilbert_tiny
from ..parallel import PowerSGDReducer, make_mesh
from ..parallel.trainer import make_train_step
from ..utils.config import ExperimentConfig
from ..utils.losses import cross_entropy_loss
from .common import (
    accum_batch_sharding,
    accumulated_batches,
    powersgd_reducer_kwargs,
    summarize,
    train_loop,
)


def run(
    config: Optional[ExperimentConfig] = None,
    preset: str = "small",
    data_dir: Optional[str] = None,  # aclImdb root; None → synthetic
    tokenizer=None,
    mesh=None,
    pretrained_variables=None,
    max_len: int = 256,
    max_steps_per_epoch: Optional[int] = None,
    remat: bool = False,
) -> Dict:
    config = config or ExperimentConfig(
        training_epochs=5,  # ddp_init.py:36
        learning_rate=5e-5,  # ddp_init.py:34
        reducer_rank=16,  # ddp_init.py:38
        global_batch_size=0,  # set below: 16 per worker — ddp_init.py:89
    )
    mesh = mesh or make_mesh()
    if not config.global_batch_size:
        config.global_batch_size = 16 * mesh.size  # total_batch = 16 * size

    make = distilbert_base if preset == "full" else distilbert_tiny
    model = make(
        num_labels=2, dtype=jnp.dtype(config.compute_dtype), remat=remat,
        # None = keep the model default ("auto": flash on TPU, einsum off)
        **({} if config.attn_impl is None else {"attn_impl": config.attn_impl}),
    )
    vocab = model.config.vocab_size
    max_len = min(max_len, model.config.max_position_embeddings)

    train_split, _val_split, is_real = prepare_imdb(
        data_dir=data_dir, tokenizer=tokenizer, max_len=max_len,
        vocab_size=vocab, seed=config.seed,
    )

    if pretrained_variables is None:
        variables = model.init(
            jax.random.PRNGKey(config.seed),
            jnp.zeros((1, max_len), jnp.int32),
            jnp.ones((1, max_len), jnp.int32),
        )
    else:
        variables = pretrained_variables  # models.import_weights.distilbert_variables_from_torch
    params = variables["params"]

    def loss_fn(params, model_state, batch):
        # HF-style: loss from labels (the reference's outputs[0] — :186-190);
        # dropout is deterministic here (functional purity; the stochastic-
        # regularization difference does not affect the comm path under study)
        logits = model.apply(
            {"params": params},
            batch["input_ids"],
            batch["attention_mask"],
            deterministic=True,
        )
        return cross_entropy_loss(logits, batch["labels"]), model_state

    reducer = PowerSGDReducer(
        random_seed=config.seed,
        compression_rank=config.reducer_rank,
        reuse_query=config.reuse_query,
        matricize="last",
        **powersgd_reducer_kwargs(config),
    )
    step = make_train_step(
        loss_fn,
        reducer,
        params,
        learning_rate=config.learning_rate,
        momentum=config.momentum,
        algorithm="ef_momentum",
        mesh=mesh,
        accum_steps=config.accum_steps,
        max_grad_norm=config.max_grad_norm,
    )
    state = step.init_state(params)

    arrays = [train_split["input_ids"], train_split["attention_mask"], train_split["labels"]]
    batches = accumulated_batches(
        arrays, config, max_steps_per_epoch=max_steps_per_epoch,
        keys=("input_ids", "attention_mask", "labels"),
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
            run_name="powersgd_imdb",
        )
    finally:
        telemetry.close()
    return summarize(
        "powersgd_imdb",
        logger,
        {
            "preset": preset,
            "real_data": is_real,
            "reducer_rank": config.reducer_rank,
            "model": {
                k: getattr(model.config, k)
                for k in ("n_layers", "dim", "n_heads", "hidden_dim", "vocab_size")
            },
            "seq_len": max_len,
        },
        reducer=reducer,
        attn_impl=model.config.attn_impl,
        state=state,
    )
