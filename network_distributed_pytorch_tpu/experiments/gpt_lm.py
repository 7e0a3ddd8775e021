"""GPT LM pretraining with compressed data parallelism (beyond parity).

The reference's flagship pairing is "transformer fine-tune + PowerSGD"
(``ddp_powersgd_distillBERT_IMDb``); this experiment extends the pairing to
the framework's decoder family: a GPT LM trained data-parallel with any
reducer (default PowerSGD, the reference's algorithm) on a synthetic
next-token corpus — cyclic sequences with noise tokens, fully learnable, no
dataset download (the same synthetic-fallback policy as the CIFAR
experiments).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import gpt_small, gpt_tiny, next_token_loss
from ..parallel import ExactReducer, PowerSGDReducer, make_mesh
from ..parallel.trainer import make_train_step, stateless_loss
from ..utils.config import ExperimentConfig
from .common import summarize, train_loop


def synthetic_lm_batches(
    vocab: int, batch: int, seq_len: int, steps: int, seed: int
):
    """Deterministic cyclic sequences (next token fully predictable) with a
    random starting offset per row — already shifted into (inputs, labels)."""
    rng = np.random.RandomState(seed)
    for _ in range(steps):
        start = rng.randint(0, vocab, (batch, 1))
        toks = (start + np.arange(seq_len + 1)[None, :]) % vocab
        toks = jnp.asarray(toks, jnp.int32)
        yield toks[:, :-1], toks[:, 1:]


def run(
    config: Optional[ExperimentConfig] = None,
    preset: str = "small",
    mesh=None,
    reducer: str = "powersgd",
    seq_len: int = 64,
    steps_per_epoch: int = 20,
    max_steps_per_epoch: Optional[int] = None,
    remat: bool = False,
    scan_layers: bool = False,
) -> Dict:
    config = config or ExperimentConfig(
        training_epochs=1, global_batch_size=32, learning_rate=0.1,
        reducer_rank=4,
    )
    mesh = mesh or make_mesh()
    if max_steps_per_epoch is not None:
        steps_per_epoch = min(steps_per_epoch, max_steps_per_epoch)

    vocab = 64 if preset == "small" else 1024
    make = gpt_tiny if preset == "small" else gpt_small
    model = make(
        vocab_size=vocab, max_position_embeddings=seq_len,
        dtype=jnp.dtype(config.compute_dtype), remat=remat,
        scan_layers=scan_layers,
        # None = keep the model default ("auto": flash on TPU, einsum off)
        **({} if config.attn_impl is None else {"attn_impl": config.attn_impl}),
    )
    ids = jnp.zeros((1, seq_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(config.seed), ids)["params"]

    def loss_fn(p, b):
        x, y = b
        return next_token_loss(model.apply({"params": p}, x), y)

    reducers = {
        "powersgd": lambda: PowerSGDReducer(
            random_seed=config.seed, compression_rank=config.reducer_rank,
            matricize="last",
        ),
        "exact": ExactReducer,
    }
    reducer_obj = reducers[reducer]()
    step = make_train_step(
        stateless_loss(loss_fn), reducer_obj, params,
        learning_rate=config.learning_rate, momentum=config.momentum,
        algorithm="ef_momentum" if reducer == "powersgd" else "sgd",
        mesh=mesh, donate_state=False,
    )
    state = step.init_state(params)

    batches = lambda epoch: synthetic_lm_batches(
        vocab, config.global_batch_size, seq_len, steps_per_epoch,
        config.seed + epoch,
    )
    state, logger = train_loop(
        step, state, batches, config.training_epochs,
        rank=config.process_id, log_every=config.log_every,
    )
    return summarize(
        "gpt_lm",
        logger,
        {
            "reducer": reducer, "vocab": vocab, "seq_len": seq_len,
        },
        perplexity=True,
        reducer=reducer_obj,
        attn_impl=model.config.attn_impl,
        state=state,
    )
