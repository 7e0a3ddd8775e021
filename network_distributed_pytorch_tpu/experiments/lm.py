"""What the seven language-model experiments share (``LM_EXPERIMENTS``):
``train_lm``, the experiment without its model, and the config each starts
from. An experiment's file builds its model and hands it here; none imports
another.

Composed like ``powersgd_imdb.run``: the same Algorithm-2 jitted step
(``make_train_step`` with ``PowerSGDReducer``, rank 16, EF-SGD lr 5e-5 λ=.9,
``matricize="last"`` so a stacked ``(experts, in, out)`` leaf compresses as
one ``(experts*in, out)`` matrix), the same ``train_loop``. Batches are
dicts of packed token ids and their next-token labels unless the caller
brings its own loss and the batches it takes (``powersgd_sdar``: a noised
copy and loss weights); the expert layers' counters ride ``model_state``
(``parallel.trainer.STEP_COUNTERS``) and land on every step's
``step/loss_sync`` span.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.layers import next_token_lm_loss, zero_counters
from ..parallel import PowerSGDReducer, make_mesh
from ..parallel.trainer import STEP_COUNTERS, make_train_step
from ..utils.config import ExperimentConfig
from .common import accumulated_batches, powersgd_reducer_kwargs, summarize, train_loop

# the experiments built on train_lm, by their names in launch.EXPERIMENTS
LM_EXPERIMENTS = (
    "powersgd_nemotron", "powersgd_afmoe", "powersgd_qwen3_next", "powersgd_lfm2", "powersgd_mellum",
    "powersgd_phi4flash", "powersgd_sdar",
)


def default_config() -> ExperimentConfig:
    return ExperimentConfig(
        training_epochs=1,
        learning_rate=5e-5,
        reducer_rank=16,
        global_batch_size=0,  # train_lm sets it: one sequence per worker
    )


def model_kwargs(config: ExperimentConfig) -> Dict:
    """What the experiment's config says of the model: its compute dtype
    and, where set, its attention engine."""
    attn = {} if config.attn_impl is None else {"attn_impl": config.attn_impl}
    return {"dtype": jnp.dtype(config.compute_dtype), **attn}


def next_token_batches(ids: np.ndarray, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """Sequences of ``seq_len + 1`` ids -> what ``next_token_lm_loss`` takes:
    the first ``seq_len`` and, as labels, the ids one on."""
    return {"input_ids": ids[:, :-1].copy(), "labels": ids[:, 1:].copy()}


def train_lm(
    run_name: str,
    model,
    config: ExperimentConfig,
    mesh,
    seq_len: int,
    pool_sequences: int,
    max_steps_per_epoch: Optional[int],
    summary: Dict,
    collections_of: Optional[Callable] = None,
    loss_of: Callable = next_token_lm_loss,
    batches_of: Callable = next_token_batches,
    drawn_ids: Optional[int] = None,
) -> Dict:
    """``model`` under PowerSGD through ``make_train_step`` and
    ``train_loop`` on synthetic packed sequences; ``summary`` is what the
    caller wants said of its model in the result. ``collections_of(params,
    ids)`` gives the model's variable collections beside its parameters
    (``powersgd_afmoe``'s balanced ``buffers``) from the ids of the pool's
    first sequences; they ride ``model_state``. ``loss_of(model)`` is the
    trainer's loss function and ``batches_of(ids, rng)`` the pool of batches
    it takes, as a dict of arrays a sample a row, made of the pool's ``seq_len
    + 1`` ids a sample; the ids are the vocabulary's first ``drawn_ids`` (all
    of them where ``None``)."""
    mesh = mesh or make_mesh()
    if not config.global_batch_size:
        config.global_batch_size = mesh.size
    vocab = drawn_ids or model.config.vocab_size

    # synthetic packed sequences (no corpus ships with the repo): Zipf ids;
    # the pool holds at least two steps of whatever batch the caller set
    pool_sequences = max(pool_sequences, 2 * config.global_batch_size)
    rng = np.random.default_rng(config.seed)
    ranks = np.minimum(rng.zipf(1.2, (pool_sequences, seq_len + 1)) - 1, vocab - 1)
    ids = rng.permutation(vocab).astype(np.int32)[ranks]

    params = model.init(
        jax.random.PRNGKey(config.seed), jnp.zeros((1, seq_len), jnp.int32)
    )["params"]
    reducer = PowerSGDReducer(
        random_seed=config.seed,
        compression_rank=config.reducer_rank,
        reuse_query=config.reuse_query,
        matricize="last",
        **powersgd_reducer_kwargs(config),
    )
    step = make_train_step(
        loss_of(model),
        reducer,
        params,
        learning_rate=config.learning_rate,
        momentum=config.momentum,
        algorithm="ef_momentum",
        mesh=mesh,
    )
    collections = collections_of(params, ids[:4, :-1]) if collections_of else {}
    state = step.init_state(
        params,
        model_state={STEP_COUNTERS: zero_counters(model.config, getattr(loss_of, "counters", ())), **collections},
    )
    pool = batches_of(ids, rng)
    batches = accumulated_batches(
        list(pool.values()), config, max_steps_per_epoch=max_steps_per_epoch, keys=tuple(pool),
    )
    from ..observe import audit_from_config, telemetry_from_config

    telemetry = telemetry_from_config(config)
    try:
        state, logger = train_loop(
            step, state, batches, config.training_epochs,
            rank=config.process_id, log_every=config.log_every,
            telemetry=telemetry,
            trace_dir=config.trace_dir,
            audit=audit_from_config(config),
            run_name=run_name,
        )
    finally:
        telemetry.close()
    counters = jax.device_get(state.model_state[STEP_COUNTERS])
    return summarize(
        run_name,
        logger,
        {
            **summary,
            "reducer_rank": config.reducer_rank,
            "seq_len": seq_len,
            # the last step's counters, summed over workers and expert layers
            "last_step_assignments": {
                k: int(sum(np.sum(c[k]) for c in counters.values()))
                for k in ("held", "absent", "dropped")
            },
        },
        reducer=reducer,
        attn_impl=model.config.attn_impl,
        state=state,
    )
