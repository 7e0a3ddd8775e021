"""Phi-4-mini-flash-reasoning language model under a gradient reducer,
composed as ``experiments/powersgd_phi4flash.run`` composes it. The
configuration file keeps HuggingFace's keys; ``model_of`` is where they meet
the program's. What a language-model builder does besides is
``builders/nemotron_h.py``'s: the model has no expert layer, so the counters'
tree its state carries is empty."""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from .. import compose, lm_traffic
from .nemotron_h import init_fn_of, init_state


def model_of(cfg: Dict):
    from network_distributed_pytorch_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashLM

    return Phi4FlashLM(
        Phi4FlashConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            layer_indices=tuple(cfg["layer_indices"]), n_published_layers=cfg["published"]["num_hidden_layers"],
            norm_eps=cfg["layer_norm_eps"], mlp_width=cfg["intermediate_size"],
            n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],  # as HuggingFace takes it: the row gives none
            sliding_window=cfg["sliding_window"], attn_impl=cfg["attn_impl"],
            d_inner=cfg["mamba_expand"] * cfg["hidden_size"], state_size=cfg["mamba_d_state"],
            conv_kernel=cfg["mamba_d_conv"], dt_rank=cfg["mamba_dt_rank"],
            dtype=jnp.dtype(cfg["compute_dtype"]), remat=cfg["remat"],
        )
    )


def step_of(cfg: Dict, seed: int, mesh, params):
    """(experiment config, model, jitted step) for parameters or their shapes."""
    from network_distributed_pytorch_tpu.models.layers import next_token_lm_loss

    model = model_of(cfg)
    exp = compose.experiment_config(cfg, seed, mesh.size)
    step = compose.make_step(
        next_token_lm_loss(model), compose.make_reducer(cfg, exp), params, cfg, mesh
    )
    return exp, model, step


def abstract(cfg: Dict, seed: int, mesh):
    """The step with the shapes of its state and of one batch, nothing on a
    device: what an AOT compile for a described chip needs."""
    import jax

    params = jax.eval_shape(init_fn_of(model_of(cfg), cfg), jax.random.PRNGKey(seed))
    exp, model, step = step_of(cfg, seed, mesh, params)
    tokens = jax.ShapeDtypeStruct((exp.global_batch_size, cfg["seq_len"]), jnp.int32)
    state = jax.eval_shape(lambda p: init_state(step, model, p), params)
    return step, state, {"input_ids": tokens, "labels": tokens}


def build(cfg: Dict, seed: int, mesh) -> compose.Built:
    from network_distributed_pytorch_tpu.experiments.common import accumulated_batches

    params = compose.init_on_device(init_fn_of(model_of(cfg), cfg), seed)
    exp, model, step = step_of(cfg, seed, mesh, params)
    state = init_state(step, model, params)
    pool = lm_traffic.lm_sequences(
        {**cfg["traffic"], "seq_len": cfg["seq_len"]}, cfg["vocab_size"], seed
    )
    keys = ("input_ids", "labels")
    batches = accumulated_batches([pool[k] for k in keys], exp, keys=keys)
    return compose.Built(step, state, compose.endless(batches), pool, exp.global_batch_size)
