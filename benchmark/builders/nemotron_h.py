"""Nemotron-H language model under a gradient reducer, composed as
``experiments/powersgd_nemotron.run`` composes it. The configuration file
keeps HuggingFace's keys; ``model_of`` is where they meet the program's."""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from .. import compose, lm_traffic


def model_of(cfg: Dict):
    from network_distributed_pytorch_tpu.models.nemotron_h import NemotronHConfig, NemotronHLM

    return NemotronHLM(
        NemotronHConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            pattern=cfg["hybrid_override_pattern"], norm_eps=cfg["norm_eps"],
            mamba_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
            mamba_groups=cfg["n_groups"], state_size=cfg["ssm_state_size"],
            conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
            time_step_min=cfg["time_step_min"], time_step_max=cfg["time_step_max"],
            time_step_floor=cfg["time_step_floor"],
            n_routed_experts=cfg["router_width"], held_experts=tuple(cfg["held_experts"]),
            experts_per_token=cfg["num_experts_per_tok"], routed_scaling=cfg["routed_scaling_factor"],
            expert_width=cfg["moe_intermediate_size"],
            shared_expert_width=cfg["moe_shared_expert_intermediate_size"],
            n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], attn_impl=cfg["attn_impl"],
            dtype=jnp.dtype(cfg["compute_dtype"]), remat=cfg["remat"],
        )
    )


def init_fn_of(model, cfg: Dict):
    return lambda key: model.init(key, jnp.zeros((1, cfg["seq_len"]), jnp.int32))["params"]


def step_of(cfg: Dict, seed: int, mesh, params):
    """(experiment config, model, jitted step) for parameters or their shapes."""
    from network_distributed_pytorch_tpu.models.nemotron_h import next_token_lm_loss

    model = model_of(cfg)
    exp = compose.experiment_config(cfg, seed, mesh.size)
    step = compose.make_step(
        next_token_lm_loss(model), compose.make_reducer(cfg, exp), params, cfg, mesh
    )
    return exp, model, step


def init_state(step, model, params):
    from network_distributed_pytorch_tpu.models.nemotron_h import zero_counters
    from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

    return step.init_state(params, model_state={STEP_COUNTERS: zero_counters(model.config)})


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
