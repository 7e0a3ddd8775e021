"""afmoe (Trinity) language model under a gradient reducer, composed as
``experiments/powersgd_afmoe.run`` composes it. The configuration file keeps
HuggingFace's keys; ``model_of`` is where they meet the program's. What a
language-model builder does besides is ``builders/nemotron_h.py``'s, but
for the model's ``buffers``: each expert layer's ``expert_bias``, balanced on
the pool's first batch as the experiment balances it on its own, since the
weights come from ``--seed`` and not from a run in training that kept them
so (the configuration's ``assumed``)."""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from .. import compose, lm_traffic
from .nemotron_h import init_fn_of


BALANCED_ON = 4  # sequences of the pool the expert_bias is balanced on (the configuration's ``assumed``)


def model_of(cfg: Dict):
    from network_distributed_pytorch_tpu.models.afmoe import AfmoeConfig, AfmoeLM

    return AfmoeLM(
        AfmoeConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            layer_types=tuple(cfg["layer_types"]), num_dense_layers=cfg["num_dense_layers"],
            norm_eps=cfg["rms_norm_eps"], mup_enabled=cfg["mup_enabled"],
            n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
            rope_theta=float(cfg["rope_theta"]), attn_impl=cfg["attn_impl"],
            dense_width=cfg["intermediate_size"], expert_width=cfg["moe_intermediate_size"],
            n_shared_experts=cfg["num_shared_experts"], n_routed_experts=cfg["router_width"],
            held_experts=tuple(cfg["held_experts"]), experts_per_token=cfg["num_experts_per_tok"],
            route_scale=cfg["route_scale"], dtype=jnp.dtype(cfg["compute_dtype"]), remat=cfg["remat"],
        )
    )


def step_of(cfg: Dict, seed: int, mesh, params):
    """(experiment config, model, jitted step) for parameters or their shapes."""
    from network_distributed_pytorch_tpu.models.nemotron_h import next_token_lm_loss

    model = model_of(cfg)
    exp = compose.experiment_config(cfg, seed, mesh.size)
    step = compose.make_step(
        next_token_lm_loss(model), compose.make_reducer(cfg, exp), params, cfg, mesh
    )
    return exp, model, step


def init_state(step, model, params, buffers):
    from network_distributed_pytorch_tpu.models.afmoe import BUFFERS
    from network_distributed_pytorch_tpu.models.nemotron_h import zero_counters
    from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

    return step.init_state(
        params, model_state={STEP_COUNTERS: zero_counters(model.config), BUFFERS: buffers}
    )


def abstract(cfg: Dict, seed: int, mesh):
    """The step with the shapes of its state and of one batch, nothing on a
    device: what an AOT compile for a described chip needs."""
    import jax

    from network_distributed_pytorch_tpu.models.afmoe import BUFFERS

    variables = jax.eval_shape(
        model_of(cfg).init, jax.random.PRNGKey(seed), jnp.zeros((1, cfg["seq_len"]), jnp.int32)
    )
    exp, model, step = step_of(cfg, seed, mesh, variables["params"])
    tokens = jax.ShapeDtypeStruct((exp.global_batch_size, cfg["seq_len"]), jnp.int32)
    state = jax.eval_shape(lambda v: init_state(step, model, v["params"], v[BUFFERS]), variables)
    return step, state, {"input_ids": tokens, "labels": tokens}


def build(cfg: Dict, seed: int, mesh) -> compose.Built:
    from network_distributed_pytorch_tpu.experiments.common import accumulated_batches
    from network_distributed_pytorch_tpu.models.afmoe import balanced_expert_bias

    params = compose.init_on_device(init_fn_of(model_of(cfg), cfg), seed)
    exp, model, step = step_of(cfg, seed, mesh, params)
    pool = lm_traffic.lm_sequences(
        {**cfg["traffic"], "seq_len": cfg["seq_len"]}, cfg["vocab_size"], seed
    )
    buffers = balanced_expert_bias(model, params, pool["input_ids"][:BALANCED_ON])
    state = init_state(step, model, params, buffers)
    keys = ("input_ids", "labels")
    batches = accumulated_batches([pool[k] for k in keys], exp, keys=keys)
    return compose.Built(step, state, compose.endless(batches), pool, exp.global_batch_size)
