"""SDAR block-diffusion language model under a gradient reducer, composed as
``experiments/powersgd_sdar.run`` composes it: the masked-token loss over
``[noised ; clean]`` rows, every pool sample noised once from ``--seed``. The
configuration file keeps HuggingFace's keys; ``model_of`` is where they meet
the program's. ``text_len`` is a sample's tokens, ``seq_len`` = 2 x
``text_len`` the rows a layer sees."""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from .. import compose, lm_traffic
from .nemotron_h import init_fn_of

KEYS = ("input_ids", "noisy_ids", "loss_weight")


def model_of(cfg: Dict):
    from network_distributed_pytorch_tpu.models.layers import Rope
    from network_distributed_pytorch_tpu.models.sdar import SdarConfig, SdarLM

    return SdarLM(
        SdarConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
            norm_eps=cfg["rms_norm_eps"], block_length=cfg["block_length"],
            n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            rope=Rope(float(cfg["rope_theta"])), attn_impl=cfg["attn_impl"],
            expert_width=cfg["moe_intermediate_size"], n_routed_experts=cfg["router_width"],
            held_experts=tuple(cfg["held_experts"]), experts_per_token=cfg["num_experts_per_tok"],
            dtype=jnp.dtype(cfg["compute_dtype"]), remat=cfg["remat"],
        )
    )


def step_of(cfg: Dict, seed: int, mesh, params):
    """(experiment config, model, jitted step) for parameters or their shapes."""
    from network_distributed_pytorch_tpu.models.layers import masked_token_loss

    model = model_of(cfg)
    exp = compose.experiment_config(cfg, seed, mesh.size)
    step = compose.make_step(masked_token_loss(model), compose.make_reducer(cfg, exp), params, cfg, mesh)
    return exp, model, step


def init_state(step, model, params):
    from network_distributed_pytorch_tpu.models.layers import masked_token_loss, zero_counters
    from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

    return step.init_state(
        params, model_state={STEP_COUNTERS: zero_counters(model.config, masked_token_loss.counters)}
    )


def noised_pool(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The cell's pool: ``pool_samples`` sequences of ``text_len`` Zipf ids over
    the slice without ``[MASK]``, each noised once from the seed."""
    from network_distributed_pytorch_tpu.data.noising import block_noised

    assert cfg["seq_len"] == 2 * cfg["text_len"] and cfg["mask_token_id"] == cfg["vocab_size"] - 1, cfg
    ids = lm_traffic.lm_sequences({**cfg["traffic"], "seq_len": cfg["text_len"]}, cfg["mask_token_id"], seed)["input_ids"]
    # a generator of its own: lm_sequences keeps its draws whatever the noising takes
    rng = np.random.default_rng([seed, 1])
    return block_noised(ids, cfg["block_length"], float(cfg["traffic"]["noise_floor"]), cfg["mask_token_id"], rng)


def abstract(cfg: Dict, seed: int, mesh):
    """The step with the shapes of its state and of one batch, nothing on a
    device: what an AOT compile for a described chip needs."""
    import jax

    params = jax.eval_shape(init_fn_of(model_of(cfg), cfg), jax.random.PRNGKey(seed))
    exp, model, step = step_of(cfg, seed, mesh, params)
    tokens = jax.ShapeDtypeStruct((exp.global_batch_size, cfg["text_len"]), jnp.int32)
    weights = jax.ShapeDtypeStruct(tokens.shape, jnp.float32)
    state = jax.eval_shape(lambda p: init_state(step, model, p), params)
    return step, state, {"input_ids": tokens, "noisy_ids": tokens, "loss_weight": weights}


def build(cfg: Dict, seed: int, mesh) -> compose.Built:
    from network_distributed_pytorch_tpu.experiments.common import accumulated_batches

    params = compose.init_on_device(init_fn_of(model_of(cfg), cfg), seed)
    exp, model, step = step_of(cfg, seed, mesh, params)
    state = init_state(step, model, params)
    pool = noised_pool(cfg, seed)
    batches = accumulated_batches([pool[k] for k in KEYS], exp, keys=KEYS)
    return compose.Built(step, state, compose.endless(batches), pool, exp.global_batch_size)
