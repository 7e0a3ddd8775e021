"""DistilBERT sequence classifier, its loss and its gradients, written out
in plain ``jax.numpy``: float32 everywhere, every product under
``jax.default_matmul_precision("highest")``, einsum attention that
materialises the weights, no kernel, no flax.

Follows Sanh et al. 2019 and HuggingFace's ``DistilBertForSequenceClassification``:
word + position embeddings -> LayerNorm(1e-12) -> post-LN blocks (multi-head
self-attention, residual, LayerNorm; Linear-GELU(erf)-Linear, residual,
LayerNorm) -> first token -> Linear -> ReLU -> Linear -> cross-entropy, mean
over the batch. Departure: dropout is off, as in the system's run.

It reads the system's parameter tree (names as ``models/distilbert.py``
creates them) and nothing else of the program.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

MICRO_BATCH = 8  # sequences per pass, so fp32 attention weights fit beside the system's state


def _layer_norm(x, p, eps=1e-12):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def logits_of(params, input_ids, attention_mask, n_heads: int):
    p = params["distilbert"]
    b, t = input_ids.shape
    x = p["word_embeddings"]["embedding"][input_ids]
    x = x + p["position_embeddings"]["embedding"][jnp.arange(t)][None]
    x = _layer_norm(x, p["embed_layer_norm"])
    bias = jnp.where(attention_mask > 0, 0.0, -jnp.inf)[:, None, None, :]
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        lp = p[f"layer_{i}"]
        a = lp["attention"]
        d = x.shape[-1]
        hd = d // n_heads
        split = lambda y: y.reshape(b, t, n_heads, hd)
        q, k, v = (split(_dense(x, a[n])) for n in ("q_lin", "k_lin", "v_lin"))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd) + bias
        weights = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, d)
        x = _layer_norm(x + _dense(ctx, a["out_lin"]), lp["sa_layer_norm"])
        h = _dense(jax.nn.gelu(_dense(x, lp["ffn_lin1"]), approximate=False), lp["ffn_lin2"])
        x = _layer_norm(x + h, lp["output_layer_norm"])
    pooled = jax.nn.relu(_dense(x[:, 0], params["pre_classifier"]))
    return _dense(pooled, params["classifier"])


def _loss(params, batch, n_heads):
    logits = logits_of(params, batch["input_ids"], batch["attention_mask"], n_heads)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1))


def make_loss_and_grads(cfg: Dict):
    """``(params, model_state, worker_batch) -> (loss, grads, model_state)``
    for one worker's batch, in micro-batches whose gradients are averaged
    (equal sizes, a mean loss: the same gradient as the whole batch)."""
    n_heads = cfg["n_heads"]

    @jax.jit
    def one(params, batch):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(_loss)(params, batch, n_heads)

    def loss_and_grads(params, model_state, batch):
        n = batch["labels"].shape[0]
        size = math.gcd(n, MICRO_BATCH)
        total, count = None, n // size
        for i in range(count):
            part = {k: v[i * size:(i + 1) * size] for k, v in batch.items()}
            out = one(params, part)
            total = out if total is None else jax.tree_util.tree_map(jnp.add, total, out)
        loss, grads = jax.tree_util.tree_map(lambda x: x / count, total)
        return loss, grads, model_state

    return loss_and_grads
