"""LFM2's (``lfm2_moe``'s) next-token loss and its gradients, written out in
plain ``jax.numpy``: float32 everywhere, every product under
``jax.default_matmul_precision("highest")``, no kernel, no flax, nothing of
the program imported.

Follows HuggingFace's ``modeling_lfm2_moe.py`` (``Lfm2MoeForCausalLM``); ``h``
is ``hidden_size``, every norm an RMSNorm with a learned scale and
``norm_eps``, no bias anywhere:

- embedding: ``x = E[ids]``; after the last block RMSNorm
  (``embedding_norm``), then the head tied to the embedding, ``logits = x
  E^T``; the loss is the mean cross-entropy of the labels (the ids shifted by
  one) over the vocabulary held here.
- block: ``x <- x + mixer(N1(x))``, then ``x <- x + ffn(N2(x))``.
- ``conv``: ``[B | C | z] = u W_in`` in that order; ``y = C * conv(B * z)``
  with ``conv(s)_t = sum_{j < K} w[j] * s_{t-K+1+j}``, the sum over the taps
  as written, zeros before the sequence; ``out = y W_out``. No activation.
- ``full_attention``: ``q = RMSNorm_head(W_q u)``, ``k = RMSNorm_head(W_k
  u)``, ``v = W_v u``; q and k turned by the rotary embedding
  (``rope_parameters.rope_theta``, the whole head, ``x cos + rotate_half(x)
  sin`` with the halves paired i and i + D/2, positions 0..T-1); query i sees
  key j iff ``j <= i``; ``o = softmax(q k^T / sqrt(head_dim)) v`` with the
  weights materialised, in blocks of queries; ``out = W_o o``. ``head_dim``
  is ``hidden_size / num_attention_heads``.
- dense feed-forward (the first ``num_dense_layers`` layers): ``W_2 (silu(W_1
  u) * W_3 u)``.
- expert feed-forward: ``s = sigmoid(u W_r)`` over all experts; the
  ``num_experts_per_tok`` largest of ``s + expert_bias``; ``w_i =
  routed_scaling_factor * s_i / sum_topk s``; ``y = sum_{i in topk, i held}
  w_i E_i(u)``, every ``E`` the gated form, by a plain loop over the held
  experts, each over every token with its weight (zero where not chosen); no
  shared expert. ``expert_bias`` is a buffer: the model state's ``buffers``
  as the system holds them (``[layer]["feed_forward"]["expert_bias"]``),
  zeros where it holds none. How many assignments each held expert took, and
  how many went to absent experts, come back as the model state's
  ``step_counters``, the system's counters by the reference's own routing.

Departures from the published model. What the absent experts would add is
left out, as in the system (the model-configs guide, section 4). HuggingFace
divides by ``sum_topk s + 1e-6``; the 1e-6 is left out here and in the system
(5e-7 of a weight: four sigmoids near a half sum to about 2). The row gives
no ``tie_word_embeddings``: the head is taken tied, as the family publishes
it. Positions run 0..T-1 over the packed sequence (no document mask, no
position reset). No auxiliary loss: the training loss is the cross-entropy
alone.

It reads the system's parameter tree (names as ``models/lfm2.py`` creates
them) and the configuration file's keys.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .afmoe import _gated, _rms_norm, _rotary
from .nemotron_h import QUERY_BLOCK, release_host_memory


def _short_conv(u, p):
    b, c, z = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
    taps, t = p["conv_kernel"], u.shape[0]
    k = taps.shape[0]
    padded = jnp.pad(b * z, ((k - 1, 0), (0, 0)))
    return (c * sum(taps[j] * padded[j:j + t] for j in range(k))) @ p["out_proj"]["kernel"]


def _attention(u, p, cfg):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, theta = cfg["hidden_size"] // hq, float(cfg["rope_parameters"]["rope_theta"])
    eps, t = cfg["norm_eps"], u.shape[0]
    q = _rotary(_rms_norm((u @ p["q_proj"]["kernel"]).reshape(t, hq, hd), p["q_norm"], eps), theta)
    k = _rotary(_rms_norm((u @ p["k_proj"]["kernel"]).reshape(t, hkv, hd), p["k_norm"], eps), theta)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, hkv, hd)
    q = q.reshape(t, hkv, hq // hkv, hd)  # query heads by their key/value head
    block = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / math.sqrt(hd)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]  # key j <= query i
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", weights, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, t, block)).reshape(t, hq * hd)
    return ctx @ p["o_proj"]["kernel"]


def _experts(u, p, cfg, expert_bias=0.0):
    """-> (the layer's output, its counters)."""
    scores = jax.nn.sigmoid(u @ p["router"])  # (T, all experts)
    _, chosen = jax.lax.top_k(scores + expert_bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = cfg["routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)
    out = jnp.zeros_like(u)
    for slot, expert in enumerate(cfg["held_experts"]):
        weight = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)  # 0 where not chosen
        out = out + weight[:, None] * _gated(
            u, p["experts_gate"][slot], p["experts_up"][slot], p["experts_down"][slot]
        )
    held = jnp.stack([jnp.sum(chosen == expert) for expert in cfg["held_experts"]])
    return out, {"held": held, "absent": chosen.size - jnp.sum(held)}


def _block(x, p, cfg, kind, dense, expert_bias):
    """-> (the block's output, its expert layer's counters: none in a dense block)."""
    u = _rms_norm(x, p["operator_norm"], cfg["norm_eps"])
    x = x + (_short_conv(u, p["conv"]) if kind == "conv" else _attention(u, p["self_attn"], cfg))
    u, ffn = _rms_norm(x, p["ffn_norm"], cfg["norm_eps"]), p["feed_forward"]
    if dense:
        kernel = lambda name: ffn[name]["kernel"]
        return x + _gated(u, kernel("gate_proj"), kernel("up_proj"), kernel("down_proj")), {}
    out, counters = _experts(u, ffn, cfg, expert_bias)
    return x + out, counters


def _forward(params, buffers, ids, cfg):
    """One sequence's ids (T,) -> logits (T, vocabulary held here) and the
    expert layers' counters."""
    x = params["embed"]["embedding"][ids]
    counters = {}
    for i, kind in enumerate(cfg["layer_types"]):
        name = f"layer_{i}"
        # recomputed in the backward pass: one layer's fp32 activations alive at a time
        layer = jax.checkpoint(
            lambda x, p, bias, kind=kind, dense=i < cfg["num_dense_layers"]: _block(x, p, cfg, kind, dense, bias)
        )
        bias = buffers[name]["feed_forward"]["expert_bias"] if name in buffers else 0.0
        x, layer_counters = layer(x, params[name], bias)
        if layer_counters:
            counters[name] = layer_counters
    x = _rms_norm(x, params["embedding_norm"], cfg["norm_eps"])
    return x @ params["embed"]["embedding"].T, counters


def _logits(params, ids, cfg):
    return _forward(params, {}, ids, cfg)[0]


def _sequence_loss(params, buffers, ids, labels, cfg):
    logits, counters = _forward(params, buffers, ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1)), counters


def make_loss_and_grads(cfg: Dict):
    """``(params, model_state, worker_batch) -> (loss, grads, model_state)``
    for one worker's batch, one sequence at a time (equal lengths and a mean
    loss: the mean of the sequences' gradients is the batch's)."""
    # as reference/nemotron_h.py: the harness builds this after its window,
    # and the check keeps some fifteen fp32 copies of the parameters on the host
    jax.clear_caches()
    release_host_memory()

    @jax.jit
    def one(params, buffers, ids, labels):
        with jax.default_matmul_precision("highest"):
            (loss, counters), grads = jax.value_and_grad(_sequence_loss, has_aux=True)(
                params, buffers, ids, labels, cfg
            )
        return (loss, grads), counters

    def loss_and_grads(params, model_state, batch):
        add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)
        total, counters, count = None, None, batch["input_ids"].shape[0]
        for ids, labels in zip(batch["input_ids"], batch["labels"]):
            out, routed = one(params, model_state.get("buffers", {}), ids, labels)
            total, counters = (out, routed) if total is None else (add(total, out), add(counters, routed))
        loss, grads = jax.tree_util.tree_map(lambda v: v / count, total)
        release_host_memory()  # the first call compiled: its working memory goes back too
        return loss, grads, {**model_state, "step_counters": counters}

    return loss_and_grads
