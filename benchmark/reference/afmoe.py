"""afmoe's (Trinity's) next-token loss and its gradients, written out in plain
``jax.numpy``: float32 everywhere, every product under
``jax.default_matmul_precision("highest")``, no kernel, no flax, nothing of
the program imported.

Follows HuggingFace's ``modeling_afmoe.py`` (``AfmoeForCausalLM``); ``h`` is
``hidden_size``, every norm an RMSNorm with a learned scale and
``rms_norm_eps``:

- embedding: ``x = E[ids] * sqrt(h)`` (``mup_enabled``); after the last block
  RMSNorm, then the untied head; the loss is the mean cross-entropy of the
  labels (the ids shifted by one) over the vocabulary held here.
- block: ``x <- x + N2(attn(N1(x)))``, then ``x <- x + N4(ffn(N3(x)))``.
- attention: ``q = RMSNorm_head(W_q u)``, ``k = RMSNorm_head(W_k u)``, ``v =
  W_v u``, ``g = W_g u``; in a ``sliding_attention`` layer q and k are turned
  by the rotary embedding (``rope_theta``, the whole head, ``x cos +
  rotate_half(x) sin`` with the halves paired i and i + D/2) and query i sees
  key j iff ``0 <= i - j < sliding_window``, a plain comparison of positions;
  in a ``full_attention`` layer nothing is turned and i sees j iff ``j <=
  i``. ``o = softmax(q k^T / sqrt(head_dim)) v`` with the weights
  materialised, in blocks of queries; ``out = W_o (o * sigmoid(g))``.
- dense feed-forward (the first ``num_dense_layers`` layers): ``W_d (silu(W_g
  u) * W_u u)``.
- expert feed-forward: ``s = sigmoid(u W_r)`` over all experts; the
  ``num_experts_per_tok`` largest of ``s + expert_bias``; ``w_i = route_scale
  * s_i / sum_topk s``; ``y = sum_{i in topk, i held} w_i E_i(u) +
  E_shared(u)``, every ``E`` the gated form, by a plain loop over the held
  experts, each over every token with its weight (zero where not chosen).
  ``expert_bias`` is a buffer: the model state's ``buffers`` as the system
  holds them (``[layer]["mlp"]["expert_bias"]``), zeros where it holds none.
  How many assignments each held expert took, and how many went to absent
  experts, come back as the model state's ``step_counters``, the system's
  counters by the reference's own routing.

Departures from the published model. What the absent experts would add is
left out, as in the system (the model-configs guide, section 4). HuggingFace
divides by ``sum_topk s + 1e-20``; the 1e-20 is below fp32's resolution of a
sum of eight sigmoids and is left out. Positions run 0..T-1 over the packed
sequence (no document mask, no position reset). No auxiliary load-balance
loss (``load_balance_coeff`` unused): the training loss is the cross-entropy
alone.

It reads the system's parameter tree (names as ``models/afmoe.py`` creates
them) and the configuration file's keys.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .nemotron_h import QUERY_BLOCK, release_host_memory
from .nemotron_h import _rms_norm as _rms_norm_scaled


def _rms_norm(x, p, eps):
    return _rms_norm_scaled(x, p["scale"], eps)


def _rotary(x, theta):
    """x (T, heads, D): position t turned by the angles t * theta^(-2i/D)."""
    t, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(u, p, cfg, sliding):
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, t = cfg["rms_norm_eps"], u.shape[0]
    q = _rms_norm((u @ p["q_proj"]["kernel"]).reshape(t, hq, hd), p["q_norm"], eps)
    k = _rms_norm((u @ p["k_proj"]["kernel"]).reshape(t, hkv, hd), p["k_norm"], eps)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, hkv, hd)
    if sliding:
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    q = q.reshape(t, hkv, hq // hkv, hd)  # query heads by their key/value head
    block = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / math.sqrt(hd)
        behind = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]  # query - key
        seen = behind >= 0
        if sliding:
            seen = seen & (behind < cfg["sliding_window"])
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", weights, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, t, block)).reshape(t, hq * hd)
    return (ctx * jax.nn.sigmoid(u @ p["gate_proj"]["kernel"])) @ p["o_proj"]["kernel"]


def _gated(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _gated_mlp(u, p):
    return _gated(u, p["gate_proj"]["kernel"], p["up_proj"]["kernel"], p["down_proj"]["kernel"])


def _experts(u, p, cfg, expert_bias=0.0):
    """-> (the layer's output, its counters)."""
    scores = jax.nn.sigmoid(u @ p["router"])  # (T, all experts)
    _, chosen = jax.lax.top_k(scores + expert_bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = cfg["route_scale"] * picked / jnp.sum(picked, axis=-1, keepdims=True)
    out = _gated_mlp(u, p["shared"])
    for slot, expert in enumerate(cfg["held_experts"]):
        weight = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)  # 0 where not chosen
        out = out + weight[:, None] * _gated(
            u, p["experts_gate"][slot], p["experts_up"][slot], p["experts_down"][slot]
        )
    held = jnp.stack([jnp.sum(chosen == expert) for expert in cfg["held_experts"]])
    return out, {"held": held, "absent": chosen.size - jnp.sum(held)}


def _block(x, p, cfg, sliding, dense, expert_bias):
    """-> (the block's output, its expert layer's counters: none in a dense block)."""
    eps = cfg["rms_norm_eps"]
    attended = _attention(_rms_norm(x, p["input_layernorm"], eps), p["self_attn"], cfg, sliding)
    x = x + _rms_norm(attended, p["post_attention_layernorm"], eps)
    u = _rms_norm(x, p["pre_mlp_layernorm"], eps)
    out, counters = (_gated_mlp(u, p["mlp"]), {}) if dense else _experts(u, p["mlp"], cfg, expert_bias)
    return x + _rms_norm(out, p["post_mlp_layernorm"], eps), counters


def _forward(params, buffers, ids, cfg):
    """One sequence's ids (T,) -> logits (T, vocabulary held here) and the
    expert layers' counters."""
    x = params["embed"]["embedding"][ids] * math.sqrt(cfg["hidden_size"])
    counters = {}
    for i, kind in enumerate(cfg["layer_types"]):
        name = f"layer_{i}"
        # recomputed in the backward pass: one layer's fp32 activations alive at a time
        layer = jax.checkpoint(
            lambda x, p, bias, sliding=kind == "sliding_attention", dense=i < cfg["num_dense_layers"]:
                _block(x, p, cfg, sliding, dense, bias)
        )
        bias = buffers[name]["mlp"]["expert_bias"] if name in buffers else 0.0
        x, layer_counters = layer(x, params[name], bias)
        if layer_counters:
            counters[name] = layer_counters
    return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]) @ params["head"], counters


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
