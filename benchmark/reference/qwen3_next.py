"""Qwen3-Next's next-token loss and its gradients, written out in plain
``jax.numpy``: float32 everywhere, every product under
``jax.default_matmul_precision("highest")``, no kernel, no flax, nothing of
the program imported.

Follows HuggingFace's ``modeling_qwen3_next.py`` (``Qwen3NextForCausalLM``).
``N(x) = x rsqrt(mean x^2 + rms_norm_eps) (1 + w)``, the zero-centred RMSNorm:

- block ``i``: ``x <- x + mixer_i(N(x))``, ``x <- x + moe(N(x))``; the mixer
  by ``layer_types``; after the last block ``N``, then the untied head; the
  loss is the mean cross-entropy of the labels (the ids shifted by one) over
  the vocabulary held here.
- ``linear_attention`` (Gated DeltaNet; ``H_k`` key heads, ``H_v`` value
  heads, ``r = H_v / H_k``): ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u
  W_ba``, both in HuggingFace's grouped column order (per key head its q, its
  k, then the v and the z of its ``r`` value heads; its ``r`` b's, then its
  ``r`` a's); ``[q | k | v] <- silu(conv([q | k | v]))``, causal, depthwise,
  no bias; ``beta = sigmoid(b)``; ``g = -exp(A_log) softplus(a + dt_bias)``;
  q and k repeated to the value heads (head ``h`` reads key head ``h // r``);
  ``q <- q rsqrt(sum q^2 + 1e-6) / sqrt(d_k)``, ``k <- k rsqrt(sum k^2 +
  1e-6)``; per value head with state ``S`` (d_k, d_v) from zero: ``S <-
  exp(g_t) S``, ``d_t = beta_t (v_t - S^T k_t)``, ``S <- S + k_t d_t^T``,
  ``o_t = S^T q_t``; ``o <- w_n o rsqrt(mean o^2 + eps) silu(z)`` per head
  (the norm, then the gate); ``out = o W_out``. The recurrence runs AS
  WRITTEN, one step at a time (``torch_recurrent_gated_delta_rule``, not the
  chunked form the system computes), in segments under ``jax.checkpoint`` so
  that the backward pass holds one segment's states and not all T.
- ``full_attention``: ``[q | gate] = u W_q`` (per head its q, then its gate),
  ``k = u W_k``, ``v = u W_v``; ``q <- N_head(q)``, ``k <- N_head(k)``; the
  first ``head_dim * partial_rotary_factor`` dims of each head turned by the
  rotary embedding (``x cos + rotate_half(x) sin`` with the halves of THAT
  part paired, ``rope_theta``, positions 0..T-1), the rest passed; causal
  softmax attention with the weights materialised, in blocks of queries,
  scale ``head_dim^-1/2``; ``out = (o * sigmoid(gate)) W_o``.
- experts: ``p = softmax(u W_r)`` over all experts; the
  ``num_experts_per_tok`` largest; ``w_i = p_i / sum_topk p``; ``y = sum_{i
  in topk, i held} w_i E_i(u) + sigmoid(u . w_sg) E_shared(u)``, every ``E``
  ``W_d (silu(W_g u) * W_u u)``, by a plain loop over the held experts, each
  over every token with its weight (zero where not chosen) and each under
  ``jax.checkpoint`` (the held experts' activations do not fit at once). How many
  assignments each held expert took, and how many went to absent experts,
  come back as the model state's ``step_counters``, by the reference's own
  routing.

Departures from the published model. What the absent experts would add is
left out, as in the system (the model-configs guide, section 4). The
multi-token-prediction head is left out (``Qwen3NextForCausalLM`` drops its
weights). No auxiliary router loss (``router_aux_loss_coef`` unused): the
training loss is the cross-entropy alone. Positions run 0..T-1 over the
packed sequence (no document mask, no position or state reset).

It reads the system's parameter tree (names as ``models/qwen3_next.py``
creates them) and the configuration file's keys.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .nemotron_h import QUERY_BLOCK, SEGMENT, release_host_memory


def _norm(x, p, eps):
    """The zero-centred RMSNorm: the learned scale is 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + p["scale"])


def _delta_rule(q, k, v, g, beta):
    """q, k (T, H, d_k), v (T, H, d_v), g and beta (T, H) -> o (T, H, d_v):
    the recurrence one step at a time."""
    t = q.shape[0]
    pad = -t % SEGMENT
    if pad:  # g = 0, beta = 0: the state neither decays nor is written
        q, k, v, g, beta = (jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in (q, k, v, g, beta))

    def step(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[:, None, None] * state
        delta = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def segment(state, inputs):
        return jax.lax.scan(step, state, inputs)

    split = lambda x: x.reshape((-1, SEGMENT) + x.shape[1:])
    state0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    _, o = jax.lax.scan(segment, state0, tuple(split(x) for x in (q, k, v, g, beta)))
    return o.reshape((-1,) + o.shape[2:])[:t]


def _gated_delta_net(u, p, cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv, r = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], hv // hk
    t = u.shape[0]
    qkvz = (u @ p["in_proj_qkvz"]["kernel"]).reshape(t, hk, 2 * dk + 2 * r * dv)
    ba = (u @ p["in_proj_ba"]["kernel"]).reshape(t, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v, z = qkvz[..., 2 * dk:2 * dk + r * dv], qkvz[..., 2 * dk + r * dv:]
    b, a = ba[..., :r].reshape(t, hv), ba[..., r:].reshape(t, hv)
    mixed = jnp.concatenate([q.reshape(t, hk * dk), k.reshape(t, hk * dk), v.reshape(t, hv * dv)], axis=-1)
    taps = p["conv_kernel"].shape[0]
    padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[j:j + t] * p["conv_kernel"][j] for j in range(taps)))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    per_value_head = lambda x: jnp.repeat(x.reshape(t, hk, dk), r, axis=1)  # head h reads key head h // r
    l2norm = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    q, k = l2norm(per_value_head(q)) / math.sqrt(dk), l2norm(per_value_head(k))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = _delta_rule(q, k, v.reshape(t, hv, dv), g, jax.nn.sigmoid(b))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg["rms_norm_eps"]) * p["norm_scale"]
    return (o * jax.nn.silu(z.reshape(t, hv, dv))).reshape(t, hv * dv) @ p["out_proj"]["kernel"]


def _partial_rotary(x, theta, rotary_dim):
    """x (T, heads, D): the first ``rotary_dim`` dims of each head at
    position t turned by the angles t * theta^(-2i/rotary_dim); the rest pass."""
    t = x.shape[0]
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    turned, passed = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = turned[..., : rotary_dim // 2], turned[..., rotary_dim // 2:]
    return jnp.concatenate([turned * cos + jnp.concatenate([-x2, x1], axis=-1) * sin, passed], axis=-1)


def _attention(u, p, cfg):
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, t = cfg["rms_norm_eps"], u.shape[0]
    rotary_dim = int(hd * cfg["partial_rotary_factor"])
    q_and_gate = (u @ p["q_proj"]["kernel"]).reshape(t, hq, 2 * hd)
    q, gate = q_and_gate[..., :hd], q_and_gate[..., hd:].reshape(t, hq * hd)
    k = (u @ p["k_proj"]["kernel"]).reshape(t, hkv, hd)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, hkv, hd)
    q = _partial_rotary(_norm(q, p["q_norm"], eps), cfg["rope_theta"], rotary_dim)
    k = _partial_rotary(_norm(k, p["k_norm"], eps), cfg["rope_theta"], rotary_dim)
    q = q.reshape(t, hkv, hq // hkv, hd)  # query heads by their key/value head
    block = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / math.sqrt(hd)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", weights, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, t, block)).reshape(t, hq * hd)
    return (ctx * jax.nn.sigmoid(gate)) @ p["o_proj"]["kernel"]


def _gated(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _shared_expert(u, p):
    shared = p["shared"]
    out = _gated(u, shared["gate_proj"]["kernel"], shared["up_proj"]["kernel"], shared["down_proj"]["kernel"])
    return jax.nn.sigmoid(u @ p["shared_gate"])[:, None] * out


def _experts(u, p, cfg):
    """-> (the layer's output, its counters)."""
    probabilities = jax.nn.softmax(u @ p["router"], axis=-1)  # (T, all experts)
    picked, chosen = jax.lax.top_k(probabilities, cfg["num_experts_per_tok"])
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)  # norm_topk_prob
    out = _shared_expert(u, p)
    # recomputed in the backward pass: one expert's (T, width) activations alive at a time, not every held expert's
    weighted = jax.checkpoint(lambda u, weight, gate, up, down: weight[:, None] * _gated(u, gate, up, down))
    for slot, expert in enumerate(cfg["held_experts"]):
        weight = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)  # 0 where not chosen
        out = out + weighted(u, weight, p["experts_gate"][slot], p["experts_up"][slot], p["experts_down"][slot])
    held = jnp.stack([jnp.sum(chosen == expert) for expert in cfg["held_experts"]])
    return out, {"held": held, "absent": chosen.size - jnp.sum(held)}


def _block(x, p, cfg, kind):
    eps = cfg["rms_norm_eps"]
    normed = _norm(x, p["input_layernorm"], eps)
    if kind == "linear_attention":
        x = x + _gated_delta_net(normed, p["linear_attn"], cfg)
    else:
        x = x + _attention(normed, p["self_attn"], cfg)
    out, counters = _experts(_norm(x, p["post_attention_layernorm"], eps), p["mlp"], cfg)
    return x + out, counters


def _forward(params, ids, cfg):
    """One sequence's ids (T,) -> logits (T, vocabulary held here) and the
    expert layers' counters."""
    x = params["embed"]["embedding"][ids]
    counters = {}
    for i, kind in enumerate(cfg["layer_types"]):
        # recomputed in the backward pass: one layer's fp32 activations alive at a time
        layer = jax.checkpoint(lambda x, p, kind=kind: _block(x, p, cfg, kind))
        x, counters[f"layer_{i}"] = layer(x, params[f"layer_{i}"])
    return _norm(x, params["final_norm"], cfg["rms_norm_eps"]) @ params["head"], counters


def _logits(params, ids, cfg):
    return _forward(params, ids, cfg)[0]


def _sequence_loss(params, ids, labels, cfg):
    logits, counters = _forward(params, ids, cfg)
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
    def one(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            (loss, counters), grads = jax.value_and_grad(_sequence_loss, has_aux=True)(params, ids, labels, cfg)
        return (loss, grads), counters

    def loss_and_grads(params, model_state, batch):
        add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)
        total, counters, count = None, None, batch["input_ids"].shape[0]
        for ids, labels in zip(batch["input_ids"], batch["labels"]):
            out, routed = one(params, ids, labels)
            total, counters = (out, routed) if total is None else (add(total, out), add(counters, routed))
        loss, grads = jax.tree_util.tree_map(lambda v: v / count, total)
        release_host_memory()  # the first call compiled: its working memory goes back too
        return loss, grads, {**model_state, "step_counters": counters}

    return loss_and_grads
