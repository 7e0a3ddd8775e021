"""Nemotron-H's next-token loss and its gradients, written out in plain
``jax.numpy``: float32 everywhere, every product under
``jax.default_matmul_precision("highest")``, no kernel, no flax, nothing of
the program imported.

Follows HuggingFace's ``modeling_nemotron_h.py`` (``NemotronHForCausalLM``)
and the Mamba-2 paper (Dao & Gu 2024, the recurrence of its eq. 16 and not
the chunked SSD form):

- block: ``x <- x + mixer(RMSNorm(x))``, eps from the config; after the last
  block RMSNorm, then the untied head; the loss is the mean cross-entropy of
  the labels (the ids shifted by one) over the vocabulary held here.
- ``M``: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) + b)``, causal
  and depthwise; ``dt <- softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per
  head with state S (P, N): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``, head ``h`` reading group ``h // (H/G)``; ``y <-
  RMSNorm_grouped(y * silu(z))``; ``out = y W_out``. The recurrence runs as
  written, one step at a time, in segments under ``jax.checkpoint`` so that
  the backward pass holds one segment's states and not all T.
- ``E``: ``s = sigmoid(x W_r)``; the top k of ``s`` (selection bias zero);
  ``w = scale * s_i / sum_topk s``; ``y = sum_{i in topk, i held} w_i W2_i
  relu(W1_i x)^2 + W2_s relu(W1_s x)^2``, by a plain loop over the held
  experts, each over every token with its weight (zero where not chosen).
  Departure, as in the system (the model-configs guide, section 4): what the
  absent experts would add is left out.
- ``*``: ``n_heads`` query heads over ``n_kv_heads`` key/value heads, causal
  softmax attention with the weights materialised, in blocks of queries; no
  positional embedding (``NemotronHAttention`` applies none).

It reads the system's parameter tree (names as ``models/nemotron_h.py``
creates them) and the configuration file's keys.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

SEGMENT = 128  # recurrence steps per checkpointed segment
QUERY_BLOCK = 1024  # queries per block of naive attention


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _recurrence(x, dt, a, b, c):
    """x (T, H, P), dt (T, H), a (H,), b and c (T, H, N) -> y (T, H, P)."""
    t = x.shape[0]
    pad = -t % SEGMENT
    if pad:  # dt = 0: the state neither decays nor is fed
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)) for v in (x, dt, b, c))

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    @jax.checkpoint
    def segment(state, inputs):
        return jax.lax.scan(step, state, inputs)

    split = lambda v: v.reshape((-1, SEGMENT) + v.shape[1:])
    state0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), jnp.float32)
    _, y = jax.lax.scan(segment, state0, (split(x), split(dt), split(b), split(c)))
    return y.reshape((-1,) + y.shape[2:])[:t]


def _mamba(u, p, cfg):
    h, hp, g, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    d_inner = h * hp
    t = u.shape[0]
    zxbcdt = u @ p["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    k = p["conv_kernel"].shape[0]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + t] * p["conv_kernel"][j] for j in range(k)) + p["conv_bias"])
    x, b, c = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    x = x.reshape(t, h, hp)
    per_head = lambda v: jnp.repeat(v.reshape(t, g, n), h // g, axis=1)  # head h reads group h // (h/g)
    y = _recurrence(x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]), per_head(b), per_head(c))
    y = (y + p["d"][None, :, None] * x).reshape(t, d_inner) * jax.nn.silu(z)
    grouped = y.reshape(t, g, d_inner // g)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + cfg["norm_eps"])
    return (grouped.reshape(t, d_inner) * p["norm_scale"]) @ p["out_proj"]["kernel"]


def _experts(u, p, cfg):
    relu2 = lambda v: jnp.square(jax.nn.relu(v))
    scores = jax.nn.sigmoid(u @ p["router"])  # (T, all experts)
    _, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = cfg["routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)
    out = relu2(u @ p["shared_in"]["kernel"]) @ p["shared_out"]["kernel"]
    for slot, expert in enumerate(cfg["held_experts"]):
        weight = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)  # 0 where not chosen
        out = out + weight[:, None] * (relu2(u @ p["experts_in"][slot]) @ p["experts_out"][slot])
    return out


def _attention(u, p, cfg):
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    t = u.shape[0]
    q = (u @ p["q_proj"]["kernel"]).reshape(t, hkv, hq // hkv, hd)  # query heads by their key/value head
    k = (u @ p["k_proj"]["kernel"]).reshape(t, hkv, hd)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, hkv, hd)
    block = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / math.sqrt(hd)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", weights, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, t, block)).reshape(t, hq * hd)
    return ctx @ p["o_proj"]["kernel"]


def _sequence_loss(params, ids, labels, cfg):
    x = params["embed"]["embedding"][ids]
    mixers = {"M": _mamba, "E": _experts, "*": _attention}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        # recomputed in the backward pass: one layer's fp32 activations alive at a time
        layer = jax.checkpoint(
            lambda x, p, mixer=mixers[kind]: x + mixer(_rms_norm(x, p["norm"]["scale"], cfg["norm_eps"]), p["mixer"], cfg)
        )
        x = layer(x, params[f"layer_{i}"])
    logits = _rms_norm(x, params["final_norm"]["scale"], cfg["norm_eps"]) @ params["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def release_host_memory() -> None:
    """Hand freed host memory back to the system. The reference check keeps
    some fifteen fp32 copies of the parameters on the host beside whatever
    the compiles left in the allocator's arenas, and a one-chip machine has
    40 GiB: what the system's step no longer needs goes first."""
    import ctypes
    import gc

    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):  # no glibc here: nothing to trim
        pass


def make_loss_and_grads(cfg: Dict):
    """``(params, model_state, worker_batch) -> (loss, grads, model_state)``
    for one worker's batch, one sequence at a time (equal lengths and a mean
    loss: the mean of the sequences' gradients is the batch's)."""
    # the harness builds this after its window: the system's executables
    # have run their last step, and their host-side copies are the largest
    # thing this process can still give back
    jax.clear_caches()
    release_host_memory()

    @jax.jit
    def one(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(_sequence_loss)(params, ids, labels, cfg)

    def loss_and_grads(params, model_state, batch):
        total, count = None, batch["input_ids"].shape[0]
        for ids, labels in zip(batch["input_ids"], batch["labels"]):
            out = one(params, ids, labels)
            total = out if total is None else jax.tree_util.tree_map(jnp.add, total, out)
        loss, grads = jax.tree_util.tree_map(lambda v: v / count, total)
        release_host_memory()  # the first call compiled: its working memory goes back too
        return loss, grads, model_state

    return loss_and_grads
