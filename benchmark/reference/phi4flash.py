"""Phi-4-mini-flash-reasoning's (``phi4flash``'s, SambaY's) next-token loss and
its gradients, written out in plain ``jax.numpy``: float32 everywhere, every
product under ``jax.default_matmul_precision("highest")``, no kernel, no flax,
nothing of the program imported.

Written from ISSUE 48's equations (the published ``config.json``, the
published modeling file's ``Phi3Mamba`` and ``SambaYAttention`` as the
configuration's ``assumed`` says, and the SambaY paper, Ren et al. 2025), not
from ``models/phi4flash.py``. ``D`` is ``hidden_size``; every norm of the
residual stream a LayerNorm with a scale, a bias and ``layer_norm_eps``. No
positional encoding of any kind.

- which layer is what, by its PUBLISHED index ``l`` (``layer_indices``) and
  the published depth ``n`` (``published.num_hidden_layers``): even ``l`` up to
  ``n/2`` a Mamba layer, ``l = n/2`` the one whose scan output is kept (the
  memory); odd ``l`` under ``n/2`` sliding-window attention; ``l = n/2 + 1``
  the one full-attention layer, whose keys and values are kept (the cache);
  past it even ``l`` a Gated Memory Unit, odd ``l`` a cross-attention.
- block: ``h <- h + Mixer(LN1(h))``, then ``h <- h + MLP(LN2(h))``; ``MLP(u) =
  (silu(g) * y) W2`` with ``[g | y] = u W1``, the gate the first half.
- Mamba-1: ``[x | z] = u W_in``; ``x <- silu(conv(x) + bias)``, ``conv(s)_t =
  sum_{j < K} w[j] * s_{t-K+1+j}`` with zeros before the sequence; ``[dt | B |
  C] = x W_x``; ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; the
  recurrence ONE STEP AT A TIME, ``S_t[c, n] = exp(delta_t[c] A[c, n])
  S_{t-1}[c, n] + delta_t[c] x_t[c] B_t[n]``, ``y_t[c] = sum_n S_t[c, n] C_t[n]
  + D[c] x_t[c]``, in checkpointed segments of steps so a layer's per-step
  states (2.7 GB at T = 8192) are never alive together; the memory is ``y``;
  out ``(y * silu(z)) W_out``.
- differential attention: ``[q | k | v] = u W_qkv + b``; even heads are the
  first of a pair, odd heads the second; query pair p of ``H/2`` reads
  key/value pair ``p // (H / Hkv)``; ``O_i = softmax(q_i k_i^T / sqrt(hd))
  [v_1 | v_2]`` over the visible keys (``0 <= i - j < sliding_window`` in a
  sliding layer, ``j <= i`` otherwise), the weights materialised in blocks of
  queries — which is ``[Att(q_i, k_i, v_1) | Att(q_i, k_i, v_2)]``; ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)``; ``O = RMSNorm_2hd(O_1 - lambda O_2) * subln * (1 -
  lambda_init)``; out ``O W_o + b_o``. The cache is the full layer's ``(k_1,
  k_2, v_1, v_2)``.
- Gated Memory Unit: ``(memory * silu(u W_in)) W_out``.
- cross-attention: ``q = u W_q + b``; keys and values the cache; causal.
- head: final LayerNorm, ``logits = h E^T`` with E the embedding; the loss is
  the mean cross-entropy of the labels (the ids shifted by one) over the
  vocabulary held here.

Departures from the published model: none in the mathematics of the layers
held; dropout is 0 as published; positions are none, so a packed sequence
needs no position reset (no document mask either).

It reads the system's parameter tree (names as ``models/phi4flash.py`` creates
them) and the configuration file's keys.

The cell's own limits. ``reference_check.TOLERANCES`` is one set for every
cell, about twice the worst of the imdb and cifar runs; a precision lower than
this configuration states must fail the comparison (ROADMAP Owed 16), so the
configuration file carries ``reference_limits`` between the sound program's
worst readings and two controls' (the scan's state and decay in bf16; the
subln and the softmax difference in bf16: PERF.md section 6, PR 48), and
``make_loss_and_grads`` — the one call ``reference_check.compare`` makes into
a cell's own files before it reads its limits — puts them in place for this
run's comparison, as ``reference/mellum.py`` does. A run is one process and
one cell; the rehearsal's sizes carry none.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .nemotron_h import QUERY_BLOCK, SEGMENT, release_host_memory


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _recurrence(x, delta, a, b, c):
    """x and delta (T, C), a (C, N), b and c (T, N) -> ``sum_n S_t[., n] C_t[n]`` (T, C)."""
    t = x.shape[0]
    pad = -t % SEGMENT
    if pad:  # delta = 0: the state neither decays nor is fed
        x, delta, b, c = (jnp.pad(v, ((0, pad), (0, 0))) for v in (x, delta, b, c))

    def step(state, inputs):
        x_t, delta_t, b_t, c_t = inputs
        state = jnp.exp(delta_t[:, None] * a) * state + (delta_t * x_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=-1)

    @jax.checkpoint
    def segment(state, inputs):
        return jax.lax.scan(step, state, inputs)

    split = lambda v: v.reshape((-1, SEGMENT) + v.shape[1:])
    _, y = jax.lax.scan(segment, jnp.zeros(a.shape, jnp.float32), (split(x), split(delta), split(b), split(c)))
    return y.reshape(-1, y.shape[-1])[:t]


def _mamba(u, p, cfg):
    """-> (the mixer's output, the scan's output y before the gate)."""
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    t = u.shape[0]
    x, z = jnp.split(u @ p["in_proj"]["kernel"], 2, axis=-1)
    k = p["conv_kernel"].shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[j:j + t] * p["conv_kernel"][j] for j in range(k)) + p["conv_bias"])
    dt, b, c = jnp.split(x @ p["x_proj"]["kernel"], [r, r + n], axis=-1)
    delta = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
    y = _recurrence(x, delta, -jnp.exp(p["a_log"]), b, c) + p["d"] * x
    return (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"], y


def _attend(q, k, v, window):
    """q (T, H, hd), k (T, G, hd), v (T, G, dv), H a multiple of G -> (T, H,
    dv): query i sees key j iff ``0 <= i - j < window``."""
    t, h, hd = q.shape
    g = k.shape[1]
    q = q.reshape(t, g, h // g, hd)  # query heads by their key/value head
    block = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / math.sqrt(hd)
        behind = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]  # query - key
        weights = jax.nn.softmax(jnp.where((behind >= 0) & (behind < window), scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kge->qgre", weights, v)

    return jax.lax.map(one_block, jnp.arange(0, t, block)).reshape(t, h, v.shape[-1])


def _diff_attention(u, p, cfg, index, window, cache=None):
    """-> (the layer's output, its (k1, k2, v1, v2)); with ``cache`` a cross-attention."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, t = cfg["hidden_size"] // hq, u.shape[0]
    if cache is None:
        q, k, v = jnp.split(u @ p["Wqkv"]["kernel"] + p["Wqkv"]["bias"], [hq * hd, (hq + hkv) * hd], axis=-1)
        k, v = k.reshape(t, hkv, hd), v.reshape(t, hkv, hd)
        cache = k[:, 0::2], k[:, 1::2], v[:, 0::2], v[:, 1::2]
    else:
        q = u @ p["Wq"]["kernel"] + p["Wq"]["bias"]
    q = q.reshape(t, hq, hd)
    k1, k2, v1, v2 = cache
    both = jnp.concatenate([v1, v2], axis=-1)  # the doubled value, (T, Hkv/2, 2 hd)
    o1 = _attend(q[:, 0::2], k1, both, window)
    o2 = _attend(q[:, 1::2], k2, both, window)
    init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.dot(p["lambda_q1"], p["lambda_k1"])) - jnp.exp(jnp.dot(p["lambda_q2"], p["lambda_k2"])) + init
    o = o1 - lam * o2
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg["layer_norm_eps"]) * p["subln"]
    return (o * (1.0 - init)).reshape(t, hq * hd) @ p["out_proj"]["kernel"] + p["out_proj"]["bias"], cache


def _block(carry, p, cfg, index):
    h, memory, cache = carry
    half = cfg["published"]["num_hidden_layers"] // 2
    eps, t = cfg["layer_norm_eps"], h.shape[0]
    u, mixer = _layer_norm(h, p["norm_1"], eps), p["mixer"]
    if index % 2 == 0 and index <= half:
        mixed, y = _mamba(u, mixer, cfg)
        memory = y if index == half else memory
    elif index % 2 == 0:
        mixed = (memory * jax.nn.silu(u @ mixer["in_proj"]["kernel"])) @ mixer["out_proj"]["kernel"]
    elif index < half:
        mixed, _ = _diff_attention(u, mixer, cfg, index, cfg["sliding_window"])
    elif index == half + 1:
        mixed, cache = _diff_attention(u, mixer, cfg, index, t)
    else:
        mixed, _ = _diff_attention(u, mixer, cfg, index, t, cache)
    h = h + mixed
    gate, up = jnp.split(_layer_norm(h, p["norm_2"], eps) @ p["mlp"]["gate_up_proj"]["kernel"], 2, axis=-1)
    return h + (jax.nn.silu(gate) * up) @ p["mlp"]["down_proj"]["kernel"], memory, cache


def _logits(params, ids, cfg):
    """One sequence's ids (T,) -> logits (T, vocabulary held here)."""
    carry = (params["embed"]["embedding"][ids], None, None)
    for i, index in enumerate(cfg["layer_indices"]):
        # recomputed in the backward pass: one layer's fp32 activations alive at a time
        layer = jax.checkpoint(lambda carry, p, index=index: _block(carry, p, cfg, index))
        carry = layer(carry, params[f"layer_{i}"])
    h = _layer_norm(carry[0], params["final_norm"], cfg["layer_norm_eps"])
    return h @ params["embed"]["embedding"].T


def _sequence_loss(params, ids, labels, cfg):
    logp = jax.nn.log_softmax(_logits(params, ids, cfg), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def make_loss_and_grads(cfg: Dict):
    """``(params, model_state, worker_batch) -> (loss, grads, model_state)``
    for one worker's batch, one sequence at a time (equal lengths and a mean
    loss: the mean of the sequences' gradients is the batch's)."""
    # as reference/nemotron_h.py: the harness builds this after its window,
    # and the check keeps some fifteen fp32 copies of the parameters on the host
    jax.clear_caches()
    release_host_memory()
    from .. import reference_check

    reference_check.TOLERANCES.update(cfg.get("reference_limits", {}))  # the cell's own: the module's text

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
        return loss, grads, model_state  # no expert layer: the counters' tree stays empty

    return loss_and_grads
