"""Mellum 2's (``model_type: mellum``'s) next-token loss and its gradients,
written out in plain ``jax.numpy``: float32 everywhere, every product under
``jax.default_matmul_precision("highest")``, no kernel, no flax, nothing of
the program imported.

Written from the published ``config.json`` and ISSUE 44's equations, not from
``models/mellum.py``; ``h`` is ``hidden_size``, every norm an RMSNorm with a
learned scale and ``rms_norm_eps``, no bias anywhere:

- embedding: ``x = E[ids]``; after the last block RMSNorm, then the untied
  head; the loss is the mean cross-entropy of the labels (the ids shifted by
  one) over the vocabulary held here.
- block: ``x <- x + attn(N1(x))``, then ``x <- x + experts(N2(x))``; every
  layer's feed-forward is the expert layer.
- attention: ``q = RMSNorm_head(W_q u)``, ``k = RMSNorm_head(W_k u)``, ``v =
  W_v u``; q and k turned over the whole head in BOTH layer kinds, ``x cos +
  rotate_half(x) sin`` with the halves paired i and i + D/2, positions
  0..T-1, by ``rope_parameters[kind]``:
  ``sliding_attention`` (``rope_type: default``): ``inv_freq_i =
  theta^(-2i/D)``, cos and sin as they are; query i sees key j iff ``0 <= i -
  j < sliding_window``.
  ``full_attention`` (``rope_type: yarn``, HuggingFace's
  ``_compute_yarn_parameters``): ``extrap_i = theta^(-2i/D)``, ``interp_i =
  extrap_i / factor``; ``c(n) = D ln(L / (2 pi n)) / (2 ln theta)`` with L
  the original positions; ``low = max(floor(c(beta_fast)), 0)``, ``high =
  min(ceil(c(beta_slow)), D - 1)``; ``ramp_i = clip((i - low) / (high - low),
  0, 1)``; ``inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i)``; cos and
  sin both times ``attention_factor``; query i sees key j iff ``j <= i``.
  ``o = softmax(q k^T / sqrt(head_dim)) v`` with the weights materialised, in
  blocks of queries; ``out = W_o o``. No gate, no sink.
- experts: ``s = softmax(u W_r)`` over all experts; the
  ``num_experts_per_tok`` largest; ``w_i = s_i / sum_topk s``; ``y =
  sum_{i in topk, i held} w_i W_d,i (silu(W_g,i u) * (W_u,i u))``, by a plain
  loop over the held experts, each over every token with its weight (zero
  where not chosen); no shared expert, no scaling, no selection bias. How
  many assignments each held expert took, and how many went to absent
  experts, come back as the model state's ``step_counters``, the system's
  counters by the reference's own routing.

Departures from the published model. What the absent experts would add is
left out, as in the system (the model-configs guide, section 4). The per-head
norm of q and k is assumed (the row has no key for it; the lineage has the
norm). The multi-token-prediction head and any auxiliary loss are left out
(no key for either). Positions run 0..T-1 over the packed sequence (no
document mask, no position reset).

It reads the system's parameter tree (names as ``models/mellum.py`` creates
them) and the configuration file's keys.

The cell's own limits. ``reference_check.TOLERANCES`` is one set for every
cell, about twice the worst of the imdb and cifar runs (0.35 / 0.30 a tensor);
this configuration's sound runs read a tenth of that (0.035-0.043 and
0.032-0.035 a tensor, 0.0033-0.0034 for the memories as one), and a precision
lower than the configuration states (rotary angles in bf16: 0.167 / 0.209 /
0.0206) passed under them (PERF.md section 6, PR 44). So the configuration
file carries ``reference_limits``, each between those two readings, and
``make_loss_and_grads`` — the one call ``reference_check.compare`` makes into a
cell's own files before it reads its limits — puts them in place for this
run's comparison. A run is one process and one cell, so no other cell sees
them; the rehearsal's sizes carry none.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .afmoe import _gated, _rms_norm
from .nemotron_h import QUERY_BLOCK, release_host_memory


def _frequencies(rope: Dict, dim: int) -> Tuple[np.ndarray, float]:
    """``rope_parameters[kind]`` -> (inv_freq (dim/2,) in fp32, what cos and
    sin are multiplied by), worked in numpy's float64 and rounded once."""
    i = np.arange(dim // 2, dtype=np.float64)
    extrap = rope["rope_theta"] ** (-2.0 * i / dim)
    if rope["rope_type"] == "default":
        return extrap.astype(np.float32), 1.0
    assert rope["rope_type"] == "yarn", rope
    interp = extrap / rope["factor"]

    def c(n):
        return dim * math.log(rope["original_max_position_embeddings"] / (2 * math.pi * n)) / (2 * math.log(rope["rope_theta"]))

    low, high = max(math.floor(c(rope["beta_fast"])), 0), min(math.ceil(c(rope["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / (high - low if high != low else 1e-3), 0.0, 1.0)
    return (interp * ramp + extrap * (1.0 - ramp)).astype(np.float32), float(rope["attention_factor"])


def _turned(x, rope):
    """x (T, heads, D): position t turned by the angles ``t * inv_freq``."""
    t, d = x.shape[0], x.shape[-1]
    inv_freq, factor = _frequencies(rope, d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos = factor * jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = factor * jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(u, p, cfg, kind):
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, t, rope = cfg["rms_norm_eps"], u.shape[0], cfg["rope_parameters"][kind]
    q = _turned(_rms_norm((u @ p["q_proj"]["kernel"]).reshape(t, hq, hd), p["q_norm"], eps), rope)
    k = _turned(_rms_norm((u @ p["k_proj"]["kernel"]).reshape(t, hkv, hd), p["k_norm"], eps), rope)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, hkv, hd)
    q = q.reshape(t, hkv, hq // hkv, hd)  # query heads by their key/value head
    block = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / math.sqrt(hd)
        behind = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]  # query - key
        seen = behind >= 0
        if kind == "sliding_attention":
            seen = seen & (behind < cfg["sliding_window"])
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", weights, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, t, block)).reshape(t, hq * hd)
    return ctx @ p["o_proj"]["kernel"]


def _experts(u, p, cfg):
    """-> (the layer's output, its counters)."""
    scores = jax.nn.softmax(u @ p["router"], axis=-1)  # (T, all experts)
    picked, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    out = jnp.zeros_like(u)
    for slot, expert in enumerate(cfg["held_experts"]):
        weight = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)  # 0 where not chosen
        out = out + weight[:, None] * _gated(
            u, p["experts_gate"][slot], p["experts_up"][slot], p["experts_down"][slot]
        )
    held = jnp.stack([jnp.sum(chosen == expert) for expert in cfg["held_experts"]])
    return out, {"held": held, "absent": chosen.size - jnp.sum(held)}


def _block(x, p, cfg, kind):
    """-> (the block's output, its expert layer's counters)."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["input_layernorm"], eps), p["self_attn"], cfg, kind)
    out, counters = _experts(_rms_norm(x, p["post_attention_layernorm"], eps), p["mlp"], cfg)
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
    return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]) @ params["head"], counters


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
    from .. import reference_check

    reference_check.TOLERANCES.update(cfg.get("reference_limits", {}))  # the cell's own: the module's text

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
