"""SDAR's (``model_type: sdar_moe``'s) block-diffusion loss and its gradients,
written out in plain ``jax.numpy``: float32 everywhere, every product under
``jax.default_matmul_precision("highest")``, no kernel, no flax, nothing of
the program imported.

Written from the published ``config.json`` (the Qwen3-MoE lineage's keys) and
ISSUE 51's equations, not from ``models/sdar.py``; every norm an RMSNorm with
a learned scale and ``rms_norm_eps``, no bias anywhere:

- the objective: a sample is L token ids ``x0`` in L / B blocks of B =
  ``block_length``. Per block b a noise level ``t_b ~ U(eps, 1)``; each token of
  block b was replaced by the ``[MASK]`` id independently with probability
  ``t_b``, giving ``xt`` (the batch brings ``xt`` and the weights ``1 / t_b`` on
  replaced positions, 0 elsewhere). Loss = ``(1 / L) sum_b (1 / t_b) sum_{i in
  b, replaced} -log p(x0_i | xt_b, x0_{<b})``, a replaced position's OWN output
  row predicting its token (no shift).
- ``p`` for block b is the model on ``x0_{<b} + xt_b`` at positions
  ``0..(b+1)B-1`` under block-causal attention (a query sees every key whose
  block is not later than its own). One forward for all blocks: rows ``[xt ;
  x0]`` (2L), positions ``[0..L) + [0..L)``, and query i sees key j iff (i, j
  both noised and block(i) = block(j)) or (i noised, j clean and block(j) <
  block(i)) or (i, j both clean and block(j) <= block(i)); a clean query sees
  no noised key. The mask is built dense from that rule, a block of queries
  at a time. The final norm and the head run on the L noised rows only.
- embedding: ``x = E[ids]``; after the last block RMSNorm, then the untied head.
- block: ``x <- x + attn(N1(x))``, then ``x <- x + experts(N2(x))``.
- attention: ``q = RMSNorm_head(W_q u)``, ``k = RMSNorm_head(W_k u)``, ``v = W_v
  u``; q and k turned over the whole head, ``x cos + rotate_half(x) sin`` with
  the halves paired i and i + D/2, ``inv_freq_i = theta^(-2i/D)``, row r at
  position ``r mod L``; ``o = softmax(q k^T / sqrt(head_dim)) v`` over the keys
  the rule shows, the weights materialised; ``out = W_o o``.
- experts: ``s = softmax(u W_r)`` over all experts; the ``num_experts_per_tok``
  largest; ``w_i = s_i / sum_topk s``; ``y = sum_{i in topk, i held} w_i W_d,i
  (silu(W_g,i u) * (W_u,i u))``, by a plain loop over the held experts, each
  over every row with its weight (zero where not chosen); no shared expert, no
  scaling, no selection bias. How many assignments each held expert took and
  how many went to absent experts come back as the model state's
  ``step_counters``, by the reference's own routing.

Departures from the published model: what the absent experts would add is left
out, as in the system (the model-configs guide, section 4); the per-head norm
of q and k, the block length and the schedule are assumed (the configuration
file's ``assumed``); no auxiliary loss.

It reads the system's parameter tree (names as ``models/sdar.py`` creates them)
and the configuration file's keys.

The cell's own limits, as ``reference/mellum.py``: the configuration file's
``reference_limits`` go over ``reference_check.TOLERANCES`` for this run's
comparison in ``make_loss_and_grads``, the one call the check makes into a
cell's files before it reads its limits; each lies between the sound
program's readings and a lower-precision control's (the file's ``assumed``).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .afmoe import _rms_norm
from .mellum import _experts  # softmax over all, top k renormalised, the held experts by a plain loop: the same lineage's
from .nemotron_h import QUERY_BLOCK, release_host_memory


def seen(rows, half: int, block: int):
    """The rule, dense: ``rows`` (Q,) query rows against all 2 ``half`` key
    rows -> (Q, 2 half) booleans."""
    keys = jnp.arange(2 * half)
    q_clean, k_clean = (rows >= half)[:, None], (keys >= half)[None, :]
    q_blk, k_blk = ((rows % half) // block)[:, None], ((keys % half) // block)[None, :]
    both_noised = ~q_clean & ~k_clean & (q_blk == k_blk)
    noised_on_clean = ~q_clean & k_clean & (k_blk < q_blk)
    both_clean = q_clean & k_clean & (k_blk <= q_blk)
    return both_noised | noised_on_clean | both_clean


def _turned(x, theta: float, positions):
    """x (T, heads, D): the row at ``positions[t]`` turned by ``positions[t] * inv_freq``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(u, p, cfg):
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, t = cfg["rms_norm_eps"], u.shape[0]
    half = t // 2
    positions = jnp.arange(t) % half
    q = _turned(_rms_norm((u @ p["q_proj"]["kernel"]).reshape(t, hq, hd), p["q_norm"], eps), cfg["rope_theta"], positions)
    k = _turned(_rms_norm((u @ p["k_proj"]["kernel"]).reshape(t, hkv, hd), p["k_norm"], eps), cfg["rope_theta"], positions)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, hkv, hd)
    q = q.reshape(t, hkv, hq // hkv, hd)  # query heads by their key/value head
    block = math.gcd(t, QUERY_BLOCK // 2)  # against 2L keys: half the queries a block that L keys take

    @jax.checkpoint
    def one_block(start):
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) / math.sqrt(hd)
        shown = seen(start + jnp.arange(block), half, cfg["block_length"])
        weights = jax.nn.softmax(jnp.where(shown, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", weights, v)

    ctx = jax.lax.map(one_block, jnp.arange(0, t, block)).reshape(t, hq * hd)
    return ctx @ p["o_proj"]["kernel"]


def _block(x, p, cfg):
    """-> (the block's output, its expert layer's counters)."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["input_layernorm"], eps), p["self_attn"], cfg)
    out, counters = _experts(_rms_norm(x, p["post_attention_layernorm"], eps), p["mlp"], cfg)
    return x + out, counters


def _forward(params, noisy_ids, ids, cfg):
    """One sample's noised and clean ids (L,) each -> logits (L, vocabulary
    held here) of the noised rows and the expert layers' counters."""
    x = params["embed"]["embedding"][jnp.concatenate([noisy_ids, ids])]
    counters = {}
    for i in range(cfg["num_hidden_layers"]):
        # recomputed in the backward pass: one layer's fp32 activations alive at a time
        layer = jax.checkpoint(lambda x, p: _block(x, p, cfg))
        x, counters[f"layer_{i}"] = layer(x, params[f"layer_{i}"])
    noised = x[: ids.shape[0]]
    return _rms_norm(noised, params["final_norm"], cfg["rms_norm_eps"]) @ params["head"], counters


def _logits(params, noisy_ids, ids, cfg):
    return _forward(params, noisy_ids, ids, cfg)[0]


def _sample_loss(params, noisy_ids, ids, weight, cfg):
    logits, counters = _forward(params, noisy_ids, ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, None], axis=-1)[:, 0]
    return jnp.sum(weight * nll) / ids.shape[0], counters


def make_loss_and_grads(cfg: Dict):
    """``(params, model_state, worker_batch) -> (loss, grads, model_state)``
    for one worker's batch, one sample at a time (equal lengths and a mean over
    positions: the mean of the samples' gradients is the batch's)."""
    # as reference/nemotron_h.py: the harness builds this after its window,
    # and the check keeps some fifteen fp32 copies of the parameters on the host
    jax.clear_caches()
    release_host_memory()
    from .. import reference_check

    reference_check.TOLERANCES.update(cfg.get("reference_limits", {}))  # the cell's own: the module's text

    @jax.jit
    def one(params, noisy_ids, ids, weight):
        with jax.default_matmul_precision("highest"):
            (loss, counters), grads = jax.value_and_grad(_sample_loss, has_aux=True)(params, noisy_ids, ids, weight, cfg)
        return (loss, grads), counters

    def loss_and_grads(params, model_state, batch):
        add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)
        total, counters, count = None, None, batch["input_ids"].shape[0]
        for noisy_ids, ids, weight in zip(batch["noisy_ids"], batch["input_ids"], batch["loss_weight"]):
            out, routed = one(params, noisy_ids, ids, weight)
            total, counters = (out, routed) if total is None else (add(total, out), add(counters, routed))
        loss, grads = jax.tree_util.tree_map(lambda v: v / count, total)
        release_host_memory()  # the first call compiled: its working memory goes back too
        return loss, grads, {**model_state, "step_counters": counters}

    return loss_and_grads
