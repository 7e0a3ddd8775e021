"""PowerSGD's Algorithm 2 (Vogels et al. 2019), written out plainly over W
simulated workers, with the numpy oracle of the reference's ``reducer.py`` as
its reducer. State lives on the host as numpy; only the model's loss and
gradients (the plain reference of the cell's builder) run on the device.

    for each step:
        g_w   = grad of worker w's loss on its shard            (line 6)
        s_w   = g_w + e_w                                        (line 7)
        D, e' = PowerSGD(s_1..s_W): rank-r mean and residuals    (lines 8-11)
        m     = lambda * m + D                                   (line 12)
        x     = x - lr * (D + m)                                 (line 13)
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence

import jax
import numpy as np

from .oracle_powersgd import powersgd_reduce_np


def unpack_qs(q_memory: np.ndarray, leaves: Sequence[np.ndarray], rank: int, matricize: str) -> List[np.ndarray]:
    """The reducer's flat warm-start buffer as one (m, r) matrix per
    compressed tensor, in leaf order: the start both sides share."""
    qs, offset = [], 0
    for leaf in leaves:
        if leaf.ndim <= 1:
            continue
        m = leaf.shape[-1] if matricize == "last" else int(np.prod(leaf.shape[1:]))
        n = leaf.size // m
        r = min(n, m, rank)
        qs.append(np.asarray(q_memory[offset:offset + m * r]).reshape(m, r))
        offset += m * r
    assert offset == q_memory.size, (offset, q_memory.size)
    return qs


def run(
    loss_and_grads: Callable,
    params0: Any,
    model_state0: Any,
    q_memory0: np.ndarray,
    step_batches: Sequence[Sequence[Any]],  # [step][worker] -> that worker's batch
    reducer: Dict,
    learning_rate: float,
    momentum: float,
) -> Dict:
    """Run ``len(step_batches)`` steps; return every step's loss (mean over
    workers) and, after step 1, the reduced update, each worker's error
    memory and the parameters."""
    leaves0, treedef = jax.tree_util.tree_flatten(params0)
    params = [np.asarray(x, np.float32) for x in leaves0]
    n_workers = len(step_batches[0])
    momenta = [np.zeros_like(x) for x in params]
    memories = [[np.zeros_like(x) for x in params] for _ in range(n_workers)]
    rank, mode = reducer["rank"], reducer.get("matricize", "last")
    qs = unpack_qs(np.asarray(q_memory0, np.float32), params, rank, mode)
    model_states = [model_state0] * n_workers
    losses, after_first = [], None
    for batches in step_batches:
        tree = jax.tree_util.tree_unflatten(treedef, params)
        sends, worker_losses = [], []
        for w, batch in enumerate(batches):
            loss, grads, model_states[w] = loss_and_grads(tree, model_states[w], batch)
            grads = [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(jax.device_get(grads))]
            sends.append([g + e for g, e in zip(grads, memories[w])])
            worker_losses.append(float(loss))
        losses.append(float(np.mean(worker_losses)))
        delta, memories, next_qs, bits = powersgd_reduce_np(sends, qs, rank, matricize_mode=mode)
        if reducer.get("reuse_query", True):
            qs = next_qs
        momenta = [momentum * m + d for m, d in zip(momenta, delta)]
        params = [p - learning_rate * (d + m) for p, d, m in zip(params, delta, momenta)]
        if after_first is None:
            after_first = {
                "delta": delta, "memories": memories, "params": params,
                "wire_bytes": bits // 8,
            }
    return {"losses": losses, "after_first": after_first}
