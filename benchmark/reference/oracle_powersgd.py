"""Pure-NumPy oracle of the reference PowerSGD reduction (``reducer.py:43-170``),
implemented literally from the reference's math for golden-value parity tests.

The oracle simulates W workers in one process: it takes each worker's send
buffers, a shared initial Q, and returns what every worker's (identical)
decompressed output, per-worker error memories, next Q, and bit count must be.
"""

from typing import List, Sequence, Tuple

import numpy as np


def orthogonalize_np(matrix: np.ndarray, eps: float = 1e-8) -> np.ndarray:
    """Sequential-column Gram-Schmidt, the reference recurrence
    (``reducer.py:183-191``)."""
    matrix = matrix.copy()
    n, m = matrix.shape
    for i in range(m):
        col = matrix[:, i : i + 1]
        col /= np.sqrt(np.sum(col**2)) + eps
        if i + 1 < m:
            rest = matrix[:, i + 1 :]
            rest -= np.sum(col * rest, axis=0) * col
    return matrix


def matricize(t: np.ndarray, mode: str = "first") -> np.ndarray:
    if mode == "first":
        return t.reshape(t.shape[0], -1)
    return t.reshape(-1, t.shape[-1])


def powersgd_reduce_np(
    sends_per_worker: Sequence[List[np.ndarray]],
    qs: List[np.ndarray],
    compression_rank: int,
    matricize_mode: str = "first",
    n_power_iterations: int = 0,
) -> Tuple[List[np.ndarray], List[List[np.ndarray]], List[np.ndarray], int]:
    """One reduction step over W simulated workers.

    Returns (out, memories_per_worker, next_qs, bits). ``qs`` must be the
    current warm-start Qs for the high-rank tensors in leaf order.
    ``n_power_iterations`` adds extra P/Q subspace rounds (the framework's
    beyond-parity extension; 0 = the reference's single fused round).
    """
    n_workers = len(sends_per_worker)
    template = sends_per_worker[0]
    rank1_idx = [i for i, t in enumerate(template) if t.ndim <= 1]
    high_idx = [i for i, t in enumerate(template) if t.ndim > 1]

    bits = 0
    out = [None] * len(template)

    # rank-1 tensors: uncompressed allreduce-mean (reducer.py:130-133)
    for i in rank1_idx:
        stacked = np.stack([w[i] for w in sends_per_worker])
        out[i] = stacked.mean(axis=0)
        bits += 32 * template[i].size

    next_qs = list(qs)
    p_hats = [None] * len(high_idx)
    for _round in range(1 + n_power_iterations):
        # P = mean_w(M_w Q); bits count the packed P buffer (reducer.py:120-128)
        p_hats = []
        for j, i in enumerate(high_idx):
            mats = [matricize(w[i], matricize_mode) for w in sends_per_worker]
            p = np.mean([m @ next_qs[j] for m in mats], axis=0)
            bits += 32 * p.size
            p_hats.append(orthogonalize_np(p))

        # Q = mean_w(M_w^T P_hat) (reducer.py:139-147)
        next_qs = []
        for j, i in enumerate(high_idx):
            mats = [matricize(w[i], matricize_mode) for w in sends_per_worker]
            q = np.mean([m.T @ p_hats[j] for m in mats], axis=0)
            bits += 32 * q.size
            next_qs.append(q)

    # decompress P_hat Q^T (reducer.py:157-163)
    for j, i in enumerate(high_idx):
        out[i] = (p_hats[j] @ next_qs[j].T).reshape(template[i].shape)

    memories = []
    for w in sends_per_worker:
        mem = [np.zeros_like(t) for t in template]
        for i in high_idx:
            mem[i] = w[i] - out[i]
        memories.append(mem)

    return out, memories, next_qs, bits
