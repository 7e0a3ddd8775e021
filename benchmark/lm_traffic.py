"""Language-model traffic: a workload file's ``traffic`` group of kind
``lm_sequences`` -> a seeded pool of full training sequences as numpy arrays.

What a pre-training job feeds a step: sequences packed to ``seq_len`` with no
padding and no document mask. Token ids are Zipf-distributed over the
vocabulary the configuration holds (rank r with probability ~ r^-exponent, the
ranks scattered over the ids by a seeded permutation so that frequent tokens
are not the low ids); the label of a position is the next position's id.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def lm_sequences(traffic: Dict, vocab_size: int, seed: int) -> Dict[str, np.ndarray]:
    """``pool_samples`` sequences of ``seq_len`` ids and their labels."""
    n, t = int(traffic["pool_samples"]), int(traffic["seq_len"])
    rng = np.random.default_rng(seed)
    weights = np.arange(1, vocab_size + 1, dtype=np.float64) ** -float(traffic.get("zipf_exponent", 1.0))
    cumulative = np.cumsum(weights / weights.sum())
    ranks = np.minimum(np.searchsorted(cumulative, rng.random((n, t + 1))), vocab_size - 1)
    ids = rng.permutation(vocab_size).astype(np.int32)[ranks]
    return {"input_ids": ids[:, :-1].copy(), "labels": ids[:, 1:].copy()}
