"""The one traffic generator: a workload file's ``traffic`` group -> a seeded
pool of training samples as numpy arrays.

A training job's traffic is its batches: how many samples a step takes per
chip, how long the sequences are, how large the pool is that an epoch walks.
All of it is data in ``benchmark/workloads/<cell>.json``; a new cell adds a
file and no code. Two kinds of sample exist because the two configurations
eat different things; both are drawn here, vectorised, from ``--seed``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

CLS, SEP, PAD = 101, 102, 0  # bert-base-uncased's special token ids
FIRST_WORD_ID = 1000  # below it the uncased vocabulary holds [unused]/special


def token_sequences(traffic: Dict, vocab_size: int, seed: int) -> Dict[str, np.ndarray]:
    """Reviews as token ids, truncated and padded to ``seq_len`` the way the
    reference's tokenizer call does (every batch is ``seq_len`` wide).

    Word counts are log-normal (``length_median``, ``length_sigma``), clipped
    to ``[length_min, seq_len - 2]``; [CLS] and [SEP] frame them. Each class
    draws ``class_word_rate`` of its words from its own slice of the
    vocabulary, the rest from a shared one, so a classifier can learn it.
    """
    n, t = int(traffic["pool_samples"]), int(traffic["seq_len"])
    rng = np.random.default_rng(seed)
    words = np.exp(
        rng.normal(np.log(traffic["length_median"]), traffic["length_sigma"], n)
    )
    words = np.clip(np.rint(words), traffic.get("length_min", 8), t - 2).astype(np.int64)
    labels = rng.integers(0, 2, n, dtype=np.int32)
    span = (vocab_size - FIRST_WORD_ID) // 4
    shared = rng.integers(FIRST_WORD_ID, FIRST_WORD_ID + 2 * span, (n, t), dtype=np.int32)
    own = rng.integers(0, span, (n, t), dtype=np.int32) + (FIRST_WORD_ID + 2 * span + labels[:, None] * span)
    ids = np.where(rng.random((n, t), dtype=np.float32) < traffic.get("class_word_rate", 0.4), own, shared)
    pos = np.arange(t)[None, :]
    ids = np.where(pos == 0, CLS, ids)
    ids = np.where(pos == words[:, None] + 1, SEP, ids)
    mask = pos <= words[:, None] + 1
    return {
        "input_ids": np.where(mask, ids, PAD).astype(np.int32),
        "attention_mask": mask.astype(np.int32),
        "labels": labels,
    }


def images(traffic: Dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """CIFAR-shaped class blobs (H x W x C float32, already normalised, as
    the reference's transform leaves them) and their labels."""
    n = int(traffic["pool_samples"])
    h, w, c = traffic["image_shape"]
    classes = int(traffic["num_classes"])
    rng = np.random.default_rng(seed)
    means = rng.standard_normal((classes, h, w, c), dtype=np.float32)
    means *= np.float32(traffic.get("class_sep", 0.5))
    labels = rng.integers(0, classes, n, dtype=np.int32)
    out = rng.standard_normal((n, h, w, c), dtype=np.float32)
    out *= np.float32(traffic.get("noise", 0.25))
    out += means[labels]
    return out, labels


def padding_share(pool) -> float:
    """Share of token positions that are padding (0.0 for image pools)."""
    if isinstance(pool, dict) and "attention_mask" in pool:
        mask = pool["attention_mask"]
        return float(1.0 - mask.sum() / mask.size)
    return 0.0
