"""Block-diffusion noising: a batch of token ids -> the noised copy and the
loss weights a masked-token loss takes (``models.layers.masked_token_loss``)."""

from __future__ import annotations

from typing import Dict

import numpy as np


def block_noised(ids: np.ndarray, block: int, eps: float, mask_id: int, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """``ids`` (N, L), L whole blocks of ``block``: each block of each sample
    draws a noise level ``t ~ U(eps, 1)`` and each of its tokens is replaced by
    ``mask_id`` independently with probability ``t``. -> ``input_ids`` (the ids
    as they came), ``noisy_ids`` and ``loss_weight`` (fp32: ``1 / t`` of its
    block on a replaced position, 0 elsewhere). No id may be ``mask_id``
    itself: a replaced position is told from a kept one by its id."""
    n, length = ids.shape
    if length % block or np.any(ids == mask_id):
        raise ValueError(f"{length} ids a sample in blocks of {block}, none of them the mask id {mask_id}")
    level = rng.uniform(eps, 1.0, (n, length // block)).astype(np.float32).repeat(block, axis=1)
    replaced = rng.random((n, length), dtype=np.float32) < level
    return {
        "input_ids": ids,
        "noisy_ids": np.where(replaced, np.asarray(mask_id, ids.dtype), ids),
        "loss_weight": np.where(replaced, 1.0 / level, 0.0).astype(np.float32),
    }
