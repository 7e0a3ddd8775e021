"""What one call of the flash attention forward kernel has to do, from its
shapes: the two products (QK^T and PV) and one pass over q, k, v and o."""

from __future__ import annotations

from typing import Tuple


def forward_cost(batch: int, heads: int, seq: int, head_dim: int, bytes_per_element: int) -> Tuple[float, float]:
    """(operations, bytes moved) of one forward call over (B, T, H, D)."""
    flops = 4.0 * batch * heads * seq * seq * head_dim
    moved = 4.0 * batch * heads * seq * head_dim * bytes_per_element
    return flops, moved
