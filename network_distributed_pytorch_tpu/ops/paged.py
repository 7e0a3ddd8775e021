"""Device primitives for the paged KV cache (block gather/scatter).

A paged KV buffer for one layer is ``(n_blocks, block_len, n_heads,
head_dim)``; a slot's logical ``(max_len, n_heads, head_dim)`` view is
stitched together through a static-shape block TABLE of
``max_len // block_len`` physical indices. Everything here is shape-static
— tables are data, not structure — so the serving engine compiles ONE
decode program and allocation/free/copy-on-write never trigger a retrace.

Bitwise contract (what lets the paged engine match the dense SlotEngine
exactly): :func:`gather_block_view` materialises a ``(B, max_len, H, D)``
array whose entries at valid positions are identical to the dense cache
rows, and the decode step's position mask turns every OTHER position into
an exact ``0.0`` softmax weight — so garbage in the reserved block 0 (or
in not-yet-written tail blocks) contributes exactly ``0.0 * finite`` to
the attention output, which is exact on IEEE arithmetic.

Out-of-range safety: scatter positions are clamped onto the garbage block
(index 0) rather than clipped onto a real block — speculative decode can
overrun a finished row's capacity by up to K-1 positions, and those writes
must not corrupt live KV (jax's default clip mode would silently redirect
them onto the row's LAST real block).
"""

from __future__ import annotations

import jax.numpy as jnp


def block_view_shape(tables, pool_buf):
    """Logical ``(B, max_len, H, D)`` shape implied by a table/pool pair."""
    n_blocks_per_slot = tables.shape[1]
    block_len = pool_buf.shape[1]
    return (
        tables.shape[0],
        n_blocks_per_slot * block_len,
        pool_buf.shape[2],
        pool_buf.shape[3],
    )


def gather_block_view(pool_buf, tables):
    """Gather per-slot logical KV rows out of the block pool.

    pool_buf: ``(n_blocks, block_len, H, D)``; tables: ``(B, T)`` int32.
    Returns ``(B, T * block_len, H, D)`` — the dense-cache-equivalent view
    each attention step reads.
    """
    g = pool_buf[tables]  # (B, T, L, H, D)
    b, t, l = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape(b, t * l, g.shape[3], g.shape[4])


def scatter_token_rows(pool_buf, tables, pos, rows):
    """Write one token's K or V rows for every slot.

    pool_buf ``(n_blocks, L, H, D)``, tables ``(B, T)``, pos ``(B,)``
    int32 logical positions, rows ``(B, H, D)``. Row ``b`` lands at
    physical ``(tables[b, pos[b] // L], pos[b] % L)``; positions >= T*L
    (speculative overrun on a nearly-done row) are redirected to the
    garbage block 0. Duplicate coordinates can then only collide inside
    block 0, where last-write-wins is harmless.
    """
    n_blk = tables.shape[1]
    block_len = pool_buf.shape[1]
    blk_idx = jnp.minimum(pos // block_len, n_blk - 1)
    phys = jnp.take_along_axis(tables, blk_idx[:, None], axis=1)[:, 0]
    in_range = pos < n_blk * block_len
    phys = jnp.where(in_range, phys, 0)
    off = jnp.mod(pos, block_len)
    return pool_buf.at[phys, off].set(rows.astype(pool_buf.dtype))


def scatter_chain(pool_buf, chain, rows):
    """Scatter a freshly-prefilled logical row into its block chain.

    pool_buf ``(n_blocks, L, H, D)``, chain ``(T,)`` int32 physical ids
    (padded with 0 past the request's reservation), rows
    ``(T * L, H, D)``. Padding entries all target block 0, which is never
    read as valid.
    """
    block_len = pool_buf.shape[1]
    t = chain.shape[0]
    blocks = rows.reshape(t, block_len, rows.shape[1], rows.shape[2])
    return pool_buf.at[chain].set(blocks.astype(pool_buf.dtype))


def copy_block(pool_buf, src, dst):
    """One-block copy-on-write: duplicate physical block ``src`` into
    ``dst`` (int32 scalars). The caller retargets the slot's table entry;
    the compiled program is shared by every COW event."""
    return pool_buf.at[dst].set(pool_buf[src])


def pool_chain_view(pool_buf, chain):
    """Gather a single chain's logical rows: chain ``(T,)`` int32 →
    ``(T * L, H, D)``. Used by shared-prefix admission to read the prefix
    KV it attends over."""
    g = pool_buf[chain]  # (T, L, H, D)
    return g.reshape(g.shape[0] * g.shape[1], g.shape[2], g.shape[3])
