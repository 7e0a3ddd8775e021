"""The flash kernels under the block-wise rule (``blockwise=(half, block)`` over
``[noised ; clean]`` rows), in interpret mode on the CPU: forward and gradients
against the dense einsum under ``models.layers.blockwise_seen``, block edges
inside a tile and on its edge, a block that straddles a tile edge, grouped
heads read in place, a lane block of two heads, the fold; the tiles the
kernels' loop bounds visit against the mask itself (288 of 1,024 tile pairs at
the cell's L = 8,192); the rule's own definition; and ``blockwise=None`` left
the program it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.models.layers import blockwise_seen, einsum_attention
from network_distributed_pytorch_tpu.ops.flash_attention import (
    blockwise_key_tiles, blockwise_query_tiles, flash_attention, tile_edge,
)

# (L, B, (H, Hkv, D), tile or None): the issue's three L / B, with block edges inside a tile (B = 4 and 32 under tiles
# of 256 and 512) and on its edge (every tile edge is a block edge there); grouped heads of 128 read in place; two heads
# of 64 a lane block; grouped heads of 64 (the fold); a block of 48 across the edge of a 128-tile; a block a whole tile
CASES = [
    pytest.param(256, 4, (8, 2, 128), None, id="L256-B4-gqa-8-over-2x128"),
    pytest.param(256, 4, (2, 2, 64), None, id="L256-B4-pair-of-64"),
    pytest.param(1024, 4, (2, 1, 128), None, id="L1024-B4-gqa-128"),
    pytest.param(1024, 32, (2, 2, 64), None, id="L1024-B32-pair-of-64"),
    pytest.param(256, 4, (4, 2, 64), None, id="L256-B4-gqa-64-fold"),
    pytest.param(384, 48, (1, 1, 128), 128, id="L384-B48-block-across-a-tile-edge"),
    pytest.param(256, 128, (1, 1, 128), 128, id="L256-B128-block-is-a-tile"),
    pytest.param(64, 64, (1, 1, 128), None, id="L64-one-block"),
]


def operands(length, heads, seed=0):
    h, hkv, d = heads
    keys = jax.random.split(jax.random.PRNGKey(seed + length), 4)
    shape = lambda n: (1, 2 * length, n, d)
    return tuple(jax.random.normal(k, shape(n)) for k, n in zip(keys, (h, hkv, hkv, h)))


@pytest.mark.parametrize("length,block,heads,tile", CASES)
def test_forward_and_gradients_match_the_dense_einsum(length, block, heads, tile):
    q, k, v, w = operands(length, heads)
    flash = lambda q, k, v: flash_attention(
        q, k, v, blockwise=(length, block), interpret=True, block_q=tile, block_k=tile
    )
    dense = lambda q, k, v: einsum_attention(q, k, v, blockwise=(length, block))
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for g, e in zip(got, want):
        np.testing.assert_allclose(g, e, rtol=2e-4, atol=2e-5)


def test_a_key_mask_still_hides_its_keys_under_the_rule():
    length, block = 128, 4
    q, k, v, _ = operands(length, (2, 2, 64))
    hidden = jnp.zeros((1, 2 * length)).at[0, length + 8:length + 12].set(-1e30)  # clean block 2
    got = flash_attention(q, k, v, mask=hidden, blockwise=(length, block), interpret=True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
    seen = blockwise_seen(length, block) & (hidden[0] == 0)[None, :]
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_rule_is_the_issues():
    """Query i sees key j iff both noised and in one block, or i noised, j
    clean and j's block earlier, or both clean and j's block not later; a clean
    query sees no noised key; every query sees itself; L^2 + L B pairs."""
    length, block = 24, 4
    seen = np.asarray(blockwise_seen(length, block))
    for i in range(2 * length):
        for j in range(2 * length):
            qn, kn, qb, kb = i < length, j < length, (i % length) // block, (j % length) // block
            want = (qn and kn and qb == kb) or (qn and not kn and kb < qb) or (not qn and not kn and kb <= qb)
            assert seen[i, j] == want, (i, j)
    assert seen.diagonal().all() and not seen[length:, :length].any()
    assert seen.sum() == length * length + length * block


def walked(length, block, tile):
    """(forward visits, backward visits) as sets of (query tile, key tile) over
    the 2L rows, with whether the visit carries the compare, read off the
    kernels' own loop bounds."""
    half_tiles = length // tile
    rule = (length, block)
    forward, backward = {}, {}
    for qi in range(2 * half_tiles):
        whole, edge, own_lo, own_hi = blockwise_key_tiles(rule, qi, tile, tile)
        for j in range(whole):
            forward[(qi, half_tiles + j)] = False
        for j in list(range(half_tiles + whole, half_tiles + edge)) + list(range(own_lo, own_hi)):
            forward[(qi, j)] = True
    for j in range(2 * half_tiles):
        part_n, full_n, whole_n, part_c, full_c = blockwise_query_tiles(rule, j, tile, tile)
        for i in list(range(whole_n, half_tiles)) + list(range(half_tiles + full_c, 2 * half_tiles)):
            backward[(i, j)] = False
        for i in list(range(part_n, full_n)) + list(range(half_tiles + part_c, half_tiles + full_c)):
            backward[(i, j)] = True
    return forward, backward


@pytest.mark.parametrize("length,block,tile", [
    (8192, 4, 512), (1024, 4, 512), (1024, 32, 256), (256, 4, 256), (384, 48, 128), (256, 128, 128), (512, 256, 128),
])
def test_the_loop_bounds_visit_the_tiles_the_rule_shows_and_no_other(length, block, tile):
    forward, backward = walked(length, block, tile)
    assert forward == backward  # one walk, transposed
    seen = np.asarray(blockwise_seen(length, block))
    n = 2 * length // tile
    tiles = seen.reshape(n, tile, n, tile).transpose(0, 2, 1, 3)
    for i in range(n):
        for j in range(n):
            if tiles[i, j].all():
                # seen whole: visited, and without the compare unless both are noised (a block of whole tiles)
                assert forward.get((i, j)) is False or (max(i, j) < n // 2 and forward[(i, j)]), (i, j)
            elif tiles[i, j].any():
                assert forward.get((i, j)) is True, (i, j)  # on a diagonal: visited with it
            else:
                assert (i, j) not in forward, (i, j)  # hidden: skipped by the bounds


def test_at_the_cells_length_288_of_1024_tile_pairs_are_visited():
    """L = 8,192 at the kernels' own tile (512): a noised query tile q visits
    its own noised tile and clean tiles 0..q, a clean one clean tiles 0..q:
    16 + 136 + 136 of 32 x 32, for 256 tiles' worth of visible pairs; only the
    three diagonals (16 tiles each) carry the compare."""
    assert tile_edge(8192) == 512
    forward, _ = walked(8192, 4, 512)
    assert len(forward) == 288 and (2 * 8192 // 512) ** 2 == 1024
    assert sum(forward.values()) == 48
    assert (8192 * 8192 + 8192 * 4) / 512**2 == pytest.approx(256, rel=1e-3)
    for q in range(16):
        assert sorted(j for (i, j) in forward if i == q) == [q] + list(range(16, 17 + q))
        assert sorted(j for (i, j) in forward if i == 16 + q) == list(range(16, 17 + q))


def test_what_the_rule_refuses():
    q, k, v, _ = operands(64, (1, 1, 128))
    for bad in (dict(causal=True), dict(window=8)):
        with pytest.raises(ValueError, match="blockwise"):
            flash_attention(q, k, v, blockwise=(64, 4), interpret=True, **bad)
    with pytest.raises(ValueError, match="blockwise"):
        flash_attention(q, k, v, blockwise=(32, 4), interpret=True)  # T is not 2 * half
    with pytest.raises(ValueError, match="blockwise"):
        flash_attention(q, k, v, blockwise=(64, 48), interpret=True)  # not whole blocks


@pytest.mark.parametrize("kwargs", [dict(causal=True), dict(causal=True, window=48), dict()], ids=["causal", "window", "full"])
def test_without_the_rule_the_traced_program_knows_nothing_of_it(kwargs):
    """``blockwise=None`` and a call that never names it trace one program:
    the rule is Python-level branches the other modes never enter."""
    q, k, v, w = operands(64, (2, 2, 64))
    fn = lambda **extra: jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, interpret=True, **kwargs, **extra) * w), (0, 1, 2)
    ))(q, k, v)
    assert str(fn()) == str(fn(blockwise=None))
