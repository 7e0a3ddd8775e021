"""afmoe's (Trinity's) layers on the CPU at small sizes, seeded weights: the
two kinds of attention layer (rotary and a window in one, neither in the
other) against the benchmark's plain reference, the gated dropless expert
layer against a plain loop (and its sixteen shares against the uncut layer),
and the ungated layer Nemotron-H runs, which must not have moved. The whole
model and its training step are in ``test_afmoe_train.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as reference
from network_distributed_pytorch_tpu.models.afmoe import AfmoeAttention, AfmoeConfig
from network_distributed_pytorch_tpu.models.layers import Rope, rotary
from network_distributed_pytorch_tpu.parallel import moe
from network_distributed_pytorch_tpu.parallel.moe import chunk_rows, held_experts_moe


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


# ---- attention ---------------------------------------------------------------

SEQ = 48
ATTN = dict(hidden_size=64, n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=100.0)
REFERENCE_ATTN = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16, rope_theta=100.0, rms_norm_eps=1e-5)


def attention_layer(sliding, window, attn_impl="einsum"):
    module = AfmoeAttention(AfmoeConfig(sliding_window=window, attn_impl=attn_impl, **ATTN), sliding, 0.02)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 64))
    params = module.init(jax.random.PRNGKey(1), x)
    # norm scales off 1, so that they count
    params = jax.tree_util.tree_map(
        lambda p: p + 0.2 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params
    )
    return module, params, x


def reference_attention(params, x, sliding, window):
    cfg = dict(REFERENCE_ATTN, sliding_window=window)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([reference._attention(row, params["params"], cfg, sliding) for row in x])


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
@pytest.mark.parametrize("sliding,window", [(True, 16), (True, 20), (False, 16)], ids=["sliding16", "sliding20", "full"])
def test_attention_layer_matches_the_plain_reference(sliding, window, attn_impl):
    """q and k normed per head, turned in the sliding layer only, the
    window (or the whole causal triangle), the sigmoid gate on the output:
    outputs and every gradient, through einsum attention and through the
    flash kernels (interpret mode; window 20 is no multiple of any tile)."""
    module, params, x = attention_layer(sliding, window, attn_impl)
    np.testing.assert_allclose(
        module.apply(params, x), reference_attention(params, x, sliding, window), rtol=2e-4, atol=2e-6
    )
    got = jax.grad(lambda p, x: jnp.sum(jnp.sin(module.apply(p, x))), argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(reference_attention(p, x, sliding, window))), argnums=(0, 1))(params, x)
    assert worst_relative(got, want) < 2e-4


def test_rotary_positions_are_in_the_sliding_layers_and_not_in_the_full_ones():
    """With a window as long as the sequence the two kinds of layer differ
    by the rotary embedding alone: each must agree with the reference of its
    own kind and disagree with the other's, so a swap fails here."""
    for sliding in (True, False):
        module, params, x = attention_layer(sliding, window=SEQ)
        own = reference_attention(params, x, sliding, SEQ)
        other = reference_attention(params, x, not sliding, SEQ)
        got = module.apply(params, x)
        assert float(jnp.linalg.norm(got - own) / jnp.linalg.norm(own)) < 1e-4
        assert float(jnp.linalg.norm(got - other) / jnp.linalg.norm(other)) > 1e-2
    # a full layer carries no positions: to the last token the earlier ones are a set, in any order
    shuffled = jnp.concatenate([jax.random.permutation(jax.random.PRNGKey(4), SEQ - 1), jnp.array([SEQ - 1])])
    for sliding, moved in ((False, False), (True, True)):
        module, params, x = attention_layer(sliding, window=SEQ)
        last, last_shuffled = module.apply(params, x)[:, -1], module.apply(params, x[:, shuffled])[:, -1]
        assert bool(float(jnp.linalg.norm(last - last_shuffled) / jnp.linalg.norm(last)) > 1e-3) == moved


def test_rotary_turns_pairs_by_fp32_angles_and_keeps_their_length():
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 2, 8))
    turned = rotary(x, Rope(10000.0))
    assert turned.dtype == jnp.float32
    np.testing.assert_allclose(turned[:, 0], x[:, 0], rtol=1e-6)  # position 0: no turn
    pair = lambda v, i: v[..., i] ** 2 + v[..., i + 4] ** 2  # i pairs with i + D/2
    for i in range(4):
        np.testing.assert_allclose(pair(turned, i), pair(x, i), rtol=1e-5)
    # the dot product of a turned q and k depends on their distance alone
    q, k = x[:, :, :1], x[:, :, 1:]
    same = lambda at: (rotary(jnp.roll(q, at, 1), Rope(1e4))[:, 5 + at] * rotary(jnp.roll(k, at, 1), Rope(1e4))[:, 2 + at]).sum()
    np.testing.assert_allclose(same(0), same(11), rtol=1e-4)


# ---- the gated expert layer --------------------------------------------------

T, D, F, E, K = 48, 16, 24, 16, 3


def expert_layer(seed=0, skew=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E)) * 0.3
    gate, up = (jax.random.normal(k, (E, D, F)) * 0.2 for k in ks[2:4])
    down = jax.random.normal(ks[4], (E, F, D)) * 0.2
    if skew:  # every token scores the first K experts far above the rest
        x = x.at[:, 0].set(skew)
        router = router.at[0, :K].set(5.0)
    return x, router, gate, up, down


def plain_gated_experts(x, router, gate, up, down, held):
    """A loop over the held experts, every token through each, weight zero
    where the expert was not chosen."""
    dot = lambda a, b: jnp.dot(a, b, precision="highest")
    scores = jax.nn.sigmoid(dot(x, router))
    _, chosen = jax.lax.top_k(scores, K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = 2.826 * picked / picked.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for slot, expert in enumerate(held):
        weight = jnp.where(chosen == expert, weights, 0.0).sum(-1)
        hidden = jax.nn.silu(dot(x, gate[slot])) * dot(x, up[slot])
        out = out + weight[:, None] * dot(hidden, down[slot])
    return out


def routed(x, router, gate, up, down, held):
    # row tiles of 8; five held of 16 at top 3 expect 45 of T = 48 rows, so parallel.moe.chunk_rows gives a
    # chunk 3/2 of that, 72 rows in nine tiles, and a skewed load (3T) takes two chunks
    return held_experts_moe(x, x, router, jnp.zeros((E,)), up, down, held, K, 2.826, block_rows=8, w_gate=gate)


def row_tiles_of(held_counts, rows=T, tile=8):
    """The row tiles one product of the first chunk visits, by hand: every
    (tile, expert) pair with a row in common among the first ``rows`` sorted
    assignments."""
    ends = np.minimum(np.cumsum(np.asarray(held_counts)), rows)
    starts = np.concatenate([[0], ends[:-1]])
    return int(sum(-(-e // tile) - s // tile for s, e in zip(starts, ends) if e > s))


@pytest.mark.parametrize("skew", [0.0, 4.0], ids=["even", "most_tokens_on_three_experts"])
def test_gated_expert_layer_matches_a_plain_loop_and_drops_nothing(skew):
    x, router, gate, up, down = expert_layer(skew=skew)
    held = (0, 1, 2, 7, 9)
    slots = jnp.asarray(held)
    mine = (gate[slots], up[slots], down[slots])
    got, counters = jax.jit(lambda *a: routed(*a, held))(x, router, *mine)
    np.testing.assert_allclose(got, plain_gated_experts(x, router, *mine, held), rtol=2e-4, atol=2e-5)
    assert int(counters["dropped"]) == 0
    assert int(counters["held"].sum() + counters["absent"]) == T * K
    assert chunk_rows(T, K, len(held), E, 8) == 72
    assert int(counters["row_tiles"]) == row_tiles_of(counters["held"], rows=72)
    assert -(-int(counters["held"].sum()) // 72) == (2 if skew else 1)  # the chunks that held live rows
    if skew:  # 3T = 144 assignments landed against chunks of 72 rows: both chunks ran
        assert int(counters["held"][:3].sum()) == 3 * T
        assert int(counters["row_tiles"]) == 9  # the first chunk is full: the first expert's 48 rows and 24 of the second's
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(routed(*a, held)[0])), argnums=range(5))(x, router, *mine)
    plain = jax.grad(lambda *a: jnp.sum(jnp.sin(plain_gated_experts(*a, held))), argnums=range(5))(x, router, *mine)
    assert worst_relative(grads, plain) < 1e-4


def test_the_shares_of_sixteen_ranks_and_the_shared_expert_once_equal_the_uncut_layer():
    """The model-configs guide's share test: each of 16 ranks holds one of
    the 16 experts and routes over all of them; the routed parts of all
    ranks, with the shared expert every rank computes alike counted once,
    add up to the whole layer as the plain reference computes it uncut."""
    x, router, gate, up, down = expert_layer(seed=3)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    shared = {
        "gate_proj": {"kernel": jax.random.normal(ks[0], (D, F)) * 0.2},
        "up_proj": {"kernel": jax.random.normal(ks[1], (D, F)) * 0.2},
        "down_proj": {"kernel": jax.random.normal(ks[2], (F, D)) * 0.2},
    }
    parts, landed = jnp.zeros_like(x), 0
    for rank in range(E):
        one = slice(rank, rank + 1)
        part, counters = routed(x, router, gate[one], up[one], down[one], (rank,))
        parts, landed = parts + part, landed + int(counters["held"].sum())
        assert int(counters["absent"]) + int(counters["held"].sum()) == T * K
        assert int(counters["row_tiles"]) == -(-int(counters["held"][0]) // 8)  # one expert: its rows in whole tiles
    assert landed == T * K  # every assignment landed on exactly one rank
    uncut = {"router": router, "experts_gate": gate, "experts_up": up, "experts_down": down, "shared": shared}
    cfg = {"num_experts_per_tok": K, "route_scale": 2.826, "held_experts": list(range(E))}
    with jax.default_matmul_precision("highest"):
        want, whole = reference._experts(x, uncut, cfg)
        once = reference._gated_mlp(x, shared)
    np.testing.assert_allclose(parts + once, want, rtol=2e-4, atol=2e-5)
    assert int(whole["held"].sum()) == T * K and int(whole["absent"]) == 0


def test_a_router_score_rounded_to_bfloat16_picks_other_experts():
    """Why the router computes in fp32 at full precision: at the model's 128
    experts and 8 a token, scores rounded to bf16 tie, and the top 8 of many
    tokens change."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x, router = jax.random.normal(ks[0], (2048, 64)), jax.random.normal(ks[1], (64, 128)) * 0.1
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    rounded = scores.astype(jnp.bfloat16).astype(jnp.float32)
    full, low = (np.sort(np.asarray(jax.lax.top_k(s, 8)[1]), -1) for s in (scores, rounded))
    assert np.any(full != low, axis=-1).mean() > 0.05


# seed, held experts, assignments that land, the chunk HELD TO T = 48 rows in 6 row tiles of 8 (below): none past
# the first chunk; 5, 12, 15 and 36 past it, in the scan's second chunk; 53, in its second and third
SECOND_CHUNK_CASES = [
    (1, (0, 1, 2, 7, 9), 39), (1, (0, 1, 2, 7, 9, 11), 53), (2, (0, 1, 2, 3, 7, 9, 11), 60),
    (1, (0, 1, 2, 3, 7, 9, 11), 63), (0, (0, 1, 2, 3, 4, 7, 9, 11), 84), (0, tuple(range(10)), 101),
]


@pytest.mark.parametrize("seed,held,landed", SECOND_CHUNK_CASES, ids=lambda v: str(v) if isinstance(v, int) else f"{len(v)}held")
def test_a_load_past_the_first_chunk_goes_on_in_the_scan(seed, held, landed, monkeypatch):
    """Whatever lands past the first chunk, a few rows or two chunks more, is
    the scan's: output and gradients as the plain loop, nothing dropped. The
    chunk is held to T rows here, as a rank with a small share has it: at
    these toy shares (5 to 10 of 16 held) ``chunk_rows`` would size the first
    chunk past every load below, which is its purpose and
    ``test_moe_chunks.py``'s subject; this test is the later chunks' own."""
    monkeypatch.setattr(moe, "chunk_rows", lambda t, top_k, n_held, e, block_rows: -(-t // block_rows) * block_rows)
    x, router, gate, up, down = expert_layer(seed=seed)
    slots = jnp.asarray(held)
    mine = (gate[slots], up[slots], down[slots])
    got, counters = jax.jit(lambda *a: routed(*a, held))(x, router, *mine)
    assert int(counters["held"].sum()) == landed and int(counters["dropped"]) == 0
    # the counter is the first chunk's: what of each expert's rows lies in its 48, whatever the scan takes on
    assert int(counters["row_tiles"]) == row_tiles_of(counters["held"])
    np.testing.assert_allclose(got, plain_gated_experts(x, router, *mine, held), rtol=2e-4, atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(routed(*a, held)[0])), argnums=range(5))(x, router, *mine)
    plain = jax.grad(lambda *a: jnp.sum(jnp.sin(plain_gated_experts(*a, held))), argnums=range(5))(x, router, *mine)
    assert worst_relative(grads, plain) < 1e-4


def test_the_ungated_layer_takes_the_same_path_without_its_gate():
    """Nemotron-H's relu² experts run the function the gated ones run, less
    one product: leaving ``w_gate`` out and passing None trace one program,
    and it has two grouped products a chunk where the gated one has three
    (a chunk is traced twice: the first, and the scan's)."""
    x, router, gate, w_in, w_out = expert_layer()
    held = (0, 1, 2, 7, 9)
    slots = jnp.asarray(held)
    args = (x, x, router, jnp.zeros((E,)), w_in[slots], w_out[slots], held, K, 2.5)
    program = lambda **kw: str(jax.make_jaxpr(lambda *a: held_experts_moe(*a, held, K, 2.5, block_rows=8, **kw)[0])(*args[:6]))
    assert program() == program(w_gate=None)
    gated = program(w_gate=gate[slots])
    assert gated != program() and gated.count("logistic") > program().count("logistic")
    assert 0 < program().count("ragged_dot") * 3 == gated.count("ragged_dot") * 2


# ---- expert_bias: a buffer, balanced for weights that come from a seed --------


def zipf_ids(seed, shape, vocab=256):
    ranks = np.minimum(np.random.default_rng(seed).zipf(1.2, shape) - 1, vocab - 1)
    return jnp.asarray(ranks, jnp.int32)


def test_balanced_expert_bias_gives_every_expert_its_share_and_zeros_are_no_buffer():
    """On the batch it was balanced on, every expert of every layer takes
    about tokens * top_k / experts assignments (between half and twice) where
    zeros leave some expert over four times that; the buffers are one (experts,) leaf a layer that no
    gradient reaches; zeros for them and no ``buffers`` at all are one
    program's output."""
    from network_distributed_pytorch_tpu.models.afmoe import afmoe_tiny
    from network_distributed_pytorch_tpu.models.layers import BUFFERS, balanced_expert_bias

    model = afmoe_tiny(held_experts=tuple(range(16)), remat=True)
    ids = zipf_ids(0, (2, 256))
    variables = model.init(jax.random.PRNGKey(0), ids)
    params, zeros = variables["params"], variables[BUFFERS]
    assert sorted(zeros) == ["layer_1", "layer_2", "layer_3", "layer_4"]
    assert all(not np.asarray(z["mlp"]["expert_bias"]).any() for z in zeros.values())
    buffers = balanced_expert_bias(model, params, ids)
    assert jax.tree_util.tree_structure(buffers) == jax.tree_util.tree_structure(zeros)
    share = ids.size * 2 / 16
    logits, plain = model.apply({"params": params}, ids)
    same, _ = model.apply({"params": params, BUFFERS: zeros}, ids)
    np.testing.assert_array_equal(logits, same)
    _, balanced = model.apply({"params": params, BUFFERS: buffers}, ids)
    worst = lambda counters: max(float(c["held"].max()) for c in counters.values()) / share
    assert worst(plain) > 4.0 and worst(balanced) < 2.0
    assert min(float(c["held"].min()) for c in balanced.values()) > 0.5 * share
    grads = jax.grad(lambda b: jnp.sum(model.apply({"params": params, BUFFERS: b}, ids)[0] ** 2))(buffers)
    assert not any(np.asarray(g).any() for g in jax.tree_util.tree_leaves(grads))
