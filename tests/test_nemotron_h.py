"""Nemotron-H's layers on the CPU at small sizes, seeded weights: the chunked
SSD scan against the recurrence as written, the share-aware dropless expert
layer against a plain loop (and its shares against the uncut layer), and
grouped-query attention through the flash kernel. The whole model and its
training step are in ``test_nemotron_train.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as reference
from network_distributed_pytorch_tpu.models.nemotron_h import (
    GroupedQueryAttention,
    NemotronHConfig,
)
from network_distributed_pytorch_tpu.ops.ssd import causal_conv1d, gated_group_rms_norm, ssd_scan
from network_distributed_pytorch_tpu.parallel.moe import chunk_rows, held_experts_moe

def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


# ---- the scan ----------------------------------------------------------------


def scan_inputs(t, h=4, p=8, g=2, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (2, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, t, h)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.0))
    b = jax.random.normal(ks[3], (2, t, g, n))
    c = jax.random.normal(ks[4], (2, t, g, n))
    d = jax.random.normal(ks[5], (h,))
    return x, dt, a, b, c, d


def recurrence_as_written(x, dt, a, b, c, d):
    per_head = lambda v: jnp.repeat(v, x.shape[2] // v.shape[2], axis=2)
    y = jax.vmap(reference._recurrence, in_axes=(0, 0, None, 0, 0))(x, dt, a, per_head(b), per_head(c))
    return y + d[None, None, :, None] * x


@pytest.mark.parametrize("t,chunk", [(32, 8), (29, 8), (5, 8), (48, 16)])
def test_chunked_scan_matches_the_recurrence_outputs_and_all_gradients(t, chunk):
    args = scan_inputs(t)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got, got_grads = jax.value_and_grad(
        lambda *a: jnp.sum(ssd_scan(*a, chunk) * weights), argnums=range(6)
    )(*args)
    want, want_grads = jax.value_and_grad(
        lambda *a: jnp.sum(recurrence_as_written(*a) * weights), argnums=range(6)
    )(*args)
    np.testing.assert_allclose(ssd_scan(*args, chunk), recurrence_as_written(*args), rtol=2e-4, atol=2e-4)
    assert abs(got - want) <= 1e-3 * abs(want) + 1e-3
    assert worst_relative(got_grads, want_grads) < 1e-4


def test_scan_in_bfloat16_keeps_its_decay_in_float32():
    x, dt, a, b, c, d = scan_inputs(64)
    low = ssd_scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16), c.astype(jnp.bfloat16), d, 16)
    assert low.dtype == jnp.bfloat16
    full = recurrence_as_written(x, dt, a, b, c, d)
    assert float(jnp.linalg.norm(low.astype(jnp.float32) - full) / jnp.linalg.norm(full)) < 2e-2


def test_causal_conv_and_gated_norm_match_their_definitions():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 6))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    want = np.zeros((2, 11, 6), np.float32)
    for t in range(11):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(x[:, t - 3 + j] * kernel[j])
    np.testing.assert_allclose(causal_conv1d(x, kernel, bias), want + np.asarray(bias), rtol=1e-5, atol=1e-5)
    y, gate = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 8)), jax.random.normal(jax.random.PRNGKey(4), (2, 5, 8))
    scale = jax.random.normal(jax.random.PRNGKey(5), (8,))
    h = np.asarray(y * jax.nn.silu(gate)).reshape(2, 5, 2, 4)
    normed = h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        gated_group_rms_norm(y, gate, scale, 2, 1e-5), normed.reshape(2, 5, 8) * np.asarray(scale), rtol=1e-5, atol=1e-5
    )


# ---- the expert layer --------------------------------------------------------

T, D, F, E, K = 48, 16, 24, 16, 3


def expert_layer(seed=0, skew=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E)) * 0.3
    w_in = jax.random.normal(ks[2], (E, D, F)) * 0.2
    w_out = jax.random.normal(ks[3], (E, F, D)) * 0.2
    if skew:  # every token scores the first K experts far above the rest
        x = x.at[:, 0].set(skew)  # one large input feature ...
        router = router.at[0, :K].set(5.0)  # ... that those experts' scores follow
    return x, router, w_in, w_out


def plain_experts(x, router, w_in, w_out, held):
    """A loop over the held experts, every token through each, weight zero
    where the expert was not chosen."""
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    _, chosen = jax.lax.top_k(scores, K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = 2.5 * picked / picked.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for slot, expert in enumerate(held):
        weight = jnp.where(chosen == expert, weights, 0.0).sum(-1)
        hidden = jnp.square(jax.nn.relu(jnp.dot(x, w_in[slot], precision="highest")))
        out = out + weight[:, None] * jnp.dot(hidden, w_out[slot], precision="highest")
    return out


# five held of 16 at top 3 expect 45 of T = 48 rows: parallel.moe.chunk_rows gives a chunk 3/2 of that in
# row tiles of 8, 72 rows in nine of them, so a skewed load (3T = 144) takes two chunks
ROWS = 72


def routed(x, router, w_in, w_out, held):
    return held_experts_moe(x, x, router, jnp.zeros((E,)), w_in, w_out, held, K, 2.5, block_rows=8)


def row_tiles_of(held_counts, rows=ROWS, tile=8):
    """The row tiles one product of the first chunk visits, by hand: every
    (tile, expert) pair with a row in common among the first ``rows`` sorted
    assignments."""
    ends = np.minimum(np.cumsum(np.asarray(held_counts)), rows)
    starts = np.concatenate([[0], ends[:-1]])
    return int(sum(-(-e // tile) - s // tile for s, e in zip(starts, ends) if e > s))


@pytest.mark.parametrize("skew", [0.0, 4.0], ids=["even", "most_tokens_on_three_experts"])
def test_expert_layer_matches_a_plain_loop_and_drops_nothing(skew):
    x, router, w_in, w_out = expert_layer(skew=skew)
    held = (0, 1, 2, 7, 9)
    slots = jnp.asarray(held)
    got, counters = jax.jit(lambda *a: routed(*a, held))(x, router, w_in[slots], w_out[slots])
    want = plain_experts(x, router, w_in[slots], w_out[slots], held)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert int(counters["dropped"]) == 0
    assert int(counters["held"].sum() + counters["absent"]) == T * K
    # the first chunk's products visit the tiles that hold rows, a tile two experts share once for each
    assert chunk_rows(T, K, len(held), E, 8) == ROWS
    assert int(counters["row_tiles"]) == row_tiles_of(counters["held"])
    assert -(-int(counters["held"].sum()) // ROWS) == (2 if skew else 1)  # the chunks that held live rows
    if skew:  # 3T = 144 assignments landed against chunks of 72 rows: both chunks ran
        assert int(counters["held"].sum()) == 3 * T
        assert int(counters["held"][:3].sum()) == 3 * T
        assert int(counters["row_tiles"]) == 9  # the first chunk is full: the first expert's 48 rows and 24 of the second's
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(routed(*a, held)[0])), argnums=(0, 1, 2, 3))(
        x, router, w_in[slots], w_out[slots]
    )
    plain = jax.grad(lambda *a: jnp.sum(jnp.sin(plain_experts(*a, held))), argnums=(0, 1, 2, 3))(
        x, router, w_in[slots], w_out[slots]
    )
    assert worst_relative(grads, plain) < 1e-4


def test_the_shares_of_sixteen_ranks_and_the_shared_expert_once_equal_the_uncut_layer():
    """The model-configs guide's share test: each of 16 ranks holds one of
    the 16 experts and routes over all of them; the routed parts of all
    ranks, with the shared expert every rank computes alike counted once,
    add up to the whole layer as the plain reference computes it uncut."""
    x, router, w_in, w_out = expert_layer(seed=3)
    shared_in = jax.random.normal(jax.random.PRNGKey(7), (D, 2 * F)) * 0.2
    shared_out = jax.random.normal(jax.random.PRNGKey(8), (2 * F, D)) * 0.2
    parts, landed = jnp.zeros_like(x), 0
    for rank in range(E):
        part, counters = routed(x, router, w_in[rank:rank + 1], w_out[rank:rank + 1], (rank,))
        parts, landed = parts + part, landed + int(counters["held"].sum())
        assert int(counters["absent"]) + int(counters["held"].sum()) == T * K
        assert int(counters["row_tiles"]) == -(-int(counters["held"][0]) // 8)  # one expert: its rows in whole tiles
        assert chunk_rows(T, K, 1, E, 8) == T >= int(counters["held"].sum())  # a sixteenth held: T rows, one chunk
    assert landed == T * K  # every assignment landed on exactly one rank
    shared = jnp.dot(jnp.square(jax.nn.relu(jnp.dot(x, shared_in, precision="highest"))), shared_out, precision="highest")
    uncut = {
        "router": router, "experts_in": w_in, "experts_out": w_out,
        "shared_in": {"kernel": shared_in}, "shared_out": {"kernel": shared_out},
    }
    cfg = {"num_experts_per_tok": K, "routed_scaling_factor": 2.5, "held_experts": list(range(E))}
    with jax.default_matmul_precision("highest"):
        want = reference._experts(x, uncut, cfg)
    np.testing.assert_allclose(parts + shared, want, rtol=2e-4, atol=2e-5)


def test_expert_layer_inside_shard_map_skips_and_runs_chunks_per_worker():
    """Two data-parallel workers, one with an even load (its later chunks
    are skipped) and one skewed (all run): the skipped chunk's zeros must
    vary over the mesh as a computed chunk does."""
    from jax.sharding import Mesh, PartitionSpec as P

    even, skewed = expert_layer(skew=0.0), expert_layer(skew=4.0)
    x = jnp.stack([even[0], skewed[0]])
    router = jnp.stack([even[1], skewed[1]])
    held = (0, 1, 2, 7, 9)
    w_in, w_out = even[2][jnp.asarray(held)], even[3][jnp.asarray(held)]
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def worker(x, router, w_in, w_out):
        out, counters = routed(x[0], router[0], w_in, w_out, held)
        return out[None], counters["dropped"][None], counters["row_tiles"][None], counters["held"][None]

    got, dropped, row_tiles, landed = jax.jit(jax.shard_map(
        worker, mesh=mesh, in_specs=(P("data"), P("data"), P(), P()), out_specs=P("data"),
    ))(x, router, w_in, w_out)
    for w in range(2):
        np.testing.assert_allclose(got[w], plain_experts(x[w], router[w], w_in, w_out, held), rtol=2e-4, atol=2e-5)
        assert int(row_tiles[w]) == row_tiles_of(landed[w])  # each worker's own count
    assert not dropped.any() and int(row_tiles[1]) == 9


def test_the_layer_over_a_mesh_axis_is_not_built_yet():
    x, router, w_in, w_out = expert_layer()
    with pytest.raises(NotImplementedError):
        held_experts_moe(x, x, router, jnp.zeros((E,)), w_in[:2], w_out[:2], (0, 1), K, axis_name="expert")


# ---- attention ---------------------------------------------------------------


@pytest.mark.parametrize("n_kv_heads", [2, 1, 4])
def test_grouped_query_attention_through_the_flash_kernel_at_head_dim_128(n_kv_heads):
    """32-over-2 heads in the model is 4-over-2 here (and over 1, and over
    4); head_dim 128 as published; the kernel in interpret mode, reading
    each shared key/value head in place, against materialised weights on
    repeated K/V."""
    cfg = dict(hidden_size=64, n_heads=4, n_kv_heads=n_kv_heads, head_dim=128)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 64))
    flash = GroupedQueryAttention(NemotronHConfig(attn_impl="flash", **cfg), 0.02)
    naive = GroupedQueryAttention(NemotronHConfig(attn_impl="einsum", **cfg), 0.02)
    params = naive.init(jax.random.PRNGKey(1), x)

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    # K and V reach the kernel with the heads they have: nothing is repeated
    traced = jax.make_jaxpr(flash.apply)(params, x).jaxpr
    kernels = [e for e in equations(traced) if e.primitive.name == "pallas_call"]
    assert [[v.aval.shape for v in e.invars[:3]] for e in kernels] == [
        [(2, 256, 4 * 128)] + [(2, 256, n_kv_heads * 128)] * 2
    ]
    np.testing.assert_allclose(flash.apply(params, x), naive.apply(params, x), rtol=2e-4, atol=2e-5)
    # causal: a later token leaves the earlier outputs alone
    moved = flash.apply(params, x.at[:, 200:].add(1.0))
    np.testing.assert_allclose(moved[:, :200], naive.apply(params, x)[:, :200], rtol=2e-4, atol=2e-5)
    loss = lambda module: lambda p, x: jnp.sum(jnp.sin(module.apply(p, x)))
    got = jax.grad(loss(flash), argnums=(0, 1))(params, x)
    want = jax.grad(loss(naive), argnums=(0, 1))(params, x)
    assert worst_relative(got, want) < 1e-3
