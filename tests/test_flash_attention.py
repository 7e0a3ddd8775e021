"""Pallas flash attention (interpret mode on CPU) vs naive einsum attention:
plain, padding-masked, causal, and causal+masked; bf16 inputs; the GPT
attn_impl="flash" path; the forward kernel and the backward kernel's dq,
dk, dv and dmask at the tiles the chip runs (``tile_edge``), alone and
inside ``shard_map``; every lane block ``heads_per_block`` picks, the fold
it falls back to and grouped key/value heads; sliding windows under, at and
across the tile edge (kinds named ``window<N>``); a value head wider or
narrower than the query/key head (heads named ``(H, Hkv, D, Dv)``): one head
a block where both widths are lane blocks, else the fold; and that nothing is
turned or folded round the kernels where a lane block serves."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.ops.flash_attention import (
    flash_attention,
    heads_per_block,
    tile_edge,
)

B, T, H, D = 2, 32, 4, 16


def _qkv(seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda k: jax.random.normal(k, (B, T, H, D), dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


def _window_of(kind: str):
    """The window a case's kind names (``window96+soft_bias`` -> 96), or None."""
    found = re.search(r"window(\d+)", kind)
    return int(found.group(1)) if found else None


def _naive(q, k, v, mask=None, causal=False, window=None):
    # fewer key/value heads: each serves a group of query heads
    k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(q.shape[-1])
    if mask is not None:
        s = s + mask[:, None, None, :]
    if causal or window:
        t = q.shape[1]
        behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]  # query - key
        seen = (behind >= 0) & (behind < (window or t))
        s = jnp.where(seen[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
def test_flash_matches_naive(devices, causal, masked):
    q, k, v = _qkv(0)
    mask = None
    if masked:
        m = np.zeros((B, T), np.float32)
        m[0, 24:] = -1e30  # padded tail on row 0
        mask = jnp.asarray(m)
    ref = _naive(q, k, v, mask=mask, causal=causal)
    out = flash_attention(
        q, k, v, mask=mask, causal=causal, block_q=8, block_k=8, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_bf16(devices):
    q, k, v = _qkv(1, jnp.bfloat16)
    ref = _naive(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=8, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=5e-2, atol=5e-2
    )


def test_flash_uneven_blocks(devices):
    """block_q != block_k and blocks that don't align with the causal
    diagonal still give exact results."""
    q, k, v = _qkv(2)
    ref = _naive(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=4, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_gpt_flash_attention_path(devices):
    from network_distributed_pytorch_tpu.models.gpt import gpt_tiny

    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 32)), jnp.int32)
    base = gpt_tiny(max_position_embeddings=32)
    params = base.init(jax.random.PRNGKey(0), ids)["params"]
    ref = base.apply({"params": params}, ids)

    flash = gpt_tiny(max_position_embeddings=32, attn_impl="flash")
    out = flash.apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_distilbert_flash_attention_path(devices):
    from network_distributed_pytorch_tpu.models.distilbert import (
        DistilBertConfig,
        DistilBertEncoder,
    )

    cfg = dict(
        vocab_size=64, max_position_embeddings=32, dim=16, n_layers=2,
        n_heads=4, hidden_dim=32, dropout=0.0, attention_dropout=0.0,
    )
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)), jnp.int32)
    amask = jnp.ones_like(ids).at[0, 24:].set(0)  # padded tail
    base = DistilBertEncoder(DistilBertConfig(**cfg))
    params = base.init(jax.random.PRNGKey(0), ids, amask)["params"]
    ref = base.apply({"params": params}, ids, amask)

    flash = DistilBertEncoder(DistilBertConfig(**cfg, attn_impl="flash"))
    out = flash.apply({"params": params}, ids, amask)
    np.testing.assert_allclose(
        np.asarray(out[:, :24]), np.asarray(ref[:, :24]), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("pad_value", [-1e30, -3.4e38], ids=["neg1e30", "f32min"])
def test_flash_fully_masked_rows(devices, pad_value):
    """An ALL-padded row must emit exactly zero output and leak NO gradient
    into the padded K/V — for both the package's -1e30 convention and the
    f32-min masks DistilBertEncoder emits (round-1 advisor finding: -1e30
    ties the running-max init, so exp doesn't underflow)."""
    q, k, v = _qkv(4)
    m = np.zeros((B, T), np.float32)
    m[0, :] = pad_value  # batch row 0: EVERY key padded
    mask = jnp.asarray(m)

    out = flash_attention(q, k, v, mask=mask, block_q=8, block_k=8, interpret=True)
    assert np.all(np.asarray(out[0]) == 0.0), "all-masked row output must be 0"
    assert np.all(np.isfinite(np.asarray(out)))

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, mask=mask, block_q=8, block_k=8, interpret=True
            ) ** 2
        )

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert np.all(np.asarray(dq[0]) == 0.0)
    assert np.all(np.asarray(dk[0]) == 0.0), "grad leaked into padded K"
    assert np.all(np.asarray(dv[0]) == 0.0), "grad leaked into padded V"
    # the unpadded batch row still gets real gradients
    assert np.any(np.asarray(dv[1]) != 0.0)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_gradients_match_naive(devices, causal):
    """The custom-VJP backward kernel at tiny blocks (4x4 of them) vs
    jax.grad through naive attention, mask rows partially padded."""
    q, k, v = _qkv(3)
    m = np.zeros((B, T), np.float32)
    m[1, 28:] = -1e30
    mask = jnp.asarray(m)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, mask=mask, causal=causal, block_q=8, block_k=8,
                interpret=True,
            )
            ** 2
        )

    def loss_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, mask=mask, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(g_flash, g_naive):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=5e-4, atol=5e-4
        )


# --- the forward kernel at the tiles the chip runs (default blocks) -----------

FWD_KINDS = ("full", "mask", "causal", "causal+mask")
FWD_CASES = [
    pytest.param(t, kind, dtype, id=f"T{t}-{kind}-{dtype.__name__}")
    for t in (96, 128, 512, 640)  # 96: one block under 128; 640: 5x5 tiles of 128
    for kind in FWD_KINDS
    for dtype in (jnp.float32, jnp.bfloat16)
] + [
    # 5x5 tiles of 128: a window under the tile, the tile, and across two
    pytest.param(640, kind, dtype, id=f"T640-{kind}-{dtype.__name__}")
    for kind in ("window100", "window128", "window300")
    for dtype in (jnp.float32, jnp.bfloat16)
]


@pytest.mark.parametrize("t,kind,dtype", FWD_CASES)
def test_flash_forward_kernel_matches_naive(devices, t, kind, dtype):
    """The forward with its default blocks against naive fp32 attention on
    the same inputs. bf16 inputs are held to the backward's 1.5% of the
    largest entry: q, k, p and v go into their products as bf16 (fp32
    accumulation) and the output is rounded to bf16 once. The masked cases
    carry a padded tail that splits a block and one fully padded sequence,
    which must come out exactly zero."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(key, (BWD_B, t, BWD_H, BWD_D), dtype) for key in ks)
    mask, live = None, np.ones(BWD_B, bool)
    if "mask" in kind:
        m = np.zeros((BWD_B, t), np.float32)
        m[0, :] = np.finfo(np.float32).min  # what DistilBertEncoder emits
        m[1, t - t // 4 - 3:] = -1e30
        mask, live[0] = jnp.asarray(m), False
    window = _window_of(kind)
    out = flash_attention(q, k, v, mask=mask, causal="causal" in kind, window=window, interpret=True)
    want = _naive(q, k, v, mask=mask, causal="causal" in kind, window=window)
    assert out.dtype == dtype and out.shape == q.shape
    got, want = np.asarray(out, np.float32), np.asarray(want)
    # naive softmax spreads an all-padded row evenly; the kernel gives nothing
    assert np.all(got[~live] == 0.0)
    tol = 1.5e-2 if dtype == jnp.bfloat16 else 2e-5
    assert np.all(np.isfinite(got))
    assert np.abs(got[live] - want[live]).max() <= tol * np.abs(want[live]).max()


def _outside_the_kernels(jaxpr):
    """Every equation of a jaxpr and of what it calls, the bodies of the
    ``pallas_call``s left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _outside_the_kernels(sub)


def _kernel_blocks(jaxpr):
    """{kernel name: the block shape of each operand and output} of every
    ``pallas_call`` in a jaxpr; the forward kernel has no name."""
    return {
        eqn.params["name"]: [
            tuple(dim.block_size for dim in m.block_shape)
            for m in eqn.params["grid_mapping"].block_mappings
        ]
        for eqn in _outside_the_kernels(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call"
    }


@pytest.mark.parametrize(
    "t,edge", [(512, 512), (1024, 512), (768, 256), (640, 128), (96, 96)]
)
def test_tile_edge_is_shared_by_forward_and_backward(devices, t, edge):
    """One rule gives the tile from T, and both kernels are built on it:
    the forward walks Q in blocks of that edge and emits the lse so, the
    backward takes the lse as rows of that edge and the mask too."""
    assert tile_edge(t) == edge
    q = jax.ShapeDtypeStruct((1, t, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=True).astype(jnp.float32).sum()

    kernels = _kernel_blocks(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert sorted(kernels, key=str) == [None, "flash_attention_bwd"]
    # two heads of 64 are one block of 128 lanes
    q_block, _, _, mask_rows, _, lse_block = kernels[None]
    assert (q_block, lse_block) == ((1, edge, 128), (1, 2, 1, edge))
    assert mask_rows == (1, t // edge, edge)
    _, _, _, _, _, lse_rows, mask_rows = kernels["flash_attention_bwd"][:7]
    assert lse_rows == (1, 2, t // edge, edge)
    assert mask_rows == (1, t // edge, edge)


# --- the backward kernel at the tile sizes the chip runs (T up to 512) -------

BWD_B, BWD_H, BWD_D = 2, 2, 64
MASK_KINDS = ("padding", "causal", "both", "padded_row", "soft_bias")
BWD_CASES = [
    pytest.param(256, kind, dtype, id=f"T256-{kind}-{dtype.__name__}")
    for kind in MASK_KINDS
    for dtype in (jnp.float32, jnp.bfloat16)
] + [
    pytest.param(t, "both", dtype, id=f"T{t}-both-{dtype.__name__}")
    for t in (128, 384, 512, 64)  # 384: 3x3 tiles of 128; 64: tile = T < 128
    for dtype in (jnp.float32, jnp.bfloat16)
] + [
    # 3x3 tiles of 128: a window under the tile, the tile, and across two
    pytest.param(384, kind, dtype, id=f"T384-{kind}-{dtype.__name__}")
    for kind in ("window100", "window128+soft_bias", "window200+padding")
    for dtype in (jnp.float32, jnp.bfloat16)
]


def _bwd_inputs(t, kind, dtype, seed=5):
    """q, k, v, the weights of the scalar loss, the additive mask and
    whether attention is causal, for one kind of mask (a ``window<N>`` in
    the kind is read by the caller: ``_window_of``)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v, w = (
        jax.random.normal(key, (BWD_B, t, BWD_H, BWD_D), dtype) for key in ks[:4]
    )
    m = np.zeros((BWD_B, t), np.float32)
    if kind in ("padding", "both"):
        m[1, t - t // 4 - 3:] = -1e30  # a padded tail that splits a block
    elif "window" in kind and "padding" in kind:
        m[1, 120:150] = -1e30  # padding inside the sequence: every window still sees a key
    elif kind == "padded_row":
        m[0, :] = np.finfo(np.float32).min  # what DistilBertEncoder emits
        m[1, t // 2:] = -1e30
    elif "soft_bias" in kind:
        m = np.asarray(jax.random.normal(ks[4], (BWD_B, t)), np.float32)
    return q, k, v, w, jnp.asarray(m), kind in ("causal", "both")


def _bwd_grads(t, kind, dtype):
    q, k, v, w, mask, causal = _bwd_inputs(t, kind, dtype)

    def loss(attend):
        def f(q, k, v, mask):
            out = attend(q, k, v, mask=mask, causal=causal, window=_window_of(kind))
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))
        return f

    flash = functools.partial(flash_attention, interpret=True)
    got = jax.grad(loss(flash), argnums=(0, 1, 2, 3))(q, k, v, mask)
    want = jax.grad(loss(_naive), argnums=(0, 1, 2, 3))(q, k, v, mask)
    return got, want, mask


@pytest.mark.parametrize("t,kind,dtype", BWD_CASES)
def test_flash_backward_kernel_matches_naive(devices, t, kind, dtype):
    """dq, dk, dv and the mask's cotangent from the Pallas backward against
    jax.grad through naive fp32 attention on the same inputs. bf16 inputs
    are held to 1.5% of each gradient's largest entry, two bf16 roundings:
    on these cases the kernel measures up to 0.68% (p and dS go into their
    products as bf16, as the scan's einsums did on the TPU at default
    precision) and the XLA scan it replaced measured up to 0.45% here on
    the CPU (true fp32 products)."""
    got, want, mask = _bwd_grads(t, kind, dtype)
    tol = 1.5e-2 if dtype == jnp.bfloat16 else 2e-5
    live = np.ones(BWD_B, bool)
    if kind == "padded_row":
        # naive softmax spreads an all-padded row evenly; the kernel's
        # contract is that such a row gives and receives nothing
        live[0] = False
        for g in got:
            assert np.all(np.asarray(g[0], np.float32) == 0.0)
    for name, a, e in zip(("dq", "dk", "dv", "dmask"), got, want):
        assert a.dtype == e.dtype, name
        a, e = np.asarray(a, np.float32)[live], np.asarray(e, np.float32)[live]
        assert np.all(np.isfinite(a)), name
        assert np.abs(a - e).max() <= tol * np.abs(e).max(), name
    # nothing leaks into hard-padded keys
    padded = np.asarray(mask) <= -1e29
    for g in got[1:3]:
        assert np.all(np.asarray(g, np.float32)[padded] == 0.0)
    assert np.all(np.asarray(got[3])[padded] == 0.0)


@pytest.mark.parametrize("masked", [True, False], ids=["batch-mask", "nomask"])
def test_flash_backward_kernel_inside_shard_map(devices, masked):
    """The x4 cell's path: the kernel inside ``shard_map`` over the data
    axis, with the batch's own mask and with the one the kernel makes
    itself. Typed as the trainer types it (``check_vma``: every
    ``out_shape`` of both kernels has to say how it varies over the mesh),
    which the trace alone decides; then run, which the Pallas interpreter
    can do only unchecked (its own block slicing mixes varying arrays with
    invariant indices; Mosaic's compile of the checked program is
    ``test_tpu_lowering``'s)."""
    from jax.sharding import PartitionSpec as P

    from network_distributed_pytorch_tpu.parallel import make_mesh

    mesh = make_mesh(devices=devices[:2])
    q, k, v, w, mask, _ = _bwd_inputs(128, "padding", jnp.float32)

    def local_loss(attend, q, k, v, w, mask):
        out = attend(q, k, v, mask=mask if masked else None, causal=True)
        return jnp.sum(out.astype(jnp.float32) * w)

    flash = functools.partial(flash_attention, interpret=True)

    def sharded(check_vma):
        return jax.shard_map(
            jax.grad(functools.partial(local_loss, flash), argnums=(0, 1, 2)),
            mesh=mesh, in_specs=(P("data"),) * 5, out_specs=(P("data"),) * 3,
            check_vma=check_vma,
        )

    typed = jax.make_jaxpr(sharded(True))(q, k, v, w, mask)
    assert str(typed).count("flash_attention_bwd") == 1
    got = jax.jit(sharded(False))(q, k, v, w, mask)
    want = jax.grad(functools.partial(local_loss, _naive), argnums=(0, 1, 2))(
        q, k, v, w, mask
    )
    for a, e in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=2e-4, atol=2e-5
        )


# --- lane blocks, the fold and grouped key/value heads -----------------------

# (H, Hkv, D) -> heads a lane block: all of them where they fit 128 lanes,
# 128 // D where they fill 128 exactly, one where D is a multiple of 128
# (then fewer key/value heads are read in place), else None: the fold
LANE_RULE = [
    ((2, 2, 16), 2), ((4, 4, 64), 2), ((12, 12, 64), 2), ((2, 2, 128), 1),
    ((4, 2, 128), 1), ((4, 1, 128), 1), ((32, 2, 128), 1), ((1, 1, 96), 1),
    ((3, 3, 64), None), ((2, 2, 96), None), ((4, 2, 16), None),
]
# (H, Hkv, D, Dv), a value head of its own width: one head a block where both
# widths are multiples of 128, else the fold — a pair of 64 a lane block
# (4, 4, 64) among them, whose block has one width
VALUE_WIDTH_RULE = [
    ((2, 2, 128, 256), 1), ((4, 2, 128, 256), 1), ((4, 2, 256, 128), 1),
    ((4, 2, 16, 32), None), ((8, 4, 64, 128), None), ((4, 4, 64, 128), None),
    ((2, 2, 16, 8), None), ((2, 1, 128, 64), None), ((4, 4, 64, 64), 2),
]


@pytest.mark.parametrize("heads,per_block", LANE_RULE + VALUE_WIDTH_RULE, ids=str)
def test_heads_per_block(heads, per_block):
    assert heads_per_block(*heads) == per_block


LANE_T = 256  # 2x2 tiles of 128: both loops of both kernels turn
LANE_CASES = [
    pytest.param(heads, kind, dtype, id=f"H{heads[0]}kv{heads[1]}D{heads[2]}-{kind}-{dtype.__name__}")
    for heads, _ in LANE_RULE
    if heads[0] <= 12 and heads != (1, 1, 96)
    for kind in ("padding", "causal", "both")
    for dtype in (jnp.float32, jnp.bfloat16)
] + [
    # sliding windows over every way heads are addressed, Trinity's 32 query
    # heads over 4 key/value heads among them, at a narrow head (the fold)
    # and at 128 (read in place): under the tile, the tile, across two tiles
    pytest.param(heads, kind, jnp.float32, id=f"H{heads[0]}kv{heads[1]}D{heads[2]}-{kind}-float32")
    for heads in ((4, 4, 64), (2, 2, 16), (4, 2, 128), (32, 4, 16), (32, 4, 128))
    for kind in ("window96", "window128+padding", "window200+padding")
] + [
    # a value head of its own width, through the fold and one head a block
    pytest.param(heads, kind, dtype, id=f"H{heads[0]}kv{heads[1]}D{heads[2]}v{heads[3]}-{kind}-{dtype.__name__}")
    for heads, _ in VALUE_WIDTH_RULE
    if heads[2] != heads[3]
    for kind in ("padding", "causal", "both")
    for dtype in (jnp.float32, jnp.bfloat16)
] + [
    pytest.param(heads, kind, dtype, id=f"H{heads[0]}kv{heads[1]}D{heads[2]}v{heads[3]}-{kind}-{dtype.__name__}")
    for heads in ((4, 2, 16, 32), (4, 2, 128, 256), (8, 4, 64, 128))
    for kind in ("window96", "window128+padding", "window200+padding")
    for dtype in (jnp.float32, jnp.bfloat16)
]


@pytest.mark.parametrize("heads,kind,dtype", LANE_CASES)
def test_flash_lane_blocks_match_naive(devices, heads, kind, dtype):
    """The output and all four cotangents against naive fp32 attention for
    every way the kernels address heads: all in one lane block, pairs of 64,
    one head of 128 a block, the fold where no lane block serves, fewer
    key/value heads than query heads (naive attention on repeated K/V, so
    its dK and dV are summed over each group), and a value head of its own
    width ``heads[3]``: the output and dV that wide, the scale the
    query/key head's."""
    h, hkv, d, dv = heads if len(heads) == 4 else heads + heads[2:]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, w, k, v = (
        jax.random.normal(key, (BWD_B, LANE_T) + head, dtype)
        for key, head in zip(ks, ((h, d), (h, dv), (hkv, d), (hkv, dv)))
    )
    m, window = np.zeros((BWD_B, LANE_T), np.float32), _window_of(kind)
    if window and "padding" in kind:
        m[1, 100:130] = -1e30  # padding inside the sequence: every window still sees a key
    elif kind in ("padding", "both"):
        m[1, LANE_T - LANE_T // 4 - 3:] = -1e30  # a padded tail that splits a block
    mask, causal = jnp.asarray(m), kind != "padding"

    def run(attend):
        out, vjp = jax.vjp(
            lambda q, k, v, mask: attend(q, k, v, mask=mask, causal=causal, window=window).astype(jnp.float32),
            q, k, v, mask,
        )
        return (out,) + vjp(w.astype(jnp.float32))

    flash = functools.partial(flash_attention, block_q=128, block_k=128, interpret=True)
    tol = 1.5e-2 if dtype == jnp.bfloat16 else 2e-5
    for name, a, e in zip(("out", "dq", "dk", "dv", "dmask"), run(flash), run(_naive)):
        assert a.shape == e.shape and a.dtype == e.dtype, name
        a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
        assert np.all(np.isfinite(a)), name
        assert np.abs(a - e).max() <= tol * np.abs(e).max(), name


def _jaxpr_text(**kwargs):
    """The gradient program of one small causal call, as text."""
    q = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=128, block_k=128, interpret=True, **kwargs).sum()

    return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))


@pytest.mark.parametrize("window", [256, 257, 4096], ids=lambda w: f"window{w}")
def test_a_window_that_covers_the_sequence_is_the_causal_program(devices, window):
    """``window >= T`` hides nothing ``causal=True`` shows: the same jaxpr
    (no lower loop bound, no second compare), so the same bits, forward and
    all three gradients."""
    assert _jaxpr_text(window=window) == _jaxpr_text(causal=True)
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q, k, v, w = (jax.random.normal(key, (2, 256, 2, 64)) for key in ks)

    def run(**kwargs):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, interpret=True, **kwargs), q, k, v)
        return (out,) + vjp(w)

    for got, want in zip(run(window=window), run(causal=True)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_no_window_leaves_both_kernels_as_they_were(devices):
    """``window=None`` is not a code path: the program equals the one traced
    with the argument left out, causal or not, and a real window changes it
    (a lower bound on the forward's K loop, an upper on the backward's Q
    loop, one more compare a tile)."""
    assert _jaxpr_text(window=None) == _jaxpr_text()
    assert _jaxpr_text(causal=True, window=None) == _jaxpr_text(causal=True)
    assert _jaxpr_text(window=100) != _jaxpr_text(causal=True)
    assert _jaxpr_text(window=100) == _jaxpr_text(causal=True, window=100)  # a window is causal
    with pytest.raises(ValueError):
        _jaxpr_text(window=0)


@pytest.mark.parametrize(
    "b,t,h,hkv,d,dv,causal",
    [(48, 512, 12, 12, 64, 64, False), (1, 8192, 32, 2, 128, 128, True), (1, 4096, 8, 2, 128, 256, True)],
    ids=["imdb", "nemotron", "value-256-over-128"],
)
def test_nothing_is_turned_or_folded_round_the_kernels(b, t, h, hkv, d, dv, causal):
    """At the cells' per-layer shapes the forward and the backward are the
    two kernels on the model's own layout: no ``transpose`` outside them, no
    (B*H, T, D) array, and K and V go in with the heads they have, V at its
    own width where that is a lane block too."""
    q, k, v = (jax.ShapeDtypeStruct((b, t, n, width), jnp.bfloat16) for n, width in ((h, d), (hkv, d), (hkv, dv)))
    # imdb's batches bring their padding mask, nemotron's are packed
    args = (q, k, v) if causal else (q, k, v, jax.ShapeDtypeStruct((b, t), jnp.float32))

    def loss(q, k, v, mask=None):
        out = flash_attention(q, k, v, mask=mask, causal=causal)
        return out.astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args)
    eqns = list(_outside_the_kernels(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in kernels] == [None, "flash_attention_bwd"]
    assert not [e for e in eqns if e.primitive.name == "transpose"]
    shapes = {v.aval.shape for e in eqns for v in list(e.invars) + list(e.outvars)}
    assert (b * h, t, d) not in shapes
    for kernel in kernels:
        assert [v.aval.shape for v in kernel.invars[:3]] == [
            (b, t, h * d), (b, t, hkv * d), (b, t, hkv * dv)
        ]
    assert kernels[0].outvars[0].aval.shape == (b, t, h * dv)


def test_a_value_width_of_its_own_folds_a_pair_of_64(devices):
    """(H, Hkv, D) = (4, 4, 64) is a pair of heads a lane block; with a value
    head of 128 beside it no lane block has one width, so the call takes the
    fold, one head a row, q and k at 64 lanes and v at 128: the program of
    ``phi4flash_psgd16_t8k``'s call, in small."""
    q, v = (jax.ShapeDtypeStruct((2, 256, 4, width), jnp.float32) for width in (64, 128))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v)
    kernels = _kernel_blocks(jaxpr)
    q_block, k_block, v_block, _, o_block, _ = kernels[None]
    assert (q_block, k_block, v_block, o_block) == ((1, 256, 64), (1, 256, 64), (1, 256, 128), (1, 256, 128))
    blocks = kernels["flash_attention_bwd"]
    assert blocks[:5] == [(1, 256, 64), (1, 256, 64), (1, 256, 128), (1, 256, 128), (1, 256, 128)]  # q, k, v, o, do
    assert blocks[7:10] == [(1, 256, 64), (1, 256, 64), (1, 256, 128)]  # dq, dk, dv
    shapes = {x.aval.shape for e in _outside_the_kernels(jaxpr.jaxpr) for x in list(e.invars) + list(e.outvars)}
    assert {(8, 256, 64), (8, 256, 128)} <= shapes
