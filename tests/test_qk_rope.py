"""The per-head norm of q and k and their rotary turn as one pass
(``ops/qk_rope.py``, through ``models/layers.normed_and_turned``) on the CPU
at small sizes: the two Pallas kernels in the interpreter against the XLA
lines the four attention modules had (``RMSNorm``, ``rotary``, the cast) under
jax's own differentiation of those lines — values and all four cotangents (q,
k, both learned scales) — at the cells' six call shapes cut to test widths:
the whole head turned, the norm alone, YaRN with its factor, two heads of 64 a
lane block, a quarter of a head of 256 turned under a zero-centred scale; three
tiles of T and two groups of heads, so that the scales' cotangents are summed
over both; fp32 and bf16. And what the backend selects: where the kernels do
not serve, or off the chip, the pass is the parent's lines to the bit, and the
parameter tree is the parent's either way.

Tolerances: with fp32 inputs 1e-5 of the largest entry. A bf16 pass computes
in fp32 and rounds once, as the lines do, so against the fp32 lines on the
same (bf16) inputs every element is within one bf16 ulp of its own size."""

import functools
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.layer_metrics.passes import pass_of
from network_distributed_pytorch_tpu.models import afmoe, lfm2, mellum, qwen3_next
from network_distributed_pytorch_tpu.models.layers import FULL, SLIDING, RMSNorm, Rope, normed_and_turned, rotary
from network_distributed_pytorch_tpu.ops import qk_rope
from network_distributed_pytorch_tpu.utils.hlo_audit import hlo_text_of_compiled

BF16_ULP = 2.0 ** -7  # the spacing of bf16 just above 1
FP32_LIMIT = 1e-5  # of the largest entry
EPS, T = 1e-6, 48
YARN = Rope(5e5, 16.0, 32, attention_factor=1.2772588722239782)

# (query heads, key heads, head, rope, rotary_dim, zero-centred scale): the cells' six call shapes at test widths
SHAPES = [
    pytest.param(4, 2, 128, Rope(1e4), None, False, id="trinity_sliding_whole_head"),
    pytest.param(4, 2, 128, None, None, False, id="trinity_full_norm_alone"),
    pytest.param(8, 2, 128, Rope(5e5), None, False, id="mellum2_sliding_whole_head"),
    pytest.param(8, 2, 128, YARN, None, False, id="mellum2_full_yarn"),
    pytest.param(8, 4, 64, Rope(1e6), None, False, id="lfm2_two_heads_a_lane_block"),
    pytest.param(4, 2, 256, Rope(1e7), 64, True, id="qwen3next_quarter_of_256_zero_centred"),
]


class Pass(nn.Module):
    """The scope ``attn.rope`` of an attention module, alone."""

    rope: Rope
    rotary_dim: int
    zero_centred: bool
    dtype: jnp.dtype
    interpret: bool = None

    @nn.compact
    def __call__(self, q, k):
        norms = [RMSNorm(EPS, self.zero_centred, name=name) for name in ("q_norm", "k_norm")]
        return normed_and_turned(*norms, q, k, self.rope, self.dtype, self.rotary_dim, self.interpret)


def parents_lines(params, q, k, rope, rotary_dim, zero_centred, dtype):
    """What the four call sites did before PR 46, line for line."""
    def one(x, scale):
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * (1.0 + scale if zero_centred else scale)
        return (x if rope is None else rotary(x, rope, rotary_dim)).astype(dtype)

    return one(q, params["q_norm"]["scale"]), one(k, params["k_norm"]["scale"])


def inputs(hq, hk, d, dtype, t=T, bsz=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (bsz, t, h, d)).astype(dtype) for key, h in zip(ks, (hq, hk)))
    # every scale off its initial value, so that none drops out of a product
    params = {name: {"scale": 0.3 * jax.random.normal(key, (d,))} for name, key in zip(("q_norm", "k_norm"), ks[2:])}
    cotangents = tuple(jax.random.normal(key, x.shape).astype(dtype) for key, x in zip(ks[4:], (q, k)))
    return params, q, k, cotangents


def both_ways(fn, params, q, k, cotangents):
    """yq, yk and the cotangents of q, k, ``q_norm/scale``, ``k_norm/scale``."""
    y, back = jax.vjp(fn, params, q, k)
    d_params, dq, dk = back(cotangents)
    return [*y, dq, dk, d_params["q_norm"]["scale"], d_params["k_norm"]["scale"]]


NAMES = ["yq", "yk", "d q", "d k", "d q_norm/scale", "d k_norm/scale"]
SUMMED = {"d q_norm/scale", "d k_norm/scale"}  # fp32 sums over B, T and heads: no rounding to the inputs' dtype


def f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.fixture
def tile_of_16(monkeypatch):
    monkeypatch.setattr(qk_rope, "_TILE", 16)


def kernels_and_lines(rope, rotary_dim, zero_centred, dtype):
    """The pass with the kernels in the interpreter, and the parent's lines."""
    served = lambda params, q, k: Pass(rope, rotary_dim, zero_centred, dtype, True).apply({"params": params}, q, k)
    lines = lambda params, q, k: parents_lines(params, q, k, rope, rotary_dim, zero_centred, dtype)
    return served, lines


@pytest.mark.parametrize("hq,hk,d,rope,rotary_dim,zero_centred", SHAPES)
def test_fp32_pass_is_the_xla_lines_and_jaxs_own_cotangents(hq, hk, d, rope, rotary_dim, zero_centred, tile_of_16):
    served, lines = kernels_and_lines(rope, rotary_dim, zero_centred, jnp.float32)
    args = inputs(hq, hk, d, jnp.float32)
    assert "pallas_call" in str(jax.make_jaxpr(served)(*args[:3]))
    for name, a, b in zip(NAMES, both_ways(served, *args), both_ways(lines, *args)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.max(np.abs(f32(a) - f32(b))) <= FP32_LIMIT * np.max(np.abs(f32(b))), name


@pytest.mark.parametrize("hq,hk,d,rope,rotary_dim,zero_centred", SHAPES)
def test_bf16_pass_rounds_once(hq, hk, d, rope, rotary_dim, zero_centred, tile_of_16):
    served, _ = kernels_and_lines(rope, rotary_dim, zero_centred, jnp.bfloat16)
    _, exact_lines = kernels_and_lines(rope, rotary_dim, zero_centred, jnp.float32)
    params, q, k, cotangents = inputs(hq, hk, d, jnp.bfloat16)
    up = lambda x: x.astype(jnp.float32)
    exact = both_ways(exact_lines, params, up(q), up(k), tuple(map(up, cotangents)))
    for name, a, c in zip(NAMES, both_ways(served, params, q, k, cotangents), exact):
        assert a.shape == c.shape, name
        if name in SUMMED:
            assert a.dtype == jnp.float32 and np.max(np.abs(f32(a) - f32(c))) <= 1e-4 * np.max(np.abs(f32(c))), name
        else:  # one rounding: an ulp of the element's own size (and a hair of the largest, for what nearly cancels)
            assert a.dtype == jnp.bfloat16, name
            slack = BF16_ULP * np.abs(f32(c)) + 1e-4 * np.max(np.abs(f32(c)))
            assert np.all(np.abs(f32(a) - f32(c)) <= slack), name


def test_a_sequence_takes_nothing_from_its_neighbour_and_a_position_its_own_angle(tile_of_16):
    """Each sequence of a batch alone gives its rows of the batch's result;
    and rows 16.. of a sequence are turned by positions 16.., not by a tile's
    own count from zero."""
    served, lines = kernels_and_lines(Rope(1e4), None, False, jnp.float32)
    params, q, k, cotangents = inputs(4, 2, 128, jnp.float32)
    together = both_ways(served, params, q, k, cotangents)
    for i in range(2):
        one = slice(i, i + 1)
        alone = both_ways(served, params, q[one], k[one], tuple(c[one] for c in cotangents))
        for name, a, b in zip(NAMES, alone, together):
            if name not in SUMMED:
                np.testing.assert_allclose(a[0], b[i], rtol=0, atol=1e-6, err_msg=name)
    later = lines(params, q, k)[0][:, 16:]
    from_zero = lines(params, q[:, 16:], k[:, 16:])[0]
    assert float(jnp.max(jnp.abs(later - from_zero))) > 0.1  # the lines tell the two apart
    np.testing.assert_allclose(together[0][:, 16:], later, rtol=0, atol=1e-5)


@pytest.mark.parametrize(
    "t,hq,hk,d,rotary_dim,served",
    [
        (8192, 32, 4, 128, 128, True), (8192, 32, 4, 128, 0, True), (8192, 32, 8, 64, 64, True), (8192, 16, 2, 256, 64, True),
        (48, 4, 2, 128, 128, True), (1024, 4, 2, 128, 32, True),
        (8200, 32, 4, 128, 128, False),  # T ends inside a tile
        (40, 4, 2, 128, 128, False),  # the same under one tile: 32 rows and 8 over
        (8, 4, 2, 128, 128, False),  # less than a sublane tile of T
        (8192, 32, 4, 96, 96, False),  # a head no lane block serves
        (8192, 32, 4, 32, 32, False),  # four heads a lane block
        (8192, 32, 3, 64, 64, False),  # the key heads are no whole lane blocks
        (8192, 32, 5, 128, 128, False),  # the query heads are no whole groups
        (8192, 16, 2, 256, 256, False),  # a half in another lane block than its other half
        (8192, 32, 4, 128, 63, False),  # no whole pairs
    ],
)
def test_serves_reads_the_shapes(t, hq, hk, d, rotary_dim, served):
    assert qk_rope.serves(t, hq, hk, d, rotary_dim) is served
    assert (qk_rope.tile_of(8192), qk_rope.tile_of(1000), qk_rope.tile_of(40)) == (512, 512, 32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize(
    "t,d,interpret", [(40, 128, True), (48, 16, True), (48, 128, None)],
    ids=["T_ends_inside_a_tile", "no_kernel_serves_16_lanes", "off_the_chip_at_128"],
)
def test_where_the_kernels_do_not_run_the_pass_is_the_parents_lines_to_the_bit(t, d, interpret, dtype, tile_of_16):
    module = Pass(YARN, None, False, dtype, interpret)
    params, q, k, cotangents = inputs(4, 2, d, dtype, t=t)
    apply = lambda params, q, k: module.apply({"params": params}, q, k)
    assert "pallas_call" not in str(jax.make_jaxpr(apply)(params, q, k))
    lines = lambda params, q, k: parents_lines(params, q, k, YARN, None, False, dtype)
    for a, b in zip(both_ways(apply, params, q, k, cotangents), both_ways(lines, params, q, k, cotangents)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("zero_centred", [False, True], ids=["ones", "zero_centred"])
def test_the_parameter_tree_is_the_parents_whichever_way_the_pass_runs(zero_centred, tile_of_16):
    """``q_norm/scale`` and ``k_norm/scale``, (D,), ones (zeros where the norm
    is zero-centred): the reference compares per tensor by path and
    checkpoints name them."""
    _, q, k, _ = inputs(4, 2, 128, jnp.float32)
    trees = [Pass(Rope(1e4), None, zero_centred, jnp.float32, interpret).init(jax.random.PRNGKey(0), q, k) for interpret in (True, None)]
    assert jax.tree_util.tree_structure(trees[0]) == jax.tree_util.tree_structure(trees[1])
    paths = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(trees[0])]
    assert paths == ["['params']['k_norm']['scale']", "['params']['q_norm']['scale']"]
    for a, b in zip(*map(jax.tree_util.tree_leaves, trees)):
        assert a.shape == (128,) and a.dtype == jnp.float32
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, 0.0 if zero_centred else 1.0)


def test_the_pass_types_inside_shard_map_where_every_step_runs(tile_of_16):
    """The kernels' outputs declare how they vary over the mesh: forward and
    backward per worker under ``check_vma``, the scales cast to varying as the
    trainer casts parameters before it differentiates."""
    from jax.sharding import Mesh, PartitionSpec as P

    served, _ = kernels_and_lines(Rope(1e4), None, False, jnp.float32)
    params, q, k, _ = inputs(4, 2, 128, jnp.float32, t=32)
    loss = lambda params, q, k: sum(jnp.sum(jnp.sin(y)) for y in served(params, q, k))

    def gradients(params, q, k):
        d_params, dq, dk = jax.grad(loss, argnums=(0, 1, 2))(params, q, k)
        return dq, dk, d_params["q_norm"]["scale"][None], d_params["k_norm"]["scale"][None]

    def worker(params, q, k):
        return gradients(jax.tree_util.tree_map(lambda p: jax.lax.pcast(p, "data", to="varying"), params), q, k)

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    sharded = lambda check_vma: jax.shard_map(worker, mesh=mesh, in_specs=(P(), P("data"), P("data")), out_specs=P("data"), check_vma=check_vma)
    typed = str(jax.make_jaxpr(sharded(True))(params, q, k))  # the trace alone decides the types
    assert "qk_rope_bwd" in typed
    # the Pallas interpreter runs only unchecked: its own block slicing mixes varying arrays with invariant indices
    got = jax.jit(sharded(False))(params, q, k)
    want = [jnp.concatenate(x) for x in zip(*(gradients(params, q[i:i + 1], k[i:i + 1]) for i in range(2)))]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(b))))


# ---- a layer of each of the four models --------------------------------------

SMALL = dict(vocab_size=64, hidden_size=64, n_heads=4, n_kv_heads=2, expert_width=32, n_routed_experts=16, held_experts=(0, 1, 2, 3), experts_per_token=2)


def attention_layers():
    """(id, the module whose ``normed_and_turned`` the layer calls, the layer) at heads the kernels serve."""
    cfg = afmoe.AfmoeConfig(**SMALL, head_dim=128, sliding_window=16, dense_width=96)
    yield "afmoe_sliding", afmoe, afmoe.AfmoeAttention(cfg, True, 0.02)
    yield "afmoe_full_no_positions", afmoe, afmoe.AfmoeAttention(cfg, False, 0.02)
    cfg = mellum.MellumConfig(**SMALL, head_dim=128, sliding_window=16, rope_sliding=Rope(5e5), rope_full=YARN)
    yield "mellum_sliding", mellum, mellum.MellumAttention(cfg, SLIDING, 0.02)
    yield "mellum_full_yarn", mellum, mellum.MellumAttention(cfg, FULL, 0.02)
    yield "lfm2_head_64", lfm2, lfm2.Lfm2Attention(lfm2.Lfm2Config(**SMALL, head_dim=64, dense_width=96), 0.02)
    cfg = qwen3_next.Qwen3NextConfig(**SMALL, head_dim=256, shared_expert_width=32, layer_types=(FULL,))
    yield "qwen3next_quarter_of_256", qwen3_next, qwen3_next.GatedAttention(cfg, 0.02)


LAYERS = [pytest.param(module, layer, id=name) for name, module, layer in attention_layers()]


def layer_and_operands(layer, t=32):
    u = jax.random.normal(jax.random.PRNGKey(0), (2, t, layer.config.hidden_size))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    params = jax.tree_util.tree_map(lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape), params)
    return params, u


@pytest.mark.parametrize("module,layer", LAYERS)
def test_a_layer_with_the_kernels_is_the_layer_without_them(module, layer, monkeypatch, tile_of_16):
    """The layer's output and every gradient, the kernels in the interpreter
    against the XLA lines; and with the backend's choice steered as the chip
    makes it, the layer's program holds the two calls, once each."""
    params, u = layer_and_operands(layer)
    loss = lambda p, u: jnp.sum(jnp.sin(layer.apply({"params": p}, u)))
    want = jax.value_and_grad(loss, argnums=(0, 1))(params, u)
    with monkeypatch.context() as on_chip:
        on_chip.setattr(jax, "default_backend", lambda: "tpu")  # what ``interpret=None`` asks
        program = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, u))
    assert sorted(re.findall(r"name=(qk_rope\w*)", program)) == ["qk_rope", "qk_rope_bwd"]
    monkeypatch.setattr(module, "normed_and_turned", functools.partial(normed_and_turned, interpret=True))
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, u)
    off = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), got, want)
    assert max(jax.tree_util.tree_leaves(off)) < 1e-4, off


# ---- the scope ---------------------------------------------------------------


@pytest.mark.parametrize(
    "module,model",
    [
        pytest.param(afmoe, afmoe.afmoe_tiny(head_dim=128, remat=True, layer_types=(SLIDING, FULL)), id="afmoe"),
        pytest.param(mellum, mellum.mellum_tiny(head_dim=128, remat=True, layer_types=(SLIDING, FULL)), id="mellum"),
    ],
)
def test_the_kernels_three_passes_are_under_attn_rope(module, model, monkeypatch, tile_of_16):
    """``attn_rope_ms`` picks a device op by the scope's name anywhere on its
    path (``benchmark/layer_metrics/attn_rope_ms.py``): in the compiled step of
    a small model that recomputes its blocks (the kernels in the interpreter,
    so their ops are XLA instructions with paths), every op of the forward
    kernel is under ``attn.rope`` in the forward and again in the
    recomputation, every op of the backward kernel in the backward, in every
    attention layer."""
    monkeypatch.setattr(module, "normed_and_turned", functools.partial(normed_and_turned, interpret=True))
    ids = jnp.zeros((1, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    state = {name: tree for name, tree in variables.items() if name != "params"}
    loss = lambda params: jnp.sum(jnp.sin(model.apply({"params": params, **state}, ids)[0]))
    program = jax.jit(jax.value_and_grad(loss)).lower(variables["params"]).compile()
    kernel = [p for p in re.findall(r'op_name="([^"]*)"', hlo_text_of_compiled(program)) if "/qk_rope" in p]
    assert kernel and all("attn.rope" in p for p in kernel)
    for layer in ("layer_0", "layer_1"):
        passes = {(pass_of(p), "qk_rope_bwd" in p) for p in kernel if f"/{layer}/" in p}
        assert passes == {("fwd", False), ("remat", False), ("bwd", True)}, (layer, passes)
