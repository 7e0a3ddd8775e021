"""What frames the gated delta rule (``ops/gated_delta_frame.py``) on the CPU
at small sizes: the two Pallas passes and their backward kernels in the
interpreter against the XLA lines the mixer had, under jax's own
differentiation of those lines — values and the cotangent of every input,
``conv_kernel`` and ``norm_scale`` among them; with one and two value heads a
key head, T a multiple of the tile and not, three tiles so that the conv's
reach crosses a tile both ways, two sequences, fp32 and bf16. And what the
backend selects: where the kernels do not serve, or off the chip, the
mixer's output is the parent's lines' to the bit.

Tolerances are in bf16 ulps. A bf16 pass computes in fp32 and rounds once, so
against the fp32 lines on the same (bf16) inputs every element is within one
ulp of its own size; against the bf16 lines, which round after every tap and
after the silu, within ``BF16_LINES_ULPS`` ulps of the output's largest
entry."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.models import qwen3_next
from network_distributed_pytorch_tpu.models.qwen3_next import GatedDeltaNet, Qwen3NextConfig
from network_distributed_pytorch_tpu.ops import gated_delta_frame as frame
from network_distributed_pytorch_tpu.ops.gated_delta import gated_delta_rule
from network_distributed_pytorch_tpu.ops.ssd import causal_conv1d

BF16_ULP = 2.0 ** -7  # the spacing of bf16 just above 1
FP32_LIMIT = 1e-5  # of the largest entry
BF16_LINES_ULPS = 4
D, EPS = 128, 1e-6

# (T, tile): three whole tiles; a last tile of 8 rows whose neighbour's HALO block is partial; one ragged tile of two
LENGTHS = [pytest.param(48, 16, id="3_tiles"), pytest.param(40, 16, id="ragged_3rd_tile"), pytest.param(40, 32, id="ragged_2nd_tile")]


def inputs(t, r, dtype, hk=2, bsz=2, taps=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    qkvz = jax.random.normal(ks[0], (bsz, t, hk * (2 + 2 * r) * D)).astype(dtype)
    conv_kernel = jax.random.uniform(ks[1], (taps, hk * (2 + r) * D), minval=-0.5, maxval=0.5)
    norm_scale = 1.0 + 0.1 * jax.random.normal(ks[2], (D,))
    o = jax.random.normal(ks[3], (bsz, t, hk * r, D)).astype(dtype)
    shapes = [(bsz, t, hk, D), (bsz, t, hk, D), (bsz, t, hk * r, D), (bsz, t, hk * r * D)]
    cotangents = [jax.random.normal(k, s).astype(dtype) for k, s in zip(ks[4:], shapes)]
    return qkvz, conv_kernel, norm_scale, o, cotangents


def flat(x):
    return x.reshape(x.shape[:2] + (-1,))


def lines(hk, r, qkvz, conv_kernel, norm_scale, o, cotangents):
    """The XLA lines and jax's own differentiation of them: q, k, v, y and the
    cotangents of o, qkvz (both passes', summed as jax sums them),
    conv_kernel and norm_scale."""
    qkv, back = jax.vjp(lambda x, w: frame._in_xla(x, w, hk, r, D, D), qkvz, conv_kernel)
    y, back_out = jax.vjp(lambda o, x, s: frame._out_xla(o, x, s, EPS, hk), o, qkvz, norm_scale)
    d_in, d_conv = back(tuple(c.reshape(x.shape) for c, x in zip(cotangents, qkv)))
    d_o, d_out, d_scale = back_out(cotangents[3])
    return [*map(flat, qkv), y, flat(d_o), d_in + d_out, d_conv, d_scale]  # the two write disjoint lanes: the sum is exact


def kernels(hk, r, qkvz, conv_kernel, norm_scale, o, cotangents):
    """The same from the four kernels, in the Pallas interpreter."""
    tile = frame.tile_of(qkvz.shape[1])
    q, k, v = frame._in_forward(hk, r, tile, True, qkvz, conv_kernel)
    y = frame._out_forward(hk, r, tile, True, EPS, flat(o), qkvz, norm_scale)
    d_o, d_z, d_scale = frame._out_backward(hk, r, tile, True, EPS, flat(o), qkvz, norm_scale, cotangents[3])
    d_qkvz, d_conv = frame._in_backward(hk, r, tile, True, qkvz, conv_kernel, *map(flat, cotangents[:3]), d_z)
    return [q, k, v, y, d_o, d_qkvz, d_conv, d_scale]


NAMES = ["q", "k", "v", "y", "d o", "d qkvz", "d conv_kernel", "d norm_scale"]
SUMMED = {"d conv_kernel", "d norm_scale"}  # fp32 sums over B and T (and heads): no rounding to the inputs' dtype


def f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("r", [1, 2], ids=["one_value_head_a_key_head", "two"])
@pytest.mark.parametrize("t,tile", LENGTHS)
def test_fp32_passes_are_the_xla_lines_and_jaxs_own_cotangents(t, tile, r, monkeypatch):
    monkeypatch.setattr(frame, "_TILE", tile)
    args = inputs(t, r, jnp.float32)
    for name, a, b in zip(NAMES, kernels(2, r, *args), lines(2, r, *args)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.max(np.abs(f32(a) - f32(b))) <= FP32_LIMIT * np.max(np.abs(f32(b))), name


@pytest.mark.parametrize("r", [1, 2], ids=["one_value_head_a_key_head", "two"])
@pytest.mark.parametrize("t,tile", LENGTHS)
def test_bf16_passes_round_once_and_stay_within_ulps_of_the_bf16_lines(t, tile, r, monkeypatch):
    monkeypatch.setattr(frame, "_TILE", tile)
    qkvz, conv_kernel, norm_scale, o, cotangents = inputs(t, r, jnp.bfloat16)
    up = lambda x: x.astype(jnp.float32)
    exact = lines(2, r, up(qkvz), conv_kernel, norm_scale, up(o), [up(c) for c in cotangents])
    got, in_bf16 = kernels(2, r, qkvz, conv_kernel, norm_scale, o, cotangents), lines(2, r, qkvz, conv_kernel, norm_scale, o, cotangents)
    for name, a, b, c in zip(NAMES, got, in_bf16, exact):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        if name in SUMMED:
            assert np.max(np.abs(f32(a) - f32(c))) <= 1e-4 * np.max(np.abs(f32(c))), name
        else:  # one rounding: an ulp of the element's own size (and a hair of the largest, for what nearly cancels)
            slack = BF16_ULP * np.abs(f32(c)) + 1e-4 * np.max(np.abs(f32(c)))
            assert np.all(np.abs(f32(a) - f32(c)) <= slack), name
        assert np.max(np.abs(f32(a) - f32(b))) <= BF16_LINES_ULPS * BF16_ULP * np.max(np.abs(f32(b))), name


@pytest.fixture
def tile_of_16(monkeypatch):
    monkeypatch.setattr(frame, "_TILE", 16)


def test_zeros_before_the_sequence_and_nothing_from_the_sequence_before(tile_of_16):
    """The first rows see zeros, not the batch's sequence before; and a
    sequence's cotangent takes nothing from the next one's first rows."""
    qkvz, conv_kernel, norm_scale, o, cotangents = inputs(32, 2, jnp.float32)
    together = kernels(2, 2, qkvz, conv_kernel, norm_scale, o, cotangents)
    for i in range(2):
        one = slice(i, i + 1)
        alone = kernels(2, 2, qkvz[one], conv_kernel, norm_scale, o[one], [c[one] for c in cotangents])
        for name, a, b in zip(NAMES, alone, together):
            if name not in SUMMED:
                np.testing.assert_allclose(a[0], b[i], rtol=0, atol=1e-6, err_msg=name)


def framed(interpret, r, chunk=8):
    rule = functools.partial(gated_delta_rule, chunk=chunk)
    return lambda qkvz, conv_kernel, norm_scale, g, beta: frame.framed_rule(
        rule, qkvz, conv_kernel, norm_scale, g, beta, EPS, 2, r, D, D, interpret=interpret
    )


def rule_inputs(t, r, dtype=jnp.float32):
    qkvz, conv_kernel, norm_scale, _, cotangents = inputs(t, r, dtype)
    kg, kb = jax.random.split(jax.random.PRNGKey(7))
    g = -jax.nn.softplus(jax.random.normal(kg, (2, t, 2 * r)))
    beta = jax.nn.sigmoid(jax.random.normal(kb, (2, t, 2 * r)))
    return (qkvz, conv_kernel, norm_scale, g, beta), cotangents[3]


@pytest.mark.parametrize("r", [1, 2], ids=["one_value_head_a_key_head", "two"])
def test_the_custom_vjp_round_the_rule_is_jaxs_own_of_the_lines_for_every_input(r, tile_of_16):
    """Both passes with the rule between them, as the mixer calls them: the
    value and the cotangents of qkvz, conv_kernel, norm_scale, g and beta."""
    args, dy = rule_inputs(40, r)
    got_y, got_back = jax.vjp(framed(True, r), *args)
    want_y, want_back = jax.vjp(framed(None, r), *args)
    for name, a, b in zip(["y", "d qkvz", "d conv_kernel", "d norm_scale", "d g", "d beta"], [got_y, *got_back(dy)], [want_y, *want_back(dy)]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.max(np.abs(f32(a) - f32(b))) <= 1e-4 * np.max(np.abs(f32(b))), name


@pytest.mark.parametrize(
    "t,r,dk,dv,taps,served",
    [
        (8192, 2, 128, 128, 4, True), (40, 1, 256, 256, 3, True), (16, 2, 128, 128, 17, True),
        (8192, 2, 64, 64, 4, False),  # a head narrower than a lane block
        (8192, 2, 128, 256, 4, False),  # a group's lanes are no whole heads of one width
        (8192, 4, 128, 128, 4, False),  # the v and z lanes of a group start at no multiple of their width
        (15, 2, 128, 128, 4, False),  # less than a sublane tile of T
        (8192, 2, 128, 128, 18, False),  # the conv reaches past one sublane tile
    ],
)
def test_serves_reads_the_shapes(t, r, dk, dv, taps, served):
    assert frame.serves(t, r, dk, dv, taps) is served
    assert (frame.tile_of(8192), frame.tile_of(1000), frame.tile_of(40)) == (512, 512, 32)


def parents_mixer(cfg, params, u32):
    """``GatedDeltaNet.__call__`` as the parent of PR 42 had it, line for line."""
    f32 = jnp.float32
    hk, hv, dk, dv = cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    r, key_dim, value_dim = hv // hk, hk * dk, hv * dv
    dense = lambda x, name: jnp.dot(x, params[name]["kernel"].astype(cfg.dtype))
    u = u32.astype(cfg.dtype)
    bsz, t, _ = u.shape
    qkvz, ba = dense(u, "in_proj_qkvz"), dense(u, "in_proj_ba")
    q, k, v, z = jnp.split(qkvz.reshape(bsz, t, hk, -1), [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    b, a = jnp.split(ba.reshape(bsz, t, hk, 2 * r), 2, axis=-1)
    flat = lambda x: x.reshape(bsz, t, -1)
    conv_kernel, dt_bias, a_log, norm_scale = (params[name] for name in ("conv_kernel", "dt_bias", "a_log", "norm_scale"))
    qkv = jax.nn.silu(causal_conv1d(jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1), conv_kernel, None))
    q, k, v = jnp.split(qkv, [key_dim, 2 * key_dim], axis=-1)
    beta = jax.nn.sigmoid(flat(b).astype(f32))
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(flat(a).astype(f32) + dt_bias)
    l2norm = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    q = (l2norm(q.reshape(bsz, t, hk, dk).astype(f32)) * dk ** -0.5).astype(cfg.dtype)
    k = l2norm(k.reshape(bsz, t, hk, dk).astype(f32)).astype(cfg.dtype)
    o = gated_delta_rule(q, k, v.reshape(bsz, t, hv, dv), g, beta, cfg.chunk_size)
    o = o.astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps) * norm_scale
    o = (o * jax.nn.silu(z.reshape(bsz, t, hv, dv).astype(f32))).astype(cfg.dtype)
    return dense(o.reshape(bsz, t, value_dim), "out_proj")


def mixer(width, dtype, r=2, t=32):
    cfg = Qwen3NextConfig(
        vocab_size=64, hidden_size=32, layer_types=(qwen3_next.LINEAR,), linear_key_heads=2, linear_value_heads=2 * r,
        linear_key_head_dim=width, linear_value_head_dim=width, chunk_size=8, dtype=dtype,
    )
    module = GatedDeltaNet(cfg, 0.02)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, t, cfg.hidden_size))
    params = module.init(jax.random.PRNGKey(1), u)["params"]
    # every parameter off its initial value, so that none drops out of a product
    params = jax.tree_util.tree_map(lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape), params)
    return cfg, module, params, u


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("width", [16, 128], ids=["no_kernel_serves_16_lanes", "off_the_chip_at_128"])
def test_where_the_kernels_do_not_run_the_mixer_is_the_parents_lines_to_the_bit(width, dtype):
    cfg, module, params, u = mixer(width, dtype)
    assert frame.serves(u.shape[1], 2, width, width, cfg.conv_kernel) == (width == 128)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda p, u: module.apply({"params": p}, u))(params, u))
    loss = lambda f: lambda p, u: jnp.sum(jnp.sin(f(p, u).astype(jnp.float32)))
    got = jax.value_and_grad(loss(lambda p, u: module.apply({"params": p}, u)), argnums=(0, 1))(params, u)
    want = jax.value_and_grad(loss(lambda p, u: parents_mixer(cfg, p, u)), argnums=(0, 1))(params, u)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)


def test_on_the_chip_the_mixer_takes_the_four_kernels_and_computes_the_same(monkeypatch):
    """The backend's choice steered as the chip makes it: the mixer's program
    holds the four calls; and with the kernels in the interpreter its value
    and every gradient are the lines'."""
    import re

    cfg, module, params, u = mixer(128, jnp.float32, t=40)
    loss = lambda p, u: jnp.sum(jnp.sin(module.apply({"params": p}, u)))
    want = jax.value_and_grad(loss, argnums=(0, 1))(params, u)
    with monkeypatch.context() as on_chip:
        on_chip.setattr(frame, "pallas_interpret", lambda: False)  # what ``interpret=None`` asks
        program = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, u))
    names = re.findall(r"name=(gdn_frame_\w+)", program)
    assert sorted(names) == ["gdn_frame_in", "gdn_frame_in_bwd", "gdn_frame_out", "gdn_frame_out_bwd"], names
    monkeypatch.setattr(frame, "_TILE", 16)
    monkeypatch.setattr(frame, "framed_rule", functools.partial(frame.framed_rule, interpret=True))
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, u)
    off = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), got, want)
    assert max(jax.tree_util.tree_leaves(off)) < 1e-4, off


def test_the_passes_type_inside_shard_map_where_every_step_runs(tile_of_16):
    """The kernels' outputs declare how they vary over the mesh: forward and
    backward per worker under ``check_vma``, the parameters cast to varying
    as the trainer casts them before it differentiates."""
    from jax.sharding import Mesh, PartitionSpec as P

    (qkvz, conv_kernel, norm_scale, g, beta), _ = rule_inputs(32, 2)

    def gradients(qkvz, g, beta, conv_kernel, norm_scale):
        loss = lambda qkvz, g, beta, conv_kernel, norm_scale: jnp.sum(jnp.sin(framed(True, 2)(qkvz, conv_kernel, norm_scale, g, beta)))
        *varying, d_conv, d_scale = jax.grad(loss, argnums=range(5))(qkvz, g, beta, conv_kernel, norm_scale)
        return (*varying, d_conv[None], d_scale[None])

    def worker(qkvz, g, beta, conv_kernel, norm_scale):
        return gradients(qkvz, g, beta, *(jax.lax.pcast(p, "data", to="varying") for p in (conv_kernel, norm_scale)))

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    per_worker, shared = (P("data"),) * 3, (P(), P())
    sharded = lambda check_vma: jax.shard_map(worker, mesh=mesh, in_specs=per_worker + shared, out_specs=P("data"), check_vma=check_vma)
    args = (qkvz, g, beta, conv_kernel, norm_scale)
    typed = str(jax.make_jaxpr(sharded(True))(*args))  # the trace alone decides the types
    assert all(name in typed for name in ("gdn_frame_in_bwd", "gdn_frame_out_bwd"))
    # the Pallas interpreter runs only unchecked: its own block slicing mixes varying arrays with invariant indices
    got = jax.jit(sharded(False))(*args)
    want = [jnp.concatenate(x) for x in zip(*(gradients(qkvz[i:i + 1], g[i:i + 1], beta[i:i + 1], conv_kernel, norm_scale) for i in range(2)))]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(b))))
