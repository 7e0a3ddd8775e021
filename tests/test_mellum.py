"""Mellum 2's layers on the CPU at small sizes, seeded weights: YaRN's
frequencies and factor against numbers worked by hand, the rotary turn that
takes them (and leaves the three earlier callers' programs as they were), the
two kinds of attention layer (BOTH turned, each by its own
``rope_parameters``) against the benchmark's plain reference, the
softmax-routed expert layer without a shared expert against the reference,
and its four 16-expert shares against the uncut layer. The whole model and
its training step are in ``test_mellum_train.py``; the chunk rule in
``test_moe_chunks.py``."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mellum as reference
from network_distributed_pytorch_tpu.models.layers import FULL, SLIDING, Rope, rope_frequencies, rotary
from network_distributed_pytorch_tpu.models.mellum import MellumAttention, MellumConfig, MellumExperts
from network_distributed_pytorch_tpu.parallel.moe import chunk_rows, held_experts_moe

PUBLISHED_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
    "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782,
}


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


# ---- YaRN --------------------------------------------------------------------


def test_yarn_frequencies_at_the_published_numbers_against_the_formula_worked_by_hand():
    """D 128, theta 5e5, factor 16, L 8192, beta 32 / 1: ``c(n) = 128 ln(8192 /
    (2 pi n)) / (2 ln 5e5)`` gives c(32) = 18.08 and c(1) = 34.98, so low 18,
    high 35: pairs 0..18 keep the plain frequency, pairs 35..63 the plain one
    over 16, and between them the ramp ``(i - 18) / 17`` blends."""
    ln_theta = math.log(5e5)
    c32, c1 = 128 * math.log(8192 / (64 * math.pi)) / (2 * ln_theta), 128 * math.log(8192 / (2 * math.pi)) / (2 * ln_theta)
    assert (math.floor(c32), math.ceil(c1)) == (18, 35) and 18.0 < c32 < 18.2 and 34.9 < c1 < 35.0
    inv_freq, factor = rope_frequencies(Rope.of(PUBLISHED_YARN), 128)
    plain = lambda i: 5e5 ** (-2 * i / 128)
    assert inv_freq.shape == (64,) and inv_freq.dtype == jnp.float32
    assert float(inv_freq[0]) == 1.0  # the first pair: theta^0, untouched
    np.testing.assert_allclose(inv_freq[18], plain(18), rtol=2e-6)  # ramp 0 at low
    np.testing.assert_allclose(inv_freq[19], plain(19) * (1 - 1 / 17) + plain(19) / 16 / 17, rtol=2e-6)
    np.testing.assert_allclose(inv_freq[35], plain(35) / 16, rtol=2e-6)  # ramp 1 at high
    np.testing.assert_allclose(inv_freq[63], 5e5 ** (-126 / 128) / 16, rtol=2e-6)  # the last: 1.5345e-07
    assert 1.53e-7 < float(inv_freq[63]) < 1.54e-7
    assert factor == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1, abs=1e-15)
    # no attention_factor given: 0.1 ln(factor) + 1
    without = {k: v for k, v in PUBLISHED_YARN.items() if k != "attention_factor"}
    assert rope_frequencies(Rope.of(without), 128)[1] == pytest.approx(1.2772588722239782, abs=1e-15)
    # the plain reference computes them a second time, in float64, from the same keys
    want, want_factor = reference._frequencies(PUBLISHED_YARN, 128)
    np.testing.assert_allclose(inv_freq, want, rtol=3e-6)
    assert want_factor == factor


def test_yarn_frequencies_at_a_toy_size():
    """D 16, theta 1e4, factor 4, L 32: c(32) < 0 so low 0, c(1) = 1.41 so
    high 2: ramp 0, 1/2, 1, 1, ...: pair 0 plain, pair 1 the mean of plain and
    plain / 4, the others plain / 4."""
    rope = {"rope_type": "yarn", "rope_theta": 1e4, "factor": 4.0, "original_max_position_embeddings": 32,
            "beta_fast": 32, "beta_slow": 1}
    inv_freq, factor = rope_frequencies(Rope.of(rope), 16)
    plain = 1e4 ** (-np.arange(8) / 8.0)
    want = plain * np.array([1.0, (1 + 0.25) / 2] + [0.25] * 6)
    np.testing.assert_allclose(inv_freq, want, rtol=2e-6)
    assert factor == pytest.approx(0.1 * math.log(4) + 1)
    np.testing.assert_allclose(reference._frequencies(dict(rope, attention_factor=factor), 16)[0], want, rtol=2e-6)
    with pytest.raises(ValueError):
        Rope.of({"rope_type": "linear", "rope_theta": 1e4})


def rotary_before_pr_44(x, theta):
    """``models/layers.rotary`` as afmoe, qwen3_next and lfm2 called it before
    it took a ``Rope``: the lines of the parent commit."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # (T, D/2)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def test_a_theta_and_default_rope_parameters_are_one_program_and_yarn_another():
    """``rotary(x, Rope(theta))`` is what afmoe, qwen3_next and lfm2 call: a
    theta alone, which is what ``rope_type: default`` gives, traces to the
    equations the function had before it took a ``Rope`` (no multiply by a
    factor of 1), so those three callers' programs are what they were; YaRN
    scales cos and sin, so a turned vector's norm is the factor times what it
    was."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 16))
    program = lambda fn: str(jax.make_jaxpr(fn)(x))
    before = program(lambda x: rotary_before_pr_44(x, 1e4))
    assert program(lambda x: rotary(x, Rope(1e4))) == before
    assert Rope.of({"rope_type": "default", "rope_theta": 1e4}) == Rope.of({"rope_theta": 1e4}) == Rope(1e4)
    np.testing.assert_array_equal(rotary(x, Rope(1e4)), rotary_before_pr_44(x, 1e4))
    rope = Rope.of({"rope_type": "yarn", "rope_theta": 1e4, "factor": 4.0, "original_max_position_embeddings": 32})
    assert rope == Rope(1e4, 4.0, 32)
    assert program(lambda x: rotary(x, rope)) != before
    turned = rotary(x, rope)
    np.testing.assert_allclose(
        jnp.linalg.norm(turned, axis=-1), (0.1 * math.log(4) + 1) * jnp.linalg.norm(x, axis=-1), rtol=1e-5
    )
    np.testing.assert_allclose(turned[:, 0], (0.1 * math.log(4) + 1) * x[:, 0], rtol=1e-6)  # position 0: no turn, the factor alone


# ---- attention ---------------------------------------------------------------

SEQ = 44
SMALL = dict(
    hidden_size=64, n_heads=4, n_kv_heads=2, head_dim=16, sliding_window=12,
    rope_sliding=Rope(100.0), rope_full=Rope(100.0, 4.0, 16, attention_factor=0.1 * math.log(4.0) + 1.0),
    expert_width=24, n_routed_experts=16, experts_per_token=3, held_experts=tuple(range(16)),
)
REFERENCE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, sliding_window=12, rms_norm_eps=1e-6,
    rope_parameters={
        SLIDING: {"rope_type": "default", "rope_theta": 100.0},
        FULL: {"rope_type": "yarn", "rope_theta": 100.0, "factor": 4.0, "original_max_position_embeddings": 16,
               "beta_fast": 32.0, "beta_slow": 1.0, "attention_factor": 0.1 * math.log(4.0) + 1.0},
    },
    num_experts_per_tok=3, held_experts=list(range(16)),
)


def seeded(module, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, 64))
    params = module.init(jax.random.PRNGKey(seed + 1), x)
    # norm scales off 1, so that they count
    params = jax.tree_util.tree_map(
        lambda p: p + 0.2 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params
    )
    return params, x


def per_sequence(fn, params, x, *cfg):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([fn(row, params["params"], *cfg) for row in x])


def test_the_models_rope_parameters_are_the_configuration_files():
    cfg = MellumConfig(**SMALL)
    assert cfg.rope_sliding == Rope.of(REFERENCE["rope_parameters"][SLIDING])
    assert cfg.rope_full == Rope.of(REFERENCE["rope_parameters"][FULL])
    published = MellumConfig()
    assert published.rope_full == Rope.of(PUBLISHED_YARN) == Rope(500000.0, 16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert published.rope_sliding == Rope.of({"rope_type": "default", "rope_theta": 500000}) == Rope(500000.0)
    hash(published)  # a flax module's field


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_attention_layer_matches_the_plain_reference(kind, attn_impl):
    """q and k normed per head, BOTH kinds turned over the whole head (the
    sliding layer by the default frequencies, the full layer by YaRN's with
    its factor on cos and sin), a window of 12 in the sliding layer (44 tokens:
    most queries lose keys to it) and the causal triangle in the full one, no
    gate: outputs and every gradient, through einsum attention and the flash
    kernels (interpret mode)."""
    module = MellumAttention(MellumConfig(attn_impl=attn_impl, **SMALL), kind, 0.02)
    params, x = seeded(module)
    assert sorted(params["params"]) == ["k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]
    assert params["params"]["q_norm"]["scale"].shape == (16,) and "bias" not in params["params"]["q_proj"]
    got = module.apply(params, x)
    np.testing.assert_allclose(got, per_sequence(reference._attention, params, x, REFERENCE, kind), rtol=2e-4, atol=2e-6)
    grads = jax.grad(lambda p, x: jnp.sum(jnp.sin(module.apply(p, x))), argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(per_sequence(reference._attention, p, x, REFERENCE, kind))), argnums=(0, 1))(params, x)
    assert worst_relative(grads, want) < 2e-4
    # the other kind's layer is another layer: window and frequencies are both in it
    other = per_sequence(reference._attention, params, x, REFERENCE, FULL if kind == SLIDING else SLIDING)
    assert float(jnp.linalg.norm(got - other) / jnp.linalg.norm(other)) > 1e-2


def test_a_full_layer_turned_by_plain_rotary_fails_the_comparison():
    """The control ISSUE 44 asks for, at the layer: ``rope_type: default`` in
    place of ``yarn`` in the full layer leaves the reference by far more than
    the layer as built does (2e-4 above): the frequencies past ``low`` and the
    factor's square on every logit are both gone."""
    module = MellumAttention(MellumConfig(**SMALL), FULL, 0.02)
    params, x = seeded(module)
    got = module.apply(params, x)
    plain = dict(REFERENCE, rope_parameters={**REFERENCE["rope_parameters"], FULL: REFERENCE["rope_parameters"][SLIDING]})
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, per_sequence(reference._attention, params, x, plain, FULL), rtol=2e-4, atol=2e-6)
    off = float(jnp.linalg.norm(got - per_sequence(reference._attention, params, x, plain, FULL)) / jnp.linalg.norm(got))
    assert off > 0.02
    # the factor alone (same frequencies, cos and sin unscaled) shows too: the logits lose its square
    unscaled = dict(REFERENCE, rope_parameters={
        **REFERENCE["rope_parameters"], FULL: dict(REFERENCE["rope_parameters"][FULL], attention_factor=1.0),
    })
    off = float(jnp.linalg.norm(got - per_sequence(reference._attention, params, x, unscaled, FULL)) / jnp.linalg.norm(got))
    assert off > 5e-3


def test_both_kinds_of_layer_carry_positions():
    """To the last token of either kind the earlier tokens are a sequence,
    not a set (afmoe's full layers carry none; this model's both turn). The
    sliding layer's last token sees its window only: shuffling what lies
    before the window moves nothing."""
    inside = jnp.concatenate([jax.random.permutation(jax.random.PRNGKey(4), SEQ - 3), jnp.arange(SEQ - 3, SEQ)])
    outside = jnp.concatenate([jax.random.permutation(jax.random.PRNGKey(5), SEQ - 12), jnp.arange(SEQ - 12, SEQ)])
    for kind in (SLIDING, FULL):
        module = MellumAttention(MellumConfig(**SMALL), kind, 0.02)
        params, x = seeded(module)
        last, moved = module.apply(params, x)[:, -1], module.apply(params, x[:, inside])[:, -1]
        assert float(jnp.linalg.norm(last - moved) / jnp.linalg.norm(last)) > 1e-3, kind
        beyond = module.apply(params, x[:, outside])[:, -1]
        if kind == SLIDING:
            np.testing.assert_allclose(beyond, last, rtol=1e-5, atol=1e-7)
        else:
            assert float(jnp.linalg.norm(last - beyond) / jnp.linalg.norm(last)) > 1e-3


# ---- the expert layer --------------------------------------------------------

T, D, F, E, K = 48, 16, 24, 64, 8


def expert_layer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, shape, scale: jax.random.normal(k, shape) * scale
    return normal(ks[0], (T, D), 1.0), {
        "router": normal(ks[1], (D, E), 0.5), "experts_gate": normal(ks[2], (E, D, F), 0.2),
        "experts_up": normal(ks[3], (E, D, F), 0.2), "experts_down": normal(ks[4], (E, F, D), 0.2),
    }


def routed(x, p, held):
    slots = jnp.asarray(held)
    return held_experts_moe(
        x, x, p["router"], jnp.zeros((E,)), p["experts_up"][slots], p["experts_down"][slots], held, K,
        block_rows=8, w_gate=p["experts_gate"][slots], score="softmax",
    )


def test_the_shares_of_four_ranks_equal_the_uncut_layer():
    """The model-configs guide's share test, at the cell's division: each of
    4 ranks holds 16 of the 64 experts and routes over all 64 (top 8 of the
    softmax, renormalised); there is no shared expert, so the routed parts of
    all ranks add up to the whole layer as the plain reference computes it
    uncut, and every assignment lands on exactly one rank. A rank's expected
    load is 2 T: its first chunk is 3 T rows and holds it."""
    x, p = expert_layer(seed=3)
    parts, landed = jnp.zeros_like(x), 0
    for rank in range(4):
        held = tuple(range(16 * rank, 16 * rank + 16))
        part, counters = routed(x, p, held)
        parts, landed = parts + part, landed + int(counters["held"].sum())
        assert int(counters["absent"]) + int(counters["held"].sum()) == T * K and int(counters["dropped"]) == 0
        assert chunk_rows(T, K, 16, E, 8) == 3 * T  # one chunk: the load below lies inside it
        assert T < int(counters["held"].sum()) < 3 * T
    assert landed == T * K
    cfg = {"num_experts_per_tok": K, "held_experts": list(range(E))}
    with jax.default_matmul_precision("highest"):
        want, whole = reference._experts(x, p, cfg)
    np.testing.assert_allclose(parts, want, rtol=2e-4, atol=2e-5)
    assert int(whole["held"].sum()) == T * K and int(whole["absent"]) == 0
    # one rank's share is a part of it and no more: the reference given the same share agrees with that rank
    held = tuple(range(16))
    slots = jnp.asarray(held)
    mine = dict(p, **{k: p[k][slots] for k in ("experts_gate", "experts_up", "experts_down")})
    with jax.default_matmul_precision("highest"):
        want_share, want_counters = reference._experts(x, mine, dict(cfg, held_experts=list(held)))
    got_share, counters = routed(x, p, held)
    np.testing.assert_allclose(got_share, want_share, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(counters["held"], want_counters["held"])
    assert int(counters["absent"]) == int(want_counters["absent"])


def test_the_models_expert_layer_is_softmax_routed_with_no_shared_expert_and_no_buffer():
    module = MellumExperts(MellumConfig(**{**SMALL, "held_experts": (0, 1, 2, 7, 9)}), 0.02)
    params, x = seeded(module)
    assert sorted(params) == ["params"]  # no buffers collection: the model publishes no selection bias
    assert sorted(params["params"]) == ["experts_down", "experts_gate", "experts_up", "router"]  # no shared expert
    assert params["params"]["experts_gate"].shape == (5, 64, 24) and params["params"]["router"].shape == (64, 16)
    with jax.default_matmul_precision("highest"):
        got, counters = module.apply(params, x)
    cfg = dict(REFERENCE, held_experts=[0, 1, 2, 7, 9])
    want = per_sequence(lambda u, p, cfg: reference._experts(u, p, cfg)[0], params, x, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert int(counters["held"].sum()) + int(counters["absent"]) == 2 * SEQ * 3 and int(counters["dropped"]) == 0
    # softmax, not sigmoid: a token's weights are the softmax's, renormalised; a sigmoid-scored reference disagrees
    sigmoid = jax.nn.sigmoid(jnp.einsum("btd,de->bte", x, params["params"]["router"], precision="highest"))
    soft = jax.nn.softmax(jnp.einsum("btd,de->bte", x, params["params"]["router"], precision="highest"), axis=-1)
    renorm = lambda s: (lambda top: top / top.sum(-1, keepdims=True))(jax.lax.top_k(s, 3)[0])
    assert float(jnp.abs(renorm(sigmoid) - renorm(soft)).max()) > 1e-3  # same order, other weights


# ---- the configuration -------------------------------------------------------


def test_layer_kinds_and_head_shapes_are_checked():
    with pytest.raises(ValueError):
        MellumConfig(layer_types=("conv",))
    with pytest.raises(ValueError):
        MellumConfig(n_heads=6, n_kv_heads=4)
    published = MellumConfig()
    assert len(published.layer_types) == 28 and published.layer_types.count(FULL) == 7
    assert published.layer_types[:8] == (SLIDING, SLIDING, SLIDING, FULL) * 2
    assert published.expert_layers == tuple(range(28))  # every layer sparse, no leading dense layer
    assert published.n_heads * published.head_dim == 4096 != published.hidden_size == 2304  # head_dim explicit
    assert published.expert_width == 7 * 128 and published.hidden_size == 18 * 128
