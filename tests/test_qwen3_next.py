"""Qwen3-Next's layers on the CPU at small sizes, seeded weights: the
Gated-DeltaNet mixer and the gated full-attention layer against the
benchmark's plain reference, the partial rotary turn, the zero-centred norm,
the softmax-routed expert layer with its gated shared expert against the
reference (and its sixteen shares against the uncut layer), and the sigmoid
layer the other two models run, which must not have moved. The whole model
and its training step are in ``test_qwen3_next_train.py``; the rule itself in
``test_gated_delta.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as reference
from network_distributed_pytorch_tpu.models.layers import RMSNorm, Rope, rotary
from network_distributed_pytorch_tpu.models.qwen3_next import (
    GatedAttention, GatedDeltaNet, Qwen3NextConfig, Qwen3NextExperts,
)
from network_distributed_pytorch_tpu.parallel.moe import held_experts_moe


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


SEQ = 44  # no multiple of the chunk of 8
SMALL = dict(
    hidden_size=64, linear_key_heads=2, linear_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
    chunk_size=8, n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=100.0, expert_width=24, shared_expert_width=24,
    n_routed_experts=16, experts_per_token=3, held_experts=tuple(range(16)),
)
REFERENCE = dict(
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25, rope_theta=100.0,
    rms_norm_eps=1e-6, num_experts_per_tok=3, held_experts=list(range(16)),
)


def seeded(module, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, 64))
    params = module.init(jax.random.PRNGKey(seed + 1), x)
    # vector leaves off their initial 0 or 1 (norm weights, dt_bias, A_log, the shared gate), so that they count
    params = jax.tree_util.tree_map(
        lambda p: p + 0.2 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params
    )
    return params, x


def per_sequence(fn, params, x, cfg=REFERENCE):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([fn(row, params["params"], cfg) for row in x])


# ---- the two mixers ----------------------------------------------------------


def test_gated_deltanet_mixer_matches_the_plain_reference():
    """The grouped column order of the two fused projections, the conv
    without a bias over [q | k | v], beta and g per value head, the l2 norms,
    the chunked rule against the recurrence as written, the norm before the
    gate: outputs and every gradient."""
    module = GatedDeltaNet(Qwen3NextConfig(**SMALL), 0.02)
    params, x = seeded(module)
    assert sorted(params["params"]) == [
        "a_log", "conv_kernel", "dt_bias", "in_proj_ba", "in_proj_qkvz", "norm_scale", "out_proj",
    ]
    assert params["params"]["in_proj_qkvz"]["kernel"].shape == (64, 2 * 32 + 2 * 64)
    assert params["params"]["conv_kernel"].shape == (4, 2 * 32 + 64) and "conv_bias" not in params["params"]
    with jax.default_matmul_precision("highest"):
        got = module.apply(params, x)
        grads = jax.grad(lambda p, x: jnp.sum(jnp.sin(module.apply(p, x))), argnums=(0, 1))(params, x)
    np.testing.assert_allclose(got, per_sequence(reference._gated_delta_net, params, x), rtol=2e-4, atol=2e-6)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(per_sequence(reference._gated_delta_net, p, x))), argnums=(0, 1))(params, x)
    # A_log's gradient is four numbers that sum T steps' worth of fp32 rounding in two different orders
    assert worst_relative(grads, want) < 5e-4


def test_deltanet_initialises_as_huggingface_does_with_a_floor_under_a():
    from network_distributed_pytorch_tpu.models.qwen3_next import A_FLOOR

    module = GatedDeltaNet(Qwen3NextConfig(**{**SMALL, "linear_value_heads": 512, "linear_key_heads": 256}), 0.02)
    p = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))["params"]
    a = np.exp(np.asarray(p["a_log"]))
    assert a.min() >= A_FLOOR * 0.999 and a.max() <= 16.0 and 6.0 < a.mean() < 10.0  # U(0, 16)
    assert np.all(np.asarray(p["dt_bias"]) == 1.0) and np.all(np.asarray(p["norm_scale"]) == 1.0)
    assert np.abs(np.asarray(p["conv_kernel"])).max() <= 0.5


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
def test_gated_attention_layer_matches_the_plain_reference(attn_impl):
    """The gate inside ``q_proj`` (per head its q, then its gate), zero-centred
    norms of q and k per head, the rotary turn on a quarter of the head,
    causal grouped-query attention, the sigmoid gate on the output: outputs
    and every gradient, through einsum attention and the flash kernels."""
    module = GatedAttention(Qwen3NextConfig(attn_impl=attn_impl, **SMALL), 0.02)
    params, x = seeded(module)
    assert params["params"]["q_proj"]["kernel"].shape == (64, 2 * 4 * 16) and "gate_proj" not in params["params"]
    got = module.apply(params, x)
    np.testing.assert_allclose(got, per_sequence(reference._attention, params, x), rtol=2e-4, atol=2e-6)
    grads = jax.grad(lambda p, x: jnp.sum(jnp.sin(module.apply(p, x))), argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(per_sequence(reference._attention, p, x))), argnums=(0, 1))(params, x)
    assert worst_relative(grads, want) < 2e-4
    # a reference that turned the whole head, or none of it, is another layer
    for factor in (1.0, 0.0):
        other = per_sequence(reference._attention, params, x, dict(REFERENCE, partial_rotary_factor=factor))
        assert float(jnp.linalg.norm(got - other) / jnp.linalg.norm(other)) > 1e-3


def test_partial_rotary_turns_the_first_dims_and_leaves_the_rest_untouched():
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 2, 32))
    turned = rotary(x, Rope(1e7), rotary_dim=8)
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])  # dims 8..31 pass as they came
    np.testing.assert_array_equal(turned[..., :8], rotary(x[..., :8], Rope(1e7)))  # as a head of 8 dims would turn
    assert float(jnp.abs(turned[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 1e-2
    pair = lambda v, i: v[..., i] ** 2 + v[..., i + 4] ** 2  # inside the rotary part i pairs with i + 4
    for i in range(4):
        np.testing.assert_allclose(pair(turned, i), pair(x, i), rtol=1e-5)
    # no rotary_dim, or the whole head: the turn every head had before the argument existed
    t, d = 40, 32
    inv_freq = 1e7 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    whole = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    np.testing.assert_array_equal(rotary(x, Rope(1e7)), whole)
    np.testing.assert_array_equal(rotary(x, Rope(1e7), rotary_dim=None), whole)
    np.testing.assert_array_equal(rotary(x, Rope(1e7), rotary_dim=32), whole)
    same_program = lambda **kw: str(jax.make_jaxpr(lambda x: rotary(x, Rope(1e7), **kw))(x))
    assert same_program() == same_program(rotary_dim=None) == same_program(rotary_dim=32)


def test_zero_centred_norm_scales_by_one_plus_a_weight_from_zero():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16)) * 3.0
    zero_centred, plain = RMSNorm(1e-6, zero_centred=True), RMSNorm(1e-6)
    p0, p1 = zero_centred.init(jax.random.PRNGKey(0), x), plain.init(jax.random.PRNGKey(0), x)
    assert not np.asarray(p0["params"]["scale"]).any() and np.all(np.asarray(p1["params"]["scale"]) == 1.0)
    np.testing.assert_array_equal(zero_centred.apply(p0, x), plain.apply(p1, x))  # 1 + 0 = 1
    w = jax.random.normal(jax.random.PRNGKey(1), (16,)) * 0.3
    np.testing.assert_allclose(
        zero_centred.apply({"params": {"scale": w}}, x), plain.apply({"params": {"scale": 1.0 + w}}, x), rtol=1e-6
    )
    np.testing.assert_allclose(zero_centred.apply({"params": {"scale": w}}, x), reference._norm(x, {"scale": w}, 1e-6), rtol=1e-6)


# ---- the softmax-routed expert layer -----------------------------------------

T, D, F, E, K = 48, 16, 24, 16, 3


def expert_layer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = lambda k, shape, scale: jax.random.normal(k, shape) * scale
    return normal(ks[0], (T, D), 1.0), {
        "router": normal(ks[1], (D, E), 0.5), "experts_gate": normal(ks[2], (E, D, F), 0.2),
        "experts_up": normal(ks[3], (E, D, F), 0.2), "experts_down": normal(ks[4], (E, F, D), 0.2),
        "shared_gate": normal(ks[5], (D,), 0.5),
        "shared": {
            "gate_proj": {"kernel": normal(ks[6], (D, F), 0.2)}, "up_proj": {"kernel": normal(ks[7], (D, F), 0.2)},
            "down_proj": {"kernel": normal(ks[8], (F, D), 0.2)},
        },
    }


def routed(x, p, held, **kw):
    slots = jnp.asarray(held)
    return held_experts_moe(
        x, x, p["router"], jnp.zeros((E,)), p["experts_up"][slots], p["experts_down"][slots], held, K,
        block_rows=8, w_gate=p["experts_gate"][slots], **kw,
    )


def test_softmax_routing_picks_what_the_reference_picks_and_weighs_it_alike():
    """``score="softmax"``: probabilities over the router's whole width, the
    top k renormalised; the held experts' part against the reference's loop
    less its shared expert, the counters against the reference's routing, the
    gradients (the router's, through the softmax, among them)."""
    x, p = expert_layer()
    held = (0, 1, 2, 7, 9)
    cfg = {"num_experts_per_tok": K, "held_experts": list(held)}
    slots = jnp.asarray(held)
    mine = dict(p, **{k: p[k][slots] for k in ("experts_gate", "experts_up", "experts_down")})

    def plain(x, p):
        with jax.default_matmul_precision("highest"):
            return reference._experts(x, p, cfg)[0] - reference._shared_expert(x, p)

    got, counters = jax.jit(lambda x, p: routed(x, p, held, score="softmax"))(x, p)
    np.testing.assert_allclose(got, plain(x, mine), rtol=2e-4, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        want_counters = reference._experts(x, mine, cfg)[1]
    np.testing.assert_array_equal(counters["held"], want_counters["held"])
    assert int(counters["absent"]) == int(want_counters["absent"]) and int(counters["dropped"]) == 0
    grads = jax.grad(lambda x, p: jnp.sum(jnp.sin(routed(x, p, held, score="softmax")[0])), argnums=(0, 1))(x, p)
    want = jax.grad(
        lambda x, p: jnp.sum(jnp.sin(plain(x, dict(p, **{k: p[k][slots] for k in ("experts_gate", "experts_up", "experts_down")})))),
        argnums=(0, 1),
    )(x, p)
    compared = lambda g: (g[0], {k: g[1][k] for k in ("router", "experts_gate", "experts_up", "experts_down")})
    assert worst_relative(compared(grads), compared(want)) < 1e-4
    # sigmoid scores pick and weigh otherwise: the two are not one layer
    other, _ = routed(x, p, held)
    assert float(jnp.linalg.norm(got - other) / jnp.linalg.norm(got)) > 1e-2


def test_sigmoid_scores_are_the_default_and_the_program_the_other_models_trace():
    """``score`` left out, or ``"sigmoid"``: one jaxpr, one output, bit for
    bit (nemotron's and trinity's layer); softmax is another program; any
    other name is refused."""
    x, p = expert_layer()
    held = (0, 1, 2, 7, 9)
    program = lambda **kw: str(jax.make_jaxpr(lambda x, p: routed(x, p, held, **kw)[0])(x, p))
    assert program() == program(score="sigmoid")
    assert program(score="softmax") != program() and "logistic" in program()
    np.testing.assert_array_equal(routed(x, p, held)[0], routed(x, p, held, score="sigmoid")[0])
    with pytest.raises(AssertionError):
        routed(x, p, held, score="tanh")


def test_the_shares_of_sixteen_ranks_and_the_gated_shared_expert_once_equal_the_uncut_layer():
    """The model-configs guide's share test: each of 16 ranks holds one of
    the 16 experts and routes over all of them by softmax; the routed parts
    of all ranks, with the shared expert behind its sigmoid gate, which every
    rank computes alike, counted once, add up to the whole layer as the plain
    reference computes it uncut."""
    x, p = expert_layer(seed=3)
    parts, landed = jnp.zeros_like(x), 0
    for rank in range(E):
        part, counters = routed(x, p, (rank,), score="softmax")
        parts, landed = parts + part, landed + int(counters["held"].sum())
        assert int(counters["absent"]) + int(counters["held"].sum()) == T * K
    assert landed == T * K  # every assignment landed on exactly one rank
    cfg = {"num_experts_per_tok": K, "held_experts": list(range(E))}
    with jax.default_matmul_precision("highest"):
        want, whole = reference._experts(x, p, cfg)
        once = reference._shared_expert(x, p)
    np.testing.assert_allclose(parts + once, want, rtol=2e-4, atol=2e-5)
    assert int(whole["held"].sum()) == T * K and int(whole["absent"]) == 0


def test_the_models_expert_layer_is_that_layer_with_its_gated_shared_expert():
    module = Qwen3NextExperts(Qwen3NextConfig(**SMALL), 0.02)
    params, x = seeded(module)
    assert params["params"]["shared_gate"].shape == (64,)  # a vector leaf: a scalar gate a token
    with jax.default_matmul_precision("highest"):
        (got, counters) = module.apply(params, x)
    want = per_sequence(lambda u, p, cfg: reference._experts(u, p, cfg)[0], params, x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert int(counters["held"].sum()) == 2 * SEQ * 3 and int(counters["absent"]) == 0 == int(counters["dropped"])


def test_a_router_probability_rounded_to_bfloat16_picks_other_experts():
    """Why the router computes in fp32 at full precision: at the model's 512
    experts and 10 a token, softmax probabilities rounded to bf16 tie, and the
    top 10 of many tokens change."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x, router = jax.random.normal(ks[0], (2048, 64)), jax.random.normal(ks[1], (64, 512)) * 0.1
    probabilities = jax.nn.softmax(jnp.dot(x, router, precision="highest"), axis=-1)
    rounded = probabilities.astype(jnp.bfloat16).astype(jnp.float32)
    full, low = (np.sort(np.asarray(jax.lax.top_k(s, 10)[1]), -1) for s in (probabilities, rounded))
    assert np.any(full != low, axis=-1).mean() > 0.02  # 3.7% of the tokens here
