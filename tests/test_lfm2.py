"""LFM2's layers on the CPU at small sizes, seeded weights: the gated
short-convolution mixer against a convolution written per channel and against
the benchmark's plain reference, the depthwise conv at any number of taps, the
attention layer (q and k normed per head and turned in EVERY attention layer)
against the reference, the sigmoid-routed expert layer without a shared expert
against the reference (and its eight shares against the uncut layer), the
selection bias the model shares with afmoe. The whole model, its tied head
and its training step are in ``test_lfm2_train.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2 as reference
from network_distributed_pytorch_tpu.models.layers import BUFFERS, FULL, balanced_expert_bias
from network_distributed_pytorch_tpu.models.lfm2 import (
    CONV, Lfm2Attention, Lfm2Config, Lfm2Experts, ShortConv, lfm2_tiny,
)
from network_distributed_pytorch_tpu.ops.ssd import causal_conv1d
from network_distributed_pytorch_tpu.parallel.moe import held_experts_moe


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


SEQ = 44
SMALL = dict(
    hidden_size=64, n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=100.0, expert_width=24,
    n_routed_experts=16, experts_per_token=3, held_experts=tuple(range(16)),
)
REFERENCE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, rope_parameters={"rope_theta": 100.0},
    norm_eps=1e-5, num_experts_per_tok=3, routed_scaling_factor=1, held_experts=list(range(16)),
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


# ---- the short convolution ---------------------------------------------------


def convolve_per_channel(signal, taps):
    """``conv(s)_t = sum_j taps[j] s_{t-K+1+j}``, one channel at a time:
    ``jnp.convolve`` flips its second operand, so the taps go in reversed,
    and its first T outputs are the causal ones (zeros before the sequence)."""
    t = signal.shape[0]
    return jnp.stack(
        [jnp.convolve(signal[:, c], taps[::-1, c])[:t] for c in range(signal.shape[1])], axis=1
    )


def test_short_conv_mixer_is_two_gates_round_a_causal_three_tap_convolution():
    """``[B | C | z] = u W_in`` in that order, ``y = C * conv(B * z)``, ``out =
    y W_out``: against ``jnp.convolve`` a channel, the first two positions
    (which see zeros before the sequence) among them; no activation, no bias."""
    module = ShortConv(Lfm2Config(**SMALL), 0.02)
    params, x = seeded(module)
    p = params["params"]
    assert sorted(p) == ["conv_kernel", "in_proj", "out_proj"] and p["conv_kernel"].shape == (3, 64)
    assert p["in_proj"]["kernel"].shape == (64, 192) and "bias" not in p["in_proj"]
    assert np.abs(np.asarray(module.init(jax.random.PRNGKey(0), x)["params"]["conv_kernel"])).max() <= 1 / np.sqrt(3)
    with jax.default_matmul_precision("highest"):
        got = module.apply(params, x)
        rows = []
        for u in x:
            b, c, z = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
            rows.append((c * convolve_per_channel(b * z, p["conv_kernel"])) @ p["out_proj"]["kernel"])
    want = jnp.stack(rows)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(got[:, :2], want[:, :2], rtol=2e-5, atol=1e-7)  # zeros before the sequence
    # position 0 sees the last tap alone: y_0 = C_0 * w[2] * (B_0 * z_0)
    b, c, z = jnp.split(jnp.dot(x[0, 0], p["in_proj"]["kernel"], precision="highest"), 3)
    first = jnp.dot(c * p["conv_kernel"][2] * (b * z), p["out_proj"]["kernel"], precision="highest")
    np.testing.assert_allclose(got[0, 0], first, rtol=2e-5, atol=1e-7)
    # causal, and no further back than two positions: a change at t moves t, t+1, t+2 and nothing else
    moved = module.apply(params, x.at[:, 10].add(1.0)) - got
    changed = np.flatnonzero(np.abs(np.asarray(moved)).max(axis=(0, 2)) > 0)
    assert changed.tolist() == [10, 11, 12]


def test_short_conv_mixer_matches_the_plain_reference_outputs_and_gradients():
    module = ShortConv(Lfm2Config(**SMALL), 0.02)
    params, x = seeded(module)
    with jax.default_matmul_precision("highest"):
        got = module.apply(params, x)
        grads = jax.grad(lambda p, x: jnp.sum(jnp.sin(module.apply(p, x))), argnums=(0, 1))(params, x)
    np.testing.assert_allclose(got, per_sequence(reference._short_conv, params, x), rtol=2e-5, atol=1e-7)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(per_sequence(reference._short_conv, p, x))), argnums=(0, 1))(params, x)
    assert worst_relative(grads, want) < 1e-5
    # a reference that silu'd the conv, as the other two mixers' convs are, is another layer
    b, c, z = jnp.split(x[0] @ params["params"]["in_proj"]["kernel"], 3, axis=-1)
    activated = (c * jax.nn.silu(convolve_per_channel(b * z, params["params"]["conv_kernel"]))) @ params["params"]["out_proj"]["kernel"]
    assert float(jnp.linalg.norm(got[0] - activated) / jnp.linalg.norm(got[0])) > 0.1


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("taps", [1, 2, 3, 4, 5])
def test_causal_conv1d_is_a_convolution_a_channel_at_any_number_of_taps(taps, bias):
    """The depthwise conv three mixers call (K 4 with and without a bias, K 3
    without): any K, against ``jnp.convolve`` a channel."""
    ks = jax.random.split(jax.random.PRNGKey(taps), 3)
    x, kernel = jax.random.normal(ks[0], (2, 19, 6)), jax.random.normal(ks[1], (taps, 6))
    b = jax.random.normal(ks[2], (6,)) if bias else None
    want = jnp.stack([convolve_per_channel(row, kernel) for row in x]) + (b if bias else 0.0)
    np.testing.assert_allclose(causal_conv1d(x, kernel, b), want, rtol=1e-5, atol=1e-6)
    assert causal_conv1d(x.astype(jnp.bfloat16), kernel, b).dtype == jnp.bfloat16  # in x's dtype


# ---- attention ---------------------------------------------------------------


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
def test_attention_layer_matches_the_plain_reference(attn_impl):
    """q and k normed per head over the head's dims, both turned by the rotary
    embedding over the whole head, causal grouped-query attention, no gate on
    the output: outputs and every gradient, through einsum attention and the
    flash kernels (interpret mode; 4 heads of 16 over 2 take the fold, as the
    model's 32 of 64 over 8 do)."""
    from network_distributed_pytorch_tpu.ops.flash_attention import heads_per_block

    assert heads_per_block(4, 2, 16) is None and heads_per_block(32, 8, 64) is None
    module = Lfm2Attention(Lfm2Config(attn_impl=attn_impl, **SMALL), 0.02)
    params, x = seeded(module)
    assert sorted(params["params"]) == ["k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]
    assert params["params"]["q_norm"]["scale"].shape == (16,)
    got = module.apply(params, x)
    np.testing.assert_allclose(got, per_sequence(reference._attention, params, x, REFERENCE), rtol=2e-4, atol=2e-6)
    grads = jax.grad(lambda p, x: jnp.sum(jnp.sin(module.apply(p, x))), argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(per_sequence(reference._attention, p, x, REFERENCE))), argnums=(0, 1))(params, x)
    assert worst_relative(grads, want) < 2e-4
    # another theta is another layer: the turn is in it
    other = per_sequence(reference._attention, params, x, dict(REFERENCE, rope_parameters={"rope_theta": 1e6}))
    assert float(jnp.linalg.norm(got - other) / jnp.linalg.norm(other)) > 1e-3


def test_the_attention_layer_carries_positions_and_the_short_conv_its_neighbours_only():
    """To the last token of an attention layer the earlier tokens are a
    sequence, not a set (every attention layer turns q and k); to the last
    token of a short conv only its two predecessors exist."""
    shuffled = jnp.concatenate([jax.random.permutation(jax.random.PRNGKey(4), SEQ - 3), jnp.arange(SEQ - 3, SEQ)])
    attention = Lfm2Attention(Lfm2Config(**SMALL), 0.02)
    params, x = seeded(attention)
    last, last_shuffled = attention.apply(params, x)[:, -1], attention.apply(params, x[:, shuffled])[:, -1]
    assert float(jnp.linalg.norm(last - last_shuffled) / jnp.linalg.norm(last)) > 1e-3
    conv = ShortConv(Lfm2Config(**SMALL), 0.02)
    params, x = seeded(conv)
    np.testing.assert_array_equal(conv.apply(params, x)[:, -1], conv.apply(params, x[:, shuffled])[:, -1])


# ---- the expert layer --------------------------------------------------------

T, D, F, E, K = 48, 16, 24, 64, 4


def expert_layer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, shape, scale: jax.random.normal(k, shape) * scale
    return normal(ks[0], (T, D), 1.0), {
        "router": normal(ks[1], (D, E), 0.5), "experts_gate": normal(ks[2], (E, D, F), 0.2),
        "experts_up": normal(ks[3], (E, D, F), 0.2), "experts_down": normal(ks[4], (E, F, D), 0.2),
    }, normal(ks[5], (E,), 0.1)


def routed(x, p, bias, held):
    slots = jnp.asarray(held)
    return held_experts_moe(
        x, x, p["router"], bias, p["experts_up"][slots], p["experts_down"][slots], held, K,
        1.0, block_rows=8, w_gate=p["experts_gate"][slots],
    )


def test_the_shares_of_eight_ranks_equal_the_uncut_layer():
    """The model-configs guide's share test, at the cell's division: each of
    8 ranks holds 8 of the 64 experts and routes over all 64 (top 4 of the
    sigmoid scores plus the selection bias); there is no shared expert, so the
    routed parts of all ranks add up to the whole layer as the plain reference
    computes it uncut, and every assignment lands on exactly one rank."""
    x, p, bias = expert_layer(seed=3)
    parts, landed = jnp.zeros_like(x), 0
    for rank in range(8):
        held = tuple(range(8 * rank, 8 * rank + 8))
        part, counters = routed(x, p, bias, held)
        parts, landed = parts + part, landed + int(counters["held"].sum())
        assert int(counters["absent"]) + int(counters["held"].sum()) == T * K and int(counters["dropped"]) == 0
    assert landed == T * K
    cfg = {"num_experts_per_tok": K, "routed_scaling_factor": 1, "held_experts": list(range(E))}
    with jax.default_matmul_precision("highest"):
        want, whole = reference._experts(x, p, cfg, bias)
    np.testing.assert_allclose(parts, want, rtol=2e-4, atol=2e-5)
    assert int(whole["held"].sum()) == T * K and int(whole["absent"]) == 0
    # one rank's share is a part of it and no more: the reference given the same share agrees with that rank
    held = tuple(range(8))
    slots = jnp.asarray(held)
    mine = dict(p, **{k: p[k][slots] for k in ("experts_gate", "experts_up", "experts_down")})
    with jax.default_matmul_precision("highest"):
        want_share, want_counters = reference._experts(x, mine, dict(cfg, held_experts=list(held)), bias)
    got_share, counters = routed(x, p, bias, held)
    np.testing.assert_allclose(got_share, want_share, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(counters["held"], want_counters["held"])
    assert int(counters["absent"]) == int(want_counters["absent"])


def test_the_models_expert_layer_is_that_layer_without_a_shared_expert():
    module = Lfm2Experts(Lfm2Config(**{**SMALL, "held_experts": (0, 1, 2, 7, 9)}), 0.02)
    params, x = seeded(module)
    assert sorted(params["params"]) == ["experts_down", "experts_gate", "experts_up", "router"]  # no shared expert
    assert params["params"]["experts_gate"].shape == (5, 64, 24)
    # ``seeded`` moved the buffer off zero with the other vector leaves: the layer routes by it
    bias = params[BUFFERS]["expert_bias"]
    assert bias.shape == (16,) and np.asarray(bias).any()
    with jax.default_matmul_precision("highest"):
        got, counters = module.apply(params, x)
    cfg = dict(REFERENCE, held_experts=[0, 1, 2, 7, 9])
    want = per_sequence(lambda u, p, cfg: reference._experts(u, p, cfg, bias)[0], params, x, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert int(counters["held"].sum()) + int(counters["absent"]) == 2 * SEQ * 3 and int(counters["dropped"]) == 0
    # the selection bias picks, and only picks: the weights stay the scores'
    everyone = jnp.zeros((16,)).at[0].set(5.0)  # expert 0 is chosen by every token
    biased, biased_counters = module.apply({"params": params["params"], BUFFERS: {"expert_bias": everyone}}, x)
    assert int(biased_counters["held"][0]) == 2 * SEQ
    want = per_sequence(lambda u, p, cfg: reference._experts(u, p, cfg, everyone)[0], params, x, cfg)
    np.testing.assert_allclose(biased, want, rtol=2e-4, atol=2e-6)
    # no buffers at all is the published initial value, zeros
    unbiased, _ = module.apply({"params": params["params"]}, x)
    want = per_sequence(lambda u, p, cfg: reference._experts(u, p, cfg)[0], params, x, cfg)
    np.testing.assert_allclose(unbiased, want, rtol=2e-4, atol=2e-6)


def zipf_ids(seed, shape, vocab=256):
    ranks = np.minimum(np.random.default_rng(seed).zipf(1.2, shape) - 1, vocab - 1)
    return jnp.asarray(ranks, jnp.int32)


def test_balanced_expert_bias_serves_this_model_as_it_serves_afmoe():
    """``models/layers.balanced_expert_bias`` takes the model it is given: on
    the batch it was balanced on, every expert of every expert layer of an
    ``Lfm2LM`` takes between 0.4 of and twice its share where zeros leave 0.02 to 3.5; the buffers are one
    (experts,) leaf a layer that no gradient reaches; zeros for them (the
    published initial value) and no ``buffers`` at all are one program's
    output."""
    model = lfm2_tiny(held_experts=tuple(range(16)), remat=True)
    ids = zipf_ids(0, (2, 256))
    variables = model.init(jax.random.PRNGKey(0), ids)
    params, zeros = variables["params"], variables[BUFFERS]
    assert sorted(zeros) == ["layer_1", "layer_2", "layer_3", "layer_4"]
    assert all(not np.asarray(z["feed_forward"]["expert_bias"]).any() for z in zeros.values())
    buffers = balanced_expert_bias(model, params, ids)
    assert jax.tree_util.tree_structure(buffers) == jax.tree_util.tree_structure(zeros)
    share = ids.size * 2 / 16
    logits, plain = model.apply({"params": params}, ids)
    same, _ = model.apply({"params": params, BUFFERS: zeros}, ids)
    np.testing.assert_array_equal(logits, same)
    _, balanced = model.apply({"params": params, BUFFERS: buffers}, ids)
    worst = lambda counters: max(float(c["held"].max()) for c in counters.values()) / share
    assert worst(balanced) < 2.0 < worst(plain)
    least = lambda counters: min(float(c["held"].min()) for c in counters.values()) / share
    assert least(plain) < 0.05 and least(balanced) > 0.4  # zeros: 0.016 to 3.5 of a share; balanced: 0.41 to 1.95
    grads = jax.grad(lambda b: jnp.sum(model.apply({"params": params, BUFFERS: b}, ids)[0] ** 2))(buffers)
    assert not any(np.asarray(g).any() for g in jax.tree_util.tree_leaves(grads))


# ---- the configuration -------------------------------------------------------


def test_layer_kinds_and_head_shapes_are_checked():
    with pytest.raises(ValueError):
        Lfm2Config(layer_types=("sliding_attention",))
    with pytest.raises(ValueError):
        Lfm2Config(n_heads=6, n_kv_heads=4)
    published = Lfm2Config()
    assert len(published.layer_types) == 40 and published.layer_types.count(FULL) == 10
    assert published.layer_types[:7] == (CONV, CONV, FULL, CONV, CONV, CONV, FULL) and published.layer_types[-2:] == (FULL, CONV)
    assert published.expert_layers == tuple(range(2, 40)) and published.n_heads * published.head_dim == published.hidden_size
