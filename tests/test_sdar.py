"""SDAR's block-diffusion training on the CPU at small sizes, seeded weights:
positions as an argument of the rotary tables (default: the trace they had),
the noising transform, the 2L-row forward's loss against the objective's
definition run block by block, the whole model's loss and gradients against
the benchmark's plain reference (both attention engines) and the eight
16-expert shares against the uncut layer. The flash kernels under the rule are
in ``test_flash_blockwise.py``; the experiment, the loop's counters and the
cell in ``test_sdar_train.py``."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar as reference
from network_distributed_pytorch_tpu.data.noising import block_noised
from network_distributed_pytorch_tpu.models.layers import (
    RMSNorm, Rope, masked_token_loss, normed_and_turned, rope_tables, rotary,
)
from network_distributed_pytorch_tpu.models.sdar import SdarConfig, sdar_tiny
from network_distributed_pytorch_tpu.parallel.moe import chunk_rows, held_experts_moe
from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

LAYERS = ["layer_0", "layer_1", "layer_2", "layer_3"]


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


def reference_cfg(c: SdarConfig) -> dict:
    """The model's config under the configuration file's (HuggingFace's) keys."""
    return dict(
        hidden_size=c.hidden_size, num_hidden_layers=c.n_layers, rms_norm_eps=c.norm_eps,
        num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads, head_dim=c.head_dim,
        rope_theta=c.rope.theta, block_length=c.block_length,
        num_experts_per_tok=c.experts_per_token, held_experts=list(c.held_experts),
    )


def noised_batch(model, n, length, seed=0):
    c = model.config
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, c.vocab_size - 1, (n, length)).astype(np.int32)
    return block_noised(ids, c.block_length, 1e-3, c.vocab_size - 1, rng)


# ---- positions ---------------------------------------------------------------


def test_default_positions_are_the_rows_own_and_trace_what_they_traced():
    rope, x = Rope(10000.0), jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 16))
    np.testing.assert_array_equal(rotary(x, rope), rotary(x, rope, positions=jnp.arange(12)))
    for got, want in zip(rope_tables(rope, 12, 16), rope_tables(rope, 12, 16, jnp.arange(12))):
        np.testing.assert_array_equal(got, want)
    # no positions: not an operand, and the program the call traced before it had the argument
    assert str(jax.make_jaxpr(lambda x: rotary(x, rope))(x)) == str(jax.make_jaxpr(lambda x: rotary(x, rope, None, None))(x))
    assert "iota" in str(jax.make_jaxpr(lambda: rope_tables(rope, 12, 16))())


def test_two_copies_at_the_same_positions_turn_alike():
    rope, half = Rope(1000000.0), 8
    x = jax.random.normal(jax.random.PRNGKey(1), (1, half, 2, 16))
    twice = rotary(jnp.concatenate([x, x], axis=1), rope, positions=jnp.arange(2 * half) % half)
    np.testing.assert_array_equal(twice[:, :half], twice[:, half:])
    np.testing.assert_array_equal(twice[:, :half], rotary(x, rope))
    partial = rotary(jnp.concatenate([x, x], axis=1), rope, 8, jnp.arange(2 * half) % half)  # the first 8 dims turn
    np.testing.assert_array_equal(partial[:, :half], partial[:, half:])
    np.testing.assert_array_equal(partial[..., 8:], jnp.concatenate([x, x], axis=1)[..., 8:])


def test_the_rope_kernels_take_the_positions_through_their_tables():
    """``ops/qk_rope.py``'s operands are the tables: given positions reach it
    with no change to the kernels, and it agrees with the XLA lines."""
    rope, half = Rope(1000000.0), 128
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    q, k = jax.random.normal(keys[0], (1, 2 * half, 2, 128)), jax.random.normal(keys[1], (1, 2 * half, 1, 128))
    positions = jnp.arange(2 * half) % half

    def turned(interpret):
        norms = RMSNorm(1e-6), RMSNorm(1e-6)
        one = lambda x: jnp.ones((x.shape[-1],))
        return normed_and_turned(
            *(n.bind({"params": {"scale": one(q)}}) for n in norms), q, k, rope, jnp.float32,
            interpret=interpret, positions=positions,
        )

    for got, want in zip(turned(True), turned(None)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got[:, :half] * 0 + got[:, half:], want[:, half:], rtol=1e-5, atol=1e-5)


# ---- the noising transform ---------------------------------------------------


def test_noising_masks_a_block_at_its_own_rate_and_weighs_by_it():
    rng = np.random.default_rng(0)
    n, length, block, mask_id = 512, 256, 4, 999
    ids = rng.integers(0, mask_id, (n, length)).astype(np.int32)
    out = block_noised(ids, block, 1e-3, mask_id, np.random.default_rng(1))
    assert sorted(out) == ["input_ids", "loss_weight", "noisy_ids"]
    assert out["input_ids"] is ids and out["noisy_ids"].dtype == np.int32 and out["loss_weight"].dtype == np.float32
    replaced = out["loss_weight"] > 0
    np.testing.assert_array_equal(out["noisy_ids"] == mask_id, replaced)  # told by the id, and by nothing else
    np.testing.assert_array_equal(out["noisy_ids"][~replaced], ids[~replaced])
    # a block's replaced positions share one weight, 1 / t_b with t_b in (eps, 1)
    weights = out["loss_weight"].reshape(n, length // block, block)
    per_block = weights.max(axis=-1)
    assert np.all((weights == 0) | (weights == per_block[..., None]))
    assert np.all(per_block[per_block > 0] >= 1.0) and per_block.max() <= 1.0 / 1e-3
    # the rate: about half of all positions (E t = 0.5005), and inside a band of levels about that level
    assert abs(replaced.mean() - 0.5005) < 0.005
    share = replaced.reshape(n, length // block, block).mean(axis=-1)
    level = np.where(per_block > 0, 1.0 / np.maximum(per_block, 1e-9), np.nan)
    for lo in (0.2, 0.5, 0.8):
        band = (level >= lo) & (level < lo + 0.1)
        # a block's level shows only where a token was replaced: of those blocks t / (1 - (1 - t)^B) is replaced
        seen_share = level[band] / (1.0 - (1.0 - level[band]) ** block)
        assert abs(share[band].mean() - seen_share.mean()) < 0.01, lo
    # E[weight] = 1 a position: the loss is an unbiased mean of -log p over positions
    assert abs(out["loss_weight"].mean() - 1.0) < 0.05
    # the same generator state, the same draw
    again = block_noised(ids, block, 1e-3, mask_id, np.random.default_rng(1))
    np.testing.assert_array_equal(again["noisy_ids"], out["noisy_ids"])


def test_noising_refuses_the_mask_id_as_a_token_and_a_ragged_block():
    ids = np.arange(16, dtype=np.int32).reshape(2, 8)
    with pytest.raises(ValueError, match="mask id"):
        block_noised(ids, 4, 1e-3, 7, np.random.default_rng(0))
    with pytest.raises(ValueError, match="blocks of 3"):
        block_noised(ids, 3, 1e-3, 99, np.random.default_rng(0))


def test_the_experiments_pool_never_draws_the_mask_id(monkeypatch):
    from network_distributed_pytorch_tpu.experiments import lm, powersgd_sdar

    seen = {}

    def capture(run_name, model, config, mesh, seq_len, pool, steps, summary, **kw):
        seen.update(kw, model=model, seq_len=seq_len)
        return {}

    monkeypatch.setattr(powersgd_sdar, "train_lm", capture)
    powersgd_sdar.run(lm.default_config())
    c = seen["model"].config
    assert seen["drawn_ids"] == c.vocab_size - 1 and seen["loss_of"] is masked_token_loss
    ids = np.random.default_rng(0).integers(0, seen["drawn_ids"], (4, seen["seq_len"] + 1)).astype(np.int32)
    pool = seen["batches_of"](ids, np.random.default_rng(0))
    assert pool["input_ids"].shape == (4, seen["seq_len"]) and pool["input_ids"].max() < c.vocab_size - 1
    assert set(np.unique(pool["noisy_ids"][pool["loss_weight"] > 0])) == {c.vocab_size - 1}


# ---- the objective -----------------------------------------------------------


def _block_causal_logits(params, ids, cfg):
    """The model of the objective's definition: one sequence ``ids`` (S,) at
    positions 0..S-1 under block-causal attention (a query sees every key
    whose block is not later than its own), in plain ``jax.numpy`` with the
    reference's row-wise pieces. No second copy, no rule over copies."""
    hq, hkv, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    s = ids.shape[0]
    blk = jnp.arange(s) // cfg["block_length"]
    shown = blk[None, :] <= blk[:, None]
    x = params["embed"]["embedding"][ids]
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        u = reference._rms_norm(x, p["input_layernorm"], eps)
        a = p["self_attn"]
        q = reference._turned(reference._rms_norm((u @ a["q_proj"]["kernel"]).reshape(s, hq, hd), a["q_norm"], eps), cfg["rope_theta"], jnp.arange(s))
        k = reference._turned(reference._rms_norm((u @ a["k_proj"]["kernel"]).reshape(s, hkv, hd), a["k_norm"], eps), cfg["rope_theta"], jnp.arange(s))
        v = jnp.repeat((u @ a["v_proj"]["kernel"]).reshape(s, hkv, hd), hq // hkv, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, hq // hkv, axis=1)) / math.sqrt(hd)
        weights = jax.nn.softmax(jnp.where(shown[None], scores, -jnp.inf), axis=-1)
        x = x + jnp.einsum("hqk,khd->qhd", weights, v).reshape(s, hq * hd) @ a["o_proj"]["kernel"]
        x = x + reference._experts(reference._rms_norm(x, p["post_attention_layernorm"], eps), p["mlp"], cfg)[0]
    return reference._rms_norm(x, params["final_norm"], eps) @ params["head"]


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
def test_the_two_copy_forwards_loss_is_the_objective_run_block_by_block(attn_impl):
    """``(1 / L) sum_b (1 / t_b) sum_{i in b, replaced} -log p(x0_i | xt_b,
    x0_<b)`` with p for block b the model on ``x0_<b + xt_b`` alone, L / B runs
    of growing length, against the one run over ``[xt ; x0]``."""
    model = sdar_tiny(attn_impl=attn_impl, n_layers=2)
    c, length = model.config, 16
    batch = noised_batch(model, 2, length, seed=3)
    assert (batch["loss_weight"] > 0).sum() > 4
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2 * length), jnp.int32))["params"]
    cfg = reference_cfg(c)
    with jax.default_matmul_precision("highest"):
        got, state = masked_token_loss(model)(params, {STEP_COUNTERS: {}}, batch)
        want = 0.0
        for ids, noisy, weight in zip(batch["input_ids"], batch["noisy_ids"], batch["loss_weight"]):
            for b in range(length // c.block_length):
                lo, hi = b * c.block_length, (b + 1) * c.block_length
                logits = _block_causal_logits(params, jnp.concatenate([ids[:lo], noisy[lo:hi]]), cfg)[lo:hi]
                nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), ids[lo:hi, None], axis=-1)[:, 0]
                want += float(jnp.sum(weight[lo:hi] * nll))
        want /= batch["input_ids"].size
    assert abs(float(got) - want) < 2e-5 * abs(want), (float(got), want)
    masked = int((batch["loss_weight"] > 0).sum())
    assert sorted(state[STEP_COUNTERS]) == ["layer_0", "layer_1"]
    assert all(int(layer["masked"]) == masked for layer in state[STEP_COUNTERS].values())


# ---- the model against the plain reference -----------------------------------


@pytest.mark.parametrize("attn_impl", ["einsum", "flash"])
def test_model_loss_and_gradients_match_the_plain_reference(attn_impl):
    model = sdar_tiny(attn_impl=attn_impl)
    length = 32
    batch = noised_batch(model, 2, length, seed=1)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 2 * length), jnp.int32))["params"]
    loss_fn = masked_token_loss(model)
    with jax.default_matmul_precision("highest"):
        (loss, state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, {STEP_COUNTERS: {}}, batch)
    want_loss, want_grads, want_state = reference.make_loss_and_grads(reference_cfg(model.config))(params, {}, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    assert worst_relative(grads, want_grads) < 2e-4
    for layer in LAYERS:
        np.testing.assert_array_equal(state[STEP_COUNTERS][layer]["held"], want_state["step_counters"][layer]["held"])
        assert int(state[STEP_COUNTERS][layer]["absent"]) == int(want_state["step_counters"][layer]["absent"])
        # every layer routed 2L rows a sample
        assert int(state[STEP_COUNTERS][layer]["held"].sum() + state[STEP_COUNTERS][layer]["absent"]) == 2 * 2 * length * 2


def test_logits_are_the_noised_rows_and_the_tree_is_the_lineages():
    model = sdar_tiny()
    length = 16
    batch = noised_batch(model, 1, length)
    params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 2 * length), jnp.int32))["params"]
    rows = jnp.concatenate([batch["noisy_ids"], batch["input_ids"]], axis=1)
    with jax.default_matmul_precision("highest"):
        logits, counters = model.apply({"params": params}, rows)
        want = reference._logits(params, batch["noisy_ids"][0], batch["input_ids"][0], reference_cfg(model.config))
    assert logits.shape == (1, length, 256) and logits.dtype == jnp.float32 and sorted(counters) == LAYERS
    np.testing.assert_allclose(logits[0], want, rtol=2e-4, atol=2e-5)
    assert sorted(params) == ["embed", "final_norm", "head"] + LAYERS
    assert sorted(params["layer_0"]) == ["input_layernorm", "mlp", "post_attention_layernorm", "self_attn"]
    assert sorted(params["layer_0"]["self_attn"]) == ["k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]
    assert sorted(params["layer_0"]["mlp"]) == ["experts_down", "experts_gate", "experts_up", "router"]
    # a clean row's change does not reach a noised row of an earlier or its own block, a noised row's no clean row
    later = rows.at[0, length + 8].set((rows[0, length + 8] + 1) % 255)  # clean, block 2
    with jax.default_matmul_precision("highest"):
        moved, _ = model.apply({"params": params}, later)
    np.testing.assert_array_equal(moved[0, :12], logits[0, :12])  # noised blocks 0-2 never see clean block 2
    assert float(jnp.abs(moved[0, 12:] - logits[0, 12:]).max()) > 1e-6  # block 3's noised rows do
    with pytest.raises(ValueError, match="whole blocks"):
        model.apply({"params": params}, rows[:, :-2])


def test_the_residual_stream_is_fp32_under_bf16_products():
    """A quarter of a step's rows are one token, so a layer's top-k for them is
    one decision: the stream that the router's norm reads is not rounded to
    bf16 (the products' operands are)."""
    from network_distributed_pytorch_tpu.models.sdar import SdarBlock

    block = SdarBlock(sdar_tiny(dtype=jnp.bfloat16).config)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))
    params = block.init(jax.random.PRNGKey(1), x)
    out, _ = block.apply(params, x)
    assert out.dtype == jnp.float32
    text = str(jax.make_jaxpr(lambda p, x: block.apply(p, x)[0])(params, x))
    assert "bf16" in text and "dot_general" in text  # the products run in bf16
    model = sdar_tiny(dtype=jnp.bfloat16)
    ids = jnp.zeros((1, 16), jnp.int32)
    embed = model.bind(model.init(jax.random.PRNGKey(2), ids))
    assert embed.config.dtype == jnp.bfloat16


# ---- the shares --------------------------------------------------------------

T, D, F, E, K = 64, 32, 16, 128, 8


def test_the_shares_of_eight_ranks_equal_the_uncut_layer():
    """The model-configs guide's share test, at the cell's division: each of 8
    ranks holds 16 of the 128 experts and routes over all 128 (top 8 of the
    softmax, renormalised); there is no shared expert, so the routed parts of
    all ranks add up to the whole layer as the plain reference computes it
    uncut, and every assignment lands on exactly one rank. A rank's expected
    load is one assignment a row: its first chunk is 3/2 of the rows."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (T, D))
    p = {
        "router": jax.random.normal(keys[1], (D, E)) * 0.5,
        "experts_gate": jax.random.normal(keys[2], (E, D, F)) * 0.2,
        "experts_up": jax.random.normal(keys[3], (E, D, F)) * 0.2,
        "experts_down": jax.random.normal(keys[4], (E, F, D)) * 0.2,
    }

    def routed(held):
        slots = jnp.asarray(held)
        with jax.default_matmul_precision("highest"):
            return held_experts_moe(
                x, x, p["router"], jnp.zeros((E,)), p["experts_up"][slots], p["experts_down"][slots], held, K, 1.0,
                w_gate=p["experts_gate"][slots], score="softmax", block_rows=8,
            )

    parts, landed = jnp.zeros_like(x), 0
    for rank in range(8):
        part, counters = routed(tuple(range(16 * rank, 16 * rank + 16)))
        parts, landed = parts + part, landed + int(counters["held"].sum())
        assert int(counters["absent"]) + int(counters["held"].sum()) == T * K and int(counters["dropped"]) == 0
    assert landed == T * K
    assert chunk_rows(T, K, 16, E, 8) == 3 * T // 2 and chunk_rows(16384, 8, 16, 128) == 24576
    cfg = {"num_experts_per_tok": K, "held_experts": list(range(E))}
    with jax.default_matmul_precision("highest"):
        want, whole = reference._experts(x, p, cfg)
    np.testing.assert_allclose(parts, want, rtol=2e-4, atol=2e-5)
    assert int(whole["held"].sum()) == T * K and int(whole["absent"]) == 0
