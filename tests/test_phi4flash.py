"""Phi-4-mini-flash-reasoning's layers and whole model on the CPU at small
sizes, seeded weights: the published layer rule; logits, loss and every
parameter's gradient against the benchmark's plain reference; differential
attention's ``lambda_init`` by the published index, its pairing and its
subln; the memory's cotangent as the sum over the GMU layers' and the cache's
over the full and cross layers' (with and without ``remat``); the window; the
carry read before its source; the parameter count at the published widths.
The training step is in ``test_phi4flash_train.py``."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4flash as reference
from network_distributed_pytorch_tpu.models import phi4flash
from network_distributed_pytorch_tpu.models.layers import einsum_attention, next_token_lm_loss, zero_counters
from network_distributed_pytorch_tpu.models.phi4flash import (
    CROSS, FULL, GMU, MAMBA, SLIDING, DiffAttention, Mamba1Mixer, Phi4FlashBlock, Phi4FlashConfig, Phi4FlashLM,
    lambda_init, layer_kind, phi4flash_tiny,
)

SEQ = 48
TWO_OF_EACH = (15, 16, 17, 18, 19, 20, 21)  # sliding, memory source, cache source, GMU, cross, GMU, cross


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


def reference_config(model) -> dict:
    """The configuration file's keys for ``model``, as ``benchmark/builders/phi4flash.py`` reads them back."""
    c = model.config
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.n_heads, num_key_value_heads=c.n_kv_heads,
        sliding_window=c.sliding_window, layer_norm_eps=c.norm_eps, mamba_d_state=c.state_size,
        mamba_dt_rank=c.dt_rank, layer_indices=list(c.layer_indices),
        published={"num_hidden_layers": c.n_published_layers},
    )


def seeded(model, seed=0, bsz=2, seq=SEQ):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (bsz, seq + 1), 0, model.config.vocab_size)
    params = model.init(jax.random.PRNGKey(seed + 1), ids[:, :-1])["params"]
    # biases off 0 and scales off 1, so that they count
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params
    )
    return params, ids[:, :-1], ids[:, 1:]


# ---- which layer is what ------------------------------------------------------


def test_the_published_layer_rule_at_32_layers():
    kinds = [layer_kind(i, 32) for i in range(32)]
    assert collections.Counter(kinds) == {MAMBA: 9, SLIDING: 8, FULL: 1, GMU: 7, CROSS: 7}
    assert kinds[16] == MAMBA and kinds[17] == FULL  # the memory source and the cache source
    assert all(k == (MAMBA if i % 2 == 0 else SLIDING) for i, k in enumerate(kinds[:16]))
    assert all(k == (GMU if i % 2 == 0 else CROSS) for i, k in enumerate(kinds[18:], start=18))
    assert Phi4FlashConfig(layer_indices=(15, 16, 17, 18, 19)).layer_kinds == (SLIDING, MAMBA, FULL, GMU, CROSS)
    assert Phi4FlashConfig().layer_kinds == tuple(kinds)
    # the rule is of l and n: at another depth the hand-over moves with n / 2
    assert [layer_kind(i, 8) for i in range(8)] == [MAMBA, SLIDING, MAMBA, SLIDING, MAMBA, FULL, GMU, CROSS]


@pytest.mark.parametrize("bad", [(), (3, 2), (2, 2), (-1, 0), (30, 32)])
def test_layer_indices_are_published_indices_in_order(bad):
    with pytest.raises(ValueError):
        Phi4FlashConfig(layer_indices=bad)


def test_the_model_has_no_expert_layer_so_its_counters_tree_is_empty():
    model = phi4flash_tiny()
    assert zero_counters(model.config) == {}
    params, ids, _ = seeded(model)
    logits, counters = model.apply({"params": params}, ids)
    assert counters == {} and logits.dtype == jnp.float32 and logits.shape == (2, SEQ, 256)


def test_the_parameter_count_at_the_published_widths():
    """The benchmark's cut, from shapes alone: ISSUE 48's table to the parameter."""
    model = Phi4FlashLM(Phi4FlashConfig(vocab_size=25008, layer_indices=(15, 16, 17, 18, 19)))
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 64), jnp.int32))["params"], jax.random.PRNGKey(0))
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    rest = 78_643_200 + 10_240  # the MLP and two LayerNorms
    assert [count(shapes[f"layer_{i}"]) for i in range(5)] == [
        19_668_864 + rest, 41_241_600 + rest, 19_668_864 + rest, 26_214_400 + rest, 13_112_704 + rest,
    ]
    assert count(shapes) == 577_199_232
    assert shapes["layer_0"]["mlp"]["gate_up_proj"]["kernel"].shape == (2560, 20480)
    assert shapes["layer_1"]["mixer"]["a_log"].shape == (5120, 16) and shapes["layer_1"]["mixer"]["dt_proj"].shape == (160, 5120)
    assert sorted(shapes) == ["embed", "final_norm"] + [f"layer_{i}" for i in range(5)]  # the head is the embedding


# ---- the whole model against the plain reference -------------------------------


@pytest.mark.parametrize("indices", [(15, 16, 17, 18, 19), TWO_OF_EACH, (14, 15, 16, 17, 18)], ids=["cut", "two_of_each", "no_cross"])
def test_logits_loss_and_every_gradient_match_the_plain_reference(indices):
    model = phi4flash_tiny(layer_indices=indices)
    params, ids, labels = seeded(model)
    cfg = reference_config(model)
    loss_fn = next_token_lm_loss(model)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, ids)[0]
        want_logits = jnp.stack([reference._logits(params, row, cfg) for row in ids])
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(p, {}, {"input_ids": ids, "labels": labels}), has_aux=True
        )(params)
        want_loss, want_grads = jax.value_and_grad(
            lambda p: jnp.mean(jnp.stack([reference._sequence_loss(p, i, l, cfg) for i, l in zip(ids, labels)]))
        )(params)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(want_grads)
    assert worst_relative(grads, want_grads) < 1e-3  # fp32 against fp32: a small leaf carries its sum's rounding
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree_util.tree_leaves(grads))  # no leaf is dead


def test_remat_changes_nothing_and_the_carry_crosses_the_checkpoints():
    """With ``remat`` every block is a ``jax.checkpoint`` and the memory and
    the cache cross its boundary as residuals: same loss, same gradients."""
    plain, recomputed = (phi4flash_tiny(layer_indices=TWO_OF_EACH, remat=r) for r in (False, True))
    params, ids, labels = seeded(plain)
    batch = {"input_ids": ids, "labels": labels}
    value = lambda model: jax.value_and_grad(lambda p: next_token_lm_loss(model)(p, {}, batch)[0])(params)
    (loss, grads), (loss_r, grads_r) = value(plain), value(recomputed)
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-6)
    assert worst_relative(grads_r, grads) < 1e-5


# ---- the memory and the cache: one source, several readers ----------------------


def blocks_of(model, params, ids):
    """The model's blocks one at a time on the CPU: ``run(carry, i)`` applies layer ``i``."""
    cfg = model.config
    h = params["embed"]["embedding"][ids]

    def run(carry, i):
        block = Phi4FlashBlock(cfg, cfg.layer_indices[i])
        return block.apply({"params": params[f"layer_{i}"]}, carry)[0]

    return h, run


def test_the_memorys_cotangent_is_the_sum_over_its_readers_and_so_is_the_caches():
    """Two GMUs read the memory, the full layer and two cross layers the
    cache. d loss / d memory through the whole stack equals the sum of what
    each reader sends back alone (the other readers given a memory no
    gradient flows to), and the same for the cache."""
    model = phi4flash_tiny(layer_indices=TWO_OF_EACH)
    params, ids, _ = seeded(model)
    h0, run = blocks_of(model, params, ids)
    carry = (h0, None, None)
    for i in range(3):  # sliding, the memory source, the cache source
        carry = run(carry, i)
    h, memory, cache = carry

    def tail(h, memories, caches):
        """Layers 3..6 = GMU, cross, GMU, cross, each reader handed its own copy."""
        carry = (h, None, None)
        for i, (m, kv) in enumerate(zip(memories, caches), start=3):
            h, _, _ = run((carry[0], m, kv), i)
            carry = (h, None, None)
        return jnp.sum(jnp.sin(carry[0]))

    with jax.default_matmul_precision("highest"):
        shared = jax.grad(lambda m, kv: tail(h, [m] * 4, [kv] * 4), argnums=(0, 1))(memory, cache)
        stop = jax.lax.stop_gradient
        alone = lambda which: jax.grad(
            lambda m, kv: tail(
                h, [m if i == which else stop(m) for i in range(4)], [kv if i == which else stop(kv) for i in range(4)]
            ),
            argnums=(0, 1),
        )(memory, cache)
        parts = [alone(i) for i in range(4)]
    d_memory = sum(p[0] for p in parts)
    assert worst_relative(shared[0], d_memory) < 1e-5
    # the GMUs (0 and 2) send the memory a cotangent, the cross layers (1 and 3) none; and the other way round
    assert all(float(jnp.linalg.norm(parts[i][0])) > 0 for i in (0, 2))
    assert all(float(jnp.linalg.norm(parts[i][0])) == 0 for i in (1, 3))
    d_cache = jax.tree_util.tree_map(lambda *v: sum(v), *[p[1] for p in parts])
    assert worst_relative(shared[1], d_cache) < 1e-5
    assert all(float(jnp.linalg.norm(parts[i][1][0])) > 0 for i in (1, 3))
    assert all(float(jnp.linalg.norm(parts[i][1][0])) == 0 for i in (0, 2))


def test_the_memory_is_the_scans_output_before_the_gate_and_the_cache_the_full_layers_pairs():
    model = phi4flash_tiny()
    params, ids, _ = seeded(model)
    h0, run = blocks_of(model, params, ids)
    carry = run((h0, None, None), 0)
    assert carry[1] is None and carry[2] is None  # a sliding layer keeps nothing
    carry = run(carry, 1)
    assert carry[1].shape == (2, SEQ, 128) and carry[2] is None  # the memory: (B, T, d_inner)
    normed = phi4flash.layer_norm(model.config, "n").apply({"params": params["layer_1"]["norm_1"]}, run((h0, None, None), 0)[0])
    _, y = Mamba1Mixer(model.config).apply({"params": params["layer_1"]["mixer"]}, normed)
    np.testing.assert_array_equal(carry[1], y)
    memory = carry[1]
    carry = run(carry, 2)
    assert carry[1] is memory and [v.shape for v in carry[2]] == [(2, SEQ, 1, 16)] * 4  # (k1, k2, v1, v2)
    after = run(run(carry, 3), 4)
    assert after[1] is memory and all(a is b for a, b in zip(after[2], carry[2]))  # readers hand both on


@pytest.mark.parametrize("indices,what", [((18, 19), "memory"), ((19, 20), "cache"), ((15, 17, 18), "memory"), ((16, 19), "cache")])
def test_reading_a_carry_before_its_source_raises_while_tracing(indices, what):
    model = phi4flash_tiny(layer_indices=indices)
    with pytest.raises(ValueError, match=what):
        jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))


# ---- differential attention ------------------------------------------------------


def test_lambda_init_follows_the_published_index():
    assert lambda_init(0) == pytest.approx(0.2)
    for index in (15, 17, 19):
        assert lambda_init(index) == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * index))
    # the cut's attention layers are 15, 17, 19, not 0, 2, 4: the layer computes with its published index
    cfg = phi4flash_tiny().config
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 64))
    params = DiffAttention(cfg, 17).init(jax.random.PRNGKey(1), u)
    outs = {index: DiffAttention(cfg, index).apply(params, u)[0] for index in (2, 17)}
    assert float(jnp.max(jnp.abs(outs[2] - outs[17]))) > 1e-4


def test_differential_attention_pairs_adjacent_heads_and_norms_each_pair():
    """Against the four attentions written out head by head: q heads (0, 1)
    pair, (2, 3) pair, both read the one key/value pair (0, 1); the subln is an
    RMSNorm over a pair's 2 * head_dim."""
    cfg = phi4flash_tiny().config
    index, hd = 17, cfg.head_dim
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 64))
    module = DiffAttention(cfg, index)
    params = module.init(jax.random.PRNGKey(1), u)
    p = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(x.size), x.shape) if x.ndim == 1 else x, params["params"]
    )
    assert sorted(p) == ["Wqkv", "lambda_k1", "lambda_k2", "lambda_q1", "lambda_q2", "out_proj", "subln"]
    assert p["subln"].shape == (2 * hd,) and p["lambda_q1"].shape == (hd,) and "bias" in p["Wqkv"] and "bias" in p["out_proj"]
    with jax.default_matmul_precision("highest"):
        got, cache = module.apply({"params": p}, u)
        qkv = u[0] @ p["Wqkv"]["kernel"] + p["Wqkv"]["bias"]
        q, k, v = (x.reshape(12, -1, hd) for x in jnp.split(qkv, [4 * hd, 6 * hd], axis=-1))

        def att(qh, kh, vh):
            scores = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), qh @ kh.T / np.sqrt(hd), -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vh

        lam = jnp.exp(p["lambda_q1"] @ p["lambda_k1"]) - jnp.exp(p["lambda_q2"] @ p["lambda_k2"]) + lambda_init(index)
        pairs = []
        for pair in range(2):
            q1, q2, k1, k2, v1, v2 = q[:, 2 * pair], q[:, 2 * pair + 1], k[:, 0], k[:, 1], v[:, 0], v[:, 1]
            o = jnp.concatenate([att(q1, k1, v1), att(q1, k1, v2)], -1) - lam * jnp.concatenate([att(q2, k2, v1), att(q2, k2, v2)], -1)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps) * p["subln"]
            pairs.append(o * (1 - lambda_init(index)))
        want = jnp.concatenate(pairs, axis=-1) @ p["out_proj"]["kernel"] + p["out_proj"]["bias"]
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(cache[0][0, :, 0], k[:, 0], rtol=1e-6)  # k1 is the even key head
    np.testing.assert_allclose(cache[3][0, :, 0], v[:, 1], rtol=1e-6)  # v2 the odd value head


def test_a_window_shorter_than_t_changes_the_sliding_layer_and_no_other():
    """Layer 15 is windowed, 17 and 19 are not: ``sliding_window`` 8 against
    one that covers the sequence moves the sliding layer's output and leaves
    the full and the cross layers' (given the same input) where they were."""
    narrow, wide = (phi4flash_tiny(sliding_window=w) for w in (8, SEQ))
    params, ids, _ = seeded(narrow)
    h0, run_narrow = blocks_of(narrow, params, ids)
    _, run_wide = blocks_of(wide, params, ids)
    first = run_narrow((h0, None, None), 0), run_wide((h0, None, None), 0)
    assert float(jnp.max(jnp.abs(first[0][0] - first[1][0]))) > 1e-4
    # position i < 8 sees the same keys either way
    np.testing.assert_allclose(first[0][0][:, :8], first[1][0][:, :8], rtol=1e-5, atol=1e-6)
    carry = first[0]
    for i in (1, 2, 3, 4):  # from one input, the other four layers do not read the window
        stepped = run_narrow(carry, i), run_wide(carry, i)
        np.testing.assert_array_equal(stepped[0][0], stepped[1][0])
        carry = stepped[0]


def test_the_four_attentions_are_one_call_and_two_softmaxes_a_pair(monkeypatch):
    """(q1, q2) over (k1, k2) and the value heads [v1 | v2] twice: H query heads
    over Hkv key/value heads, the value head 2 * head_dim wide, one engine call
    a layer, the sliding layer's with its window."""
    calls = []
    real = phi4flash.causal_attention

    def counted(cfg, q, k, v, window=None):
        calls.append((q.shape, k.shape, v.shape, window))
        return real(cfg, q, k, v, window)

    model = phi4flash_tiny()
    params, ids, _ = seeded(model)
    monkeypatch.setattr(phi4flash, "causal_attention", counted)
    model.apply({"params": params}, ids)
    q, k, v = (2, SEQ, 4, 16), (2, SEQ, 2, 16), (2, SEQ, 2, 32)
    assert calls == [(q, k, v, 16), (q, k, v, None), (q, k, v, None)]


def four_attentions(cfg, index, window, p, u, cache=None):
    """Differential attention as four attentions on heads stacked (q1, q1, q2,
    q2) over (k1, k1, k2, k2) and (v1, v2, v1, v2), each softmax made once a
    value half: the lines ``DiffAttention`` had before the value head took its
    own width, on explicit parameters, in fp32."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bsz, t, _ = u.shape
    if cache is None:
        q, k, v = jnp.split(u @ p["Wqkv"]["kernel"] + p["Wqkv"]["bias"], [hq * hd, (hq + hkv) * hd], axis=-1)
        cache = phi4flash.paired(k.reshape(bsz, t, hkv, hd)) + phi4flash.paired(v.reshape(bsz, t, hkv, hd))
    else:
        q = u @ p["Wq"]["kernel"] + p["Wq"]["bias"]
    q1, q2 = phi4flash.paired(q.reshape(bsz, t, hq, hd))
    k1, k2, v1, v2 = cache
    heads = lambda *parts: jnp.concatenate(parts, axis=2)
    stacked = heads(q1, q1, q2, q2), heads(k1, k1, k2, k2), heads(v1, v2, v1, v2)
    a11, a12, a21, a22 = jnp.split(einsum_attention(*stacked, window), 4, axis=2)
    init = lambda_init(index)
    lam = jnp.exp(p["lambda_q1"] @ p["lambda_k1"]) - jnp.exp(p["lambda_q2"] @ p["lambda_k2"]) + init
    side = lambda first, second: jnp.concatenate([first, second], axis=-1)
    o = phi4flash.difference(side(a11, a12), side(a21, a22), lam, p["subln"], cfg.norm_eps, 1.0 - init, jnp.float32)
    return o.reshape(bsz, t, hq * hd) @ p["out_proj"]["kernel"] + p["out_proj"]["bias"], cache


@pytest.mark.parametrize("engine", ["einsum", "flash"])
@pytest.mark.parametrize("kind", [SLIDING, FULL, CROSS])
def test_two_softmaxes_a_pair_are_the_four_attentions(kind, engine):
    """``DiffAttention``'s output, the cache it hands on and the gradient of
    every parameter, of its input and (a cross layer) of the cache it reads,
    against :func:`four_attentions`, by either engine (flash: the kernels in
    interpret mode, a value head of 32 over a head of 16 through the fold)."""
    cfg = phi4flash_tiny(n_heads=8, n_kv_heads=4, attn_impl=engine).config
    index, window = {SLIDING: (15, 16), FULL: (17, None), CROSS: (19, None)}[kind]
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    u = jax.random.normal(keys[0], (2, SEQ, 64))
    cache = None
    if kind == CROSS:
        cache = tuple(jax.random.normal(key, (2, SEQ, 2, 16)) for key in keys[1:5])
    module = DiffAttention(cfg, index, window)
    p = module.init(keys[5], u, cache)["params"]
    p = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(x.size), x.shape) if x.ndim == 1 else x, p
    )
    w = jax.random.normal(keys[6], (2, SEQ, 64))

    def loss(attend):
        def f(p, u, cache):
            out, kept = attend(p, u, cache)
            return jnp.sum(out * w) + sum(jnp.sum(jnp.sin(x)) for x in kept), (out, kept)
        return jax.value_and_grad(f, argnums=(0, 1, 2) if kind == CROSS else (0, 1), has_aux=True)

    with jax.default_matmul_precision("highest"):
        (_, got), got_grads = loss(lambda p, u, cache: module.apply({"params": p}, u, cache))(p, u, cache)
        (_, want), want_grads = loss(lambda p, u, cache: four_attentions(cfg, index, window, p, u, cache))(p, u, cache)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    assert worst_relative(got, want) < 1e-5
    assert jax.tree_util.tree_structure(got_grads) == jax.tree_util.tree_structure(want_grads)
    assert worst_relative(got_grads, want_grads) < 2e-5
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree_util.tree_leaves(got_grads))


def test_no_parameter_of_differential_attention_changes_name_or_shape():
    """At the published widths the attention layers' leaves are what they
    were when the four attentions were four softmaxes: a checkpoint of that
    model loads."""
    model = Phi4FlashLM(Phi4FlashConfig(vocab_size=256, layer_indices=(15, 17, 19)))
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 64), jnp.int32))["params"], jax.random.PRNGKey(0))
    shape_of = lambda tree: jax.tree_util.tree_map(lambda x: x.shape, tree)
    shared = {f"lambda_{name}": (64,) for name in ("q1", "k1", "q2", "k2")}
    shared.update(subln=(128,), out_proj={"kernel": (2560, 2560), "bias": (2560,)})
    own = {"Wqkv": {"kernel": (2560, 5120), "bias": (5120,)}}
    assert shape_of(shapes["layer_0"]["mixer"]) == shape_of(shapes["layer_1"]["mixer"]) == {**own, **shared}
    assert shape_of(shapes["layer_2"]["mixer"]) == {"Wq": {"kernel": (2560, 2560), "bias": (2560,)}, **shared}
