"""The gated delta rule in chunks (``ops/gated_delta.py``) on the CPU at small
sizes: against the recurrence as the benchmark's plain reference writes it,
one step at a time — values and every gradient, at lengths that are no
multiple of the chunk, with two value heads a key head and with one, with a
decay that wipes the state every step and one that hardly decays; the
triangular inverse and its two-product cotangent; the precision that must
stay fp32; and the conv without a bias that frames the rule in its mixer.
Every test of the rule runs twice (``impl``): with the chunk-local stage as
XLA, which is what a CPU runs, and as the Pallas kernel the chip runs, here in
the interpreter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as reference
from network_distributed_pytorch_tpu.ops import gated_delta
from network_distributed_pytorch_tpu.ops.gated_delta import gated_delta_rule, unit_lower_inverse
from network_distributed_pytorch_tpu.ops.ssd import causal_conv1d

# what an fp32 rule is held to against the fp32 recurrence, and a rule with bf16 operands (decay in fp32)
FP32_LIMIT, BF16_LIMIT = 1e-4, 2e-2


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


def rule_inputs(t, hk=2, r=2, dk=8, dv=12, decay="mixed", seed=0, bsz=2):
    """q, k normalised as the mixer hands them over, v, g <= 0, beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (bsz, t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (bsz, t, hk, dk)))
    v = jax.random.normal(ks[2], (bsz, t, hk * r, dv))
    rate = jnp.exp(jax.random.normal(ks[3], (bsz, t, hk * r)))
    # "strong": exp(g) ~ 1e-9, the state is gone every step; "near_zero": hardly any decay over the sequence
    g = -rate * {"mixed": 1.0, "strong": 20.0, "near_zero": 1e-3}[decay]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, t, hk * r)))
    return q, k, v, g, beta


@pytest.fixture(params=["xla", "kernel"])
def impl(request, monkeypatch):
    """Which chunk-local stage ``gated_delta_rule`` takes: the backend's own
    choice (XLA on the CPU) or the kernel in the Pallas interpreter."""
    if request.param == "kernel":
        monkeypatch.setattr(gated_delta, "chunk_local", functools.partial(gated_delta.chunk_local, interpret=True))
    return request.param


def recurrence_as_written(q, k, v, g, beta):
    """``reference/qwen3_next.py``'s step-by-step rule, a sequence at a time,
    q and k repeated to the value heads."""
    r = v.shape[2] // q.shape[2]
    per_value_head = lambda x: jnp.repeat(x, r, axis=2)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(reference._delta_rule)(per_value_head(q), per_value_head(k), v, g, beta)


XLA_STAGE = gated_delta.chunk_local  # the backend's choice, whatever a test's ``impl`` puts in its place


@pytest.mark.parametrize("r", [2, 1], ids=["two_value_heads_a_key_head", "one"])
@pytest.mark.parametrize("t,chunk,width", [(32, 8, 8), (29, 8, 8), (5, 8, 8), (48, 16, 8), (70, 64, 8), (96, 64, 128)])
def test_chunked_rule_matches_the_recurrence_outputs_and_all_gradients(t, chunk, width, r, impl, monkeypatch):
    """``width`` 128 with a chunk of 64 is the shape the chip's tiles serve
    (``gated_delta_kernel.serves``); the others only the interpreter takes."""
    args = rule_inputs(t, r=r, dk=width, dv=width if width > 8 else 12, bsz=2 if width == 8 else 1)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk) * weights), argnums=range(5)
        ))(*args)
        out = gated_delta_rule(*args, chunk=chunk)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(recurrence_as_written(*a) * weights), argnums=range(5)
    ))(*args)
    assert out.shape == args[2].shape and out.dtype == jnp.float32
    np.testing.assert_allclose(out, recurrence_as_written(*args), rtol=2e-4, atol=2e-5)
    assert abs(got - want) <= 1e-4 * abs(want) + 1e-4
    assert worst_relative(got_grads, want_grads) < FP32_LIMIT
    if impl == "kernel":  # and against the XLA body: the same mathematics, the products of the inverse in another order
        monkeypatch.setattr(gated_delta, "chunk_local", XLA_STAGE)
        with jax.default_matmul_precision("highest"):
            body_grads = jax.jit(jax.grad(
                lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk) * weights), argnums=range(5)
            ))(*args)
            np.testing.assert_allclose(out, gated_delta_rule(*args, chunk=chunk), rtol=1e-5, atol=1e-6)
        assert worst_relative(got_grads, body_grads) < 1e-5


@pytest.mark.parametrize("decay", ["strong", "near_zero"])
def test_a_decay_that_wipes_the_state_and_one_that_hardly_decays(decay, impl):
    """Every ``exp`` in the chunked form has a non-positive argument: a
    cumulative log-decay of -1,000 inside a chunk underflows to 0 and nothing
    overflows or divides by it; a decay near 0 keeps 40 steps of state."""
    args = rule_inputs(40, decay=decay)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule(*args, chunk=16)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(gated_delta_rule(*a, chunk=16))), argnums=range(5)))(*args)
    want_grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(recurrence_as_written(*a))), argnums=range(5)))(*args)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in (got, *grads))
    np.testing.assert_allclose(got, recurrence_as_written(*args), rtol=2e-4, atol=2e-5)
    # g's own gradient is ~1e-9 of the others' under the strong decay: compared on the scale of all five
    whole = np.sqrt(sum(float(jnp.vdot(w, w)) for w in want_grads))
    assert all(float(jnp.linalg.norm(a - b)) < FP32_LIMIT * whole for a, b in zip(grads, want_grads))


def test_a_chunk_is_no_approximation_whatever_its_length(impl):
    args = rule_inputs(48)
    with jax.default_matmul_precision("highest"):
        outs = [gated_delta_rule(*args, chunk=c) for c in (4, 16, 48, 64)]
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], rtol=2e-4, atol=2e-5)


def test_the_triangular_inverse_and_its_two_product_cotangent():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 2, 16, 16)) * 0.3, -1)
    eye = jnp.eye(16)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(unit_lower_inverse(a), np.linalg.inv(np.asarray(eye - a)), rtol=1e-4, atol=1e-5)
        weights = jax.random.normal(jax.random.PRNGKey(1), a.shape)
        got = jax.grad(lambda a: jnp.sum(unit_lower_inverse(a) * weights))(a)
        want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye - a) * weights))(a)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # a chunk of one step, and one that is no power of two
    assert float(unit_lower_inverse(jnp.zeros((1, 1)))[0, 0]) == 1.0
    odd = jnp.tril(jax.random.normal(jax.random.PRNGKey(2), (6, 6)) * 0.5, -1)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(unit_lower_inverse(odd), np.linalg.inv(np.asarray(jnp.eye(6) - odd)), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)], ids=["fp32", "bf16"])
def test_the_kernel_stages_cotangents_are_jaxs_own_of_the_xla_stage(dtype, limit):
    """The custom VJP round the kernels keeps the stage's inputs and the fp32
    inverse, and the backward kernel forms the cotangents of q, k, v, gamma
    and beta from them; jax differentiates the XLA stage
    (``unit_lower_inverse``'s two-product rule inside). Same five outputs,
    same five cotangents."""
    chunk = 16
    q, k, v, g, beta = rule_inputs(64, dk=16, dv=24, seed=4)
    q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)
    _, by_value = gated_delta._layouts(k, v, chunk)
    inputs = (q, k, v, jnp.cumsum(by_value(g), axis=-1), by_value(beta))
    shapes = jax.eval_shape(lambda *a: gated_delta.chunk_local(*a, chunk), *inputs)
    weights = [jax.random.normal(jax.random.PRNGKey(i), s.shape) for i, s in enumerate(shapes)]

    def loss(interpret):
        def of(*stage_inputs):
            outputs = gated_delta.chunk_local(*stage_inputs, chunk, interpret=interpret)
            return sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(outputs, weights)), outputs
        return jax.jit(jax.value_and_grad(of, argnums=range(5), has_aux=True))

    with jax.default_matmul_precision("highest"):
        ((_, got_out), got), ((_, want_out), want) = loss(True)(*inputs), loss(None)(*inputs)
    assert [o.dtype for o in got_out] == [jnp.float32] + [dtype] * 4
    as_f32 = lambda tree: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)
    assert worst_relative(as_f32(got_out), as_f32(want_out)) < limit
    assert worst_relative(as_f32(got), as_f32(want)) < limit


def in_bfloat16(q, k, v, g, beta, chunk=16):
    low = lambda x: x.astype(jnp.bfloat16)
    return gated_delta_rule(low(q), low(k), low(v), g, beta, chunk=chunk)


def test_rule_in_bfloat16_keeps_its_decay_in_float32_and_a_decay_summed_in_bfloat16_fails(monkeypatch, impl):
    """The large products take bf16 operands and stay inside ``BF16_LIMIT`` of
    the fp32 recurrence; the same rule with g's cumulative sum rounded to
    bf16 (a chunk's log-decay reaches -60 here: steps of 0.25) does not. The
    control the configuration's ``assumed`` promises fp32 for."""
    q, k, v, g, beta = rule_inputs(256, dk=16, dv=16, seed=3)
    g = g * 0.5
    full = recurrence_as_written(q, k, v, g, beta)
    off = lambda out: float(jnp.linalg.norm(out.astype(jnp.float32) - full) / jnp.linalg.norm(full))
    low = in_bfloat16(q, k, v, g, beta, chunk=64)
    assert low.dtype == jnp.bfloat16 and off(low) < BF16_LIMIT
    cumsum = jnp.cumsum
    rounded = lambda x, **kw: cumsum(x.astype(jnp.bfloat16), **kw).astype(x.dtype) if x.dtype == jnp.float32 else cumsum(x, **kw)
    monkeypatch.setattr(gated_delta.jnp, "cumsum", rounded)
    assert off(in_bfloat16(q, k, v, g, beta, chunk=64)) > BF16_LIMIT


def test_the_rule_types_inside_shard_map_where_every_step_runs(impl):
    """The scan's carry starts from zeros, which are invariant over the mesh
    until cast, and the kernel's outputs declare how they vary: forward and
    backward per worker under ``check_vma``."""
    from jax.sharding import Mesh, PartitionSpec as P

    args = rule_inputs(24, bsz=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    loss = lambda *a: jnp.sum(jnp.sin(gated_delta_rule(*a, chunk=8)))
    worker = jax.grad(loss, argnums=range(5))
    sharded = lambda check_vma: jax.shard_map(worker, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=check_vma)
    typed = str(jax.make_jaxpr(sharded(True))(*args))  # the trace alone decides the types
    assert ("gated_delta_chunk_local" in typed) == (impl == "kernel")
    # the Pallas interpreter runs only unchecked: its own block slicing mixes varying arrays with invariant indices
    assert worst_relative(jax.jit(sharded(impl == "xla"))(*args), worker(*args)) < 1e-5


def test_causal_conv_without_a_bias_is_the_conv_with_a_zero_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 6))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    np.testing.assert_array_equal(causal_conv1d(x, kernel, None), causal_conv1d(x, kernel, jnp.zeros((6,))))
    # and a program without the add: no bias is no broadcast of zeros
    with_bias = str(jax.make_jaxpr(causal_conv1d)(x, kernel, jnp.zeros((6,))))
    without = str(jax.make_jaxpr(lambda x, k: causal_conv1d(x, k, None))(x, kernel))
    assert without.count(" add ") == with_bias.count(" add ") - 1
