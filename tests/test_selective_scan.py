"""Mamba-1's selective scan in chunks (``ops/selective_scan.py``) on the CPU at
small sizes: against the recurrence as the benchmark's plain reference writes
it, one step at a time — values and all six cotangents, at chunk lengths that
do and do not divide T, the state carried across chunks, an fp32 state under
bf16 inputs, the tail's padding, inside ``shard_map``; and no value the size
of (T, C, N) anywhere outside the chunk's body, in the function or in its
gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import phi4flash as reference
from network_distributed_pytorch_tpu.ops import selective_scan as module
from network_distributed_pytorch_tpu.ops.selective_scan import selective_scan

FP32_LIMIT = 1e-5


def worst_relative(got, want) -> float:
    off = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b) / (jnp.linalg.norm(b) + 1e-30)), got, want
    )
    return max(jax.tree_util.tree_leaves(off))


def scan_inputs(t, ch=24, n=4, bsz=2, seed=0, dtype=jnp.float32):
    """x, delta > 0 (some steps strong enough to wipe the state, some near
    zero), a < 0 its own for every (channel, index), b, c, d_skip."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (bsz, t, ch)).astype(dtype)
    delta = jax.nn.softplus(3.0 * jax.random.normal(ks[1], (bsz, t, ch)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[2], (ch, n)))
    b = jax.random.normal(ks[3], (bsz, t, n)).astype(dtype)
    c = jax.random.normal(ks[4], (bsz, t, n)).astype(dtype)
    d_skip = jax.random.normal(ks[5], (ch,))
    return x, delta, a, b, c, d_skip


def recurrence_as_written(x, delta, a, b, c, d_skip):
    """``reference/phi4flash.py``'s step-by-step recurrence, a sequence at a time, in fp32."""
    f32 = lambda v: v.astype(jnp.float32)
    one = lambda x, delta, b, c: reference._recurrence(f32(x), f32(delta), a, f32(b), f32(c)) + d_skip * f32(x)
    return jax.vmap(one)(x, delta, b, c)


@pytest.mark.parametrize("t,chunk", [(32, 8), (32, 32), (29, 8), (7, 16), (64, None), (130, None)])
def test_values_and_all_six_cotangents_against_the_recurrence(t, chunk):
    args = scan_inputs(t)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = selective_scan(*args, chunk=chunk)
    want = recurrence_as_written(*args)
    assert got.shape == want.shape and got.dtype == args[0].dtype
    assert worst_relative(got, want) < FP32_LIMIT
    every = tuple(range(6))
    grads = jax.grad(lambda *v: jnp.sum(selective_scan(*v, chunk=chunk) * weight), argnums=every)(*args)
    wanted = jax.grad(lambda *v: jnp.sum(recurrence_as_written(*v) * weight), argnums=every)(*args)
    assert worst_relative(grads, wanted) < 1e-4


def test_the_state_is_carried_across_chunks():
    """A scan over the whole equals a scan over its first half followed by
    one over the second only if the second starts from the first's state: cut
    in two WITHOUT the state the second half differs, so the chunks' carry is
    what makes the whole right."""
    x, delta, a, b, c, d_skip = scan_inputs(32)
    whole = selective_scan(x, delta, a, b, c, d_skip, chunk=8)
    assert worst_relative(whole, recurrence_as_written(x, delta, a, b, c, d_skip)) < FP32_LIMIT
    cut = lambda v: v[:, 16:]
    alone = selective_scan(cut(x), cut(delta), a, cut(b), cut(c), d_skip, chunk=8)
    assert worst_relative(alone, whole[:, 16:]) > 1e-2
    one_chunk = selective_scan(x, delta, a, b, c, d_skip, chunk=32)
    assert worst_relative(whole, one_chunk) < FP32_LIMIT


def test_the_chunk_length_does_not_change_the_result():
    args = scan_inputs(48)
    results = [selective_scan(*args, chunk=chunk) for chunk in (1, 4, 16, 48)]
    for other in results[1:]:
        assert worst_relative(other, results[0]) < FP32_LIMIT


def test_bf16_inputs_keep_an_fp32_state_and_a_bf16_state_is_worse(monkeypatch):
    """With x, b, c in bf16 the decay, the state and the sum stay fp32: the
    result is the fp32 recurrence of the rounded inputs but for ONE rounding, of
    the output. A state and decay in bf16 (what the benchmark's control runs)
    is a different result: on fp32 inputs, where nothing else rounds, it is
    hundreds of times further from the recurrence."""
    x, delta, a, b, c, d_skip = scan_inputs(256, dtype=jnp.bfloat16)
    delta = 0.02 * delta  # slow decay: the state remembers hundreds of steps, so its rounding compounds
    want = recurrence_as_written(x, delta, a, b, c, d_skip)
    got = selective_scan(x, delta, a, b, c, d_skip, chunk=16)
    assert got.dtype == jnp.bfloat16
    assert worst_relative(got, want) < 4e-3  # one rounding of y to bf16
    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    sound = worst_relative(selective_scan(x, delta, a, b, c, d_skip, chunk=16), want)
    assert sound < FP32_LIMIT
    monkeypatch.setattr(module, "STATE_DTYPE", jnp.bfloat16)
    lowered = worst_relative(selective_scan(x, delta, a, b, c, d_skip, chunk=16), want)
    assert lowered > 100 * sound and lowered > 1e-3


def test_delta_zero_steps_neither_decay_nor_feed_the_state():
    """What the tail's padding relies on: a step with delta = 0 passes the state on unchanged."""
    x, delta, a, b, c, d_skip = scan_inputs(24)
    still = delta.at[:, 8:16].set(0.0)
    y = selective_scan(x, still, a, b, c, jnp.zeros_like(d_skip), chunk=8)
    keep = lambda v: jnp.concatenate([v[:, :8], v[:, 16:]], axis=1)
    skipped = selective_scan(keep(x), keep(still), a, keep(b), keep(c), jnp.zeros_like(d_skip), chunk=8)
    assert worst_relative(keep(y), skipped) < FP32_LIMIT


def test_inside_shard_map_the_carry_varies_as_the_data_does():
    from network_distributed_pytorch_tpu.parallel import make_mesh

    mesh = make_mesh()
    x, delta, a, b, c, d_skip = scan_inputs(16, bsz=mesh.size)
    data, whole = P("data"), P()

    def per_worker(x, delta, a, b, c, d_skip):
        loss = lambda *v: jnp.sum(selective_scan(*v, chunk=4) ** 2)
        return selective_scan(x, delta, a, b, c, d_skip, chunk=4), jax.grad(loss, argnums=1)(x, delta, a, b, c, d_skip)

    y, d_delta = jax.jit(jax.shard_map(
        per_worker, mesh=mesh, in_specs=(data, data, whole, data, data, whole), out_specs=(data, data),
    ))(x, delta, a, b, c, d_skip)
    assert worst_relative(y, selective_scan(x, delta, a, b, c, d_skip, chunk=4)) < FP32_LIMIT
    assert np.isfinite(np.asarray(d_delta)).all()


def sizes_outside_scan_bodies(jaxpr, found=None, inside=False):
    """The largest value (in elements) among the equations' outputs of
    ``jaxpr`` and every sub-jaxpr that is NOT a ``scan``'s body, and the
    largest inside the bodies: ``(outside, inside)``."""
    found = found if found is not None else {"outside": 0, "inside": 0}
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            size = int(np.prod(var.aval.shape)) if hasattr(var.aval, "shape") else 0
            key = "inside" if inside else "outside"
            found[key] = max(found[key], size)
        for name, value in eqn.params.items():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    sizes_outside_scan_bodies(inner, found, inside or eqn.primitive.name == "scan")
    return found["outside"], found["inside"]


def test_no_value_of_the_size_of_t_c_n_lives_outside_the_chunks_body():
    """T = 8 chunks: in the function and in its gradient every value outside
    the ``lax.scan`` over chunks is at most (T, C) — the inputs, ``y`` and
    their cotangents, and the carried (chunks, N, C) states the backward keeps,
    an eighth of (T, C, N) — while inside the body a step's (N, C) state lives
    forward and a chunk's (chunk, N, C) states backward."""
    t, ch, n, chunk = 64, 32, 8, 8
    args = scan_inputs(t, ch=ch, n=n, bsz=1)
    forward = jax.make_jaxpr(lambda *v: selective_scan(*v, chunk=chunk))(*args)
    outside, inside = sizes_outside_scan_bodies(forward.jaxpr)
    assert outside <= t * ch and ch * n <= inside <= chunk * ch * n  # forward a body holds a step's state
    backward = jax.make_jaxpr(
        jax.grad(lambda *v: jnp.sum(selective_scan(*v, chunk=chunk)), argnums=tuple(range(6)))
    )(*args)
    outside, inside = sizes_outside_scan_bodies(backward.jaxpr)
    assert outside < t * ch * n and outside <= max(t * ch, (t // chunk) * ch * n)
    assert chunk * ch * n <= inside < t * ch * n
