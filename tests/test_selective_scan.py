"""Mamba-1's selective scan (``ops/selective_scan.py``) on the CPU at small
sizes, the plain walk in chunks and the two Pallas kernels in the interpreter:
against the recurrence as the benchmark's plain reference writes it, one step
at a time — values and all six cotangents, at chunk lengths that do and do not
divide T, the state carried across chunks, an fp32 state under bf16 inputs,
the tail's padding, inside ``shard_map``; and no value the size of (T, C, N)
anywhere outside the chunk's body or as an operand or result of a kernel, in
the function or in its gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import phi4flash as reference
from network_distributed_pytorch_tpu.ops import selective_scan as module
from network_distributed_pytorch_tpu.ops.selective_scan import selective_scan, serves

FP32_LIMIT = 1e-5


@pytest.fixture(scope="module", autouse=True)
def release_the_interpreted_kernels_programs():
    """A kernel in the Pallas interpreter is a large XLA:CPU program, and this
    file's leave 7,000 memory mappings in the worker that ran them, of the
    65,530 a process may hold: with them one worker of a whole run died inside
    a later file's compile. Dropping jax's caches unmaps them."""
    yield
    jax.clear_caches()


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


WALKED = dict(ch=24, n=4, interpret=None)  # off the TPU the backend's own choice is the plain walk
# lane-aligned shapes through the kernels in the interpreter: four time blocks, a T the time
# block does not divide, one time block
KERNELS = [dict(t=64, chunk=16, ch=128, n=8, bsz=2), dict(t=72, chunk=32, ch=256, n=16, bsz=2), dict(t=48, chunk=None, ch=128, n=16, bsz=1)]
CASES = [dict(t=t, chunk=chunk, **WALKED) for t, chunk in [(32, 8), (32, 32), (29, 8), (7, 16), (64, None), (130, None)]]
CASES += [dict(case, interpret=True) for case in KERNELS]
case_id = lambda case: "-".join(f"{key}{value}" for key, value in case.items() if key not in ("interpret", "bsz")) + ("-kernels" if case["interpret"] else "")


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_values_and_all_six_cotangents_against_the_recurrence(case):
    case = dict(case)
    chunk, interpret = case.pop("chunk"), case.pop("interpret")
    scan = lambda *v: selective_scan(*v, chunk=chunk, interpret=interpret)
    args = scan_inputs(**case)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = scan(*args)
    want = recurrence_as_written(*args)
    assert got.shape == want.shape and got.dtype == args[0].dtype
    assert worst_relative(got, want) < FP32_LIMIT
    every = tuple(range(6))
    grads = jax.grad(lambda *v: jnp.sum(scan(*v) * weight), argnums=every)(*args)
    wanted = jax.grad(lambda *v: jnp.sum(recurrence_as_written(*v) * weight), argnums=every)(*args)
    assert worst_relative(grads, wanted) < 1e-4
    if interpret:  # and the kernels are the walk, value and cotangents
        walked = lambda *v: selective_scan(*v, chunk=chunk)
        assert worst_relative(got, walked(*args)) < FP32_LIMIT
        assert worst_relative(grads, jax.grad(lambda *v: jnp.sum(walked(*v) * weight), argnums=every)(*args)) < 1e-4


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


@pytest.mark.parametrize("shape,interpret", [(dict(), None), (dict(ch=128, n=8, bsz=1), True)], ids=["walk", "kernels"])
def test_bf16_inputs_keep_an_fp32_state_and_a_bf16_state_is_worse(monkeypatch, shape, interpret):
    """With x, b, c in bf16 the decay, the state and the sum stay fp32: the
    result is the fp32 recurrence of the rounded inputs but for ONE rounding, of
    the output. A state and decay in bf16 (what the benchmark's control runs)
    is a different result: on fp32 inputs, where nothing else rounds, it is
    hundreds of times further from the recurrence. The walk and the kernels
    alike: both read ``STATE_DTYPE`` as they are traced."""
    scan = lambda *v: selective_scan(*v, chunk=16, interpret=interpret)
    x, delta, a, b, c, d_skip = scan_inputs(256, dtype=jnp.bfloat16, **shape)
    delta = 0.02 * delta  # slow decay: the state remembers hundreds of steps, so its rounding compounds
    want = recurrence_as_written(x, delta, a, b, c, d_skip)
    got = scan(x, delta, a, b, c, d_skip)
    assert got.dtype == jnp.bfloat16
    assert worst_relative(got, want) < 4e-3  # one rounding of y to bf16
    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    sound = worst_relative(scan(x, delta, a, b, c, d_skip), want)
    assert sound < FP32_LIMIT
    monkeypatch.setattr(module, "STATE_DTYPE", jnp.bfloat16)
    lowered = worst_relative(scan(x, delta, a, b, c, d_skip), want)
    assert lowered > 100 * sound and lowered > 1e-3


def test_bf16_inputs_through_the_kernels_round_each_cotangent_once():
    """x, b, c in bf16: their cotangents leave the backward kernel in bf16, rounded once from fp32 sums."""
    args = scan_inputs(64, ch=128, n=8, bsz=2, dtype=jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    every = tuple(range(6))
    grads = jax.grad(lambda *v: jnp.sum(selective_scan(*v, chunk=32, interpret=True) * weight), argnums=every)(*args)
    wanted = jax.grad(lambda *v: jnp.sum(recurrence_as_written(*v) * weight), argnums=every)(*args)
    assert [g.dtype for g in grads] == [v.dtype for v in args]
    assert worst_relative(grads, wanted) < 8e-3  # y's rounding reaches every cotangent, and x's, b's, c's own are rounded


def test_the_kernels_serve_the_cells_shape_and_the_walk_answers_elsewhere():
    """``serves`` reads the shapes: the channels whole lane blocks, the state's
    indices whole sublane tiles. A shape it declines is walked whatever
    ``interpret`` says."""
    assert serves(8192, 5120, 16) and serves(200, 256, 8)
    assert not serves(32, 24, 4) and not serves(32, 128, 4) and not serves(32, 100, 8)
    args = scan_inputs(32)
    walked = selective_scan(*args, chunk=8)
    for interpret in (True, False):
        np.testing.assert_array_equal(selective_scan(*args, chunk=8, interpret=interpret), walked)


def test_delta_zero_steps_neither_decay_nor_feed_the_state():
    """What the tail's padding relies on: a step with delta = 0 passes the state on unchanged."""
    x, delta, a, b, c, d_skip = scan_inputs(24)
    still = delta.at[:, 8:16].set(0.0)
    y = selective_scan(x, still, a, b, c, jnp.zeros_like(d_skip), chunk=8)
    keep = lambda v: jnp.concatenate([v[:, :8], v[:, 16:]], axis=1)
    skipped = selective_scan(keep(x), keep(still), a, keep(b), keep(c), jnp.zeros_like(d_skip), chunk=8)
    assert worst_relative(keep(y), skipped) < FP32_LIMIT


@pytest.mark.parametrize("shape,interpret", [(dict(t=16), None), (dict(t=32, ch=128, n=8), True)], ids=["walk", "kernels"])
def test_inside_shard_map_the_carry_varies_as_the_data_does(shape, interpret):
    """Per worker under ``check_vma``, ``a`` and ``d_skip`` whole on every
    worker: the walk's carry and the kernels' operands are cast to vary as the
    data does, the kernels' outputs declare how they vary, and the cotangent of
    an invariant operand is summed over the mesh by the cast's transpose."""
    from network_distributed_pytorch_tpu.parallel import make_mesh

    mesh = make_mesh()
    chunk = shape["t"] // 2
    scan = lambda *v: selective_scan(*v, chunk=chunk, interpret=interpret)
    args = scan_inputs(bsz=mesh.size, **shape)
    data, whole = P("data"), P()

    def per_worker(*args):
        d_delta, d_a = jax.grad(lambda *v: jnp.sum(scan(*v) ** 2), argnums=(1, 2))(*args)
        return scan(*args), d_delta, d_a

    sharded = lambda check_vma: jax.shard_map(
        per_worker, mesh=mesh, in_specs=(data, data, whole, data, data, whole), out_specs=(data, data, whole), check_vma=check_vma,
    )
    typed = str(jax.make_jaxpr(sharded(True))(*args))  # the trace alone decides the types
    assert "psum" in typed and ("selective_scan_bwd" in typed) == bool(interpret)
    # the Pallas interpreter runs only unchecked: its own block slicing mixes varying arrays with invariant indices
    y, d_delta, _ = jax.jit(sharded(not interpret))(*args)
    assert worst_relative(y, scan(*args)) < FP32_LIMIT
    assert worst_relative(d_delta, jax.grad(lambda *v: jnp.sum(scan(*v) ** 2), argnums=1)(*args)) < 1e-4


def inner_jaxprs(eqn):
    """The jaxprs among an equation's parameters: a loop's body, a call's function, a kernel."""
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


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
        for inner in inner_jaxprs(eqn):
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


def kernels_and_the_largest_value_outside_them(jaxpr, calls=None, largest=0):
    """Every ``pallas_call`` of ``jaxpr`` and its sub-jaxprs, and the largest
    value (in elements) among every equation's outputs outside the kernels'
    bodies, the kernels' own results among them."""
    calls = calls if calls is not None else []
    for eqn in jaxpr.eqns:
        largest = max([largest] + [int(np.prod(var.aval.shape)) for var in eqn.outvars if hasattr(var.aval, "shape")])
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
            continue
        for inner in inner_jaxprs(eqn):
            _, largest = kernels_and_the_largest_value_outside_them(inner, calls, largest)
    return calls, largest


def test_no_value_of_the_size_of_t_c_n_is_an_operand_or_a_result_of_a_kernel_or_lives_outside_them():
    """Four time blocks of 16: the forward is one kernel whose results are
    ``y`` and the (T / 16, N, C) states the time blocks start from, a quarter
    of (T, C, N) here and 1 / 128 of it at the cell's time block; the gradient
    is that kernel and the backward's; every operand and result of either and
    every value outside them is at most (T, C) or those states."""
    bsz, t, ch, n, chunk = 1, 64, 128, 8, 16
    args = scan_inputs(t, ch=ch, n=n, bsz=bsz)
    scan = lambda *v: selective_scan(*v, chunk=chunk, interpret=True)
    name = lambda call: call.params["name"]
    elements = lambda call: [int(np.prod(var.aval.shape)) for var in (*call.invars, *call.outvars)]
    allowed = max(t * ch, (t // chunk) * n * ch)
    assert allowed < t * ch * n

    calls, largest = kernels_and_the_largest_value_outside_them(jax.make_jaxpr(scan)(*args).jaxpr)
    assert [name(call) for call in calls] == ["selective_scan"]
    assert [var.aval.shape for var in calls[0].outvars] == [(bsz, t, ch), (bsz, t // chunk, n, ch)]
    assert max(elements(calls[0])) <= allowed and largest <= allowed

    backward = jax.make_jaxpr(jax.grad(lambda *v: jnp.sum(scan(*v)), argnums=tuple(range(6))))(*args)
    calls, largest = kernels_and_the_largest_value_outside_them(backward.jaxpr)
    assert [name(call) for call in calls] == ["selective_scan", "selective_scan_bwd"]
    assert max(max(elements(call)) for call in calls) <= allowed and largest <= allowed
