"""Round-6 chunked, software-pipelined reduction (``parallel.comm``):
chunked-vs-monolithic BIT-exactness for both reducers, ledger byte
invariance, the explicit ppermute ring, chunked FSDP gathers, and the
compiled collective structure (K chunks must survive XLA as K collectives
whose payloads reconcile byte-exactly with the wire ledger)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from network_distributed_pytorch_tpu.parallel import (
    DATA_AXIS,
    ExactReducer,
    PowerSGDReducer,
    make_mesh,
)
from network_distributed_pytorch_tpu.parallel.comm import (
    chunk_bounds,
    chunked_all_reduce_mean,
    fence,
    ring_all_reduce_mean,
)
from network_distributed_pytorch_tpu.parallel.reducers import PowerSGDState

W = 8
CHUNK_COUNTS = (1, 2, 3, 7)  # 7 leaves a ragged last chunk on every payload


def _bits(x):
    """uint bit-pattern view — equality here is BITWISE, not allclose."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[x.dtype.itemsize])


def _template_leaves(key):
    ks = jax.random.split(key, 5)
    return [
        jax.random.normal(ks[0], (8, 3, 3, 3)),
        jax.random.normal(ks[1], (16, 8)),
        jax.random.normal(ks[2], (16,)),
        jax.random.normal(ks[3], (10, 16)),
        jax.random.normal(ks[4], (10,)),
    ]


def _stacked_sends(seed):
    """One distinct template per worker, stacked along the device axis."""
    per_worker = [_template_leaves(jax.random.PRNGKey(seed + w)) for w in range(W)]
    return [jnp.stack([pw[i] for pw in per_worker]) for i in range(5)]


# ---- chunk_bounds / fence units -------------------------------------------


def test_chunk_bounds_partition_and_balance():
    for total in (1, 7, 8, 530, 1000):
        for k in (1, 2, 3, 7, 16):
            bounds = chunk_bounds(total, k)
            assert len(bounds) == min(k, total)
            # contiguous partition of [0, total)
            assert bounds[0][0] == 0 and bounds[-1][1] == total
            for (_, e0), (s1, _) in zip(bounds, bounds[1:]):
                assert e0 == s1
            sizes = [e - s for s, e in bounds]
            # balanced: sizes differ by at most 1, larger chunks first
            assert max(sizes) - min(sizes) <= 1
            assert sizes == sorted(sizes, reverse=True)


def test_chunk_bounds_edge_cases():
    assert chunk_bounds(0, 4) == []
    assert chunk_bounds(-3, 4) == []
    assert chunk_bounds(3, 10) == [(0, 1), (1, 2), (2, 3)]  # clamped to size
    assert chunk_bounds(5, 1) == [(0, 5)]
    assert chunk_bounds(5, 0) == [(0, 5)]  # k floors at 1


def test_fence_preserves_values():
    a, b = jnp.arange(4.0), jnp.ones((2, 3))
    fa = fence(a)
    np.testing.assert_array_equal(_bits(fa), _bits(a))
    fa, fb = fence(a, b)
    np.testing.assert_array_equal(_bits(fa), _bits(a))
    np.testing.assert_array_equal(_bits(fb), _bits(b))
    assert fence() == ()


def test_fence_is_transparent_to_grad():
    # chunked FSDP gathers differentiate through the barrier, so
    # grad(f ∘ fence) must equal grad(f)
    def f(x):
        return jnp.sum(fence(x) ** 2)

    x = jnp.arange(5.0)
    np.testing.assert_array_equal(
        _bits(jax.grad(f)(x)), _bits(jax.grad(lambda x: jnp.sum(x**2))(x))
    )


# ---- chunked flat all-reduce ----------------------------------------------


def _run_flat(fn, flat_per_device):
    mesh = make_mesh()

    def body(xs):
        return fn(xs[0])[None]

    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS))
    )(flat_per_device)


@pytest.mark.parametrize("k", CHUNK_COUNTS)
def test_chunked_flat_allreduce_bitwise(devices, k):
    # 531 elements: ragged under every K in CHUNK_COUNTS except 1
    flat = jax.random.normal(jax.random.PRNGKey(0), (W, 531))
    mono = _run_flat(lambda x: chunked_all_reduce_mean(x, DATA_AXIS, 1), flat)
    chunked = _run_flat(lambda x: chunked_all_reduce_mean(x, DATA_AXIS, k), flat)
    np.testing.assert_array_equal(_bits(chunked), _bits(mono))


def test_chunked_flat_allreduce_single_process():
    # axis None falls through to the per-chunk identity fallback
    x = jnp.arange(11.0)
    out = chunked_all_reduce_mean(x, None, 3)
    np.testing.assert_array_equal(_bits(out), _bits(x))


# ---- explicit ppermute ring -----------------------------------------------


def test_ring_allreduce_close_to_pmean(devices):
    flat = jax.random.normal(jax.random.PRNGKey(1), (W, 530))
    mean = _run_flat(lambda x: jax.lax.pmean(x, DATA_AXIS), flat)
    ring = _run_flat(lambda x: ring_all_reduce_mean(x, DATA_AXIS), flat)
    # the ring REASSOCIATES (each shard sums in a different rank rotation):
    # deterministic and ~1-ulp close, but not bitwise pmean — DESIGN.md R6
    np.testing.assert_allclose(np.asarray(ring), np.asarray(mean), rtol=1e-5, atol=1e-7)


def test_ring_allreduce_exact_on_dyadic(devices):
    # sums of small integers over W=8 divide exactly in binary floating
    # point, so reassociation cannot change the result: bitwise equal
    flat = jnp.asarray(
        np.random.RandomState(2).randint(-8, 8, size=(W, 37)), jnp.float32
    )
    mean = _run_flat(lambda x: jax.lax.pmean(x, DATA_AXIS), flat)
    ring = _run_flat(lambda x: ring_all_reduce_mean(x, DATA_AXIS), flat)
    np.testing.assert_array_equal(_bits(ring), _bits(mean))


def test_ring_allreduce_ragged_and_shape(devices):
    # 13 !% 8: the ring pads to 16, reduces, slices back
    flat = jax.random.normal(jax.random.PRNGKey(3), (W, 13))
    ring = _run_flat(lambda x: ring_all_reduce_mean(x, DATA_AXIS), flat)
    mean = _run_flat(lambda x: jax.lax.pmean(x, DATA_AXIS), flat)
    assert ring.shape == flat.shape
    np.testing.assert_allclose(np.asarray(ring), np.asarray(mean), rtol=1e-5, atol=1e-7)


def test_ring_allreduce_single_process_fallbacks():
    x = jnp.arange(6.0)
    np.testing.assert_array_equal(_bits(ring_all_reduce_mean(x, None)), _bits(x))


@pytest.mark.parametrize("k", (2, 3))
def test_chunked_ring_strategy_close(devices, k):
    flat = jax.random.normal(jax.random.PRNGKey(4), (W, 201))
    mean = _run_flat(lambda x: jax.lax.pmean(x, DATA_AXIS), flat)
    ring = _run_flat(
        lambda x: chunked_all_reduce_mean(x, DATA_AXIS, k, strategy="ring"), flat
    )
    np.testing.assert_allclose(np.asarray(ring), np.asarray(mean), rtol=1e-5, atol=1e-7)


# ---- reducers: chunked == monolithic, bitwise -----------------------------


def _run_exact(reducer, stacked):
    mesh = make_mesh()

    def f(*send):
        send = [s[0] for s in send]
        _, out, _, _ = reducer.reduce({}, send, DATA_AXIS)
        return tuple(o[None] for o in out)

    return jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(P(DATA_AXIS),) * 5, out_specs=(P(DATA_AXIS),) * 5
        )
    )(*stacked)


@pytest.mark.parametrize("k", CHUNK_COUNTS)
def test_exact_chunked_bitwise_equals_monolithic(devices, k):
    stacked = _stacked_sends(50)
    mono = _run_exact(ExactReducer(), stacked)
    chunked = _run_exact(ExactReducer(comm_chunks=k), stacked)
    for a, b in zip(chunked, mono):
        np.testing.assert_array_equal(_bits(a), _bits(b))


def _run_powersgd(reducer, template, stacked):
    mesh = make_mesh()
    state = reducer.init(template)

    def f(q_memory, key, *send):
        send = [s[0] for s in send]
        st, out, mem, _ = reducer.reduce(PowerSGDState(q_memory, key), send, DATA_AXIS)
        return (
            st.q_memory,
            st.key,
            tuple(o[None] for o in out),
            tuple(m[None] for m in mem),
        )

    return jax.jit(
        jax.shard_map(
            f,
            mesh=mesh,
            in_specs=(P(), P()) + (P(DATA_AXIS),) * 5,
            out_specs=(P(), P(), (P(DATA_AXIS),) * 5, (P(DATA_AXIS),) * 5),
        )
    )(state.q_memory, state.key, *stacked)


@pytest.mark.parametrize("k", CHUNK_COUNTS)
def test_powersgd_chunked_bitwise_equals_monolithic(devices, k):
    template = [jnp.zeros_like(l) for l in _template_leaves(jax.random.PRNGKey(0))]
    stacked = _stacked_sends(80)
    kwargs = dict(random_seed=11, compression_rank=2, matricize="last")
    q_m, key_m, out_m, mem_m = _run_powersgd(
        PowerSGDReducer(**kwargs), template, stacked
    )
    q_c, key_c, out_c, mem_c = _run_powersgd(
        PowerSGDReducer(comm_chunks=k, **kwargs), template, stacked
    )
    np.testing.assert_array_equal(_bits(q_c), _bits(q_m))
    for a, b in zip(out_c + mem_c, out_m + mem_m):
        np.testing.assert_array_equal(_bits(a), _bits(b))


# ---- ledger: byte-invariant under K, counts itemize the chunks ------------


@pytest.mark.parametrize("k", CHUNK_COUNTS)
def test_exact_ledger_bytes_invariant_counts_chunked(k):
    template = _template_leaves(jax.random.PRNGKey(0))
    mono = ExactReducer()
    chunked = ExactReducer(comm_chunks=k)
    base = mono.ledger_entries(template, axis=DATA_AXIS)
    entries = chunked.ledger_entries(template, axis=DATA_AXIS)
    # same bytes (the chunks PARTITION the flat buffer), count = chunks
    assert sum(e.payload_bytes for e in entries) == sum(
        e.payload_bytes for e in base
    )
    assert sum(e.count for e in entries) == chunked.n_collectives(template) == k
    # and the ledger still sums exactly to the analytic bits model
    _, _, _, bits = mono.reduce({}, template, None)
    assert 8 * sum(e.payload_bytes for e in entries) == bits


@pytest.mark.parametrize("k", CHUNK_COUNTS)
def test_powersgd_ledger_bytes_invariant_counts_chunked(k):
    template = _template_leaves(jax.random.PRNGKey(0))
    kwargs = dict(random_seed=11, compression_rank=2, matricize="last")
    mono = PowerSGDReducer(**kwargs)
    chunked = PowerSGDReducer(comm_chunks=k, **kwargs)
    base = mono.ledger_entries(template, axis=DATA_AXIS)
    entries = chunked.ledger_entries(template, axis=DATA_AXIS)
    assert sum(e.payload_bytes for e in entries) == sum(
        e.payload_bytes for e in base
    )
    assert 8 * sum(e.payload_bytes for e in entries) == mono.bits_per_step(template)
    # each payload (P, Q, rank1) chunks independently — clamped by its size
    from network_distributed_pytorch_tpu.parallel.reducers import (
        _n_chunk_collectives,
    )

    metas = chunked._metas(template)
    p_packer, q_packer, r1_packer = chunked._packers(template, metas)
    by_tag = {e.tag: e.count for e in entries}
    assert by_tag["powersgd.P"] == _n_chunk_collectives(p_packer.total_size, k)
    assert by_tag["powersgd.Q"] == _n_chunk_collectives(q_packer.total_size, k)
    assert by_tag["powersgd.rank1"] == _n_chunk_collectives(r1_packer.total_size, k)


def test_comm_chunks_requires_packed():
    with pytest.raises(AssertionError):
        ExactReducer(packed=False, comm_chunks=2)
    with pytest.raises(AssertionError):
        ExactReducer(comm_strategy="bogus")


# ---- trainer end-to-end: chunked step == unchunked step, bitwise ----------


def test_train_step_chunked_bitwise(devices):
    from network_distributed_pytorch_tpu.models import SmallCNN
    from network_distributed_pytorch_tpu.parallel.trainer import (
        make_train_step,
        stateless_loss,
    )
    from network_distributed_pytorch_tpu.utils import cross_entropy_loss

    img = (8, 8, 3)
    model = SmallCNN(width=4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *img)))["params"]

    def loss_fn(params, batch):
        x, y = batch
        return cross_entropy_loss(model.apply({"params": params}, x), y)

    loss_fn = stateless_loss(loss_fn)
    mesh = make_mesh()

    def run(reducer):
        step = make_train_step(
            loss_fn, reducer, params, learning_rate=0.05, momentum=0.9,
            algorithm="sgd", mesh=mesh, donate_state=False,
        )
        state = step.init_state(params)
        for i in range(3):
            ky, kx = jax.random.split(jax.random.PRNGKey(i))
            y = jax.random.randint(ky, (64,), 0, 10)
            x = jax.random.normal(kx, (64, *img))
            state, loss = step(state, (x, y))
        return state, step

    s_mono, _ = run(ExactReducer())
    s_chunk, step_chunk = run(ExactReducer(comm_chunks=3))
    for a, b in zip(
        jax.tree_util.tree_leaves(s_chunk.params),
        jax.tree_util.tree_leaves(s_mono.params),
    ):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    # the step's compile-time ledger itemizes the chunks and still sums to
    # bits_per_step (step_ledger's construction-time assert also ran)
    assert step_chunk.ledger.total_bits() == step_chunk.bits_per_step


# ---- FSDP: chunked gathers == monolithic, bitwise -------------------------


def test_fsdp_chunked_bitwise(devices):
    from network_distributed_pytorch_tpu.models import SmallCNN
    from network_distributed_pytorch_tpu.parallel.fsdp import make_fsdp_train_step
    from network_distributed_pytorch_tpu.parallel.trainer import stateless_loss
    from network_distributed_pytorch_tpu.utils import cross_entropy_loss

    img = (8, 8, 3)
    model = SmallCNN(width=4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *img)))["params"]

    def loss_fn(params, batch):
        x, y = batch
        return cross_entropy_loss(model.apply({"params": params}, x), y)

    loss_fn = stateless_loss(loss_fn)
    mesh = make_mesh()

    def run(comm_chunks):
        step = make_fsdp_train_step(
            loss_fn, params, learning_rate=0.05, momentum=0.9, algorithm="sgd",
            mesh=mesh, donate_state=False, comm_chunks=comm_chunks,
        )
        state = step.init_state(params)
        for i in range(2):
            ky, kx = jax.random.split(jax.random.PRNGKey(i))
            y = jax.random.randint(ky, (64,), 0, 10)
            x = jax.random.normal(kx, (64, *img))
            state, _ = step(state, (x, y))
        return step.unshard(state)

    mono = run(None)
    chunked = run(2)
    for a, b in zip(
        jax.tree_util.tree_leaves(chunked), jax.tree_util.tree_leaves(mono)
    ):
        np.testing.assert_array_equal(_bits(a), _bits(b))


# ---- compiled structure: K chunks survive XLA as K collectives ------------


@pytest.mark.parametrize("k", (3, 7))
def test_compiled_chunk_collectives_survive_and_reconcile(devices, k):
    """The pipeline's whole point: the barrier-fenced chunks must NOT be
    re-fused by XLA — the compiled step carries exactly the ledger's
    collective count, and the HLO payload bytes equal the ledger's."""
    from network_distributed_pytorch_tpu.observe.ledger import WireLedger
    from network_distributed_pytorch_tpu.utils.hlo_audit import (
        collective_summary,
        hlo_text_of_compiled,
    )

    mesh = make_mesh()
    reducer = ExactReducer(comm_chunks=k)
    template = _template_leaves(jax.random.PRNGKey(0))
    stacked = tuple(jnp.stack([l] * W) for l in template)

    def f(*send):
        send = [s[0] for s in send]
        _, out, _, _ = reducer.reduce({}, send, DATA_AXIS)
        return tuple(o[None] for o in out)

    jitted = jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(P(DATA_AXIS),) * 5, out_specs=(P(DATA_AXIS),) * 5
        )
    )
    hlo = hlo_text_of_compiled(jitted.lower(*stacked).compile())
    summary = collective_summary(hlo)
    entries = reducer.ledger_entries(template, axis=DATA_AXIS)
    assert summary["count"] == sum(e.count for e in entries) == k
    rec = WireLedger(entries).reconcile(hlo)
    assert rec["exact"], rec
