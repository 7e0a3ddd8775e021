"""Trainer end-to-end on the 8-device mesh (SURVEY §4 integration tier):
exact-DDP ≡ single-device large-batch; PowerSGD trains; bits accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.models import SmallCNN, resnet18
from network_distributed_pytorch_tpu.parallel import (
    ExactReducer,
    PowerSGDReducer,
    make_mesh,
)
from network_distributed_pytorch_tpu.parallel.trainer import (
    make_train_step,
    stateless_loss,
)
from network_distributed_pytorch_tpu.utils import cross_entropy_loss

BATCH = 64
IMG = (8, 8, 3)


def _synthetic_batch(key, n=BATCH):
    """Learnable synthetic task: Gaussian class blobs (x = class mean + noise)."""
    ky, kx = jax.random.split(key)
    means = jax.random.normal(jax.random.PRNGKey(999), (10, *IMG))
    y = jax.random.randint(ky, (n,), 0, 10)
    x = means[y] + 0.5 * jax.random.normal(kx, (n, *IMG))
    return x, y


def _cnn_setup():
    model = SmallCNN(width=4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *IMG)))["params"]

    def loss_fn(params, batch):
        x, y = batch
        return cross_entropy_loss(model.apply({"params": params}, x), y)

    return params, stateless_loss(loss_fn)


def test_exact_ddp_equals_single_device_large_batch(devices):
    params, loss_fn = _cnn_setup()
    mesh = make_mesh()

    dist_step = make_train_step(
        loss_fn, ExactReducer(), params, learning_rate=0.05, momentum=0.9,
        algorithm="sgd", mesh=mesh, donate_state=False,
    )
    single_step = make_train_step(
        loss_fn, ExactReducer(), params, learning_rate=0.05, momentum=0.9,
        algorithm="sgd", mesh=None, donate_state=False,
    )

    sd = dist_step.init_state(params)
    ss = single_step.init_state(params)
    for i in range(5):
        batch = _synthetic_batch(jax.random.PRNGKey(i))
        sd, loss_d = dist_step(sd, batch)
        ss, loss_s = single_step(ss, batch)
        np.testing.assert_allclose(float(loss_d), float(loss_s), rtol=1e-5)

    # identical parameters: pmean of per-shard grads == grad of global mean
    for a, b in zip(jax.tree_util.tree_leaves(sd.params), jax.tree_util.tree_leaves(ss.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_powersgd_training_reduces_loss(devices):
    params, loss_fn = _cnn_setup()
    mesh = make_mesh()
    reducer = PowerSGDReducer(random_seed=714, compression_rank=2, matricize="last")
    step = make_train_step(
        loss_fn, reducer, params, learning_rate=0.05, momentum=0.9,
        algorithm="ef_momentum", mesh=mesh,
    )
    state = step.init_state(params)
    losses = []
    for i in range(50):
        state, loss = step(state, _synthetic_batch(jax.random.PRNGKey(1000 + i)))
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.8, losses


def test_bits_compressed_below_exact():
    params, loss_fn = _cnn_setup()
    exact = make_train_step(loss_fn, ExactReducer(), params, 0.01, mesh=None)
    psgd = make_train_step(
        loss_fn, PowerSGDReducer(compression_rank=2, matricize="last"), params, 0.01, mesh=None
    )
    assert 0 < psgd.bits_per_step < exact.bits_per_step
    total = sum(l.size for l in jax.tree_util.tree_leaves(params))
    assert exact.bits_per_step == 32 * total


@pytest.mark.slow
def test_resnet_batchnorm_distributed_step(devices):
    """ResNet-18 with BatchNorm: model_state (running stats) is carried
    per-worker (unsynced, like torch DDP); one distributed PowerSGD step
    runs and updates the stats."""
    model = resnet18(norm="batch", stem="cifar", width=8, num_classes=10)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *IMG)), train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, model_state, batch):
        x, y = batch
        logits, new_vars = model.apply(
            {"params": params, "batch_stats": model_state["batch_stats"]},
            x,
            train=True,
            mutable=["batch_stats"],
        )
        return cross_entropy_loss(logits, y), {"batch_stats": new_vars["batch_stats"]}

    reducer = PowerSGDReducer(compression_rank=2, matricize="last")
    mesh = make_mesh()
    step = make_train_step(
        loss_fn, reducer, params, 0.01, algorithm="ef_momentum", mesh=mesh, donate_state=False
    )
    state = step.init_state(params, model_state={"batch_stats": batch_stats})
    state2, loss = step(state, _synthetic_batch(jax.random.PRNGKey(3)))
    assert np.isfinite(float(loss))
    before = jax.tree_util.tree_leaves(state.model_state)
    after = jax.tree_util.tree_leaves(state2.model_state)
    assert any(not np.allclose(np.asarray(a), np.asarray(b)) for a, b in zip(before, after))


@pytest.mark.slow
def test_scanned_epoch_equals_stepwise(devices):
    """lax.scan multi-step runner must be numerically identical to the
    step-at-a-time loop (same collectives, same EF chain)."""
    from network_distributed_pytorch_tpu.parallel.trainer import make_scanned_train_fn

    params, loss_fn = _cnn_setup()
    mesh = make_mesh()
    reducer = PowerSGDReducer(random_seed=5, compression_rank=2, matricize="last")
    kw = dict(
        learning_rate=0.05, momentum=0.9, algorithm="ef_momentum",
        mesh=mesh, donate_state=False,
    )
    step = make_train_step(loss_fn, reducer, params, **kw)
    epoch = make_scanned_train_fn(loss_fn, reducer, params, **kw)

    batches = [_synthetic_batch(jax.random.PRNGKey(50 + i)) for i in range(4)]
    stacked = (
        jnp.stack([b[0] for b in batches]),
        jnp.stack([b[1] for b in batches]),
    )

    s1 = step.init_state(params)
    losses1 = []
    for b in batches:
        s1, l = step(s1, b)
        losses1.append(float(l))

    s2 = epoch.init_state(params)
    s2, losses2 = epoch(s2, stacked)
    np.testing.assert_allclose(np.asarray(losses2), losses1, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_max_grad_norm_clips_like_torch(devices):
    """max_grad_norm applies torch clip_grad_norm_ semantics to the reduced
    delta: the distributed clipped step equals a manually-clipped
    single-device step, and None leaves the trajectory unchanged."""
    import numpy as np

    from network_distributed_pytorch_tpu.parallel import ExactReducer, make_mesh

    rng = np.random.RandomState(0)
    w_true = 50.0 * rng.randn(16, 4).astype(np.float32)  # big grads
    x = rng.randn(64, 16).astype(np.float32)
    y = x @ w_true
    params = {"w": jnp.zeros((16, 4)), "b": jnp.zeros((4,))}
    loss_fn = stateless_loss(
        lambda p, b: jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2)
    )
    batch = (jnp.asarray(x), jnp.asarray(y))
    mesh = make_mesh()
    max_norm = 1.0
    step = make_train_step(
        loss_fn, ExactReducer(), params, 0.05, algorithm="sgd_plain",
        mesh=mesh, donate_state=False, max_grad_norm=max_norm,
    )
    state = step.init_state(params)
    state, _ = step(state, batch)

    # manual replica: global-batch gradient, clipped, one plain-SGD step
    g = jax.grad(lambda p: loss_fn(p, {}, batch)[0])(params)
    norm = float(
        jnp.sqrt(sum(jnp.sum(l ** 2) for l in jax.tree_util.tree_leaves(g)))
    )
    assert norm > max_norm  # the clip must actually engage
    scale = max_norm / (norm + 1e-6)
    ref_w = np.asarray(params["w"]) - 0.05 * scale * np.asarray(g["w"])
    np.testing.assert_allclose(
        np.asarray(state.params["w"]), ref_w, rtol=1e-5, atol=1e-7
    )
    # update norm is capped at lr * max_norm
    upd = np.asarray(state.params["w"]).ravel().tolist() + np.asarray(
        state.params["b"]
    ).ravel().tolist()
    assert np.linalg.norm(np.asarray(upd)) <= 0.05 * max_norm * 1.001


def test_collapse_per_worker_is_host_side(devices):
    """The eval collapse must produce host (numpy) leaves from a
    device-sharded model_state WITHOUT compiling a fresh multi-device
    program — an eager cross-device reduction here deadlock-aborted whole
    processes on hosts with fewer cores than devices (see
    collapse_per_worker's docstring). Pins the semantics: "mean" averages
    the per-worker axis, "first" takes worker 0, both on host arrays."""
    from jax.sharding import NamedSharding, PartitionSpec

    from network_distributed_pytorch_tpu.parallel.trainer import (
        collapse_per_worker,
    )

    mesh = make_mesh()
    w = mesh.size
    stats = np.arange(w * 3, dtype=np.float32).reshape(w, 3)
    sharded = jax.device_put(
        stats, NamedSharding(mesh, PartitionSpec("data", None))
    )
    mean = collapse_per_worker({"bn": sharded}, "mean")
    first = collapse_per_worker({"bn": sharded}, "first")
    assert isinstance(mean["bn"], np.ndarray)
    assert isinstance(first["bn"], np.ndarray)
    np.testing.assert_allclose(mean["bn"], stats.mean(axis=0))
    np.testing.assert_allclose(first["bn"], stats[0])


@pytest.mark.parametrize("n_devices", [8, 1])
def test_carry_goes_in_as_it_comes_out(devices, n_devices):
    """``init_state`` places the carry with the shardings the step hands
    back, so the second call is the first call's program: one jit entry
    after three calls. The one-device mesh is the one-chip case — left to
    itself jit returns ``P('data')`` over a one-device axis as ``P()`` and
    the second call dispatched through the slow path again."""
    from network_distributed_pytorch_tpu.data import global_batch_from_local

    mesh = make_mesh(devices=devices[:n_devices])
    params, loss_fn = _cnn_setup()
    step = make_train_step(
        loss_fn, PowerSGDReducer(random_seed=0, compression_rank=2), params,
        learning_rate=0.05, algorithm="ef_momentum", mesh=mesh,
    )
    state = step.init_state(params)
    placed = [leaf.sharding for leaf in jax.tree_util.tree_leaves(state)]
    assert {d for s in placed for d in s.device_set} == set(mesh.devices.flat)
    for i in range(3):
        x, y = _synthetic_batch(jax.random.PRNGKey(i), n=8 * n_devices)
        state, _ = step(
            state, global_batch_from_local((np.asarray(x), np.asarray(y)), mesh)
        )
    assert [leaf.sharding for leaf in jax.tree_util.tree_leaves(state)] == placed
    assert step.fn._cache_size() == 1


def test_summary_names_what_ran(devices):
    """Every run summary says what the run actually had under it, so a
    record taken without a chip cannot be read as a chip run: the device as
    jax reports it, the kernels ``"auto"`` resolved to against it, who fed
    the data, where the carry and the batches sat, and the wire bytes."""
    from network_distributed_pytorch_tpu.experiments.common import (
        summarize,
        train_loop,
    )

    params, loss_fn = _cnn_setup()
    reducer = PowerSGDReducer(random_seed=0, compression_rank=2)
    step = make_train_step(
        loss_fn, reducer, params, learning_rate=0.05,
        algorithm="ef_momentum", mesh=make_mesh(),
    )

    def batches(epoch):
        for i in range(2):
            x, y = _synthetic_batch(jax.random.PRNGKey(i))
            yield np.asarray(x), np.asarray(y)

    state, logger = train_loop(step, step.init_state(params), batches, epochs=1)
    out = summarize("t", logger, reducer=reducer, attn_impl="auto", state=state)
    assert (out["platform"], out["device_kind"], out["n_devices"]) == (
        "cpu", devices[0].device_kind, 8
    )
    assert out["pallas_interpret"] is True
    assert (out["attn_impl"], out["orthogonalize_impl"]) == ("einsum", "xla")
    assert out["host_data_tier"].startswith(("native", "numpy ("))
    assert out["device_memory"] == []  # the CPU allocator reports nothing
    assert out["steps"] == 2
    assert out["bytes_communicated"] * 8 == 2 * out["bits_per_step"] > 0
    everywhere = list(range(8))
    assert out["placement"] == {
        "params": everywhere, "memories": everywhere, "batch": everywhere
    }
