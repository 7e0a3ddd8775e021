"""Data pipeline tests: CIFAR-10 binary format round-trip, IMDb directory
parsing (reference ``read_imdb_split`` semantics), tokenizer determinism,
batch iteration static shapes."""

import pickle

import numpy as np

from network_distributed_pytorch_tpu.data import (
    HashTokenizer,
    iterate_batches,
    load_cifar10,
    load_cifar10_or_synthetic,
    prepare_imdb,
    read_imdb_split,
    steps_per_epoch,
    synthetic_cifar10,
)


def _write_fake_cifar(tmp_path):
    base = tmp_path / "cifar-10-batches-py"
    base.mkdir(parents=True)
    rng = np.random.RandomState(0)
    for i in range(1, 6):
        entry = {
            "data": rng.randint(0, 256, (20, 3072), dtype=np.uint8),
            "labels": rng.randint(0, 10, 20).tolist(),
        }
        with open(base / f"data_batch_{i}", "wb") as f:
            pickle.dump(entry, f)
    entry = {
        "data": rng.randint(0, 256, (10, 3072), dtype=np.uint8),
        "labels": rng.randint(0, 10, 10).tolist(),
    }
    with open(base / "test_batch", "wb") as f:
        pickle.dump(entry, f)


def test_cifar10_binary_format(tmp_path):
    _write_fake_cifar(tmp_path)
    x, y = load_cifar10(str(tmp_path), train=True)
    assert x.shape == (100, 32, 32, 3) and x.dtype == np.float32
    assert y.shape == (100,) and y.dtype == np.int32
    # normalization: ((u8/255) - .5)/.5 in [-1, 1]
    assert -1.0 <= x.min() and x.max() <= 1.0
    xt, yt = load_cifar10(str(tmp_path), train=False)
    assert xt.shape == (10, 32, 32, 3)
    # channel unpacking: first 1024 bytes are the R plane
    with open(tmp_path / "cifar-10-batches-py" / "data_batch_1", "rb") as f:
        raw = pickle.load(f, encoding="latin1")["data"]
    np.testing.assert_allclose(
        x[0, 0, 0, 0], ((raw[0, 0] / 255.0) - 0.5) / 0.5, rtol=1e-6
    )


def test_cifar10_fallback(tmp_path):
    x, y, real = load_cifar10_or_synthetic(str(tmp_path / "nope"), synthetic_n=64)
    assert not real and x.shape == (64, 32, 32, 3)
    sx, sy = synthetic_cifar10(32, seed=1)
    sx2, sy2 = synthetic_cifar10(32, seed=1)
    np.testing.assert_array_equal(sx, sx2)  # deterministic


def test_read_imdb_split(tmp_path):
    for label in ["pos", "neg"]:
        d = tmp_path / "train" / label
        d.mkdir(parents=True)
        for i in range(3):
            (d / f"{i}.txt").write_text(f"{label} review {i}")
    texts, labels = read_imdb_split(str(tmp_path / "train"))
    assert len(texts) == 6
    # pos first (label 1), then neg (label 0) — reference iteration order
    assert labels == [1, 1, 1, 0, 0, 0]
    assert texts[0].startswith("pos")


def test_hash_tokenizer():
    tok = HashTokenizer(vocab_size=1000, max_len=16)
    out = tok(["hello world", "hello world hello"])
    assert out["input_ids"].shape == (2, 16)
    # [CLS] first, [SEP] terminated, deterministic ids, mask aligned
    assert out["input_ids"][0, 0] == 1
    assert out["input_ids"][0, 3] == 2
    assert out["attention_mask"][0].sum() == 4
    assert out["input_ids"][0, 1] == out["input_ids"][1, 1]  # same word, same id
    assert (out["input_ids"] < 1000).all()


def test_prepare_imdb_synthetic():
    train, val, real = prepare_imdb(max_len=32, vocab_size=512, synthetic_n=100)
    assert not real
    assert train["input_ids"].shape == (80, 32)
    assert val["input_ids"].shape == (20, 32)
    assert set(np.unique(train["labels"])) <= {0, 1}


def test_iterate_batches_static_shapes():
    x = np.arange(103)
    y = np.arange(103) * 2
    batches = list(iterate_batches([x, y], 10, seed=1, epoch=0))
    assert len(batches) == 10 == steps_per_epoch(103, 10)
    for bx, by in batches:
        assert bx.shape == (10,)
        np.testing.assert_array_equal(by, bx * 2)  # alignment preserved
    # different epoch -> different order; same epoch -> same order
    b0 = list(iterate_batches([x], 10, seed=1, epoch=0))
    b1 = list(iterate_batches([x], 10, seed=1, epoch=1))
    b0b = list(iterate_batches([x], 10, seed=1, epoch=0))
    assert not all(np.array_equal(a[0], b[0]) for a, b in zip(b0, b1))
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(b0, b0b))


def test_device_prefetch_preserves_trajectory():
    """train_loop with async device prefetch must produce the IDENTICAL
    training trajectory as the unprefetched loop (staging is pure overlap,
    never reordering), on the real 8-device mesh step."""
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.experiments.common import train_loop
    from network_distributed_pytorch_tpu.parallel import ExactReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import (
        make_train_step,
        stateless_loss,
    )

    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype(np.float32)
    y = (x @ rng.randn(8, 1).astype(np.float32))[:, 0]
    params = {"w": jnp.zeros((8,))}
    loss = stateless_loss(
        lambda p, b: ((b[0] @ p["w"] - b[1]) ** 2).mean()
    )
    step = make_train_step(
        loss, ExactReducer(), params, 0.05, mesh=make_mesh(),
        algorithm="sgd_plain", donate_state=False,
    )

    def batches(epoch):
        yield from iterate_batches([x, y], 16, seed=7, epoch=epoch)

    outs = []
    for prefetch in (0, 2):
        state = step.init_state(params)
        state, logger = train_loop(
            step, state, batches, epochs=2, log_every=0, prefetch=prefetch
        )
        outs.append((np.asarray(state.params["w"]), logger.summary()["final_loss"]))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    assert outs[0][1] == outs[1][1]


def test_train_loop_data_load_tree_shares_its_step():
    """Everything the loop does to fetch a batch is a span under that
    step's ``data_load`` and carries its step: the numpy gather, the copy to
    device 0 that ``accumulated_batches`` makes, and the staging."""
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.experiments.common import (
        accumulated_batches,
        train_loop,
    )
    from network_distributed_pytorch_tpu.observe.sinks import MemorySink
    from network_distributed_pytorch_tpu.observe.telemetry import Telemetry
    from network_distributed_pytorch_tpu.parallel import ExactReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import (
        make_train_step,
        stateless_loss,
    )
    from network_distributed_pytorch_tpu.utils.config import ExperimentConfig

    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype(np.float32)
    y = (x @ rng.randn(8, 1).astype(np.float32))[:, 0]
    params = {"w": jnp.zeros((8,))}
    loss = stateless_loss(
        lambda p, b: ((b["x"] @ p["w"] - b["y"]) ** 2).mean()
    )
    step = make_train_step(
        loss, ExactReducer(), params, 0.05, mesh=make_mesh(),
        algorithm="sgd_plain", donate_state=False,
    )
    config = ExperimentConfig(global_batch_size=16, seed=7)
    # dict batches keep the numpy loader: its assemble span is in the tree
    batches = accumulated_batches((x, y), config, keys=("x", "y"))
    sink = MemorySink()
    train_loop(
        step, step.init_state(params), batches, epochs=1, log_every=0,
        prefetch=2, telemetry=Telemetry([sink]),
    )
    spans = sink.of_kind("span")
    loads = {r["span_id"]: r for r in spans if r["name"] == "data_load"}
    assert sorted(r["step"] for r in loads.values()) == [0, 1, 2, 3, 4]
    children = [r for r in spans if r["name"].startswith("data_load/")]
    assert {r["name"] for r in children} == {
        "data_load/assemble", "data_load/to_device", "data_load/stage"
    }
    to_device = [r for r in children if r["name"] == "data_load/to_device"]
    assert len(to_device) == 4  # one per batch of the epoch
    for r in children:
        assert r["step"] == loads[r["parent_id"]]["step"]
    # the first fetch fills the prefetch ring: three batches under step 0
    assert sum(1 for r in to_device if r["step"] == 0) == 3


def test_cifar10_bin_format_matches_pickle(tmp_path):
    """The SAME dataset written as cifar-10-batches-bin (native decoder) and
    cifar-10-batches-py (pickle) loads to identical arrays."""
    _write_fake_cifar(tmp_path)
    xp, yp = load_cifar10(str(tmp_path), train=True)
    xpt, ypt = load_cifar10(str(tmp_path), train=False)

    bin_root = tmp_path / "bin"
    base = bin_root / "cifar-10-batches-bin"
    base.mkdir(parents=True)

    def write_bin(pickle_name, bin_name):
        with open(tmp_path / "cifar-10-batches-py" / pickle_name, "rb") as f:
            entry = pickle.load(f, encoding="latin1")
        np.concatenate(
            [
                np.asarray(entry["labels"], np.uint8)[:, None],
                np.asarray(entry["data"], np.uint8),
            ],
            axis=1,
        ).tofile(base / bin_name)

    for i in range(1, 6):
        write_bin(f"data_batch_{i}", f"data_batch_{i}.bin")
    write_bin("test_batch", "test_batch.bin")

    xb, yb = load_cifar10(str(bin_root), train=True)
    xbt, ybt = load_cifar10(str(bin_root), train=False)
    np.testing.assert_array_equal(yb, yp)
    np.testing.assert_array_equal(ybt, ypt)
    np.testing.assert_allclose(xb, xp, rtol=0, atol=1e-6)
    np.testing.assert_allclose(xbt, xpt, rtol=0, atol=1e-6)


def test_cifar10_bin_rejects_truncated_file(tmp_path):
    base = tmp_path / "cifar-10-batches-bin"
    base.mkdir(parents=True)
    for i in range(1, 6):
        np.zeros(99, np.uint8).tofile(base / f"data_batch_{i}.bin")
    np.zeros(100, np.uint8).tofile(base / "test_batch.bin")  # not a record multiple
    import pytest

    with pytest.raises(ValueError, match="3073"):
        load_cifar10(str(tmp_path), train=False)
    with pytest.raises(ValueError, match="3073"):
        load_cifar10(str(tmp_path), train=True)


def test_cifar10_stale_empty_dir_does_not_shadow(tmp_path):
    """An empty cifar-10-batches-py dir (interrupted download) must not
    shadow a complete cifar-10-batches-bin dir; and a 0-byte bin file fails
    loudly instead of silently shrinking the dataset."""
    from network_distributed_pytorch_tpu.data.cifar10 import cifar10_on_disk

    (tmp_path / "cifar-10-batches-py").mkdir(parents=True)  # empty: unusable
    base = tmp_path / "cifar-10-batches-bin"
    base.mkdir()
    rng = np.random.RandomState(3)
    for i in range(1, 6):
        rec = np.concatenate(
            [
                rng.randint(0, 10, (4, 1), dtype=np.uint8),
                rng.randint(0, 256, (4, 3072), dtype=np.uint8),
            ],
            axis=1,
        )
        rec.tofile(base / f"data_batch_{i}.bin")
    assert cifar10_on_disk(str(tmp_path)) == str(base)
    x, y = load_cifar10(str(tmp_path), train=True)
    assert x.shape == (20, 32, 32, 3)

    # truncate one file to zero bytes: loud failure, not a 16-image epoch
    (base / "data_batch_2.bin").write_bytes(b"")
    import pytest

    with pytest.raises(ValueError, match="3073"):
        load_cifar10(str(tmp_path), train=True)


def test_cifar10_split_aware_format_fallthrough(tmp_path):
    """An eval-only pickle drop must not shadow a bin dir that HAS the
    training split: format selection is per requested split."""
    from network_distributed_pytorch_tpu.data.cifar10 import cifar10_on_disk

    py = tmp_path / "cifar-10-batches-py"
    py.mkdir(parents=True)
    entry = {
        "data": np.zeros((4, 3072), np.uint8),
        "labels": [0, 1, 2, 3],
    }
    with open(py / "test_batch", "wb") as f:
        pickle.dump(entry, f)  # eval-only drop
    bin_dir = tmp_path / "cifar-10-batches-bin"
    bin_dir.mkdir()
    rng = np.random.RandomState(5)
    for i in range(1, 6):
        np.concatenate(
            [
                rng.randint(0, 10, (4, 1), dtype=np.uint8),
                rng.randint(0, 256, (4, 3072), dtype=np.uint8),
            ],
            axis=1,
        ).tofile(bin_dir / f"data_batch_{i}.bin")
    assert cifar10_on_disk(str(tmp_path), train=True) == str(bin_dir)
    assert cifar10_on_disk(str(tmp_path), train=False) == str(py)
    x, _ = load_cifar10(str(tmp_path), train=True)   # bin format
    assert x.shape == (20, 32, 32, 3)
    xt, _ = load_cifar10(str(tmp_path), train=False)  # pickle format
    assert xt.shape == (4, 32, 32, 3)


def test_cifar10_partial_train_dir_falls_through(tmp_path):
    """A pickle dir holding only data_batch_1 (interrupted extraction) must
    not satisfy the train probe — load_cifar10 reads batches 1-5 and would
    crash with a raw FileNotFoundError from open(). The probe requires all
    five, so the complete bin dir wins (and with no alternative, the loader
    raises its own clear FileNotFoundError)."""
    from network_distributed_pytorch_tpu.data.cifar10 import cifar10_on_disk

    py = tmp_path / "cifar-10-batches-py"
    py.mkdir(parents=True)
    with open(py / "data_batch_1", "wb") as f:
        pickle.dump({"data": np.zeros((4, 3072), np.uint8),
                     "labels": [0, 1, 2, 3]}, f)
    # partial dir alone: train probe fails outright -> clear error path
    assert cifar10_on_disk(str(tmp_path), train=True) is None
    import pytest

    with pytest.raises(FileNotFoundError, match="CIFAR-10 not found"):
        load_cifar10(str(tmp_path), train=True)

    # ...and it must not shadow a COMPLETE bin dir
    bin_dir = tmp_path / "cifar-10-batches-bin"
    bin_dir.mkdir()
    rng = np.random.RandomState(7)
    for i in range(1, 6):
        np.concatenate(
            [rng.randint(0, 10, (4, 1), dtype=np.uint8),
             rng.randint(0, 256, (4, 3072), dtype=np.uint8)], axis=1,
        ).tofile(bin_dir / f"data_batch_{i}.bin")
    assert cifar10_on_disk(str(tmp_path), train=True) == str(bin_dir)
    x, _ = load_cifar10(str(tmp_path), train=True)
    assert x.shape == (20, 32, 32, 3)
