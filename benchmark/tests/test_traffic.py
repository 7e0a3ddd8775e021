"""The one traffic generator: seeded, and shaped as the workload file says."""

import numpy as np

from benchmark import cells, traffic


def test_token_sequences_follow_the_workload_file():
    cell = cells.cell("imdb_psgd16_b16")
    spec = {**cell["workload"]["traffic"], "seq_len": 512}
    pool = traffic.token_sequences(spec, 30522, seed=7)
    again = traffic.token_sequences(spec, 30522, seed=7)
    other = traffic.token_sequences(spec, 30522, seed=8)
    assert all(np.array_equal(pool[k], again[k]) for k in pool)
    assert not np.array_equal(pool["input_ids"], other["input_ids"])
    n = spec["pool_samples"]
    assert pool["input_ids"].shape == (n, 512) and pool["input_ids"].dtype == np.int32
    lengths = pool["attention_mask"].sum(axis=1)
    assert 165 <= np.median(lengths) - 2 <= 185  # log-normal, median 175 words
    assert lengths.max() == 512 and lengths.min() >= 10
    assert (pool["input_ids"][:, 0] == traffic.CLS).all()
    assert (pool["input_ids"][pool["attention_mask"] == 0] == traffic.PAD).all()
    assert pool["input_ids"].max() < 30522 and set(np.unique(pool["labels"])) == {0, 1}
    assert 0.5 < traffic.padding_share(pool) < 0.65


def test_images_follow_the_workload_file():
    cell = cells.cell("cifar_psgd4_b128")
    spec = {**cell["workload"]["traffic"], "pool_samples": 256, "image_shape": [32, 32, 3], "num_classes": 10}
    x, y = traffic.images(spec, seed=7)
    x2, y2 = traffic.images(spec, seed=7)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    assert x.shape == (256, 32, 32, 3) and x.dtype == np.float32 and y.dtype == np.int32
    assert traffic.padding_share((x, y)) == 0.0
