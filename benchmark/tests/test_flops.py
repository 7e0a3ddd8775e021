"""The functions that say what a step has to compute, against hand counts."""

import pytest

from benchmark import cells
from benchmark.flops import distilbert_imdb, flash_attention, resnet_cifar10


def test_distilbert_base_at_512():
    cfg = cells.cell("imdb_psgd16_b16")["config"]
    # per layer: q, k, v, out (4 x 768^2) and the two FFN products (2 x 768 x 3072)
    assert distilbert_imdb.matmul_params(cfg) == 6 * (4 * 768 * 768 + 2 * 768 * 3072) == 42_467_328
    forward = 2 * 42_467_328 * 512 + 4 * 512 * 512 * 768 * 6 + 2 * (768 * 768 + 768 * 2)
    assert distilbert_imdb.forward_flops_per_sample(cfg) == forward
    assert distilbert_imdb.flops_per_sample(cfg) == pytest.approx(144.96e9, rel=1e-3)


def test_resnet152_on_cifar():
    cfg = cells.cell("cifar_psgd4_b128")["config"]
    convs = resnet_cifar10.convolutions(cfg)
    assert len(convs) == 155  # 1 stem + 50 blocks x 3 + 4 projections
    assert convs[0] == (16, 16, 7, 3, 64)  # 7x7 stride 2 on 32x32
    assert convs[1] == (8, 8, 1, 64, 64)  # after the 3x3 max pool, stride 2
    assert convs[4] == (8, 8, 1, 64, 256)  # the first block's projection
    assert convs[-1] == (1, 1, 1, 512, 2048)
    # the stem by hand: 16*16 outputs x 7*7*3 taps x 64 filters, 2 per multiply-add
    assert 2 * 16 * 16 * 49 * 3 * 64 == 4_816_896
    assert resnet_cifar10.flops_per_sample(cfg) == pytest.approx(1.41e9, rel=5e-3)
    # a tiny net small enough to add up by hand
    tiny = {"image_shape": [8, 8, 3], "width": 4, "stem": "cifar", "stage_sizes": [1], "num_classes": 10}
    by_hand = 2 * (8 * 8 * 9 * 3 * 4 + 8 * 8 * 4 * 4 + 8 * 8 * 9 * 4 * 4 + 8 * 8 * 4 * 16 + 8 * 8 * 4 * 16) + 2 * 16 * 10
    assert resnet_cifar10.forward_flops_per_sample(tiny) == by_hand


def test_flash_forward_cost():
    flops, moved = flash_attention.forward_cost(16, 12, 512, 64, 2)
    assert flops == 4 * 16 * 12 * 512 * 512 * 64
    assert moved == 4 * 16 * 12 * 512 * 64 * 2
