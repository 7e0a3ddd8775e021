"""Operations a bottleneck ResNet's forward and backward passes require,
from shapes: the convolutions and the head, a multiply-add as 2, backward
twice the forward. Batch norm, ReLU, pooling and the residual adds are left
out. The walk follows torchvision's v1.5 layout (stride on the 3x3)."""

from __future__ import annotations

from typing import Dict, List, Tuple


def convolutions(cfg: Dict) -> List[Tuple[int, int, int, int, int]]:
    """Every convolution as (out_h, out_w, kernel, c_in, c_out)."""
    h, w, c = cfg["image_shape"]
    width = cfg["width"]
    out = []
    if cfg["stem"] == "imagenet":
        h, w = (h + 1) // 2, (w + 1) // 2  # 7x7 stride 2, pad 3
        out.append((h, w, 7, c, width))
        h, w = (h + 1) // 2, (w + 1) // 2  # 3x3 max pool stride 2, pad 1
    else:
        out.append((h, w, 3, c, width))
    c_in = width
    for stage, blocks in enumerate(cfg["stage_sizes"]):
        f = width * 2**stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out.append((h, w, 1, c_in, f))
            oh, ow = (h + stride - 1) // stride, (w + stride - 1) // stride
            out.append((oh, ow, 3, f, f))
            out.append((oh, ow, 1, f, 4 * f))
            if c_in != 4 * f or stride != 1:
                out.append((oh, ow, 1, c_in, 4 * f))
            h, w, c_in = oh, ow, 4 * f
    return out


def forward_flops_per_sample(cfg: Dict) -> float:
    convs = sum(2.0 * h * w * k * k * ci * co for h, w, k, ci, co in convolutions(cfg))
    c_last = cfg["width"] * 2 ** (len(cfg["stage_sizes"]) - 1) * 4
    return convs + 2.0 * c_last * cfg["num_classes"]


def flops_per_sample(cfg: Dict) -> float:
    """Forward plus backward, one image."""
    return 3.0 * forward_flops_per_sample(cfg)
