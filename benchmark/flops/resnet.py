"""Operations ResNet-50 needs per trained image, counted from its layer
shapes.  Forward and backward (backward = twice the forward); a
multiply-add is two operations.  At 224x224 and 1,000 classes this gives
the 4.1 GMAC / 24.6 GFLOP that root `bench.py` (FLOPS_PER_IMG) and the
literature quote; batch norm, ReLU and pooling are not counted."""

from __future__ import annotations

STAGES = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)


def forward_macs(image_size: int, classes: int) -> int:
    hw = image_size // 2                       # 7x7 stride-2 stem
    macs = hw * hw * 7 * 7 * 3 * 64
    hw //= 2                                   # 3x3 stride-2 max-pool
    cin = 64
    for stage, (blocks, w) in enumerate(zip(STAGES, WIDTHS)):
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            out = hw // stride
            macs += hw * hw * cin * w              # 1x1 reduce
            macs += out * out * 9 * w * w          # 3x3, carries the stride
            macs += out * out * w * 4 * w          # 1x1 expand
            if b == 0:
                macs += out * out * cin * 4 * w    # projection shortcut
            hw, cin = out, 4 * w
    return macs + cin * classes


def train_flops_per_image(config: dict, traffic: dict) -> float:
    return 3 * 2.0 * forward_macs(config["image_size"], config["classes"])
