"""Operations ResNet-50 needs per image, from the configuration's shapes.

Counts multiply-adds of every convolution and of the head, at 2 operations
each. BatchNorm, ReLU, pooling and the loss are left out: under 1% of the
total. Training is forward plus backward, three times the forward pass;
nothing recomputed is counted.
"""

from __future__ import annotations


def forward_macs(cfg: dict) -> int:
    w, e = cfg["width"], cfg["bottleneck_expansion"]
    size = cfg["image_size"] // 2                       # stem, stride 2
    macs = size * size * 7 * 7 * cfg["channels"] * w
    size //= 2                                          # max pool, stride 2
    cin = w
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        f = w * 2 ** i
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = size // stride
            # v1.5: the 1x1 runs at the input's size, the 3x3 strides
            in1 = size if cfg["stride_on_3x3"] else out
            macs += in1 * in1 * cin * f
            macs += out * out * 9 * f * f
            macs += out * out * f * f * e
            if j == 0:
                macs += out * out * cin * f * e
            cin, size = f * e, out
    return macs + cin * cfg["num_classes"]


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    return 3 * 2 * float(forward_macs(cfg))
