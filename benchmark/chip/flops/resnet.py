"""Operations a ResNet v1 (bottleneck) training step needs, counted layer by
layer from the configuration's shapes. A multiply-add counts as two
operations and the backward pass as twice the forward. For resnet50_v1 at
224^2 the forward comes to 7.7 GFLOP an image of convolutions and the
classifier (bench.py's `resnet50_train_flops_per_image` uses 3 x 7.64)."""
from __future__ import annotations


def _forward(cfg, size):
    chans, total = cfg["channels"], 0
    hw = (size + 2 * 3 - 7) // 2 + 1                    # stem 7x7 / 2
    total += 2 * hw * hw * chans[0] * 3 * 49
    hw = (hw + 2 - 3) // 2 + 1                          # max pool 3x3 / 2
    for s, n in enumerate(cfg["layers"]):
        out, mid = chans[s + 1], chans[s + 1] // 4
        for b in range(n):
            inp = chans[s] if b == 0 else out
            stride = 2 if (b == 0 and s > 0) else 1
            hw_out = (hw - 1) // stride + 1
            total += 2 * hw_out * hw_out * (inp * mid + 9 * mid * mid
                                            + mid * out)
            if b == 0 and chans[s] != out:
                total += 2 * hw_out * hw_out * inp * out
            hw = hw_out
    return total + 2 * chans[-1] * cfg["classes"]


def train_flops_per_item(cfg, traffic):
    """Forward and backward operations per image."""
    return 3 * _forward(cfg, traffic["image"])


def mxu_flops_per_item(cfg, traffic, exclude_attention=False):
    """All of them are convolutions and one matrix product."""
    return train_flops_per_item(cfg, traffic)
