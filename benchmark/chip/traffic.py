"""The one generator of training traffic: a pool of host batches from the
seed, as an input pipeline delivers them, cycled for as long as the run lasts.

A traffic file gives `items` ("tokens": int32 ids (batch, seq) with labels of
the same shape; "images": float32 NCHW (batch, 3, image, image) with int32
labels (batch,)), `batch`, `seq` or `image`, and `pool`, the number of
distinct batches. Every seed gives the same shapes, so the seed changes the
rows and never the work. Copied in substance from chip_smoke.py's
`token_batches` and `resnet_batches`.
"""
from __future__ import annotations

import numpy as np


def make_pool(traffic, config, seed):
    rng = np.random.default_rng(int(seed))
    b, out = traffic["batch"], []
    for _ in range(traffic["pool"]):
        if traffic["items"] == "tokens":
            shape, v = (b, traffic["seq"]), config["vocab_size"]
            out.append((rng.integers(0, v, shape, dtype=np.int32),
                        rng.integers(0, v, shape, dtype=np.int32)))
        elif traffic["items"] == "images":
            size = traffic["image"]
            x = rng.random((b, 3, size, size), dtype=np.float32)
            x *= 2.0
            x -= 1.0
            out.append((x, rng.integers(0, config["classes"], (b,),
                                        dtype=np.int32)))
        else:
            raise ValueError(f"traffic items {traffic['items']!r}")
    return out


class Cycled:
    """The pool as an endless source: what `DeviceFeed` wraps."""

    def __init__(self, pool):
        self.pool = pool

    def __iter__(self):
        i = 0
        while True:
            yield self.pool[i % len(self.pool)]
            i += 1
