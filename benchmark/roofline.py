"""Chip roofline probes for the ResNet-50 bench (single accelerator).

Measures sustained bf16 throughput of (a) carry-dependent matmul chains and
(b) 3x3 conv chains at ResNet-50 stage shapes, all inside ONE jitted
lax.scan: per-call dispatch is excluded, and the loop-carried dependency
prevents XLA from hoisting the work out.

No result of this probe on the current installation (jax 0.9.0 / libtpu
0.0.34, local PJRT device) is recorded yet; PERF.md is where one goes.
"""
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def _bench(fn, *args):
    float(fn(*args))                       # compile + warm
    t0 = time.perf_counter()
    float(fn(*args))
    return time.perf_counter() - t0


def matmul_chain(m, k, steps=100):
    a = jnp.asarray(np.random.randn(m, k) * 0.02, jnp.bfloat16)
    b = jnp.asarray(np.random.randn(k, k) * 0.02, jnp.bfloat16)

    @jax.jit
    def run(a, b):
        def body(c, _):
            return (c @ b) * jnp.bfloat16(0.05), None
        out, _ = lax.scan(body, a, None, length=steps)
        return jnp.sum(out.astype(jnp.float32))

    dt = _bench(run, a, b)
    return 2 * m * k * k * steps / dt / 1e12


def conv_chain(shape, ch, steps=100, dims=("NCHW", "OIHW", "NCHW")):
    x = jnp.asarray(np.random.randn(*shape), jnp.bfloat16)
    w = jnp.asarray(np.random.randn(ch, ch, 3, 3) * 0.02, jnp.bfloat16)
    if dims[0] == "NHWC":
        x = jnp.transpose(x, (0, 2, 3, 1))
        w = jnp.transpose(w, (2, 3, 1, 0))

    @jax.jit
    def run(x, w):
        def body(c, _):
            y = lax.conv_general_dilated(c, w, (1, 1), [(1, 1), (1, 1)],
                                         dimension_numbers=dims)
            return y * jnp.bfloat16(0.05), None
        out, _ = lax.scan(body, x, None, length=steps)
        return jnp.sum(out.astype(jnp.float32))

    dt = _bench(run, x, w)
    n, _, h, wd = shape
    return 2 * n * h * wd * ch * ch * 9 * steps / dt / 1e12


def main():
    print(f"device: {jax.devices()[0]}")
    for m, k in [(4096, 4096), (8192, 8192), (25088, 2304)]:
        print(f"matmul ({m},{k})x({k},{k}): {matmul_chain(m, k):6.1f} TF/s")
    for shape in [(32, 64, 56, 56), (32, 256, 14, 14), (128, 256, 14, 14)]:
        tf = conv_chain(shape, shape[1])
        print(f"conv3x3 {shape}: {tf:6.1f} TF/s")


if __name__ == "__main__":
    sys.exit(main())
