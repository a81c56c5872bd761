"""Attention-variant microprobe at BERT-base shapes (B=16,H=12,T=512,d=64):
plain XLA (materialized scores) vs Pallas flash at several block sizes,
fwd+bwd, timed as one jitted carry-dependent lax.scan with a scalar result
and a stabilized warmup, so per-call dispatch stays out of the number. Prints one JSON line per
variant."""
from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

B = int(os.environ.get("AP_B", 16))
H = int(os.environ.get("AP_H", 12))
T = int(os.environ.get("AP_T", 512))
D = int(os.environ.get("AP_D", 64))
STEPS = int(os.environ.get("AP_STEPS", 30))


def plain_attn(q, k, v):
    scale = 1.0 / (D ** 0.5)
    BH = q.shape[0]
    s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    return lax.dot_general(p.astype(v.dtype), v,
                           (((2,), (1,)), ((0,), (0,))),
                           preferred_element_type=jnp.float32).astype(q.dtype)


def make_fn(attn):
    def step(carry, _):
        q, k, v = carry

        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2) * 1e-6

        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        # carry-dependent: outputs feed the next iteration's inputs
        q2 = (q + 0.001 * grads[0].astype(q.dtype))
        k2 = (k + 0.001 * grads[1].astype(k.dtype))
        v2 = (v + 0.001 * grads[2].astype(v.dtype))
        return (q2, k2, v2), l

    @functools.partial(jax.jit, static_argnums=(3,))
    def run(q, k, v, n):
        (_, _, _), ls = lax.scan(step, (q, k, v), None, length=n)
        return ls[-1]

    return run


def timed(run, q, k, v):
    def once():
        t0 = time.perf_counter()
        float(run(q, k, v, STEPS))
        return time.perf_counter() - t0

    from bench_util import measure_stabilized
    return measure_stabilized(once, max_warm=8)


def main():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B * H, T, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B * H, T, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B * H, T, D), jnp.bfloat16)
    # attention fwd flops: 4*T*T*D per head-batch; bwd ~2.5x more
    fwd_flops = 4.0 * B * H * T * T * D
    total_flops = 3.5 * fwd_flops  # fwd + standard flash bwd recompute

    variants = {"plain_xla": plain_attn}
    for blk in (128, 256, 512):
        if blk <= T:
            variants[f"flash_b{blk}"] = functools.partial(
                _wrap_flash, blk=blk)
    for name, attn in variants.items():
        run = make_fn(attn)
        dt = timed(run, q, k, v)
        per_step = dt / STEPS
        tf = total_flops / per_step / 1e12
        print(json.dumps({"variant": name, "ms_per_step": round(
            per_step * 1e3, 3), "tflops_est": round(tf, 1)}))


def _wrap_flash(q, k, v, blk):
    from mxnet_tpu.ops.pallas.flash_attention import _flash
    scale = 1.0 / (D ** 0.5)
    return _flash(q, k, v, False, scale, blk, blk, False)


if __name__ == "__main__":
    main()
