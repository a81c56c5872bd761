"""ResNet-50 bs32 step ablation — where the non-conv time lives.

Complements resnet_layer_ledger.py (isolated conv ceilings): times the
REAL model graph in three cuts, all jitted, bf16, same lowering as the
fused trainer:

  fwd        forward pass only
  fwd+bwd    value_and_grad (no optimizer)
  full       DataParallelTrainer fused step (fwd+bwd+SGD-momentum update)

fwd+bwd - fwd ~ backward cost; full - fwd+bwd ~ optimizer + BN-carry
overhead. Against the ledger's conv-only sum this attributes the gap
between isolated conv speed and whole-step speed.

Usage: python benchmark/resnet_step_ablation.py     (real chip)
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

BATCH = int(os.environ.get("BENCH_BATCH", 32))
IMAGE = int(os.environ.get("BENCH_IMAGE", 224))
REPS = int(os.environ.get("ABL_REPS", 20))


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    from mxnet_tpu.parallel.data_parallel import _make_apply_fn
    from benchmark.bench_util import measure_stabilized
    from bench import _loss_tokens

    mx.engine.enable_compile_cache()
    with mx.cpu():
        net = resnet50_v1()
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, 3, IMAGE, IMAGE), ctx=mx.cpu()))
    plist = [p for p in net.collect_params().values() if p._data is not None]
    apply_fn = _make_apply_fn(net, plist, train=True)
    params = [jnp.asarray(np.asarray(p._data._data)) for p in plist]
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.uniform(-1, 1, (BATCH, 3, IMAGE, IMAGE)),
                    jnp.float32)
    y = jnp.asarray(rng.randint(0, 1000, (BATCH,)), jnp.int32)
    from mxnet_tpu import random as _rng_mod
    key = np.asarray(_rng_mod.next_key_raw())

    def low(p):
        return p.astype(jnp.bfloat16) if jnp.issubdtype(p.dtype, jnp.floating) \
            else p

    def fwd_loss(ps, xi):
        out, _ = apply_fn(key, [low(p) for p in ps], low(xi))
        pred = out if not isinstance(out, tuple) else out[0]
        return _loss_tokens(pred, y)

    @jax.jit
    def run_fwd(ps, xi):
        def body(acc, i):
            l = fwd_loss(ps, xi + acc * 1e-12)
            return l, None
        acc, _ = lax.scan(body, jnp.float32(0.0), jnp.arange(REPS))
        return acc

    @jax.jit
    def run_fwd_bwd(ps, xi):
        def body(acc, i):
            l, gs = jax.value_and_grad(fwd_loss)(
                [p + acc.astype(p.dtype) * 0 for p in ps], xi + acc * 1e-12)
            # EVERY grad must stay live or XLA dead-code-eliminates the
            # unused wgrad convs and the backward reads ~2x fast
            for g in gs:
                l = l + jnp.sum(g.astype(jnp.float32)) * 1e-12
            return l, None
        acc, _ = lax.scan(body, jnp.float32(0.0), jnp.arange(REPS))
        return acc

    def timed(fn, *args):
        def once():
            t0 = time.perf_counter()
            float(fn(*args))
            return time.perf_counter() - t0
        return measure_stabilized(once, max_warm=6) / REPS

    t_fwd = timed(run_fwd, params, x)
    t_fb = timed(run_fwd_bwd, params, x)

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = DataParallelTrainer(net, _loss_tokens, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.05,
                                               "momentum": 0.9, "wd": 1e-4},
                             mesh=mesh, dtype="bfloat16")
    xb = nd.array(np.asarray(x))
    yb = nd.array(np.asarray(y), dtype="int32")

    def once_full():
        t0 = time.perf_counter()
        losses = tr.run_steps(xb, yb, REPS)
        float(losses[-1])
        return time.perf_counter() - t0
    t_full = measure_stabilized(once_full, max_warm=6) / REPS

    print(json.dumps({
        "metric": "resnet50_bs32_step_ablation",
        "fwd_ms": round(t_fwd * 1e3, 3),
        "fwd_bwd_ms": round(t_fb * 1e3, 3),
        "full_step_ms": round(t_full * 1e3, 3),
        "bwd_share_ms": round((t_fb - t_fwd) * 1e3, 3),
        "optimizer_and_carry_ms": round((t_full - t_fb) * 1e3, 3),
    }))


if __name__ == "__main__":
    main()
