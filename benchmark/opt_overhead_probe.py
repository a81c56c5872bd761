"""Where do the ~6 ms between fwd+bwd (16.1 ms) and the full fused
ResNet-50 step (22.1 ms) go? (benchmark/resnet_step_ablation.py)

An SGD-momentum update on 25.6M params is ~0.5 GB of HBM traffic
(~0.6 ms at 819 GB/s) — the measured 6 ms is 10x that, so something in
the trainer's step construction, not the update math, is the cost.
Three scans, identical REPS and measurement:

  bare       fwd+bwd only (ablation's number, re-measured here)
  inline     fwd+bwd + hand-inlined SGD-momentum on a scan carry
  trainer    DataParallelTrainer.run_steps (the bench path)

inline-bare = true optimizer cost; trainer-inline = trainer machinery
(aux BN-carry wiring, per-param python loop structure, loss stacking).

Usage: python benchmark/opt_overhead_probe.py    (real chip)
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
    x = jnp.asarray(rng.uniform(-1, 1, (BATCH, 3, IMAGE, IMAGE)), jnp.float32)
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

    def timed(fn, *args):
        # sync via host transfer of a scalar
        def once():
            t0 = time.perf_counter()
            out = fn(*args)
            leaf = jax.tree_util.tree_leaves(out)[0]
            float(leaf if leaf.ndim == 0
                  else jnp.sum(leaf.astype(jnp.float32)))
            return time.perf_counter() - t0
        return measure_stabilized(once, max_warm=6) / REPS

    @jax.jit
    def bare(ps, xi):
        def body(acc, i):
            l, gs = jax.value_and_grad(fwd_loss)(
                [p + acc.astype(p.dtype) * 0 for p in ps], xi)
            for g in gs:
                l = l + jnp.sum(g.astype(jnp.float32)) * 1e-12
            return l, None
        acc, _ = lax.scan(body, jnp.float32(0.0), jnp.arange(REPS))
        return acc

    t_bare = timed(bare, params, x)

    momenta = [jnp.zeros_like(p) if jnp.issubdtype(p.dtype, jnp.floating)
               else None for p in params]

    @jax.jit
    def inline(ps, ms, xi):
        def body(carry, i):
            ps_c, ms_c = carry
            l, gs = jax.value_and_grad(fwd_loss)(ps_c, xi)
            new_p, new_m = [], []
            for g, w, m in zip(gs, ps_c, ms_c):
                if m is None or not jnp.issubdtype(w.dtype, jnp.floating):
                    new_p.append(w)
                    new_m.append(m)
                    continue
                m2 = 0.9 * m + g + 1e-4 * w
                new_p.append(w - 0.05 * m2)
                new_m.append(m2)
            return (new_p, new_m), l
        (ps_o, ms_o), ls = lax.scan(body, (ps, ms), jnp.arange(REPS))
        return ls[-1]

    t_inline = timed(inline, params, momenta, x)

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = DataParallelTrainer(net, _loss_tokens, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.05,
                                               "momentum": 0.9, "wd": 1e-4},
                             mesh=mesh, dtype="bfloat16")
    xb = nd.array(np.asarray(x))
    yb = nd.array(np.asarray(y), dtype="int32")

    def once_tr():
        t0 = time.perf_counter()
        losses = tr.run_steps(xb, yb, REPS)
        float(losses[-1])
        return time.perf_counter() - t0
    t_tr = measure_stabilized(once_tr, max_warm=6) / REPS

    print(json.dumps({
        "metric": "resnet50_opt_overhead",
        "bare_fwd_bwd_ms": round(t_bare * 1e3, 3),
        "inline_sgd_ms": round(t_inline * 1e3, 3),
        "trainer_ms": round(t_tr * 1e3, 3),
        "true_opt_cost_ms": round((t_inline - t_bare) * 1e3, 3),
        "trainer_machinery_ms": round((t_tr - t_inline) * 1e3, 3),
    }))


if __name__ == "__main__":
    main()
