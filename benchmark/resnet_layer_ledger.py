"""Per-layer roofline ledger for the ResNet-50 bs32 training step.

Settles WHERE the step time goes (VERDICT r4 ask #1): every conv of the
real model is timed in ISOLATION — forward + its backward convs, same
lax.conv_general_dilated lowering, same bf16 dtypes the fused trainer
emits — giving each layer's achieved-in-isolation TF/s, i.e. its own
ceiling on this chip. The ledger then compares

    sum_i  count_i * isolated_time_i      (the no-overhead lower bound)

against the measured fused-step time. If the two agree to within ~15%,
every dominant layer inside the chain is running at ~its isolated speed
and the framework adds nothing — the gap to nominal MFU is the chip's
own small-batch conv ceiling, layer by layer, not scheduling overhead.

Usage:
  python benchmark/resnet_layer_ledger.py            # real chip (driver env)
  JAX_PLATFORMS=cpu LEDGER_QUICK=1 python ...        # logic smoke on CPU
Writes benchmark/results/resnet_layer_ledger.md and prints a JSON summary.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

BATCH = int(os.environ.get("BENCH_BATCH", 32))
IMAGE = int(os.environ.get("BENCH_IMAGE", 224))
QUICK = os.environ.get("LEDGER_QUICK") == "1"
REPS = int(os.environ.get("LEDGER_REPS", 2 if QUICK else 8))


def capture_conv_configs():
    """Run one CPU forward of resnet50_v1 with _Conv.hybrid_forward patched
    to record (input shape, conv kwargs) in execution order."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.nn import conv_layers
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    records = []
    orig = conv_layers._Conv.hybrid_forward

    def patched(self, F, x, weight, bias=None):
        records.append((tuple(x.shape), dict(self._kwargs)))
        return orig(self, F, x, weight, bias)

    conv_layers._Conv.hybrid_forward = patched
    try:
        with mx.cpu():
            net = resnet50_v1()
            net.initialize(ctx=mx.cpu())
            net(nd.zeros((BATCH, 3, IMAGE, IMAGE), ctx=mx.cpu()))
    finally:
        conv_layers._Conv.hybrid_forward = orig
    return records


def dedup(records):
    table = {}
    for shape, kw in records:
        key = (shape, kw["kernel"], kw["stride"], kw["pad"],
               kw["num_filter"], kw["num_group"])
        if key in table:
            table[key]["count"] += 1
        else:
            table[key] = {"shape": shape, "kernel": kw["kernel"],
                          "stride": kw["stride"], "pad": kw["pad"],
                          "filters": kw["num_filter"],
                          "groups": kw["num_group"], "count": 1}
    return list(table.values())


def conv_out_hw(h, k, s, p):
    return (h + 2 * p - k) // s + 1


def probe_conv(cfg, with_dx=True):
    """Time REPS isolated (fwd + bwd) passes of one conv config in bf16,
    chained in a single jit via lax.scan (amortizes per-call dispatch); sync by
    host transfer. Returns seconds per single fwd+bwd pass."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from benchmark.bench_util import measure_stabilized

    N, C, H, W = cfg["shape"]
    kh, kw_ = cfg["kernel"]
    sh, sw = cfg["stride"]
    ph, pw = cfg["pad"]
    O = cfg["filters"]
    Ho, Wo = conv_out_hw(H, kh, sh, ph), conv_out_hw(W, kw_, sw, pw)

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.normal(0, 1, (N, C, H, W)), dtype=jnp.bfloat16)
    w = jnp.asarray(rs.normal(0, 0.1, (O, C // cfg["groups"], kh, kw_)),
                    dtype=jnp.bfloat16)
    cot = jnp.asarray(rs.normal(0, 1, (N, O, Ho, Wo)), dtype=jnp.bfloat16)

    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))

    def f(xi, wi):
        y = lax.conv_general_dilated(
            xi, wi, window_strides=(sh, sw), padding=((ph, ph), (pw, pw)),
            dimension_numbers=dn, feature_group_count=cfg["groups"])
        return jnp.sum((y * cot).astype(jnp.float32))

    argnums = (0, 1) if with_dx else (1,)
    grad_f = jax.value_and_grad(f, argnums=argnums)

    def build_chain(R):
        @jax.jit
        def chain(x, w):
            def body(acc, i):
                # fold the carry into BOTH operands: with w loop-invariant
                # XLA hoists the dX conv (conv(cot, w) has no rep
                # dependence) out of the scan and the probe reads >peak
                a16 = acc.astype(jnp.bfloat16) * 1e-12
                xi = x + a16
                wi = w + a16
                v, gs = grad_f(xi, wi)
                for g in gs:
                    v = v + jnp.sum(g.astype(jnp.float32)) * 1e-12
                return v, None
            acc, _ = lax.scan(body, jnp.float32(0.0), jnp.arange(R))
            return acc
        return chain

    def measure(R):
        chain = build_chain(R)

        def once():
            t0 = time.perf_counter()
            float(chain(x, w))
            return time.perf_counter() - t0
        return measure_stabilized(once, max_warm=6) / R

    # every call pays a fixed dispatch cost regardless of content: scale
    # the chained rep count until the chain itself dominates, else every
    # small conv reads as the dispatch floor / REPS
    reps = REPS
    dt = measure(reps)
    # iterate: the first estimate is itself floor-inflated, so one rescale
    # is not enough for sub-ms kernels
    for _ in range(3):
        if QUICK or dt * reps >= 0.8:
            break
        reps = min(int(np.ceil(1.0 / max(dt, 1e-6))), 4096)
        dt = measure(reps)
    # fwd MACs; bwd = dW (+ dX when taken)
    mac = N * O * (C // cfg["groups"]) * kh * kw_ * Ho * Wo
    n_convs = 3 if with_dx else 2
    return dt, 2 * mac * n_convs


def measure_full_step():
    """The actual fused bs32 train step, identical to bench.py's path."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    from benchmark.bench_util import measure_stabilized
    import jax.numpy as jnp

    def loss_fn(logits, labels):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    with mx.cpu():
        net = resnet50_v1()
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, 3, IMAGE, IMAGE), ctx=mx.cpu()))
    tr = DataParallelTrainer(net, loss_fn, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.05,
                                               "momentum": 0.9, "wd": 1e-4},
                             mesh=mesh, dtype="bfloat16")
    rs = np.random.RandomState(0)
    x = nd.array(rs.uniform(-1, 1, (BATCH, 3, IMAGE, IMAGE)).astype(np.float32))
    y = nd.array(rs.randint(0, 1000, (BATCH,)), dtype="int32")
    steps = 2 if QUICK else 20

    def once():
        t0 = time.perf_counter()
        losses = tr.run_steps(x, y, steps)
        float(losses[-1])
        return time.perf_counter() - t0

    return measure_stabilized(once, max_warm=6) / steps


def main():
    from mxnet_tpu import engine
    engine.enable_compile_cache()
    cfgs = dedup(capture_conv_configs())
    print(f"{len(cfgs)} unique conv configs "
          f"({sum(c['count'] for c in cfgs)} conv calls) at bs{BATCH}",
          file=sys.stderr)

    rows = []
    for i, cfg in enumerate(cfgs):
        first = cfg["shape"][1] == 3  # the stem conv has no dX in the model
        dt, flops = probe_conv(cfg, with_dx=not first)
        tfs = flops / dt / 1e12
        rows.append({**cfg, "ms": dt * 1e3, "tflops": round(tfs, 2),
                     "gflop": round(flops / 1e9, 2)})
        print(f"[{i+1}/{len(cfgs)}] {cfg['shape']}x{cfg['kernel']}"
              f"/{cfg['stride']} -> {cfg['filters']}f x{cfg['count']}: "
              f"{dt*1e3:.3f} ms  {tfs:.1f} TF/s", file=sys.stderr)

    step_s = measure_full_step()
    conv_sum = sum(r["ms"] * r["count"] for r in rows) / 1e3
    total_gflop = sum(r["gflop"] * r["count"] for r in rows)
    overhead = (step_s - conv_sum) / step_s

    os.makedirs(os.path.join(os.path.dirname(__file__), "results"),
                exist_ok=True)
    out = os.path.join(os.path.dirname(__file__), "results",
                       "resnet_layer_ledger.md")
    with open(out, "w") as fh:
        fh.write(f"# ResNet-50 bs{BATCH} per-layer roofline ledger\n\n")
        fh.write(f"Backend: {_backend()}; isolated fwd+bwd per conv, bf16, "
                 f"same lowering as the fused step.\n\n")
        fh.write("| input | kernel/stride | out ch | count | ms/call "
                 "(fwd+bwd) | isolated TF/s | GFLOP/call |\n|---|---|---|---|"
                 "---|---|---|\n")
        for r in sorted(rows, key=lambda r: -r["ms"] * r["count"]):
            fh.write(f"| {r['shape']} | {r['kernel']}/{r['stride']} | "
                     f"{r['filters']} | {r['count']} | {r['ms']:.3f} | "
                     f"{r['tflops']:.1f} | {r['gflop']:.2f} |\n")
        fh.write(f"\n- sum of isolated conv times: **{conv_sum*1e3:.2f} ms**\n"
                 f"- measured fused step:          **{step_s*1e3:.2f} ms**\n"
                 f"- non-conv + scheduling share:  **{overhead*100:.1f}%** "
                 f"(BN/relu/pool/dense/optimizer + any framework overhead)\n"
                 f"- conv FLOPs covered: {total_gflop:.0f} GFLOP/step\n")
    print(json.dumps({
        "metric": "resnet50_layer_ledger",
        "conv_sum_ms": round(conv_sum * 1e3, 2),
        "step_ms": round(step_s * 1e3, 2),
        "non_conv_share": round(overhead, 4),
        "n_configs": len(cfgs),
        "worst_tflops": min(r["tflops"] for r in rows),
        "best_tflops": max(r["tflops"] for r in rows),
        "table": out,
    }))


def _backend():
    import jax
    return jax.devices()[0].platform


if __name__ == "__main__":
    main()
