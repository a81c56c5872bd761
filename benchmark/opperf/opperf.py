#!/usr/bin/env python
"""Per-operator benchmark harness (reference benchmark/opperf/opperf.py:1).

Two complementary modes, matching the reference's split between its full
imperative sweep and its curated kernel profiles:

  --full   Sweep EVERY op that has a case in tests/op_sweep_defs.py (354
           unique frontend ops; a superset of the 315-op parity surface)
           through the eager imperative path: warmed, min-of-k latency for
           forward, and — where the case is gradient-capable — for
           forward+backward through the autograd tape. Sync is a host
           transfer (`asnumpy`). Shapes are the case's native shapes; the
           numbers catch
           dispatch/compile/lowering regressions per op, the committed
           results file makes them diffable (benchmark/opperf/results/).

  default  Curated large-shape profiles for the hot NN ops, timed
           kernel-side: `inner` chained iterations inside ONE jit amortize
           the per-launch dispatch cost so the number approximates device
           time rather than launch time.

Usage:
  python benchmark/opperf/opperf.py                   # curated hot set
  python benchmark/opperf/opperf.py --full            # registry-wide sweep
  python benchmark/opperf/opperf.py --full --emit     # + write results/
  python benchmark/opperf/opperf.py --ops exp,dot     # subset of hot set
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time
import zlib

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tests"))

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")


# ---------------------------------------------------------------------------
# Full registry-wide eager sweep (driven by tests/op_sweep_defs.py)
# ---------------------------------------------------------------------------

def _resolve_frontend(case):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    if case.ns == "nd":
        return getattr(nd, case.op)
    if case.ns == "np":
        return getattr(mx.np, case.op)
    if case.ns == "npx":
        return getattr(mx.npx, case.op)
    if case.ns == "np.linalg":
        return getattr(mx.np.linalg, case.op)
    raise AssertionError(case.ns)


def _case_inputs(case):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    rng = np.random.RandomState(zlib.crc32(case.id.encode()) % (2 ** 31))
    arrs = case.make_inputs(rng)
    if case.ns == "nd":
        return [nd.array(a, dtype=str(a.dtype)) for a in arrs]
    return [mx.np.array(a, dtype=str(a.dtype)) for a in arrs]


def _sync(out):
    if isinstance(out, (list, tuple)):
        for o in out:
            o.asnumpy()
    else:
        out.asnumpy()


def _eager_latency(fn, ndin, kwargs, varargs, warmup=2, runs=3):
    call = (lambda: fn(ndin, **kwargs)) if varargs else \
           (lambda: fn(*ndin, **kwargs))
    for _ in range(warmup):
        _sync(call())
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        _sync(call())
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e3


def _eager_bwd_latency(fn, ndin, kwargs, varargs, warmup=2, runs=3):
    """Forward+backward through the autograd tape, like the reference's
    run_backward=True opperf mode."""
    from mxnet_tpu import autograd
    for x in ndin:
        try:
            x.attach_grad()
        except Exception:
            pass

    def call():
        with autograd.record():
            out = fn(ndin, **kwargs) if varargs else fn(*ndin, **kwargs)
            if isinstance(out, (list, tuple)):
                out = out[0]
        out.backward()
        for x in ndin:
            if getattr(x, "grad", None) is not None:
                x.grad.asnumpy()

    for _ in range(warmup):
        call()
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        call()
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e3


def _compiled_stats(fn, ndin, kwargs, varargs, runs=3):
    """AOT-compile the op as one pure jitted function and report XLA's
    memory plan + its jitted latency (reference opperf records pool memory
    alongside latency via its profiler, benchmark/opperf/utils/
    benchmark_utils.py:23-57 — here the compiled memory_analysis IS the
    planner's answer, no allocator sampling needed).

    Returns (temp_bytes, peak_bytes, jit_ms): temp = XLA scratch beyond
    args/outputs (the quantity a lowering regression inflates); peak =
    args + outputs + temp; jit_ms = min-of-runs latency of the compiled
    executable (on TPU this approximates device time — dispatch overhead
    is out of the measurement)."""
    import jax
    from mxnet_tpu.ndarray import NDArray

    raws = [x._data if isinstance(x, NDArray) else x for x in ndin]

    def pure(*raw_in):
        ins = [type(x)(r) if isinstance(x, NDArray) else r
               for x, r in zip(ndin, raw_in)]
        out = fn(ins, **kwargs) if varargs else fn(*ins, **kwargs)
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out._data if isinstance(out, NDArray) else out

    compiled = jax.jit(pure).lower(*raws).compile()
    ma = compiled.memory_analysis()
    temp = int(getattr(ma, "temp_size_in_bytes", 0))
    peak = temp + int(getattr(ma, "argument_size_in_bytes", 0)) + \
        int(getattr(ma, "output_size_in_bytes", 0))
    compiled(*raws).block_until_ready()
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        compiled(*raws).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return temp, peak, min(ts) * 1e3


def _pin_cpu():
    """Pin the default device and context to the CPU the way
    tests/conftest.py does. The full sweep's committed numbers are
    CPU-backend on purpose: they exist to be DIFFED across commits (a
    lowering regression moves the ratio)."""
    import jax
    import mxnet_tpu as mx
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    mx.test_utils.set_default_context(mx.cpu())


def full_sweep(runs=3, ops_filter=None):
    """One row per unique op in the sweep table; grad timing where the
    case declares gradient capability."""
    from op_sweep_defs import CASES

    by_op = {}
    for c in CASES:
        prev = by_op.get(c.op)
        # prefer a gradient-capable case so fwd+bwd gets measured
        if prev is None or (c.grad and not prev.grad):
            by_op[c.op] = c

    rows, failures = [], []
    for name in sorted(by_op):
        if ops_filter and name not in ops_filter:
            continue
        case = by_op[name]
        try:
            fn = _resolve_frontend(case)
            ndin = _case_inputs(case)
            fwd = _eager_latency(fn, ndin, case.kwargs, case.varargs,
                                 runs=runs)
            # attempt fwd+bwd for every op (not only finite-diff-safe
            # cases); non-differentiable ops raise and stay blank
            try:
                ndin2 = _case_inputs(case)
                bwd = _eager_bwd_latency(fn, ndin2, case.kwargs,
                                         case.varargs, runs=runs)
            except Exception:
                bwd = None
            # memory plan + compiled latency (ops whose frontends are not
            # purely traceable — e.g. host-side RNG consumers — stay blank)
            try:
                temp_b, peak_b, jit_ms = _compiled_stats(
                    fn, _case_inputs(case), case.kwargs, case.varargs,
                    runs=runs)
            except Exception:
                temp_b = peak_b = jit_ms = None
            rows.append({"op": name, "ns": case.ns,
                         "fwd_ms": round(fwd, 4),
                         "fwd_bwd_ms": round(bwd, 4) if bwd else None,
                         "jit_ms": round(jit_ms, 4) if jit_ms is not None
                         else None,
                         "temp_bytes": temp_b, "peak_bytes": peak_b,
                         "shapes": [list(np.shape(a)) for a in ndin]})
        except Exception as e:  # noqa: BLE001
            failures.append({"op": name, "error": f"{type(e).__name__}: {e}"[:120]})
    return rows, failures


def emit_results(rows, failures, path_json=None, path_md=None):
    import jax
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path_json = path_json or os.path.join(RESULTS_DIR, "opperf_full.json")
    path_md = path_md or os.path.join(RESULTS_DIR, "opperf_full.md")
    meta = {
        "backend": jax.default_backend(),
        "n_ops": len(rows),
        "n_failures": len(failures),
        "date": datetime.date.today().isoformat(),
        "methodology": "eager imperative path, asnumpy host-transfer sync, "
                       "warmup 2, min of 3; shapes = sweep-table native",
    }
    with open(path_json, "w") as f:
        json.dump({"meta": meta, "results": rows, "failures": failures},
                  f, indent=1)
    lines = [
        "# Per-operator latency table",
        "",
        f"Backend `{meta['backend']}`, {meta['n_ops']} ops, "
        f"{meta['date']}. {meta['methodology']}.",
        "",
        "Eager latency includes dispatch + sync overhead (~0.1-0.3 ms on "
        "this host) — the column is for *diffing against itself* across "
        "commits, not for absolute kernel time (see the curated hot-set "
        "mode for kernel-side numbers).",
        "",
        "The jit/temp/peak columns come from the AOT-compiled op: jit = "
        "compiled-executable latency (device time on TPU), temp = XLA "
        "scratch bytes beyond args+outputs (the number a lowering "
        "regression inflates), peak = args+outputs+temp.",
        "",
        "| operator | ns | fwd (ms) | fwd+bwd (ms) | jit (ms) | temp (B) "
        "| peak (B) | shapes |",
        "|---|---|---:|---:|---:|---:|---:|---|",
    ]
    for r in sorted(rows, key=lambda r: -r["fwd_ms"]):
        bwd = f"{r['fwd_bwd_ms']:.3f}" if r["fwd_bwd_ms"] else ""
        jit = f"{r['jit_ms']:.3f}" if r.get("jit_ms") is not None else ""
        tmp = str(r["temp_bytes"]) if r.get("temp_bytes") is not None else ""
        pk = str(r["peak_bytes"]) if r.get("peak_bytes") is not None else ""
        shp = "×".join(str(tuple(s)) for s in r["shapes"][:3])
        lines.append(f"| {r['op']} | {r['ns']} | {r['fwd_ms']:.3f} | "
                     f"{bwd} | {jit} | {tmp} | {pk} | {shp} |")
    if failures:
        lines += ["", "## Failures", ""]
        for f_ in failures:
            lines.append(f"- `{f_['op']}`: {f_['error']}")
    with open(path_md, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path_json, path_md


# ---------------------------------------------------------------------------
# Curated hot-set kernel-side profiles (chained-jit)
# ---------------------------------------------------------------------------

def _default_profiles():
    """op -> (arg shapes, params). Large MXU-relevant shapes."""
    L = (1024, 1024)
    return {
        # elementwise / activation
        "exp": ([L], {}),
        "log": ([L], {}),
        "sqrt": ([L], {}),
        "relu": ([L], {}),
        "sigmoid": ([L], {}),
        "tanh": ([L], {}),
        "softmax": ([L], {}),
        # binary broadcast
        "broadcast_add": ([L, L], {}),
        "broadcast_mul": ([L, L], {}),
        "elemwise_add": ([L, L], {}),
        # reductions
        "sum": ([L], {}),
        "mean": ([L], {}),
        "max": ([L], {}),
        "topk": ([L], {"k": 16, "axis": -1}),
        "argsort": ([L], {"axis": -1}),
        # linear algebra
        "dot": ([(512, 512), (512, 512)], {}),
        "batch_dot": ([(16, 256, 256), (16, 256, 256)], {}),
        "FullyConnected": ([(128, 1024), (1024, 1024), (1024,)],
                           {"num_hidden": 1024}),
        "Convolution": ([(32, 64, 56, 56), (64, 64, 3, 3), (64,)],
                        {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1)}),
        "Pooling": ([(32, 64, 56, 56)],
                    {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"}),
        "BatchNorm": ([(32, 64, 56, 56), (64,), (64,), (64,), (64,)], {}),
        "LayerNorm": ([(64, 512, 768), (768,), (768,)], {}),
        # data movement
        "transpose": ([(512, 512)], {}),
        "Reshape": ([L], {"shape": (512, 2048)}),
        "Concat": ([(512, 512), (512, 512)], {"dim": 1, "num_args": 2}),
        "take": ([(10000, 64), (4096,)], {}),
        "one_hot": ([(4096,)], {"depth": 1000}),
        # attention
        "_contrib_flash_attention": ([(4, 8, 512, 64)] * 3, {}),
    }


def _make_inputs(op_name, shapes):
    import jax.numpy as jnp
    rs = np.random.RandomState(0)
    arrs = []
    for i, s in enumerate(shapes):
        if op_name in ("take",) and i == 1:
            arrs.append(jnp.asarray(
                rs.randint(0, shapes[0][0], size=s), dtype=jnp.int32))
        elif op_name == "one_hot":
            arrs.append(jnp.asarray(rs.randint(0, 1000, size=s),
                                    dtype=jnp.int32))
        else:
            arrs.append(jnp.asarray(rs.uniform(-1, 1, s).astype(np.float32)))
    return arrs


def bench_op(op_name, shapes, params, warmup=2, runs=5, inner=10):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op

    op = get_op(op_name)
    raw = _make_inputs(op_name, shapes)

    def chained(*args):
        out = None
        acc = jnp.float32(0)
        for _ in range(inner):
            out = op.unbound(params)(*args)
            first = out[0] if isinstance(out, tuple) else out
            acc = acc + first.astype(jnp.float32).sum()
        return acc

    fwd = jax.jit(chained)

    def sync(r):
        # host transfer of one scalar closes the async chain; grads are
        # arrays, forward is a scalar — sum handles both
        return float(jnp.asarray(r).astype(jnp.float32).sum())

    def timeit(f, *a):
        for _ in range(warmup):
            sync(f(*a))
        ts = []
        for _ in range(runs):
            t0 = time.perf_counter()
            sync(f(*a))
            ts.append((time.perf_counter() - t0) / inner)
        return min(ts) * 1e3  # ms

    fwd_ms = timeit(fwd, *raw)
    bwd_ms = None
    if op.differentiable:
        try:
            gradfn = jax.jit(jax.grad(lambda *a: chained(*a)))
            bwd_ms = timeit(gradfn, *raw)
        except Exception:
            bwd_ms = None
    return fwd_ms, bwd_ms


def run_hot(args):
    profiles = _default_profiles()
    if args.ops:
        sel = args.ops.split(",")
        profiles = {k: v for k, v in profiles.items() if k in sel}

    results = []
    print(f"{'operator':<28} {'fwd (ms)':>10} {'fwd+bwd (ms)':>13}")
    print("-" * 53)
    for name, (shapes, params) in profiles.items():
        try:
            fwd, bwd = bench_op(name, shapes, params, runs=args.runs,
                                inner=args.inner)
        except Exception as e:  # noqa: BLE001
            print(f"{name:<28} failed: {str(e)[:40]}")
            continue
        bwd_s = f"{bwd:13.3f}" if bwd is not None else f"{'n/a':>13}"
        print(f"{name:<28} {fwd:10.3f} {bwd_s}")
        results.append({"op": name, "fwd_ms": round(fwd, 4),
                        "bwd_ms": round(bwd, 4) if bwd else None,
                        "shapes": [list(s) for s in shapes]})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {args.json}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="registry-wide eager sweep from the op case table")
    ap.add_argument("--emit", action="store_true",
                    help="with --full: write results/ JSON + markdown")
    ap.add_argument("--ops", type=str, default=None,
                    help="comma-separated subset")
    ap.add_argument("--json", type=str, default=None)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--inner", type=int, default=10)
    args = ap.parse_args()

    if args.full:
        _pin_cpu()
        sel = set(args.ops.split(",")) if args.ops else None
        rows, failures = full_sweep(runs=min(args.runs, 3), ops_filter=sel)
        print(f"{'operator':<40} {'fwd (ms)':>10} {'fwd+bwd (ms)':>13}")
        print("-" * 65)
        for r in sorted(rows, key=lambda r: -r["fwd_ms"]):
            bwd = f"{r['fwd_bwd_ms']:13.3f}" if r["fwd_bwd_ms"] else f"{'':>13}"
            print(f"{r['op']:<40} {r['fwd_ms']:10.3f} {bwd}")
        print(f"\n{len(rows)} ops measured, {len(failures)} failed")
        for f_ in failures:
            print(f"  FAIL {f_['op']}: {f_['error']}")
        if args.emit:
            pj, pm = emit_results(rows, failures)
            print(f"wrote {pj}\nwrote {pm}")
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
        return

    run_hot(args)


if __name__ == "__main__":
    main()
