#!/usr/bin/env python3
"""Device time of one attention call's forward and backward, from the profiler.

    chiprun -- python tools/flash_probe.py 72,8192,128,causal,512 48,8192,128,causal

A case is `heads,T,d[,causal][,window][,bLIMIT]` (`b256`: the block limit
handed to `flash_attention`; 512 unsaid). For each case the flash kernels'
forward and the gradient of their sum run `--reps` times on bfloat16 operands
under `jax.profiler.trace`; the Mosaic calls `mx_flash_fwd` and `mx_flash_bwd`
are then read from the device's line of the xplane (`benchmark/chip/trace.py`)
and one JSON line a case is printed: milliseconds a call (the median over the
repetitions), the calls seen, and the schedule the plan names where the tree
has one. `--tree DIR` probes another checkout's kernels (the parent's, unpacked
in an ignored directory) with the same driver, so one chip call compares two
trees. Nothing here is run by a cell or imported by the package; off the TPU
it refuses, a time being the device's or nothing.
"""
import argparse
import json
import os
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_case(text):
    heads, T, d, *rest = text.split(",")
    case = {"heads": int(heads), "T": int(T), "d": int(d), "causal": False,
            "window": None, "block": 512}
    for word in rest:
        if word == "causal":
            case["causal"] = True
        elif word.startswith("b"):
            case["block"] = int(word[1:])
        else:
            case["window"] = int(word)
    return case


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cases", nargs="+", type=parse_case)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tree", default=HERE,
                    help="checkout whose mxnet_tpu is probed")
    ap.add_argument("--set", action="append", default=[], metavar="NAME=INT",
                    help="a constant of the kernels' module for this run "
                         "(_UNROLL_PAIRS=4): what the tests monkeypatch")
    ap.add_argument("--keep", default=None,
                    help="directory that keeps each case's xplane")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path[:0] = [tree, os.path.join(HERE, "benchmark", "chip")]

    import jax
    import jax.numpy as jnp
    import numpy as np
    import trace as xtrace
    from mxnet_tpu.ops.pallas import flash_attention as _  # noqa: F401
    fa = sys.modules["mxnet_tpu.ops.pallas.flash_attention"]

    for item in args.set:
        name, value = item.split("=")
        assert hasattr(fa, name), name
        setattr(fa, name, int(value))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"flash_probe: needs the TPU, found {dev.platform}")

    for case in args.cases:
        H, T, d = case["heads"], case["T"], case["d"]
        kw = {"causal": case["causal"], "block_q": case["block"],
              "block_k": case["block"]}
        if case["window"] is not None:
            kw["window"] = case["window"]
        rs = np.random.RandomState(H + T)
        q, k, v = (jnp.asarray(rs.normal(0, 1, (1, H, T, d)), jnp.bfloat16)
                   for _ in range(3))
        fwd = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, **kw))
        grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, **kw).astype(jnp.float32)), argnums=(0, 1, 2)))
        jax.block_until_ready((fwd(q, k, v), grad(q, k, v)))     # compile
        out = args.keep and os.path.join(
            args.keep, "_".join(str(x) for x in case.values()))
        with tempfile.TemporaryDirectory() as tmp:
            out = out or tmp
            with jax.profiler.trace(out):
                for _ in range(args.reps):
                    jax.block_until_ready(fwd(q, k, v))
                for _ in range(args.reps):
                    jax.block_until_ready(grad(q, k, v))
            loaded = xtrace.load(xtrace.find_xplane(out))
        ops = next(iter(loaded["devices"].values()))["ops"]
        line = dict(case, tree=os.path.relpath(tree, HERE), set=args.set,
                    device=dev.device_kind)
        for kind in ("fwd", "bwd"):
            durs = [dur for name, _, _, dur, _ in ops
                    if f"mx_flash_{kind}" in name]
            line[f"{kind}_ms"] = round(statistics.median(durs) / 1e6, 4) \
                if durs else None
            line[f"{kind}_calls"] = len(durs)
        plan = getattr(fa, "_plan", None)
        try:
            blk = fa._block(T, case["block"])
            line["schedule"] = [plan(back, H, T, T, d, jnp.bfloat16,
                                     case["causal"], blk, blk,
                                     case["window"]).schedule
                                for back in (False, True)]
        except (TypeError, AttributeError):     # a tree from before the names
            pass
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
