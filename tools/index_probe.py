#!/usr/bin/env python3
"""Device time of one indexed layer's key selection, alone on the chip.

    chiprun -- bash -c "python tools/index_probe.py keye;
                        python tools/index_probe.py keye --tree .chipwork/parent"

A case is `keye` (the cell's layer: 16,384 tokens, a hidden size of 2,048,
16 heads of 64, top_k 2,048, chunks of 512) or `T,hidden,heads,di,top_k,
chunk`. The indexer's inputs are made as the layer makes them, on bfloat16:
seeded hidden states, the query, key and head-weight projections
(normal(0, 0.02) weights) and the key's layer norm. Then
`ops/sparse_select.py: indexer_select` (the tree's own, so the parent's
plain-XLA path or this tree's kernel) and `unpack_selection` run `--reps`
times each under `jax.profiler.trace`, and one JSON line a case is printed:
ms a call of each program on the device (the median over the repetitions),
ms a call of the `mx_index_select` kernels where the tree has them, the
call's report (`SELECT_REPORT`: keys kept a query, empty tiles and, where
the tree has it, the query blocks that ran the position passes) and a digest
of the packed set, which two trees given the same seed should share.
`--tree DIR` probes another checkout (the parent's, unpacked in an ignored
directory); `--set NAME=INT` sets a constant of the kernel's module
(`_ROWS=256`; a key block is at most a chunk, so `_KEYS` above 512 changes
nothing at the cell). Nothing here is run by a cell or imported by the package; off
the TPU it refuses, a time being the device's or nothing.
"""
import argparse
import hashlib
import json
import os
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = {"keye": (16384, 2048, 16, 64, 2048, 512)}


def parse_case(text):
    return CELLS[text] if text in CELLS else tuple(
        int(x) for x in text.split(","))


def inputs(T, hidden, heads, di, seed):
    """q (1, heads, T, di), k (1, T, di), w (1, T, heads) in bfloat16, as
    `GroupedQueryAttention._select` makes them (positions left out)."""
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    a = jax.random.normal(keys[0], (T, hidden), bf)
    proj = lambda key, n: a @ (0.02 * jax.random.normal(key, (n, hidden),
                                                        bf)).T
    q = proj(keys[1], heads * di).reshape(1, T, heads, di).transpose(0, 2, 1, 3)
    k = proj(keys[2], di).astype(jnp.float32)
    k = (k - k.mean(-1, keepdims=True)) * jax.lax.rsqrt(
        k.var(-1, keepdims=True) + 1e-6)
    return q, k.astype(bf)[None], proj(keys[3], heads)[None]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cases", nargs="+", type=parse_case)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--tree", default=HERE,
                    help="checkout whose mxnet_tpu is probed")
    ap.add_argument("--set", action="append", default=[], metavar="NAME=INT",
                    help="a constant of the kernel's module for this run")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path[:0] = [tree, os.path.join(HERE, "benchmark", "chip")]

    import jax
    import numpy as np
    import trace as xtrace
    from mxnet_tpu.ops import sparse_select as S
    kernel = None
    if os.path.exists(os.path.join(tree, "mxnet_tpu", "ops", "pallas",
                                   "index_select.py")):
        from mxnet_tpu.ops.pallas import index_select as kernel
    for item in args.set:
        name, value = item.split("=")
        if kernel is None or not hasattr(kernel, name):
            ap.error(f"--set {name}: not a constant of the kernel's module")
        setattr(kernel, name, int(value))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"index_probe: needs the TPU, found {dev.platform}")

    for case in args.cases:
        T, hidden, heads, di, top_k, chunk = case
        q, k, w = jax.jit(inputs, static_argnums=(0, 1, 2, 3, 4))(
            T, hidden, heads, di, args.seed % (1 << 31))
        select = jax.jit(lambda q, k, w: S.indexer_select(
            q, k, w, top_k=top_k, chunk=chunk))
        packed, report = jax.block_until_ready(select(q, k, w))  # compile
        unpack = jax.jit(lambda p: S.unpack_selection(p, keys=T))
        jax.block_until_ready(unpack(packed))
        ms = {}
        for name, fn, operands in (("select", select, (q, k, w)),
                                   ("unpack", unpack, (packed,))):
            with tempfile.TemporaryDirectory() as tmp:
                with jax.profiler.trace(tmp):
                    for _ in range(args.reps):
                        jax.block_until_ready(fn(*operands))
                loaded = xtrace.load(xtrace.find_xplane(tmp))
            dev_ops = next(iter(loaded["devices"].values()))
            ms[name + "_ms"] = round(statistics.median(
                d for _, _, d in dev_ops["modules"]) / 1e6, 4)
            if name == "select" and kernel is not None:
                ms["kernel_ms"] = round(sum(
                    o[3] for o in dev_ops["ops"]
                    if "mx_index_select" in o[0]) / args.reps / 1e6, 4)
        line = {"case": case, "tree": os.path.relpath(tree, HERE),
                "set": args.set, "device": dev.device_kind, **ms,
                "report": dict(zip(S.SELECT_REPORT,
                                   np.asarray(report).tolist())),
                "packed_sha256": hashlib.sha256(
                    np.asarray(packed).tobytes()).hexdigest()[:16]}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
