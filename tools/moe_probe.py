#!/usr/bin/env python3
"""Device time of the held-experts layer's grouped products, alone on the chip.

    chiprun -- python tools/moe_probe.py keye laguna
    python tools/moe_probe.py --split chiprun_out/<dir>/<cell>.xplane.pb

A case is a cell's name (`keye`: 32,768 rows, D 2,048, F 768, 16 experts,
16,384 tokens; `laguna`: 4,096, 3,072, 1,024, 8, 8,192) or
`rows,D,F,experts,tokens`. For each case and
each `--kept` share of the buffer (0.5, 0.75, 1.0: the rows that belong to an
expert, the others behind them) it times, on bfloat16 operands, XLA's
`lax.ragged_dot` beside this repo's kernels (`ops/pallas/grouped_matmul.py`):

  `up`, `down`     the forward-kind product at the gate-and-up and the down
                   product's shapes (XLA: groups that stop at `kept`, and
                   `up+tail` with the tail in the last group, the parent's)
  `d_rows`, `d_w`  the two transposes of the gate-and-up product (XLA: the
                   `vjp` of `ragged_dot`; kernels: `mx_moe_gate_up_bwd`,
                   `mx_moe_dweights`)
  `swiglu`, `swiglu+bwd`  gate-and-up, `silu(g) * u`, down and the combine
                   weight, forward alone and with the gradient of every
                   operand (XLA: as `held_moe_ffn` wrote it until PR 34)
  `add`            the rows added back to their tokens in float32 (XLA: one
                   scatter-add of the buffer; ours: `_add_by_token`, the kept
                   rows sorted by token and a one-hot grouped product)
  `layer`, `layer+bwd`  the sorted path whole, from the tokens and back to
                   them: gather, `swiglu`, `add` (ours: `routed_swiglu`)

and prints one JSON line a case and share: how far the kernels' gradients
of `layer` lie from XLA's (largest difference over the largest entry, one
number an operand), ms a call (the median over
`--reps` of the program's time on the device, from the profiler) and the
share of the bfloat16 peak that the kept rows' operations come to. `--split`
reads a cell's kept xplane instead and prints the ms a step of the events
under the scope `mx.moe` by pass, child scope and kind of operation. Nothing
here is run by a cell or imported by the package; off the TPU the timing
refuses, a time being the device's or nothing.
"""
import argparse
import collections
import json
import os
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(HERE, "benchmark", "chip")]
CELLS = {"keye": (32768, 2048, 768, 16, 16384),
         "laguna": (4096, 3072, 1024, 8, 8192)}
PEAK = 197e12       # bfloat16 operations a second, TPU v5e


def parse_case(text):
    return CELLS[text] if text in CELLS else tuple(
        int(x) for x in text.split(","))


def split(path):
    """ms a step of the events under `mx.moe` (and of the grouped products
    XLA names `ragged-dot`, which carry no scope)."""
    import trace as xtrace
    loaded = xtrace.load(path)
    dev = loaded["devices"][xtrace.fullest(xtrace.reduce(loaded))]
    steps = sum(name.startswith("jit_step") for name, _, _ in dev["modules"])
    table = collections.Counter()
    calls = collections.Counter()
    for name, cat, _, dur, scope in dev["ops"]:
        if "mx.moe" not in scope and "ragged-dot" not in name \
                and "mx_moe" not in name:
            continue
        if cat in ("conditional", "while", "call"):
            continue                    # a parent: its children are counted
        which = "recomputed" if "rematted_computation" in scope else \
            "backward" if "transpose(" in scope else \
            "forward" if scope else "-"
        child = next((c for c in ("mx.moe.route", "mx.moe.experts",
                                  "mx.moe.shared") if c in scope),
                     "mx.moe" if scope else "(no scope)")
        kind = "ragged-dot" if "ragged-dot" in name else \
            name.rsplit(".", 1)[0] if "mx_moe" in name else cat
        table[which, child, kind] += dur
        calls[which, child, kind] += 1
    total = sum(table.values())
    print(json.dumps({"steps": steps, "mx_moe_ms_a_step":
                      round(total / steps / 1e6, 3)}))
    for key, ns in table.most_common():
        print(json.dumps({"pass": key[0], "scope": key[1], "kind": key[2],
                          "ms_a_step": round(ns / steps / 1e6, 3),
                          "events_a_step": round(calls[key] / steps, 2)}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cases", nargs="*", type=parse_case)
    ap.add_argument("--kept", type=float, nargs="+",
                    default=[0.5, 0.75, 1.0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--only", nargs="+", default=None, metavar="NAME",
                    help="time only the entries whose name ends so "
                         "(`add`, `layer+bwd`)")
    ap.add_argument("--split", metavar="XPLANE", default=None)
    args = ap.parse_args()
    if args.split:
        return split(args.split)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import trace as xtrace
    from jax import lax
    from mxnet_tpu.ops.pallas import grouped_matmul as gm

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"moe_probe: needs the TPU, found {dev.platform}")
    bf16, f32 = jnp.bfloat16, jnp.float32

    def timed(fn, *operands):
        """Median device time of the jitted `fn`'s program, in ms."""
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*operands))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(args.reps):
                    jax.block_until_ready(fn(*operands))
            loaded = xtrace.load(xtrace.find_xplane(tmp))
        modules = next(iter(loaded["devices"].values()))["modules"]
        return statistics.median(d for _, _, d in modules) / 1e6

    def total(*outs):
        return sum(jnp.sum(o.astype(f32)) for o in outs)

    for rows, D, F, G, N in args.cases:
        rs = np.random.RandomState(rows + D)
        arr = lambda scale, *s: jnp.asarray(rs.normal(0, scale, s), bf16)
        x, w_gu, w_d = arr(1, rows, D), arr(0.02, G, D, 2 * F), \
            arr(0.02, G, F, D)
        h, dgu = arr(1, rows, F), arr(1, rows, 2 * F)
        w_row = jnp.asarray(rs.uniform(0, 1, rows), f32)
        tokens = arr(1, N, D)
        token = jnp.asarray(rs.randint(0, N, rows), jnp.int32)
        for share in args.kept:
            kept = int(rows * share)
            # uneven groups whose edges fall off the tiles
            cut = np.sort(rs.choice(kept - 1, G - 1, replace=False) + 1)
            counts = jnp.asarray(np.diff([0, *cut, kept]), jnp.int32)
            tail = counts.at[G - 1].add(rows - kept)
            valid = (jnp.arange(rows) < kept)[:, None]

            def xla_swiglu(x, w_gu, w_d, w_row, groups=tail):
                gu = lax.ragged_dot(jnp.where(valid, x, 0), w_gu, groups)
                y = lax.ragged_dot((jax.nn.silu(gu[:, :F]) * gu[:, F:])
                                   .astype(bf16), w_d, groups)
                return y.astype(f32) * jnp.where(valid[:, 0], w_row,
                                                 0)[:, None]

            def our_swiglu(x, w_gu, w_d, w_row):
                z = gm.grouped_swiglu(jnp.where(valid, x, 0), w_gu, w_d,
                                      counts, jnp.where(valid[:, 0], w_row,
                                                        0), False)
                return jnp.where(valid, z, 0)

            def xla_add(y):
                return jnp.zeros((N, D), f32).at[token].add(
                    jnp.where(valid, y, 0).astype(f32)).astype(bf16)

            def xla_layer(tokens, w_gu, w_d, w_row):
                y = xla_swiglu(tokens[token], w_gu, w_d, w_row)
                return jnp.zeros((N, D), f32).at[token].add(y).astype(bf16)

            def our_layer(tokens, w_gu, w_d, w_row):
                return gm.routed_swiglu(
                    tokens, w_gu, w_d, jnp.where(valid[:, 0], w_row, 0),
                    token, counts, False)

            def grad(fn):
                return jax.grad(lambda *a: total(fn(*a)),
                                argnums=(0, 1, 2, 3))

            up_t = lambda groups: jax.vjp(
                lambda a, b: lax.ragged_dot(a, b, groups), x, w_gu)[1]
            layer = (tokens, w_gu, w_d, w_row)
            entries = [
                ("xla.up", lambda a, b: lax.ragged_dot(a, b, counts),
                 (x, w_gu)),
                ("xla.up+tail", lambda a, b: lax.ragged_dot(a, b, tail),
                 (x, w_gu)),
                ("xla.down", lambda a, b: lax.ragged_dot(a, b, counts),
                 (h, w_d)),
                ("xla.d_rows", lambda d: up_t(counts)(d)[0], (dgu,)),
                ("xla.d_w", lambda d: up_t(counts)(d)[1], (dgu,)),
                ("xla.swiglu", xla_swiglu, (x, w_gu, w_d, w_row)),
                ("xla.swiglu+bwd", grad(xla_swiglu), (x, w_gu, w_d, w_row)),
                ("xla.add", xla_add, (x,)),
                ("xla.layer", xla_layer, layer),
                ("xla.layer+bwd", grad(xla_layer), layer),
                ("our.up", lambda a, b: gm._gate_up(
                    a, b, counts, False, False)[0], (x, w_gu)),
                ("our.down", lambda a, b: gm._down(
                    a, b, counts, w_row, False), (h, w_d)),
                ("our.d_rows", lambda d, b: gm._gate_up_bwd(
                    d, b, counts, False), (dgu, w_gu)),
                ("our.d_w", lambda a, d: gm._dweights(
                    a, d, counts, bf16, False), (x, dgu)),
                ("our.swiglu", our_swiglu, (x, w_gu, w_d, w_row)),
                ("our.swiglu+bwd", grad(our_swiglu), (x, w_gu, w_d, w_row)),
                ("our.add", lambda y: gm._add_by_token(
                    y, token, kept, N, False), (x,)),
                ("our.layer", our_layer, layer),
                ("our.layer+bwd", grad(our_layer), layer),
            ]
            ms = {name: timed(fn, *operands)
                  for name, fn, operands in entries
                  if not args.only or name.split(".")[1] in args.only}
            # the compiled kernels against XLA's products, on the chip
            far = [float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))
                         / jnp.max(jnp.abs(b.astype(f32))))
                   for a, b in zip(jax.jit(grad(our_layer))(*layer),
                                   jax.jit(grad(xla_layer))(*layer))]
            up, down = 2 * kept * D * 2 * F, 2 * kept * F * D
            flops = {"up": up, "up+tail": up, "down": down, "d_rows": up,
                     "d_w": up, "swiglu": up + down, "layer": up + down,
                     "swiglu+bwd": 3 * (up + down),
                     "layer+bwd": 3 * (up + down)}
            print(json.dumps({
                "rows": rows, "D": D, "F": F, "experts": G, "kept": kept,
                "device": dev.device_kind,
                "gradients_off_by": [round(x, 5) for x in far],
                "ms": {k: round(v, 4) for k, v in ms.items()},
                "peak_share_of_kept_rows": {
                    k: round(100 * flops[k.split(".")[1]] / PEAK
                             / (v / 1e3), 1) for k, v in ms.items()
                    if k.split(".")[1] in flops}}),
                  flush=True)


if __name__ == "__main__":
    main()
