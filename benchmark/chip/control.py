"""Readings that a cell's limits are set from, taken in one process.

    python benchmark/chip/control.py --workload <name> --seeds 1,2,.. \
        [--controls 3] [--out chiprun_out/control_<name>.jsonl]

For every seed: the program's first steps against the float32 reference
(the lower readings). For the first `--controls` seeds also, against the same
reference: the control, which is the reference put in the program's place
with every tensor its forward pass keeps, and the gradients flowing back,
rounded to per-tensor scaled FP8 (the precision below the configuration's
bfloat16, in which the program keeps those tensors), and the fault "half of
the batch left out, the mean taken over the rest", planted in the reference
put in the program's place, and the fault "a step that returns its state
unchanged" (the reference followed with a learning rate and decay of 0). The
last reads 1 in the norms of the change by their definition; it is run for
what it reads in the losses of steps 2 and 3. One JSON line per seed. The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def half_batches(batches):
    return [(x[:len(x) // 2], y[:len(y) // 2]) for x, y in batches]


def state_unchanged(config):
    """The configuration with an optimizer that moves nothing."""
    return dict(config, optimizer=dict(config["optimizer"], learning_rate=0.0,
                                       wd=0.0))


def readings(workload, seeds, controls, root=HERE, bench_json=None,
             require_tpu=True, control_precision="float8", raw=False,
             program=True):
    """Yields one dict per seed: {"seed", "program": numbers, "control":
    numbers or None, "half_batch": numbers or None, "unchanged": numbers or
    None}."""
    import cells
    import check
    import runner
    import traffic as traffic_mod
    from reference import steps as ref_steps

    cell = cells.Cell(workload, bench_json=bench_json, root=root)
    devices = runner.claim_devices(cell, require_tpu)
    runner.enable_compile_cache()
    model = cell.module("reference")
    spec = model.param_spec(cell.config)
    blocks = cell.reference.get("row_blocks", 1)
    net = None
    for i, seed in enumerate(seeds):
        weights = ref_steps.make_weights(spec, seed)
        pool = traffic_mod.make_pool(
            dict(cell.traffic, pool=runner.FIRST_STEPS), cell.config, seed)
        got = None
        if program:
            prog = runner.Program(cell, weights, pool, seed,
                                  devices[:cell.chips], net=net)
            net = prog.net
            got, mismatch = prog.first_steps()
            prog.close(keep_executables=True)
        ref = ref_steps.follow(model, cell.config, weights, pool,
                               row_blocks=blocks)
        out = {"seed": seed,
               "program": got and check.numbers(got, ref, mismatch)[0],
               "reference_losses": ref["losses"],
               "control": None, "half_batch": None, "unchanged": None}
        if raw:     # the per-leaf readings themselves, to look at by hand
            out["raw"] = {"program": got, "reference": ref}
        if i < controls:
            ctl = ref_steps.follow(model, cell.config, weights, pool,
                                   precision=control_precision,
                                   row_blocks=blocks)
            out["control"] = check.numbers(ctl, ref)[0]
            if raw:
                out["raw"]["control"] = ctl
            half = ref_steps.follow(model, cell.config, weights,
                                    half_batches(pool),
                                    row_blocks=max(blocks // 2, 1))
            out["half_batch"] = check.numbers(half, ref)[0]
            if raw:
                out["raw"]["half_batch"] = half
            still = ref_steps.follow(model, state_unchanged(cell.config),
                                     weights, pool, row_blocks=blocks)
            out["unchanged"] = check.numbers(still, ref)[0]
        yield out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-program", action="store_true",
                    help="the control and the fault alone")
    ap.add_argument("--raw", action="store_true",
                    help="also write the per-leaf readings")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    for row in readings(args.workload, seeds, args.controls, raw=args.raw,
                        program=not args.no_program):
        line = json.dumps(row)
        print(json.dumps({k: v for k, v in row.items() if k != "raw"}),
              flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()


if __name__ == "__main__":
    main()
