"""Questions about the cell `keye_vl2_30b_a3b_train_t16384`, and the
recording of its tests' trace fixture, asked of the chip by hand, never by a
test or by the benchmark (tools/laguna_trial.py's questions, with this
model's own faults).

    chiprun -- python3 benchmark/chip/tools/keye_trial.py fit
    chiprun -- python3 benchmark/chip/tools/keye_trial.py faults --seeds 1,2,3
    chiprun -- python3 benchmark/chip/tools/keye_trial.py selection --steps 40
    chiprun -- python3 benchmark/chip/tools/keye_trial.py record_toy --out chiprun_out/keye_toy
    python3 benchmark/chip/tools/keye_trial.py cut --xplane <file.xplane.pb> --out <fixture.txt>

`fit`: do the program's step and the donated reference's step hold 659M
parameters (16 B each trained, 4 B each frozen) and the activations of
1 x 16384 tokens inside one chip? Three steps each, as a run of the cell
drives them, and the numbers `correct` would compare.

`faults`: the reference put in the program's place with one mechanism left
out (`cfg["fault"]` in reference/keye_vl2.py: `selection_ignored`,
`selection_first`, `qknorm_dropped`, `positions_dropped`, `routed_dropped`),
against the sound reference. Each has to fail a held limit on every seed,
or `correct` cannot see that mechanism. One JSON line a seed. `--faults`
also takes the three general ones, computed here because the cell's batch is
one row: `control` (FP8), `unchanged`, `half_tokens` (the second half of the
row's positions left out, the mean taken over the rest).

`selection`: what the layers say of themselves while the cell trains:
`--steps` steps of the program as a run drives them, and after every fifth
each layer's `selection` state (keys kept a query, tiles of the selection
with nothing kept) and each expert layer's `routing` state (assignments
kept here, the largest and the mean load of a held expert, 1 where the exact
dense path ran), the step's time beside them. One JSON line a reading.

`record_toy` and `cut`: the trace that
`tests/chip_benchmark/fixtures/trace_v5e_keye_toy.txt` was cut from: one
traced run of the toy cell `keye_toy_train` (tests/chip_benchmark/
keye_toy.py) on the chip with the crossover under its 32 positions, its
`.xplane.pb` kept; then, here, the device's `XLA Modules` and `XLA Ops`
lines of the first `--steps` training steps.
"""
import argparse
import importlib.util
import json
import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(TOOLS)
REPO = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [REPO, CHIP]

FAULTS = ("selection_ignored", "selection_first", "qknorm_dropped",
          "positions_dropped", "routed_dropped")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def selection(cell, seed, devices, steps, every=5):
    import time
    import runner
    import traffic
    from reference import steps as ref_steps
    weights = ref_steps.make_weights(
        cell.module("reference").param_spec(cell.config), seed)
    pool = traffic.make_pool(cell.traffic, cell.config, seed)
    prog = runner.Program(cell, weights, pool, seed, devices)
    del weights
    states = {kind: [p for p in prog.net.collect_params().values()
                     if p.name.endswith(kind)]
              for kind in ("selection", "routing")}
    rows, tr, t0 = [], prog.trainer, time.perf_counter()
    for i in range(1, steps + 1):
        tr.step(*prog.feed.next())
        if i % every == 0 or i <= 3:
            tr.sync()       # drains; the states are device values until here
            t1 = time.perf_counter()
            rows.append({"seed": seed, "step": i,
                         "ms_a_step_since_last": 1e3 * (t1 - t0)
                         / (every if i > 3 else 1),
                         **{kind: [[float(v) for v in p.data().asnumpy()]
                                   for p in ps]
                            for kind, ps in states.items()}})
            print(json.dumps(rows[-1]), flush=True)
            t0 = time.perf_counter()
    prog.close()
    return rows


def record_toy(out, seed):
    """One traced run of the toy cell on the chip; the xplane goes to `out`.
    The crossover is set under the toy's 32 positions first, so that its
    attention takes the selecting kernels as the real cell's does."""
    import tempfile
    sys.path.insert(0, os.path.join(REPO, "tests", "chip_benchmark"))
    os.environ["MXNET_FLASH_ATTENTION_MIN_SEQ"] = "16"
    import keye_toy
    import run
    import cells
    flops = cells.load_module("flops", "keye_vl2")
    with tempfile.TemporaryDirectory() as root:
        keye_toy.lay_out(root)
        # the toy benchmark has every cell report every metric: keep those
        # whose work this family's flops file counts
        path = os.path.join(root, "BENCHMARK.json")
        bench = cells.load_json(path)
        bench["per_layer"] = [
            m for m in bench["per_layer"] if hasattr(flops, cells.load_json(
                os.path.join(root, "layer_metrics", m["name"] + ".json"))
                .get("params", {}).get("work", "train_flops_per_item"))]
        with open(path, "w") as f:
            json.dump(bench, f)
        result = run.run_cell(
            "keye_toy_train", seed, 1.0, 1, root=root,
            bench_json=os.path.join(root, "BENCHMARK.json"), keep_trace=out)
    return {"metrics": result["metrics"], "device": result["device"],
            "correct": result["correct"], "compared": result["compared"]}


def main(argv=None, require_tpu=True, **cell_args):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("question", choices=("fit", "faults", "selection",
                                         "record_toy", "cut"))
    ap.add_argument("--workload", default="keye_vl2_30b_a3b_train_t16384")
    ap.add_argument("--seeds", default=str((1 << 31) + 34))
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", help="record_toy: directory; cut: fixture file")
    ap.add_argument("--xplane", help="cut: the recorded .xplane.pb")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)
    if args.question == "cut":
        return _tool("granite_trial").cut(args.xplane, args.out, args.steps)
    if args.question == "record_toy":
        row = record_toy(args.out, int(args.seeds.split(",")[0]))
        print(json.dumps(row), flush=True)
        return [row]
    import cells
    import runner
    cell = cells.Cell(args.workload, **cell_args)
    devices = runner.claim_devices(cell, require_tpu)
    runner.enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.question == "selection":
            rows += selection(cell, seed, devices, args.steps)
            continue
        # the faults of the reference are laguna_trial's loop: a fault is a
        # `cfg["fault"]` that this family's reference knows
        rows.append(_tool("granite_trial").fit(cell, seed, devices)
                    if args.question == "fit"
                    else _tool("laguna_trial").faults(
                        cell, seed, args.faults.split(",")))
        print(json.dumps(rows[-1]), flush=True)
    return rows


if __name__ == "__main__":
    main()
