"""Questions about the cell `laguna_s_2_1_train_t8192`, and the recording of
its tests' trace fixture, asked of the chip by hand, never by a test or by the
benchmark (tools/granite_trial.py's `fit`, `record_toy` and `cut`, with this
model's own faults).

    chiprun -- python3 benchmark/chip/tools/laguna_trial.py fit
    chiprun -- python3 benchmark/chip/tools/laguna_trial.py faults --seeds 1,2,3
    chiprun -- python3 benchmark/chip/tools/laguna_trial.py record_toy --out chiprun_out/laguna_toy
    chiprun -- python3 benchmark/chip/tools/laguna_trial.py routing --steps 80
    python3 benchmark/chip/tools/laguna_trial.py cut --xplane <file.xplane.pb> --out <fixture.txt>

`fit`: do the program's step and the donated reference's step hold 811M
parameters at 16 B each and the activations of 1 x 8192 tokens inside one
chip? Three steps each, as a run of the cell drives them.

`faults`: the reference put in the program's place with one mechanism left
out (`cfg["fault"]` in reference/laguna.py: `window_ignored`,
`positions_dropped`, `routed_dropped`, `gate_dropped`), against the sound
reference. Each has to fail a held limit on every seed, or `correct` cannot
see that mechanism. One JSON line a seed. `--faults` also takes control.py's
three, computed here because the cell's batch is one row and control.py's
"half of the batch" would leave none: `control` (every kept tensor and the
gradients flowing back rounded to per-tensor scaled FP8), `unchanged` (a
step that returns its state unchanged) and `half_tokens` (the second half of
the row's positions left out, the mean taken over the rest).

`routing`: what the expert layers say of themselves while the cell trains:
`--steps` steps of the program as a run drives them, and after every fifth
each layer's `routing` state (`HeldExpertsFFN.routing`: assignments kept
here, the largest and the mean load of a held expert, 1 where the exact
dense path ran), the step's time beside it. One JSON line a reading.

`record_toy` and `cut`: the trace that
`tests/chip_benchmark/fixtures/trace_v5e_laguna_toy.txt` was cut from: one
traced run of the toy cell `laguna_toy_train` (tests/chip_benchmark/
laguna_toy.py) on the chip, its `.xplane.pb` kept; then, here, the device's
`XLA Modules` and `XLA Ops` lines of the first `--steps` training steps.
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

FAULTS = ("window_ignored", "positions_dropped", "routed_dropped",
          "gate_dropped")
GENERAL = ("control", "unchanged", "half_tokens")


def _granite_trial():
    spec = importlib.util.spec_from_file_location(
        "granite_trial", os.path.join(TOOLS, "granite_trial.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def faults(cell, seed, kinds=FAULTS):
    import check
    from reference import steps
    model, weights, pool = _granite_trial()._inputs(cell, seed)
    donate = cell.reference.get("donate", False)
    ref = steps.follow(model, cell.config, weights, pool, donate=donate)
    row = {"seed": seed}
    for kind in kinds:
        config, batches, precision = cell.config, pool, "float32"
        if kind == "control":
            precision = "float8"
        elif kind == "unchanged":
            import control
            config = control.state_unchanged(config)
        elif kind == "half_tokens":
            batches = [(x[:, :x.shape[1] // 2], y[:, :y.shape[1] // 2])
                       for x, y in pool]
        else:
            config = dict(config, fault=kind)
        bad = steps.follow(model, config, weights, batches,
                           precision=precision, donate=donate)
        row[kind] = check.numbers(bad, ref)[0]
    return row


def routing(cell, seed, devices, steps, every=5):
    import time
    import runner
    import traffic
    from reference import steps as ref_steps
    weights = ref_steps.make_weights(
        cell.module("reference").param_spec(cell.config), seed)
    pool = traffic.make_pool(cell.traffic, cell.config, seed)
    prog = runner.Program(cell, weights, pool, seed, devices)
    del weights
    states = [p for p in prog.net.collect_params().values()
              if p.name.endswith("routing")]
    rows, tr, t0 = [], prog.trainer, time.perf_counter()
    for i in range(1, steps + 1):
        tr.step(*prog.feed.next())
        if i % every == 0 or i <= 3:
            tr.sync()       # drains; the states are device values until here
            t1 = time.perf_counter()
            rows.append({"seed": seed, "step": i,
                         "ms_a_step_since_last": 1e3 * (t1 - t0)
                         / (every if i > 3 else 1),
                         "routing": [[float(v) for v in p.data().asnumpy()]
                                     for p in states]})
            print(json.dumps(rows[-1]), flush=True)
            t0 = time.perf_counter()
    prog.close()
    return rows


def record_toy(out, seed):
    """One traced run of the toy cell on the chip; the xplane goes to `out`.
    The crossover is set under the toy's 32 positions first, so that its
    attention takes the flash kernels as the real cell's does."""
    import tempfile
    sys.path.insert(0, os.path.join(REPO, "tests", "chip_benchmark"))
    os.environ["MXNET_FLASH_ATTENTION_MIN_SEQ"] = "16"
    import laguna_toy
    import run
    import cells
    flops = cells.load_module("flops", "laguna")
    with tempfile.TemporaryDirectory() as root:
        laguna_toy.lay_out(root)
        # the toy benchmark has every cell report every metric; here the
        # attention runs in kernels, so a roofline whose work this family's
        # flops file does not count would find events and nothing to divide
        path = os.path.join(root, "BENCHMARK.json")
        bench = cells.load_json(path)
        bench["per_layer"] = [
            m for m in bench["per_layer"] if hasattr(flops, cells.load_json(
                os.path.join(root, "layer_metrics", m["name"] + ".json"))
                .get("params", {}).get("work", "train_flops_per_item"))]
        with open(path, "w") as f:
            json.dump(bench, f)
        result = run.run_cell(
            "laguna_toy_train", seed, 1.0, 1, root=root,
            bench_json=os.path.join(root, "BENCHMARK.json"), keep_trace=out)
    return {"metrics": result["metrics"], "device": result["device"],
            "correct": result["correct"], "compared": result["compared"]}


def main(argv=None, require_tpu=True, **cell_args):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("question", choices=("fit", "faults", "routing",
                                         "record_toy", "cut"))
    ap.add_argument("--workload", default="laguna_s_2_1_train_t8192")
    ap.add_argument("--seeds", default=str((1 << 31) + 32))
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", help="record_toy: directory; cut: fixture file")
    ap.add_argument("--xplane", help="cut: the recorded .xplane.pb")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)
    if args.question == "cut":
        return _granite_trial().cut(args.xplane, args.out, args.steps)
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
        if args.question == "routing":
            rows += routing(cell, seed, devices, args.steps)
            continue
        rows.append(_granite_trial().fit(cell, seed, devices)
                    if args.question == "fit"
                    else faults(cell, seed, args.faults.split(",")))
        print(json.dumps(rows[-1]), flush=True)
    return rows


if __name__ == "__main__":
    main()
