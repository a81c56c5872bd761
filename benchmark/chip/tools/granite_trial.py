"""Two questions about the cell `granite4_h_micro_train_t2048`, and the recording
of its tests' trace fixture, asked of the chip by hand, never by a test or by
the benchmark.

    chiprun -- python3 benchmark/chip/tools/granite_trial.py fit
    chiprun -- python3 benchmark/chip/tools/granite_trial.py chunk_reset --seeds 1,2,3
    chiprun -- python3 benchmark/chip/tools/granite_trial.py record_toy --out chiprun_out/granite_toy
    python3 benchmark/chip/tools/granite_trial.py cut --xplane <file.xplane.pb> --out <fixture.txt>

`fit`: do the program's step and the donated reference's step hold 772M
parameters at 16 B each and the activations of 2 x 2048 tokens inside one
chip? Three steps each, through `runner.Program` and `steps.follow` as a run
of the cell drives them; prints the losses, the numbers `check.py` compares
and the peaks (`run.memory_peak`), or the error's first lines where a step
did not fit.

`chunk_reset`: this model's own fault. The reference put in the program's
place **with the state-space layers' state emptied at every boundary of
`mamba_chunk_size` positions** (`cfg["fault"]` in reference/granite_hybrid.py)
against the sound reference: what a chunked scan that drops its carry would
read. It has to fail a held limit on every seed, or `correct` cannot see
the carried state. One JSON line a seed.

`record_toy` and `cut`: the trace that
`tests/chip_benchmark/fixtures/trace_v5e_granite_toy.txt` was cut from, by
tools/record_scopes.py's method: one traced run of the toy cell
`granite_toy_train` (tests/chip_benchmark/granite_toy.py: three layers, 64
wide) on the chip, its `.xplane.pb` kept; then, here, `record_scopes.cut`
(the device's `XLA Modules` and `XLA Ops` lines, each event's name and
`tf_op`) and of that the first `--steps` training steps alone.
"""
import argparse
import json
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(os.path.dirname(CHIP)), CHIP]


def _error(e):
    return f"{type(e).__name__}: " + " | ".join(str(e).splitlines()[:6])[:1500]


def _inputs(cell, seed):
    """(reference module, the seed's weights, the first steps' batches)."""
    import runner
    import traffic
    from reference import steps
    model = cell.module("reference")
    weights = steps.make_weights(model.param_spec(cell.config), seed)
    pool = traffic.make_pool(dict(cell.traffic, pool=runner.FIRST_STEPS),
                             cell.config, seed)
    return model, weights, pool


def fit(cell, seed, devices):
    import jax
    import check
    import run
    import runner
    from reference import steps
    model, weights, pool = _inputs(cell, seed)
    out = {"seed": seed, "parameters": sum(w.size for w in weights.values()),
           "bytes_limit": (devices[0].memory_stats() or {}).get("bytes_limit")}
    got = ref = None
    try:
        prog = runner.Program(cell, weights, pool, seed, devices)
        got, mismatch = prog.first_steps()
        out["program_losses"] = got["losses"]
        prog.close()
    except jax.errors.JaxRuntimeError as e:     # the trial's answer: no fit
        out["program_error"] = _error(e)
    out["program_peak_gb"] = [b / 1e9 for b in run.memory_peak(devices)]
    try:
        ref = steps.follow(model, cell.config, weights, pool,
                           donate=cell.reference.get("donate", False))
        out["reference_losses"] = ref["losses"]
    except jax.errors.JaxRuntimeError as e:
        out["reference_error"] = _error(e)
    out["peak_gb"] = [b / 1e9 for b in run.memory_peak(devices)]
    if got and ref:
        out["numbers"] = check.numbers(got, ref, mismatch)[0]
    return out


def chunk_reset(cell, seed):
    import check
    from reference import steps
    model, weights, pool = _inputs(cell, seed)
    donate = cell.reference.get("donate", False)
    ref = steps.follow(model, cell.config, weights, pool, donate=donate)
    bad = steps.follow(model, dict(cell.config, fault="chunk_reset"), weights,
                       pool, donate=donate)
    return {"seed": seed, "chunk_reset": check.numbers(bad, ref)[0]}


def record_toy(out, seed):
    """One traced run of the toy cell on the chip; the xplane goes to `out`."""
    import tempfile
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                    "tests", "chip_benchmark"))
    import granite_toy
    import run
    with tempfile.TemporaryDirectory() as root:
        granite_toy.lay_out(root)
        result = run.run_cell(
            "granite_toy_train", seed, 1.0, 1, root=root,
            bench_json=os.path.join(root, "BENCHMARK.json"), keep_trace=out)
    return {"metrics": result["metrics"], "device": result["device"]}


def cut(xplane, fixture, steps):
    """`record_scopes.cut`, then the events up to the end of the first
    `steps` training steps alone (the key's small programs between them
    stay)."""
    import tempfile
    from google.protobuf import text_format
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import record_scopes
    import trace as T
    with tempfile.NamedTemporaryFile("r", suffix=".txt") as whole:
        record_scopes.cut(xplane, whole.name)
        space = T.xspace_from_text(whole.read())
    for plane in space.planes:
        modules = next(l for l in plane.lines if l.name == T.MODULES_LINE)
        last = sorted((e for e in modules.events if plane.event_metadata[
            e.metadata_id].name.startswith("jit_step")),
            key=lambda e: e.offset_ps)[steps - 1]
        end = last.offset_ps + last.duration_ps
        used = set()
        for line in plane.lines:
            kept = [e for e in line.events if e.offset_ps < end]
            del line.events[:]
            line.events.extend(kept)
            used.update(e.metadata_id for e in kept)
        for key in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[key]
    with open(fixture, "w") as f:
        f.write(text_format.MessageToString(space))


def main(argv=None, require_tpu=True, **cell_args):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("question", choices=("fit", "chunk_reset", "record_toy",
                                         "cut"))
    ap.add_argument("--workload", default="granite4_h_micro_train_t2048")
    ap.add_argument("--seeds", default=str((1 << 31) + 28))
    ap.add_argument("--out", help="record_toy: directory; cut: fixture file")
    ap.add_argument("--xplane", help="cut: the recorded .xplane.pb")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)
    if args.question == "cut":
        return cut(args.xplane, args.out, args.steps)
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
        rows.append(fit(cell, seed, devices) if args.question == "fit"
                    else chunk_reset(cell, seed))
        print(json.dumps(rows[-1]), flush=True)
    return rows


if __name__ == "__main__":
    main()
