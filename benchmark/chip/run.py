"""One run of one cell of the chip benchmark.

    python benchmark/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Makes weights and traffic from the seed, builds the cell's trainer and feed,
drives the first steps (set-up: the one compile, and the readings `correct`
is decided from), measures a window of `--seconds`, and with `--trace 1` a
few more seconds under the profiler. Then it frees the program, follows the
same first steps with the plain float32 reference, and prints one JSON line.
Off the TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_SECONDS = 3.0


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def device_info(devices, memory):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": sum(memory),
            "allocator_peak_bytes": memory[0],
            "program_temp_bytes": memory[1]}


def memory_peak(devices):
    """(allocator peak on the fullest chip, temporaries of the largest loaded
    program per chip). On this runtime `memory_stats()["peak_bytes_in_use"]`
    counts the buffers that live between programs (weights, optimizer state,
    batches) and leaves out what a program allocates while it runs (PERF.md,
    Findings of PR 24), which XLA's memory analysis of the loaded executables
    gives; a chip runs one program at a time, so the peak is their sum."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    temps = [0]
    for ex in devices[0].client.live_executables():
        try:
            temps.append(ex.get_compiled_memory_stats().temp_size_in_bytes)
        except RuntimeError:
            pass   # an executable without an analysis adds nothing
    return int(max(peaks)), int(max(temps))


def read_layer_metrics(cell, view):
    import cells
    out = {}
    for entry in cell.metrics("per_layer"):
        spec = cell.layer_metric(entry["name"])
        reader = cells.load_module("readers", spec["reader"], cell.root)
        value = reader.read(view, spec.get("params", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def run_cell(workload, seed, seconds, trace, root=HERE, bench_json=None,
             require_tpu=True, loss=None, program_hook=None, keep_trace=None):
    """The whole run; returns the result object (and prints nothing on
    standard output). `require_tpu`, `loss` and `program_hook` are for the
    tests under tests/chip_benchmark, which drive the rest of a run on the
    CPU, also with the timed path broken underneath."""
    import cells
    import check
    import runner
    import trace as trace_mod
    import traffic as traffic_mod

    cell = cells.Cell(workload, bench_json=bench_json, root=root)

    import jax
    devices = runner.claim_devices(cell, require_tpu)
    peaks = cells.peaks(devices[0].device_kind, root) if require_tpu else None
    cache_dir = runner.enable_compile_cache()
    events = runner.Events()
    say(f"cell {workload}: config {cell.entry['config']}, traffic "
        f"{cell.entry['traffic']}, {cell.chips} x {devices[0].device_kind}, "
        f"seed {seed}, compile cache {cache_dir}")

    # -- set-up ---------------------------------------------------------------
    from reference import steps as ref_steps
    ref_model = cell.module("reference")
    weights = ref_steps.make_weights(ref_model.param_spec(cell.config), seed)
    pool = traffic_mod.make_pool(cell.traffic, cell.config, seed)
    t_made = time.perf_counter()
    prog = runner.Program(cell, weights, pool, seed, devices, loss=loss)
    if program_hook is not None:
        program_hook(prog)
    t_built = time.perf_counter()
    readings, mismatch = prog.first_steps()
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.1f} s: weights and traffic "
        f"{t_made - T_START:.1f}, program {t_built - t_made:.1f}, first "
        f"steps {time.perf_counter() - t_built:.1f}; compiles "
        f"{events.count('compiles', 'setup')}, persistent cache hits "
        f"{events.count('cache_hits', 'setup')} misses "
        f"{events.count('cache_misses', 'setup')}")

    # -- the window -----------------------------------------------------------
    events.phase = "window"
    window = prog.stretch(seconds)
    traced = loaded = reduced = None
    if trace:
        trace_dir = os.path.join(REPO, ".chipbench", "trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            traced = prog.stretch(min(TRACE_SECONDS, seconds), annotate=True)
        finally:
            jax.profiler.stop_trace()
    events.phase = "after"
    memory = memory_peak(devices)
    peak_bytes = sum(memory)
    e2e = runner.end_to_end(window, cell.items_per_step())
    e2e["setup_s"] = setup_s
    say(f"window {window['seconds']:.2f} s: {window['steps']} steps, "
        f"{e2e['train_items_per_s']:.1f} {cell.traffic['items']}/s, step "
        f"p95 {e2e['step_p95_ms']:.2f} ms, feed stall "
        f"{window['feed_stall_s']:.3f} s, dispatch wait "
        f"{window['dispatch_wait_s']:.2f} s, peak memory "
        f"{peak_bytes / 1e9:.2f} GB (allocator {memory[0] / 1e9:.2f} + "
        f"program temporaries {memory[1] / 1e9:.2f})")
    if trace:
        xplane = trace_mod.find_xplane(trace_dir)
        loaded = trace_mod.load(xplane)
        reduced = trace_mod.reduce(loaded)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane,
                        os.path.join(keep_trace, workload + ".xplane.pb"))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- the comparison, once the program's state is freed --------------------
    prog.close()
    del prog
    t_ref = time.perf_counter()
    ref = ref_steps.follow(
        ref_model, cell.config, weights, pool[:runner.FIRST_STEPS],
        row_blocks=cell.reference.get("row_blocks", 1))
    say(f"reference followed {runner.FIRST_STEPS} steps in "
        f"{time.perf_counter() - t_ref:.1f} s")
    nums, where = check.numbers(readings, ref, mismatch)
    correct, compared, shown = check.decide(nums, cell.limits)
    events.close()

    device = device_info(devices, memory)
    result = {"correct": bool(correct), "attempted": window["steps"],
              "failed": 0}
    if trace:
        # what a per-layer reader may read
        view = types.SimpleNamespace(
            cell=cell, peaks=peaks, events=events, window=window,
            traced=traced, loaded=loaded, devices=reduced,
            memory_peak_bytes=peak_bytes, chips=cell.chips,
            flops=cell.module("flops"), say=say)
        result["metrics"] = read_layer_metrics(cell, view)
        if reduced:
            device["busy_s"] = sum(d["busy_ns"] for d in reduced.values()) \
                / len(reduced) / 1e9
            device["window_s"] = max(d["window_ns"]
                                     for d in reduced.values()) / 1e9
            result["breakdown"] = trace_mod.breakdown(loaded, reduced)
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")}
    result["device"] = device
    result["not_compared"] = shown
    result["compared"] = compared
    check.report(compared, shown, where, correct)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="with --trace 1, copy the .xplane.pb there")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                      keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
