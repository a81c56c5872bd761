#!/usr/bin/env python3
"""Set-up of one cell of the chip benchmark as a partition on one clock.

    chiprun -- python tools/setup_probe.py --workload resnet50_train_bs128
    chiprun --chips 4 -- python tools/setup_probe.py --workload bert_base_train_dp4

Drives the cell's set-up as `benchmark/chip/run.py` does (the same calls in
the same order through `runner.Program` and its `first_steps`), stops before
the window, and prints where the seconds went, every line on
`time.perf_counter`:

  - the interpreter, `import jax`, the devices claimed, weights and pool (the
    benchmark's own);
  - each `setup` record of the program (`mx.block.initialize`,
    `mx.block.deferred_init`, `mx.dp.init`) with its phases and the builds
    that ran under it: how many, and their seconds of `trace`, `lower`,
    `compile` and `cache_load` (`tracing.step_records`, `tracing.parent_of`);
  - the first three `mx.dp.step` records with their phases and the builds
    under each by `fun`;
  - what no record of the program covers: between the records inside
    `Program(...)` (the block's construction, `set_data` of the seed's
    weights, the mesh, the feed) and inside `first_steps` (the feed's
    batches, the drains and the read-backs `correct` is decided from).

The last line on standard output is one JSON object with all of it;
`--out FILE` also writes it there. `--trace DIR` opens the profiler before
the net is built and keeps the xplane: the `setup` records are
`TraceAnnotation`s, so the host plane holds them beside the placement's
transfers on the device's clock. `--window S` drives the trainer S more
seconds and reports the builds inside them (there should be none). This is
the instrument behind PERF.md's table of set-up by phase; nothing here is run
by a cell or imported by the package. Off the TPU it refuses unless `--cpu`
(a logic check: no second it prints then is a device's).
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("trace", "lower", "compile", "cache_load")


def process_start():
    """`perf_counter` when this process started (Linux: both count from
    boot), or None where /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        start = ticks / os.sysconf("SC_CLK_TCK")
        mono = time.clock_gettime(time.CLOCK_MONOTONIC)
        return start + (time.perf_counter() - mono)
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def stages_of(builds):
    out = {"count": len(builds)}
    for s in STAGES:
        out[s] = sum(b["phases"][s] for b in builds)
    out["seconds"] = sum(out[s] for s in STAGES)
    out["cache"] = {k: sum(b["cache"] == k for b in builds)
                    for k in ("hit", "miss", "off")}
    return out


def by_fun(builds):
    funs = {}
    for b in builds:
        funs.setdefault(b["fun"], []).append(b)
    return {fun: stages_of(bs) for fun, bs in
            sorted(funs.items(), key=lambda kv: -sum(b["dur"] for b in kv[1]))}


def describe_builds(builds, top=4):
    out = stages_of(builds)
    out["by_fun"] = dict(list(by_fun(builds).items())[:top])
    return out


def describe(record, builds):
    """One record with the builds that ran under it."""
    under = describe_builds(builds)
    attrs = {k: v for k, v in record.items()
             if k not in ("kind", "name", "ts", "dur", "phases", "thread")}
    return {"name": record["name"], "at": record["ts"] - T0,
            "dur": record["dur"], "phases": record["phases"],
            "attrs": attrs, "builds": under}


def grouped(records, under):
    """(record, its builds) pairs. A run of more than three records of one
    name is one pair, summed: an unhybridized net leaves a
    `mx.block.deferred_init` a cold child."""
    out = []
    for name, run in itertools.groupby(records, key=lambda r: r["name"]):
        run = list(run)
        if len(run) <= 3:
            out += [(r, under(r)) for r in run]
            continue
        phases = {}
        for r in run:
            for k, v in r["phases"].items():
                phases[k] = phases.get(k, 0.0) + v
        out.append(({"name": f"{name} x{len(run)}", "ts": run[0]["ts"],
                     "dur": sum(r["dur"] for r in run), "phases": phases,
                     "until": run[-1]["ts"] + run[-1]["dur"] - T0},
                    [b for r in run for b in under(r)]))
    return out


def say_record(d, indent="    "):
    b = d["builds"]
    phases = ", ".join(f"{k} {v:.3f}" for k, v in d["phases"].items())
    print(f"{indent}{d['name']:<24} at {d['at']:7.2f}  {d['dur']:8.3f} s"
          f"  {d['attrs'] or ''}")
    if phases:
        print(f"{indent}  phases: {phases}")
    if b["count"]:
        print(f"{indent}  builds: {b['count']} in {b['seconds']:.3f} s "
              f"(trace {b['trace']:.3f}, lower {b['lower']:.3f}, compile "
              f"{b['compile']:.3f}, cache_load {b['cache_load']:.3f}; "
              f"{b['cache']})")
        for fun, s in b["by_fun"].items():
            print(f"{indent}    {fun:<28} x{s['count']:<4} trace "
                  f"{s['trace']:.3f} lower {s['lower']:.3f} compile "
                  f"{s['compile']:.3f} cache_load {s['cache_load']:.3f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=(1 << 31) + 36)
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="profile set-up from before the net is built and "
                         "keep the xplane under DIR")
    ap.add_argument("--window", type=float, default=0.0, metavar="S",
                    help="drive S seconds after set-up and report the builds "
                         "inside them")
    ap.add_argument("--out", metavar="FILE", default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="run off the TPU (a logic check, no device time)")
    ap.add_argument("--root", metavar="DIR", default=None,
                    help="a benchmark laid out elsewhere (the tests' toy: "
                         "its BENCHMARK.json and data files)")
    args = ap.parse_args(argv)
    sys.path[:0] = [REPO, os.path.join(REPO, "benchmark", "chip")]
    t_started = process_start()

    import cells
    import runner
    import traffic as traffic_mod
    cell = cells.Cell(args.workload) if args.root is None else cells.Cell(
        args.workload, os.path.join(args.root, "BENCHMARK.json"), args.root)
    t_files = time.perf_counter()
    import jax
    t_jax = time.perf_counter()
    devices = runner.claim_devices(cell, require_tpu=not args.cpu)
    t_devices = time.perf_counter()
    cache_dir = runner.enable_compile_cache()
    from mxnet_tpu import engine
    from mxnet_tpu.telemetry import tracing
    if args.trace:
        jax.profiler.start_trace(args.trace)
    try:
        from reference import steps as ref_steps
        ref_model = cell.module("reference")
        weights = ref_steps.make_weights(ref_model.param_spec(cell.config),
                                         args.seed)
        pool = traffic_mod.make_pool(cell.traffic, cell.config, args.seed)
        t_made = time.perf_counter()
        prog = runner.Program(cell, weights, pool, args.seed, devices)
        t_built = time.perf_counter()
        prog.first_steps()
        t_first = time.perf_counter()
    finally:
        if args.trace:
            jax.profiler.stop_trace()

    records = tracing.step_records(until=t_first)
    calls = [r for r in records if r["kind"] in ("step", "setup")]
    builds = [r for r in records if r["kind"] == "build"]
    parents = {id(b): tracing.parent_of(b, calls) for b in builds}

    def under(call):
        return [b for b in builds if parents[id(b)] is call]

    def outermost(kind, since, until):
        return [r for r in calls if r["kind"] == kind
                and since <= r["ts"] < until
                and tracing.parent_of(r, calls) is None]

    setups = outermost("setup", t_made, t_built)
    steps = outermost("step", t_built, t_first)
    orphans = [b for b in builds if parents[id(b)] is None]
    spans = [("interpreter, before the script", None if t_started is None
              else T0 - t_started),
             ("argparse, the cell's files", t_files - T0),
             ("import jax", t_jax - t_files),
             ("devices claimed", t_devices - t_jax),
             ("weights and pool", t_made - t_devices),
             ("Program(...)", t_built - t_made),
             ("first_steps()", t_first - t_built)]
    setup_s = t_first - T0
    covered = sum(r["dur"] for r in setups + steps)
    result = {
        "workload": args.workload, "seed": args.seed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "compile_cache": cache_dir, "setup_s": setup_s,
        "timeline": dict(spans),
        "setup_records": [describe(r, bs) for r, bs in grouped(setups, under)],
        "step_records": [describe(r, under(r)) for r in steps],
        "builds_under_no_record": {
            where: describe_builds([b for b in orphans if lo <= b["ts"] < hi])
            for where, lo, hi in (("before_program", T0, t_made),
                                  ("in_program", t_made, t_built),
                                  ("in_first_steps", t_built, t_first))},
        "not_covered": {
            "in_program": t_built - t_made - sum(r["dur"] for r in setups),
            "in_first_steps": t_first - t_built - sum(r["dur"]
                                                      for r in steps)},
        "covered_by_records_s": covered,
        "covered_share": covered / setup_s,
        "builds": stages_of(builds),
        "compile_seconds": engine.cache_stats()["compile_seconds"],
    }

    print(f"{args.workload} on {len(devices)} x {devices[0].device_kind}, "
          f"seed {args.seed}, compile cache {cache_dir}")
    print(f"set-up {setup_s:.2f} s from the script's first line "
          f"(run.py's setup_s starts there)")
    at = 0.0 if t_started is None else -(T0 - t_started)
    for name, s in spans:
        if s is None:
            continue
        print(f"  {at:7.2f}  {s:8.3f} s  {name}")
        at += s
        if name == "Program(...)":
            for r in result["setup_records"]:
                say_record(r)
            print(f"    {'(no record: blocks built, set_data, mesh, feed)':<24}"
                  f"  {result['not_covered']['in_program']:8.3f} s")
        if name == "first_steps()":
            for r in result["step_records"]:
                say_record(r)
            print(f"    {'(no record: batches, drains, read-backs)':<24}"
                  f"  {result['not_covered']['in_first_steps']:8.3f} s")
    for where, s in result["builds_under_no_record"].items():
        if s["count"]:
            print(f"  builds under no record, {where}: {s['count']} in "
                  f"{s['seconds']:.3f} s: " + ", ".join(
                      f"{fun} x{b['count']} {b['seconds']:.3f}"
                      for fun, b in s["by_fun"].items()))
    b = result["builds"]
    print(f"  all builds: {b['count']} in {b['seconds']:.3f} s (trace "
          f"{b['trace']:.3f}, lower {b['lower']:.3f}, compile "
          f"{b['compile']:.3f}, cache_load {b['cache_load']:.3f}; "
          f"{b['cache']}); engine compile_seconds "
          f"{result['compile_seconds']:.3f}")
    print(f"  the program's records cover {covered:.2f} of {setup_s:.2f} s "
          f"({100 * covered / setup_s:.1f}%)")

    if args.window > 0:
        window = prog.stretch(args.window)
        inside = tracing.step_records("mx.build", since=window["t0"])
        result["window"] = {"seconds": window["seconds"],
                            "steps": window["steps"],
                            "builds": [dict(b, under=(tracing.parent_of(b)
                                                      or {}).get("name"))
                                       for b in inside]}
        print(f"  window of {window['seconds']:.2f} s, {window['steps']} "
              f"steps: {len(inside)} builds "
              f"{[(b['fun'], b['dur']) for b in inside]}")
    prog.close()
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
