"""Set-up from inside the program: seconds and counts from the `setup` and
`build` records the program keeps before its first timed step.

`mxnet_tpu.telemetry.tracing.step_records` returns, beside the step and
batch records, one `setup` record a boundary set-up crosses
(`mx.block.initialize`, `mx.block.deferred_init`, `mx.dp.init`: start,
duration, seconds by phase) and one `build` record (`mx.build`) for every
program the process traced, lowered, compiled or loaded (`phases`: `trace`,
`lower`, `compile`, `cache_load`; `fun`: the function's name), all stamped on
`time.perf_counter`, the clock of the runner's window. What call a build ran
under is `tracing.parent_of(build)`: the innermost step or setup record of
its thread that holds its start. params:

    records   names of setup records; `add`: their phases to sum, "dur" the
              whole call (every such record that began before the window)
    builds    "under": the builds that ran under a record named in `under`
              (the trainer's `mx.dp.step` / `mx.dp.run_steps`) and began in
              one of its phases `in` (`get_step`, `capture_cost`, `launch`,
              the phases `setup_step_build_s` adds up: the step's own
              program); "other": every other build (the per-op host programs
              of deferred init, the per-leaf zeros, the key's split in the
              step's `rng_key`); `add`: their stages to sum, or `count`: how
              many they are

A record's phases are contiguous and in the order its dictionary holds them,
so the phase a build began in is the one whose running sum first passes the
build's offset into the call (a phase entered twice, as `run_steps`'
`put_scalars` is, counts where it was first entered: `launch` comes after
every such phase and is not moved by it).

Only what began before the window's `t0` is read: the reference's builds come
after the window and are not the program's. A program that declares a kind
(`tracing.RECORD_KINDS`) and kept no record of it is an error: every
program initializes a net, builds a trainer and builds programs before its
first step, and one that stopped recording must not read as one that got
faster. A program from before the kinds (no `RECORD_KINDS`: the parent of
the PR that brought them, which the driver runs under this reader) gives
nothing and the metric is left out.
"""


def _tracing():
    from mxnet_tpu.telemetry import tracing
    if not {"setup", "build"} <= set(getattr(tracing, "RECORD_KINDS", ())):
        return None
    return tracing


def _phase_at(record, ts):
    name, end = None, record["ts"]
    for name, seconds in record["phases"].items():
        end += seconds
        if ts < end:
            break
    return name


def _sum(records, keys):
    return sum(r["dur"] if k == "dur" else r["phases"].get(k, 0.0)
               for r in records for k in keys)


def read(view, params):
    tracing = _tracing()
    if tracing is None:
        return None
    before = tracing.step_records(until=view.window["t0"])
    if "records" in params:
        found = [r for r in before if r["name"] in params["records"]
                 and "error" not in r]
        if not found:
            raise RuntimeError(f"the program kept no record "
                               f"{params['records']} before the window")
        return _sum(found, params["add"])
    builds = [r for r in before if r["kind"] == "build"]
    if not builds:
        raise RuntimeError("the program kept no build record before the "
                           "window")
    calls = [r for r in before if r["kind"] in ("step", "setup")]

    def is_the_steps(build):
        call = tracing.parent_of(build, calls)
        return call is not None and call["name"] in params["under"] \
            and _phase_at(call, build["ts"]) in params["in"]

    chosen = [b for b in builds
              if is_the_steps(b) == (params["builds"] == "under")]
    if params.get("count"):
        return len(chosen)
    return _sum(chosen, params["add"])
