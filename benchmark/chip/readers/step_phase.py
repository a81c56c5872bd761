"""Seconds by phase, from the records the program keeps of its own calls.

`mxnet_tpu.telemetry.tracing.step_records` returns one record per
`DataParallelTrainer.step` / `run_steps` call and per batch the feed's
producer made, each with its start and duration on `time.perf_counter` (the
clock of the runner's window) and the seconds of each phase of the call.
params:

    records   names of the records to read ("mx.dp.step", "mx.feed.batch")
    add       phases to sum; "dur" is the whole call
    subtract  phases to take off again (optional)
    over      "step": mean per step of the window; the steps the records
              count must be the window's
              "window": share of the window's seconds
              "first": the first such record of the process
    scale     the unit: 1e3 for ms, 100 for %, 1 for s

A record of a call that did not deliver (`error`: it raised, or the feed's
source ended; `aborted`: the feed was stopped in its wait) is left out: it
is no step and no batch. A phase that no record holds reads 0. A window
without a single record, or with another count of steps than the runner
drove, is an error: a program that stopped recording must not read as a
program that got faster.

The one program that gives nothing to read is the parent of the PR that
brought the records (no `step_records`): the driver runs its traced cells
with this reader laid over it. The four metrics list no `workloads`, so a
traced run of any later program whose line lacks one of them is refused:
dropping the accessor cannot pass for a reading.
"""


def _records(names, since=None, until=None):
    from mxnet_tpu.telemetry import tracing
    get = getattr(tracing, "step_records", None)
    if get is None:
        return None
    found = [r for name in names for r in get(name, since, until)
             if "error" not in r and not r.get("aborted")]
    return sorted(found, key=lambda r: r["ts"])


def _seconds(record, add, subtract):
    def take(key):
        return record["dur"] if key == "dur" \
            else record["phases"].get(key, 0.0)
    return sum(map(take, add)) - sum(map(take, subtract))


def read(view, params):
    names, over, scale = params["records"], params["over"], params["scale"]
    add, subtract = params["add"], params.get("subtract", [])
    w = view.window
    span = () if over == "first" else (w["t0"], w["t0"] + w["seconds"])
    records = _records(names, *span)
    if records is None:
        return None
    if not records:
        raise RuntimeError(f"the program kept no record {names} "
                           f"{'at all' if over == 'first' else 'in the window'}")
    if over == "first":
        return scale * _seconds(records[0], add, subtract)
    total = sum(_seconds(r, add, subtract) for r in records)
    if over == "window":
        return scale * total / w["seconds"]
    steps = sum(r.get("steps", 1) for r in records)
    if steps != w["steps"]:
        raise RuntimeError(f"the records {names} of the window count {steps} "
                           f"steps, the runner drove {w['steps']}")
    if "dur" in add:
        inside = sum(r["dur"] for r in records)
        view.say(f"step records: {len(records)} in the window, "
                 f"{1e3 * inside / steps:.3f} ms a step inside the call, "
                 f"{1e3 * w['step_call_s'] / steps:.3f} ms round it")
    return scale * total / steps
