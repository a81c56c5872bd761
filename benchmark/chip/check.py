"""The comparison that decides `correct` for a training cell.

Both sides give the same readings of the first steps (reference/steps.py's
`follow` for the reference; runner.py reads the program's): a loss per step,
the norm of the first step's gradient per leaf as the optimizer got it, the
norm of each leaf's change over the steps. From them:

  loss<i>_gap        |program - reference| / |reference| of step i's loss
  grad_norm_gap      worst leaf: |program's norm - reference's| over the
                     reference's norm of that leaf or of the median leaf,
                     whichever is larger
  change_norm_gap    the same of the change, over the leaves whose reference
                     gradient is at least a thousandth of the median leaf's
                     (the others move by round-off alone)
  grad_total_gap     |program - reference| / reference of the norm of the
                     whole first gradient (all leaves together)
  change_total_gap   the same of the whole change
  feed_mismatch      elements of the first batches, read back from the device
                     as the step got them, that differ from the pool (exact)

A number is sound where it is at most its limit (limits/<workload>.json). A
number whose limit there is null has no upper reading in that cell (PERF.md
says which and why); it is shown and not compared.
"""
from __future__ import annotations

import json
import math
import statistics
import sys


def _worst_gap(prog, ref, leaves):
    med = statistics.median(ref[n] for n in leaves)
    worst, at = 0.0, None
    for n in leaves:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        if gap >= worst:
            worst, at = gap, n
    return worst, at


def numbers(prog, ref, feed_mismatch=0):
    """{name: value} of everything compared, and {name: leaf} of the worst
    leaves."""
    out, where = {}, {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        gap = abs(lp - lr) / abs(lr)
        out[f"loss{i + 1}_gap"] = gap if math.isfinite(gap) else math.inf
    leaves = list(ref["grad_norms"])
    out["grad_norm_gap"], where["grad_norm_gap"] = _worst_gap(
        prog["grad_norms"], ref["grad_norms"], leaves)
    floor = 1e-3 * statistics.median(ref["grad_norms"].values())
    moved = [n for n in leaves if ref["grad_norms"][n] >= floor]
    out["change_norm_gap"], where["change_norm_gap"] = _worst_gap(
        prog["change_norms"], ref["change_norms"], moved)
    for key, name in (("grad_norms", "grad_total_gap"),
                      ("change_norms", "change_total_gap")):
        p, r = (math.sqrt(sum(v * v for v in side[key].values()))
                for side in (prog, ref))
        gap = abs(p - r) / max(r, 1e-30)
        out[name] = gap if math.isfinite(gap) else math.inf
    out["feed_mismatch"] = feed_mismatch
    return out, where


def decide(nums, limits):
    """(correct, compared, shown): `compared` is {name: [value, limit]} of
    every number that has a limit, `shown` {name: value} of the others."""
    compared = {n: [v, limits[n]] for n, v in nums.items()
                if limits.get(n) is not None}
    shown = {n: v for n, v in nums.items() if n not in compared}
    missing = [n for n in limits if n not in nums]
    ok = all(v <= lim for v, lim in compared.values())
    return bool(compared) and ok and not missing, compared, shown


def report(compared, shown, where, correct, stream=None):
    """The numbers compared, each beside its limit, as the last lines of
    standard error (before them, those that are shown and not compared)."""
    stream = stream or sys.stderr
    for n, v in shown.items():
        print(f"not compared {n} = {v:.6g}", file=stream)
    for n, (v, lim) in compared.items():
        leaf = f" at {where[n]}" if where.get(n) else ""
        flag = "" if v <= lim else "  <-- over its limit"
        print(f"compared {n} = {v:.6g} limit {lim:g}{leaf}{flag}",
              file=stream)
    print(f"correct = {json.dumps(bool(correct))}", file=stream, flush=True)
