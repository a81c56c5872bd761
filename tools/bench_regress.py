#!/usr/bin/env python3
"""Bench-regression gate: compare the newest two BENCH_r*.json rounds.

Each bench round (driver-written ``BENCH_r<NN>.json`` at the repo root)
records ``{"n", "cmd", "rc", "tail", "parsed"}`` where ``parsed`` is
bench.py's headline metric plus an ``extra`` map of per-scenario numeric
results. This gate diffs the newest two usable rounds (rc == 0, non-empty
parsed), flags any per-scenario movement in the BAD direction beyond a
noise threshold, and exits nonzero — the CI hook BENCHMARKS.md's
"Regression gate" section documents.

Direction is inferred per key: throughput-style values (img_s, tokens_s,
tflops, mfu, anything with a "/s" unit) regress when they DROP;
time/overhead-style values (*seconds*, *_ms, *overhead*, *pct*) regress
when they RISE. Keys with no inferable direction are reported as
informational only.

    python tools/bench_regress.py                  # gate the repo root
    python tools/bench_regress.py --threshold 5    # tighter noise bound
    python tools/bench_regress.py --dir /some/dir  # e.g. the self-test

Exit codes: 0 clean (or fewer than two usable rounds), 1 regression(s)
flagged, 2 usage/IO errors.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

_ROUND = re.compile(r"^BENCH_r(\d+)\.json$")

# key-name direction table (checked on the leaf key, lowercased)
_HIGHER_BETTER = re.compile(r"(^|_)(img_s|tokens_s|tflops|mfu|value|"
                            r"examples_s|steps_s|throughput)($|_vs)")
_LOWER_BETTER = re.compile(r"(seconds|_ms$|overhead|_pct$|pct_|latency|"
                           r"stall|bubble)")


def _direction(key: str, unit: str = "") -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 unknown."""
    k = key.lower()
    if _LOWER_BETTER.search(k):
        return -1
    if _HIGHER_BETTER.search(k) or "/s" in unit:
        return 1
    return 0


def load_rounds(directory: Path):
    """Usable rounds sorted by round number: [(n, parsed), ...]."""
    rounds = []
    for p in sorted(directory.iterdir()):
        m = _ROUND.match(p.name)
        if not m:
            continue
        try:
            with open(p) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = rec.get("parsed")
        if rec.get("rc", 1) != 0 or not isinstance(parsed, dict) \
                or not parsed:
            continue
        rounds.append((int(m.group(1)), parsed))
    rounds.sort()
    return rounds


def _leaves(parsed):
    """{(scenario, key): (value, unit)} over the headline metric and every
    numeric leaf under parsed["extra"]."""
    out = {}
    unit = str(parsed.get("unit", ""))
    if isinstance(parsed.get("value"), (int, float)):
        scen = str(parsed.get("metric", "headline"))
        out[(scen, "value")] = (float(parsed["value"]), unit)
    for scen, block in (parsed.get("extra") or {}).items():
        if not isinstance(block, dict):
            continue
        for k, v in block.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[(str(scen), str(k))] = (float(v), "")
    return out


def compare(old, new, threshold_pct: float):
    """Diff two parsed rounds; returns (regressions, improvements, infos)
    as lists of dicts."""
    a, b = _leaves(old), _leaves(new)
    regressions, improvements, infos = [], [], []
    for key in sorted(set(a) & set(b)):
        (va, unit), (vb, _) = a[key], b[key]
        if va == 0:
            continue
        delta_pct = 100.0 * (vb - va) / abs(va)
        d = _direction(key[1], unit)
        row = {"scenario": key[0], "key": key[1], "old": va, "new": vb,
               "delta_pct": delta_pct}
        if d == 0:
            infos.append(row)
        elif d * delta_pct < -threshold_pct:
            regressions.append(row)
        elif d * delta_pct > threshold_pct:
            improvements.append(row)
    return regressions, improvements, infos


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="flag per-scenario regressions between the newest "
                    "two bench rounds")
    ap.add_argument("--dir", default=str(Path(__file__).resolve()
                                         .parent.parent),
                    help="directory holding BENCH_r*.json (default: repo "
                         "root)")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="noise threshold in percent (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    args = ap.parse_args(argv)

    directory = Path(args.dir)
    if not directory.is_dir():
        print(f"not a directory: {directory}", file=sys.stderr)
        return 2
    rounds = load_rounds(directory)
    if len(rounds) < 2:
        print(f"{len(rounds)} usable bench round(s) under {directory}: "
              "nothing to compare")
        return 0
    (n_old, old), (n_new, new) = rounds[-2], rounds[-1]
    regressions, improvements, infos = compare(old, new, args.threshold)
    if args.json:
        print(json.dumps({
            "old_round": n_old, "new_round": n_new,
            "threshold_pct": args.threshold, "regressions": regressions,
            "improvements": improvements, "informational": infos,
        }, indent=2, sort_keys=True))
    else:
        print(f"bench rounds r{n_old:02d} -> r{n_new:02d} "
              f"(threshold {args.threshold:g}%)")
        for row in regressions:
            print(f"  REGRESSION  {row['scenario']}.{row['key']}: "
                  f"{row['old']:g} -> {row['new']:g} "
                  f"({row['delta_pct']:+.1f}%)")
        for row in improvements:
            print(f"  improved    {row['scenario']}.{row['key']}: "
                  f"{row['old']:g} -> {row['new']:g} "
                  f"({row['delta_pct']:+.1f}%)")
        if not regressions and not improvements:
            print(f"  no movement beyond {args.threshold:g}% across "
                  f"{len(infos) + len(improvements)} compared values")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
