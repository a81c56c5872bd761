"""Shared benchmark timing helpers.

The first calls of a freshly compiled executable are not steady state
(compilation, constant upload, allocator growth), so a fixed "one warm
call" can still measure set-up. `measure_stabilized` requires two
consecutive timings to agree within a symmetric window before measuring — a
one-sided rule (cur > 0.6 * prev) can stop while timings are still falling —
and reports the MINIMUM of several measured reps so a one-off host stall
cannot become the recorded result.
"""
from __future__ import annotations

import os


def measure_stabilized(timed_fn, max_warm: int = 10, ratio: float = 0.92,
                       measure: int = 3):
    """timed_fn() -> seconds for one full measured unit (must sync).
    First call may include compilation. Warms until two consecutive
    timings agree within the symmetric window (each > ratio * other),
    bounded by max_warm; then returns min over `measure` reps."""
    max_warm = int(os.environ.get("BENCH_MAX_WARM", max_warm))
    measure = max(int(os.environ.get("BENCH_MEASURE", measure)), 1)
    prev = timed_fn()
    for _ in range(max_warm):
        cur = timed_fn()
        if cur > ratio * prev and prev > ratio * cur:
            break
        prev = cur
    return min(timed_fn() for _ in range(measure))
