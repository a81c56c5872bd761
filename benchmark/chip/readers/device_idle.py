"""Idle share of the busiest device over the traced stretch: 1 - union of
the intervals in which an operation ran on it, over the stretch."""
import trace as trace_mod


def read(view, params):
    if not view.devices:
        return None
    d = view.devices[trace_mod.fullest(view.devices)]
    return 100.0 * (1.0 - d["busy_ns"] / d["window_ns"])
