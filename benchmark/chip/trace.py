"""From a profiler trace (`.xplane.pb`) to numbers.

`load` turns the file into plain lists, `reduce` those into what the
per-layer readers read. Kept apart so that the arithmetic is tested on a
small recorded trace (tests/chip_benchmark/fixtures) without a chip.

What a TPU trace holds (jax 0.9.0, libtpu 0.0.34; looked at by hand, PR 24):
one plane `/device:TPU:<n>` per chip. Its line `XLA Ops` has one event per
executed HLO instruction, named by the instruction's whole text in the
optimized HLO (`%fusion.84 = (bf16[256]..) fusion(..), kind=kOutput,
calls=..`); no event carries an `hlo_category`, so the category is read
from that text: the opcode, for a fusion its kind, for a custom call its
target (`fusion:kOutput`, `fusion:kLoop`, `copy`, `all-reduce`,
`custom-call:tpu_custom_call`). On the TPU a convolution or matrix product
is the root of a `kOutput` fusion, with the elementwise work fused into it;
the trace cannot split such a fusion. Events of that line do not nest or
overlap. The line `XLA Modules` has one event per executed program
(`jit_step(..)`), `Async XLA Ops` the copies and collectives in flight, and
there is a line `Steps`. Host threads are lines of the plane `/host:CPU`;
`jax.profiler.TraceAnnotation`s appear there under their own names.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text):
    """`%fusion.84` of `%fusion.84 = (...) fusion(...), kind=kOutput`."""
    return text.split(" = ", 1)[0].lstrip("%")


def category(text):
    """The category of a device event, from its HLO text: the opcode, with a
    fusion's kind or a custom call's target (see the module's docstring)."""
    m = _OPCODE.search(text)
    if not m:
        return ""
    op = m.group(1)
    extra = _KIND.search(text) if op == "fusion" else \
        _TARGET.search(text) if op == "custom-call" else None
    return f"{op}:{extra.group(1)}" if extra else op


def load(path_or_data, host_prefix="bench."):
    """{"devices": {plane: {"ops": [(name, category, start_ns, dur_ns)],
    "modules": [(name, start_ns, dur_ns)]}}, "host": [(name, start_ns,
    dur_ns)]} with host events limited to names that start with
    `host_prefix`."""
    from jax.profiler import ProfileData
    data = path_or_data if not isinstance(path_or_data, (str, os.PathLike)) \
        else ProfileData.from_file(str(path_or_data))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [(short_name(e.name), category(e.name),
                                   e.start_ns, e.duration_ns)
                                  for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith(host_prefix)]
    out["host"].sort(key=lambda e: e[1])
    return out


def union_ns(intervals):
    """Total length of the union of (start, duration) intervals, and the
    merged intervals as (start, end)."""
    merged = []
    for s, d in sorted(intervals):
        e = s + d
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _overlap_ns(merged, start, end):
    return sum(max(0, min(e, end) - max(s, start)) for s, e in merged)


def matches(name, category, categories=(), name_has=()):
    """Whether a device event belongs to a group: its category is one of
    `categories`, or its name holds one of `name_has`."""
    return category in categories or any(p in name for p in name_has)


def reduce(loaded):
    """Per device: busy time (union of its op events), the traced window
    (the span of the device's program events, or of its ops: first start to
    last end), the time by category and by op name, the idle gaps."""
    devices = {}
    for plane, dev in loaded["devices"].items():
        ops = dev["ops"]
        if not ops:
            continue
        spans = dev["modules"] or [(n, s, d) for n, _, s, d in ops]
        w0 = min(s for _, s, _ in spans)
        w1 = max(s + d for _, s, d in spans)
        busy, merged = union_ns((s, d) for _, _, s, d in ops)
        by_cat, by_name = {}, {}
        for name, cat, _, d in ops:
            by_cat[cat] = by_cat.get(cat, 0) + d
            by_name[name] = by_name.get(name, 0) + d
        gaps, prev = [], w0
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if w1 > prev:
            gaps.append((prev, w1))
        devices[plane] = {
            "busy_ns": busy, "window_ns": w1 - w0,
            "op_ns": sum(d for _, _, _, d in ops), "by_category": by_cat,
            "by_name": by_name, "gaps": gaps,
            "programs": len(dev["modules"])}
    return devices


def group_ns(loaded, plane, categories=(), name_has=()):
    """Summed device time of one group's events on one device."""
    return sum(d for n, c, _, d in loaded["devices"][plane]["ops"]
               if matches(n, c, categories, name_has))


def exposed_ns(loaded, plane, categories=(), name_has=()):
    """Time of one group's events (collectives) on a device during which no
    other event runs there."""
    ops = loaded["devices"][plane]["ops"]
    mine = [(s, d) for n, c, s, d in ops
            if matches(n, c, categories, name_has)]
    _, others = union_ns((s, d) for n, c, s, d in ops
                         if not matches(n, c, categories, name_has))
    total, merged_mine = union_ns(mine)
    return total - sum(_overlap_ns(others, s, e) for s, e in merged_mine)


def fullest(devices):
    """The busiest device's name: the one a share is reported of."""
    return max(devices, key=lambda p: devices[p]["busy_ns"])


def breakdown(loaded, devices, top=10):
    """{"device_ops": [[name, seconds]...], "idle_gaps": [[what the host was
    doing, seconds]...]}: the ops that took most device time on the busiest
    device, and its idle time summed by the benchmark's host span that
    covers most of each gap (nested spans: the innermost that began last
    wins a tie only by covering more)."""
    if not devices:
        return None
    plane = fullest(devices)
    dev = devices[plane]
    cats = {n: c for n, c, _, _ in loaded["devices"][plane]["ops"]}
    ops = [(f"{n} [{cats[n]}]", d) for n, d in
           sorted(dev["by_name"].items(), key=lambda kv: -kv[1])[:top]]
    host = loaded["host"]
    starts = [h[1] for h in host]
    idle = {}
    for s, e in dev["gaps"]:
        # the host span that covers most of the gap (spans are sorted by
        # start; look at those that begin before the gap ends)
        best, cover = "no_host_span", 0
        for name, hs, hd in host[max(0, bisect.bisect_left(starts, s) - 8):
                                 bisect.bisect_right(starts, e)]:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > cover:
                best, cover = name, ov
        idle[best] = idle.get(best, 0) + (e - s)
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, d / 1e9] for n, d in ops],
            "idle_gaps": [[n, d / 1e9] for n, d in gaps]}
