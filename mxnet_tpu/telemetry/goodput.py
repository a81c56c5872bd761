"""Goodput ledger: per-step wall-clock waterfall attribution.

Decomposes every wall-clock second of a training run into named
categories — where the roofline ledger (roofline.py, arXiv:2301.13062
framing) says what each compiled region *achieved*, this plane says where
the run's *time went*:

    compute               wall not attributed to any badput category
                          (derived remainder; see the reconciliation rule)
    comm_exposed          collective wire traffic converted to seconds at
                          peak_bytes_per_second(), split per mesh axis
                          (telemetry.comm_bytes_by_axis)
    feed_stall            consumer waits on an empty DeviceFeed queue
                          (mx_feed_stall_seconds_total)
    dispatch_backpressure DispatchWindow admit()/drain() block time
    snapshot              snapshot wall seconds, dispatch to manifest
                          commit (mx_checkpoint_save_seconds_total)
    compile               engine trace+compile stamps (cache_stats)
    pipeline_bubble       analytic schedule bubble fraction x the step's
                          device-bound share (set_pipeline_bubble)
    restart_downtime      boot-to-resume wall after a restart (run-level,
                          not folded into any single step's waterfall)
    other                 the reconciliation residual: seconds the
                          independently-clocked categories double-counted
                          past measured wall (e.g. the background snapshot
                          writer overlapping compute)

Reconciliation rule (the roofline-FLOP discipline): for every step record

    compute + sum(badput categories) - other == wall     (exactly)

with all values >= 0. ``other`` therefore IS the attribution error bar;
the acceptance gate keeps it <= 5% of wall.

Zero new host syncs: every category is a *delta of cumulative host-side
stamps the layers already take* (feed stall totals, window wait totals,
snapshot-writer seconds, engine compile seconds, comm byte counters),
consumed once per recorded step at DispatchWindow-admission pace through
the one ``telemetry.record_step`` funnel. The disarmed path is a single
module-flag check (the telemetry._ENABLED idiom).

Each armed host appends fixed-schema NDJSON records to an on-disk
time-series ring (``<root>/telemetry/host-<rank>.tsr``, bounded by
MXNET_TPU_GOODPUT_RING_BYTES with one ``.old`` rotation segment,
fsync-free buffered appends) that survives the process. ``aggregate()``
rides the elastic coordinator's shared root to merge every host's series
into a generation-stamped run summary with straggler detection (per-host
median step time vs the fleet median, booked as
``mx_straggler_score{rank}`` and surfaced in /statusz + the flight
recorder on eviction). ``tools/goodput_report.py`` renders a merged run
offline; docs/observability.md ("Goodput waterfall") documents the
category definitions and the CLI workflow.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from ..base import MXNetError, env

__all__ = [
    "CATEGORIES", "enable", "disable", "is_enabled", "reset", "note_step",
    "set_generation", "set_pipeline_bubble", "record_restart_downtime",
    "on_eviction", "totals", "goodput_ratio", "report", "dump_json",
    "aggregate", "statusz_view", "ring_path",
]

env.declare("MXNET_TPU_GOODPUT", False, bool,
            "Arm the goodput waterfall ledger at import (implies telemetry)")
env.declare("MXNET_TPU_GOODPUT_RING_BYTES", 8 << 20, int,
            "On-disk time-series ring size per segment; the ring keeps the "
            "active segment plus one rotated .old segment")
env.declare("MXNET_TPU_STRAGGLER_SKEW", 1.75, float,
            "Straggler threshold: a host whose median step time exceeds "
            "skew x the fleet median is flagged")

# badput categories in attribution order; compute and other are derived
BADPUT = ("restart_downtime", "feed_stall", "dispatch_backpressure",
          "snapshot", "compile", "comm_exposed", "pipeline_bubble")
CATEGORIES = ("compute",) + BADPUT + ("other",)

_SCHEMA = 1

# process-boot anchor for restart-downtime accounting (module import is
# the earliest stamp available without patching the interpreter)
_PROCESS_T0 = time.perf_counter()

_LOCK = threading.RLock()

# the one flag every instrumentation site checks (telemetry._ENABLED idiom)
_ENABLED = False


class _Ring:
    """Bounded fsync-free NDJSON appender: active segment + one ``.old``
    rotation, meta header line per segment (the flight-recorder dump
    convention), so a reader can re-anchor perf-counter timestamps."""

    def __init__(self, path: str, max_bytes: int, meta: Dict[str, Any]):
        self.path = path
        self.max_bytes = int(max_bytes)
        self.meta = meta
        self._f = None
        self._n = 0

    def _open(self):
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        self._f = open(self.path, "a")
        self._n = self._f.tell()
        if self._n == 0:
            line = json.dumps({"k": "meta", **self.meta},
                              separators=(",", ":"))
            self._f.write(line + "\n")
            self._n += len(line) + 1

    def append(self, rec: Dict[str, Any]):
        if self._f is None:
            self._open()
        elif self._n >= self.max_bytes:
            # rotate: the previous segment survives as .old — a bounded
            # ring of two segments, never an unbounded log
            self._f.close()
            os.replace(self.path, self.path + ".old")
            self._f = None
            self._open()
        line = json.dumps(rec, separators=(",", ":"))
        self._f.write(line + "\n")
        # flush to the OS (crash-of-process safe) but never fsync: the
        # ledger must not put a disk barrier on the step path
        self._f.flush()
        self._n += len(line) + 1

    def close(self):
        if self._f is not None:
            try:
                self._f.close()
            except OSError:
                pass
            self._f = None


class _Ledger:
    def __init__(self):
        self.rank = 0
        self.generation = 0
        self.steps = 0
        self.wall = 0.0
        self.totals = {c: 0.0 for c in CATEGORIES}
        self.comm_axes: Dict[str, float] = {}
        self.per_source: Dict[str, Dict[str, Any]] = {}
        self.bubble_fraction: Dict[str, float] = {}
        # cumulative upstream stamps at the last recorded step (None until
        # the first record anchors them — the record_step anchor idiom)
        self.last: Optional[Dict[str, Any]] = None
        self.last_dispatch: Dict[str, float] = {}
        self.note_anchor: Dict[str, float] = {}
        self.pending_restart = 0.0
        self.straggler: Dict[str, float] = {}
        self.ring: Optional[_Ring] = None


_L = _Ledger()


def _telem():
    from .. import telemetry as _t
    return _t


# ---------------------------------------------------------------------------
# Arming
# ---------------------------------------------------------------------------

def _resolve_rank(rank: Optional[int]) -> int:
    if rank is not None:
        return int(rank)
    v = os.environ.get("MXNET_TPU_RANK")
    if v:
        try:
            return int(v)
        except ValueError:
            pass
    # consult jax only if something else already imported it — a pure
    # host-side process (drill child) never pays the import for a label
    jx = sys.modules.get("jax")
    if jx is not None:
        try:
            return int(jx.process_index())
        except Exception:
            pass
    return 0


def enable(root: Optional[str] = None, rank: Optional[int] = None,
           ring_bytes: Optional[int] = None):
    """Arm the ledger (arms telemetry too — every category is a delta of
    telemetry stamps). With ``root`` (the elastic coordinator's shared
    root) per-step records append to ``<root>/telemetry/host-<rank>.tsr``;
    without it the ledger is in-memory only."""
    global _ENABLED
    t = _telem()
    t.enable()
    with _LOCK:
        _L.rank = _resolve_rank(rank)
        if root is not None:
            path = os.path.join(os.path.abspath(root), "telemetry",
                                f"host-{_L.rank}.tsr")
            meta = {"schema": _SCHEMA, "rank": _L.rank, "pid": os.getpid(),
                    "generation": _L.generation, "wall_time": time.time(),
                    "perf": time.perf_counter()}
            nbytes = int(env.get("MXNET_TPU_GOODPUT_RING_BYTES")
                         if ring_bytes is None else ring_bytes)
            if _L.ring is not None:
                _L.ring.close()
            _L.ring = _Ring(path, nbytes, meta)
        _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False
    with _LOCK:
        if _L.ring is not None:
            _L.ring.close()
        # re-arm re-anchors: stamps that accumulated while disarmed must
        # never be attributed to the first step after re-enable()
        _L.last = None
        _L.last_dispatch.clear()


def is_enabled() -> bool:
    return _ENABLED


def reset():
    global _L, _ENABLED
    with _LOCK:
        if _L.ring is not None:
            _L.ring.close()
        _L = _Ledger()
        _ENABLED = False


def ring_path() -> Optional[str]:
    with _LOCK:
        return _L.ring.path if _L.ring is not None else None


# ---------------------------------------------------------------------------
# Category sources (cumulative upstream stamps; all host-side)
# ---------------------------------------------------------------------------

def _fam_sum(t, name: str) -> float:
    fam = t.get_metric(name)
    return float(fam.get()) if fam is not None else 0.0


def _compile_seconds() -> float:
    try:
        from .. import engine as _engine
        return float(_engine.cache_stats().get("compile_seconds", 0.0))
    except Exception:
        return 0.0


def _snapshot_upstream(t) -> Dict[str, Any]:
    return {
        "feed_stall": _fam_sum(t, "mx_feed_stall_seconds_total"),
        "dispatch": _fam_sum(t, "mx_dispatch_wait_seconds_total"),
        "snapshot": _fam_sum(t, "mx_checkpoint_save_seconds_total"),
        "compile": _compile_seconds(),
        # the exposed-comm numerator: no step body hides a collective
        # behind compute by construction, so every byte booked counts
        "comm": {ax or "none": v
                 for ax, v in t.comm_bytes_by_axis().items()},
    }


# ---------------------------------------------------------------------------
# Recording (the hot path: called from telemetry.record_step)
# ---------------------------------------------------------------------------

def set_generation(generation: int):
    """Stamp subsequent records with the coordinator's group generation
    (called from Coordinator.join/view when armed)."""
    with _LOCK:
        _L.generation = generation


def set_pipeline_bubble(source: str, fraction: float):
    """Register the analytic schedule-bubble fraction for ``source`` —
    (idle ticks / total ticks) from the 1F1B/GPipe tick counts; the ledger
    multiplies it into the step's device-bound share (the measured tick
    slope), never into feed/snapshot time."""
    with _LOCK:
        _L.bubble_fraction[source] = min(max(fraction, 0.0), 1.0)


def record_restart_downtime(outcome: str, seconds: Optional[float] = None):
    """Book boot-to-resume wall time after a restart (called from
    elastic.run.resume_or_init for resumed/resharded outcomes). Run-level:
    appended to the ring and the totals, never folded into one step's
    waterfall (it would swamp that step and read as overattribution)."""
    if not _ENABLED:
        return
    if seconds is None:
        seconds = time.perf_counter() - _PROCESS_T0
    seconds = max(float(seconds), 0.0)
    with _LOCK:
        _L.totals["restart_downtime"] += seconds
        if _L.ring is not None:
            try:
                _L.ring.append({"k": "restart", "t": round(
                    time.perf_counter(), 6), "outcome": outcome,
                    "seconds": round(seconds, 6), "gen": _L.generation})
            except OSError:
                pass
    t = _telem()
    t.counter("mx_goodput_seconds_total",
              "Wall seconds attributed by the goodput waterfall ledger",
              ("category",)).labels("restart_downtime").inc(seconds)


def note_step(source: str = "step", seconds: Optional[float] = None,
              steps: int = 1):
    """Self-anchored per-step recording for loops that do not go through
    telemetry.record_step (the drill's toy trainer): the first call only
    anchors the clock, like record_step."""
    if not _ENABLED:
        return
    now = time.perf_counter()
    with _LOCK:
        prev = _L.note_anchor.get(source)
        _L.note_anchor[source] = now
    if seconds is None:
        if prev is None:
            return
        seconds = now - prev
    _on_step(source, seconds, steps)


def _on_step(source: str, seconds: float, steps: int = 1,
             dispatch_wait: Optional[float] = None):
    """The per-step funnel (telemetry.record_step calls this when armed):
    attribute ``seconds`` of wall across the categories from deltas of
    the cumulative stamps the layers already took. Host arithmetic only —
    no device access, no clock reads beyond record_step's own."""
    t = _telem()
    wall = max(seconds, 0.0)
    cur = _snapshot_upstream(t)
    with _LOCK:
        prev, _L.last = _L.last, cur
        cats = {c: 0.0 for c in BADPUT}
        axes: Dict[str, float] = {}
        if prev is not None:
            cats["feed_stall"] = max(
                cur["feed_stall"] - prev["feed_stall"], 0.0)
            cats["snapshot"] = max(cur["snapshot"] - prev["snapshot"], 0.0)
            cats["compile"] = max(cur["compile"] - prev["compile"], 0.0)
            if dispatch_wait is not None:
                # precise per-source window wait handed down by the trainer
                last = _L.last_dispatch.get(source)
                _L.last_dispatch[source] = dispatch_wait
                if last is not None:
                    cats["dispatch_backpressure"] = max(
                        dispatch_wait - last, 0.0)
            else:
                cats["dispatch_backpressure"] = max(
                    cur["dispatch"] - prev["dispatch"], 0.0)
            bw = t.peak_bytes_per_second()
            for ax, nbytes in cur["comm"].items():
                d = nbytes - prev["comm"].get(ax, 0.0)
                if d > 0 and bw > 0:
                    axes[ax] = d / bw
            cats["comm_exposed"] = sum(axes.values())
        frac = _L.bubble_fraction.get(source, 0.0)
        if frac > 0.0:
            # the bubble lives inside the device-bound share of the step
            # (wall minus host-side stalls), per the analytic fraction
            device_share = max(wall - cats["feed_stall"] - cats["snapshot"]
                               - cats["compile"], 0.0)
            cats["pipeline_bubble"] = frac * device_share
        badput = sum(cats.values())
        compute = max(wall - badput, 0.0)
        other = max(badput - wall, 0.0)   # the double-count residual
        booked = dict(cats)
        booked["compute"] = compute
        booked["other"] = other
        _L.steps += steps
        _L.wall += wall
        for c, v in booked.items():
            _L.totals[c] += v
        for ax, v in axes.items():
            _L.comm_axes[ax] = _L.comm_axes.get(ax, 0.0) + v
        src = _L.per_source.setdefault(
            source, {"steps": 0, "wall": 0.0, "walls": []})
        src["steps"] += steps
        src["wall"] += wall
        w = src["walls"]
        w.append(wall / max(steps, 1))
        if len(w) > 4096:
            del w[:len(w) - 4096]
        total_wall, total_compute = _L.wall, _L.totals["compute"]
        gen = _L.generation
        ring = _L.ring
        if ring is not None:
            rec = {"k": "step", "t": round(time.perf_counter(), 6),
                   "step": _L.steps, "src": source, "n": steps,
                   "wall": round(wall, 9), "gen": gen,
                   "c": {c: round(v, 9) for c, v in booked.items() if v}}
            if axes:
                rec["ax"] = {a: round(v, 9) for a, v in axes.items()}
            try:
                ring.append(rec)
            except OSError:
                pass
    c = t.counter("mx_goodput_seconds_total",
                  "Wall seconds attributed by the goodput waterfall ledger",
                  ("category",))
    for cat, v in booked.items():
        if v > 0.0:
            c.labels(cat).inc(v)
    if total_wall > 0.0:
        t.gauge("mx_goodput_ratio",
                "Goodput fraction: compute seconds / wall seconds over "
                "every recorded step").set(total_compute / total_wall)


# ---------------------------------------------------------------------------
# Local views
# ---------------------------------------------------------------------------

def totals() -> Dict[str, Any]:
    """This process's cumulative waterfall: per-category seconds, wall,
    steps, per-axis exposed comm, goodput ratio."""
    with _LOCK:
        return {
            "steps": _L.steps, "wall_seconds": _L.wall,
            "generation": _L.generation, "rank": _L.rank,
            "categories": dict(_L.totals),
            "comm_exposed_axes": dict(_L.comm_axes),
            "goodput_ratio": (_L.totals["compute"] / _L.wall)
            if _L.wall > 0 else 0.0,
        }


def goodput_ratio() -> float:
    with _LOCK:
        return (_L.totals["compute"] / _L.wall) if _L.wall > 0 else 0.0


def _render_waterfall(cats: Dict[str, float], wall: float,
                      axes: Optional[Dict[str, float]] = None) -> List[str]:
    lines = []
    width = max(len(c) for c in CATEGORIES)
    for c in CATEGORIES:
        v = cats.get(c, 0.0)
        pct = 100.0 * v / wall if wall > 0 else 0.0
        bar = "#" * int(round(pct / 2))
        note = "  (overattribution residual)" if c == "other" and v else ""
        lines.append(f"  {c:<{width}}  {v:>10.4f}s  {pct:>5.1f}%  "
                     f"{bar}{note}")
        if c == "comm_exposed" and axes:
            for ax in sorted(axes):
                lines.append(f"  {'  axis=' + ax:<{width}}  "
                             f"{axes[ax]:>10.4f}s")
    return lines


def report(summary: Optional[Dict[str, Any]] = None) -> str:
    """Human waterfall table + goodput fraction. With no ``summary``
    renders this process's ledger; pass an ``aggregate()`` result to
    render a merged fleet run."""
    if summary is None:
        d = totals()
        lines = [f"=== goodput waterfall (rank {d['rank']}, "
                 f"{d['steps']} steps, {d['wall_seconds']:.3f}s wall, "
                 f"generation {d['generation']}) ==="]
        lines += _render_waterfall(d["categories"], d["wall_seconds"],
                                   d["comm_exposed_axes"])
        lines.append(f"  goodput fraction: {d['goodput_ratio']:.3f}")
        return "\n".join(lines)
    fleet = summary.get("fleet", {})
    wall = fleet.get("wall_seconds", 0.0)
    lines = [f"=== goodput waterfall (fleet: {len(summary.get('hosts', {}))}"
             f" hosts, {fleet.get('steps', 0)} steps, {wall:.3f}s wall, "
             f"generation {summary.get('generation', 0)}) ==="]
    lines += _render_waterfall(fleet.get("categories", {}), wall,
                               fleet.get("comm_exposed_axes"))
    lines.append(f"  goodput fraction: {fleet.get('goodput_ratio', 0.0):.3f}")
    strag = summary.get("straggler", {})
    if strag.get("scores"):
        lines.append("  straggler scores (median step / fleet median):")
        for rank in sorted(strag["scores"], key=int):
            flag = "  <-- STRAGGLER" \
                if int(rank) in strag.get("flagged", []) else ""
            lines.append(f"    rank {rank}: "
                         f"{strag['scores'][rank]:.2f}x{flag}")
    return "\n".join(lines)


def dump_json(path: Optional[str] = None, indent: Optional[int] = None) \
        -> str:
    """This process's ledger totals as JSON; optionally written to
    ``path`` (atomic rename)."""
    body = json.dumps(totals(), indent=indent, sort_keys=True)
    if path is not None:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(body)
        os.replace(tmp, path)
    return body


def statusz_view() -> Dict[str, Any]:
    """The /statusz section (telemetry.statusz merges it)."""
    if not _ENABLED:
        return {"enabled": False}
    d = totals()
    d["enabled"] = True
    with _LOCK:
        if _L.straggler:
            d["straggler_scores"] = dict(_L.straggler)
        if _L.ring is not None:
            d["ring"] = _L.ring.path
    return d


# ---------------------------------------------------------------------------
# Fleet aggregation + straggler detection
# ---------------------------------------------------------------------------

def _median(xs: List[float]) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _read_series(path: str) -> List[Dict[str, Any]]:
    recs: List[Dict[str, Any]] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue        # torn tail line of a killed host
    except OSError:
        pass
    return recs


def aggregate(root: str, book_metrics: bool = True) -> Dict[str, Any]:
    """Merge every host's on-disk series under ``<root>/telemetry/`` into
    a generation-stamped run summary with straggler scores.

    A host evicted mid-run leaves a partial series (possibly with a torn
    final line) — it still merges; its records carry the generation they
    were written under, so the summary has no hole. Straggler score =
    host median per-step wall / fleet median of those medians; hosts past
    MXNET_TPU_STRAGGLER_SKEW are flagged. With ``book_metrics`` (and
    telemetry armed) scores land on ``mx_straggler_score{rank}``."""
    tdir = os.path.join(os.path.abspath(root), "telemetry")
    hosts: Dict[int, Dict[str, Any]] = {}
    try:
        names = sorted(os.listdir(tdir))
    except OSError:
        names = []
    for name in names:
        if not name.startswith("host-") or ".tsr" not in name:
            continue
        try:
            rank = int(name.split("-", 1)[1].split(".")[0])
        except ValueError:
            continue
        h = hosts.setdefault(rank, {
            "rank": rank, "steps": 0, "wall_seconds": 0.0,
            "categories": {c: 0.0 for c in CATEGORIES},
            "comm_exposed_axes": {}, "walls": [],
            "generations": [], "restarts": 0})
        # both the active segment and its .old rotation merge into the
        # same per-rank bucket; the summary is order-insensitive (sums,
        # medians, max-generation), so segment read order is immaterial
        for rec in _read_series(os.path.join(tdir, name)):
            k = rec.get("k")
            if k == "step":
                h["steps"] += int(rec.get("n", 1))
                w = float(rec.get("wall", 0.0))
                h["wall_seconds"] += w
                h["walls"].append(w / max(int(rec.get("n", 1)), 1))
                for c, v in rec.get("c", {}).items():
                    if c in h["categories"]:
                        h["categories"][c] += float(v)
                for ax, v in rec.get("ax", {}).items():
                    h["comm_exposed_axes"][ax] = \
                        h["comm_exposed_axes"].get(ax, 0.0) + float(v)
                h["generations"].append(int(rec.get("gen", 0)))
            elif k == "restart":
                h["restarts"] += 1
                h["categories"]["restart_downtime"] += \
                    float(rec.get("seconds", 0.0))
            elif k == "meta":
                h.setdefault("meta", rec)
    fleet = {"steps": 0, "wall_seconds": 0.0,
             "categories": {c: 0.0 for c in CATEGORIES},
             "comm_exposed_axes": {}}
    medians: Dict[int, float] = {}
    for rank, h in sorted(hosts.items()):
        fleet["steps"] += h["steps"]
        fleet["wall_seconds"] += h["wall_seconds"]
        for c, v in h["categories"].items():
            fleet["categories"][c] += v
        for ax, v in h["comm_exposed_axes"].items():
            fleet["comm_exposed_axes"][ax] = \
                fleet["comm_exposed_axes"].get(ax, 0.0) + v
        medians[rank] = h["median_step_seconds"] = _median(h["walls"])
        gens = h.pop("generations", [])
        h["generation_range"] = [min(gens), max(gens)] if gens else [0, 0]
        h.pop("walls", None)
    fleet["goodput_ratio"] = (fleet["categories"]["compute"]
                              / fleet["wall_seconds"]) \
        if fleet["wall_seconds"] > 0 else 0.0
    fleet_median = _median([m for m in medians.values() if m > 0])
    skew = float(env.get("MXNET_TPU_STRAGGLER_SKEW"))
    scores = {str(r): (m / fleet_median if fleet_median > 0 else 0.0)
              for r, m in medians.items()}
    flagged = [r for r, m in medians.items()
               if fleet_median > 0 and m / fleet_median >= skew]
    # the run's current coordinator generation, when the shared root has
    # a control plane next to the telemetry dir
    generation = max((h["generation_range"][1] for h in hosts.values()),
                     default=0)
    try:
        with open(os.path.join(os.path.abspath(root), "coord",
                               "generation.json")) as f:
            generation = max(generation,
                             int(json.load(f).get("generation", 0)))
    except (OSError, ValueError):
        pass
    summary = {
        "schema": _SCHEMA, "generation": generation, "hosts": hosts,
        "fleet": fleet,
        "straggler": {"scores": scores, "flagged": sorted(flagged),
                      "fleet_median_step_seconds": fleet_median,
                      "skew_threshold": skew},
    }
    if book_metrics:
        t = _telem()
        if t._ENABLED:
            g = t.gauge("mx_straggler_score",
                        "Per-host median step time relative to the fleet "
                        "median (goodput.aggregate)", ("rank",))
            for r, sc in scores.items():
                g.labels(r).set(sc)
        with _LOCK:
            _L.straggler = dict(scores)
    return summary


def on_eviction(ranks: List[int], root: Optional[str] = None):
    """Surface straggler evidence when the coordinator evicts hosts: score
    the fleet from the on-disk series and drop an event into the flight
    recorder, so a post-mortem dump says whether the dead peer was the
    slow one. Incident-path only (never per step); failures are absorbed."""
    if not _ENABLED:
        return
    scores: Dict[str, float] = {}
    try:
        if root is not None:
            scores = aggregate(root)["straggler"]["scores"]
    except Exception:
        scores = {}
    from . import tracing as _tracing
    if _tracing._ENABLED:
        _tracing.event("mx.goodput.eviction",
                       ranks=[int(r) for r in ranks],
                       scores={r: round(s, 3) for r, s in scores.items()})


if env.get("MXNET_TPU_GOODPUT"):
    enable(rank=None)
