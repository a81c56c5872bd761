"""Cross-layer span tracing and the black-box flight recorder.

The metrics registry (mx.telemetry) and the roofline ledger answer *how
much*; this module answers *which request*, *which step* and *which phase
of it*. One primitive, :func:`span`, marks a region of host code:

- it always enters a ``jax.profiler.TraceAnnotation`` of the same name, with
  no flag in front. Without a profiler session that is an atomic load; under
  any session (``jax.profiler.start_trace``, ``telemetry.trace_steps``, the
  chip benchmark's ``--trace 1``) the span lands on the host plane of the
  same xplane as the device's ``XLA Ops``, on that file's clock, nested
  under whatever called it. Host spans and device gaps share one axis by
  construction;
- armed (``MXNET_TPU_TRACING`` / :func:`enable`) it also records the span,
  with process-unique trace/span ids and its parent link, into one bounded
  ring buffer, next to **events** (instants: fault firings, io retries,
  anomalies).

:func:`phased` is the same primitive for a call that is cut into contiguous
phases (the fused trainer's ``step``, the feed's producer): every phase is a
child span, and the call appends one small **record** (kind ``"step"``,
``"batch"`` or ``"setup"``: start, duration, seconds by phase) to the ring
whether tracing is armed or not: one ``perf_counter`` read at each phase
boundary and one GIL-atomic append a call. A fourth kind, ``"build"``, is
appended by the engine's jax.monitoring listener, one record for every
program the process traces, lowers, compiles or loads
(``engine._BuildListener``); :func:`parent_of` finds the call a build ran
under. :func:`step_records` returns the records of every kind in
:data:`RECORD_KINDS`; :func:`spans` returns spans and events only.

The ring doubles as a black-box flight recorder. Armed, on preemption
(``elastic.run``) or an unhandled exception (``sys.excepthook``/
``threading.excepthook`` chain installed by :func:`enable`) the last N
entries are dumped as NDJSON so the moments *before* a crash survive it.
Every automatic dump is tied to arming: a default job that crashes leaves no
file. The ring holds its last steps and batches all the same, and what reads
them at the default is :func:`step_records`, ``/statusz`` (:func:`recent`)
and a :func:`dump_flight_recorder` that the job calls itself.

Export surfaces:

- the profiler's xplane (above): the only surface on the device's clock.
- :func:`dump_chrome_trace` — Perfetto-loadable Chrome trace-event JSON of
  the ring's spans and events, on ``perf_counter``.
- :func:`dump_flight_recorder` — NDJSON, one entry per line (records
  included), with a leading meta line carrying wall-clock ↔ perf_counter
  alignment.
- ``telemetry.statusz()`` / the ``/statusz`` HTTP endpoint — includes
  the last ``MXNET_TPU_STATUSZ_EVENTS`` recorder entries.

Cross-thread parent propagation is explicit: a producer captures
``tracing.current()`` (or allocates a root with :func:`new_root`) and
the worker thread adopts it with ``with tracing.attach(ctx):`` or by
passing ``parent=ctx`` to :func:`span`/:func:`record_span`.  Request
objects carry their ``(trace_id, span_id)`` tuple the same way.

The anomaly watchdog rides existing host-side values only — EWMA
step-time regression from ``telemetry.record_step`` seconds and
nonfinite-loss detection at ``PendingScalar`` sync points — so arming
it never adds a device sync.  Findings book ``mx_anomalies_total{kind}``
and write recorder events.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import math
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ..base import env

__all__ = [
    "enable", "disable", "is_enabled",
    "span", "phased", "record_span", "event",
    "current", "attach", "new_root",
    "spans", "step_records", "parent_of", "RECORD_KINDS", "recent",
    "set_max_spans", "reset",
    "dump_chrome_trace", "dump_flight_recorder",
    "watch_step_time", "check_loss", "install_crash_hooks",
]

env.declare("MXNET_TPU_TRACING", False, bool,
            "Arm the span ring at import (tracing.enable() at runtime): "
            "spans then carry ids and parents into the flight recorder. "
            "Disarmed, a span is a profiler TraceAnnotation and nothing "
            "else; step and batch records are kept either way.")
env.declare("MXNET_TPU_TRACING_MAX_SPANS", 100_000, int,
            "Flight-recorder ring capacity (spans, events, step and batch "
            "records); "
            "same bounding convention as MXNET_PROFILER_MAX_EVENTS.")
env.declare("MXNET_TPU_FLIGHT_RECORDER", "mx_flight_recorder.ndjson", str,
            "Default path for the NDJSON flight-recorder dump (preemption, "
            "crash hook, dump_flight_recorder() without a path).")
env.declare("MXNET_TPU_STATUSZ_EVENTS", 32, int,
            "How many trailing recorder entries /statusz reports.")
env.declare("MXNET_TPU_ANOMALY_STEP_RATIO", 2.5, float,
            "Watchdog: a step slower than ratio x EWMA (after warmup) books "
            "mx_anomalies_total{kind=step_time_regression}.")
env.declare("MXNET_TPU_ANOMALY_WARMUP", 10, int,
            "Watchdog: steps per source before regression checks arm "
            "(EWMA needs a baseline; compile steps would false-positive).")

_ENABLED = bool(env.get("MXNET_TPU_TRACING"))
_LOCK = threading.Lock()
_RING: "deque[Dict[str, Any]]" = deque(
    maxlen=max(int(env.get("MXNET_TPU_TRACING_MAX_SPANS")), 0))
_TLS = threading.local()
_IDS = itertools.count(1)
# Process-unique prefix: pid + 4 random bytes so ids from different
# processes (or restarts of the same pid) never collide in merged dumps.
_PREFIX = "%x-%08x" % (os.getpid(),
                       int.from_bytes(os.urandom(4), "big"))

# EWMA smoothing for the step-time watchdog.
_WD_ALPHA = 0.1
_WD: Dict[str, List[float]] = {}  # source -> [count, ewma]


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``; the class is bound on first
    use, so that importing this module stays stdlib-only."""
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    return TraceAnnotation(name)


# ---------------------------------------------------------------------------
# Arming
# ---------------------------------------------------------------------------

def enable() -> None:
    """Arm tracing and install the crash-dump excepthook chain."""
    global _ENABLED
    _ENABLED = True
    install_crash_hooks()


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def is_enabled() -> bool:
    return _ENABLED


# ---------------------------------------------------------------------------
# Ids and thread-local context
# ---------------------------------------------------------------------------

def _next_id() -> str:
    return format(next(_IDS), "x")


def new_root(name: str = "") -> Tuple[str, str]:
    """Allocate a fresh (trace_id, span_id) root context without recording
    anything. Use when the root span's duration is only known later (e.g. a
    serving request records its root at completion) or as a grouping parent
    for a worker thread's spans."""
    trace_id = "%s-%s" % (_PREFIX, _next_id())
    if name:
        trace_id = "%s-%s" % (trace_id, name)
    return (trace_id, _next_id())


def current() -> Optional[Tuple[str, str]]:
    """The innermost open (trace_id, span_id) on this thread, or None."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def attach(ctx: Optional[Tuple[str, str]]):
    """Adopt a context captured on another thread: spans opened inside the
    block parent under ``ctx``. No-op when disarmed or ``ctx`` is None."""
    if not _ENABLED or ctx is None:
        yield None
        return
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append((ctx[0], ctx[1]))
    try:
        yield ctx
    finally:
        stack.pop()


def _resolve_parent(parent) -> Tuple[str, Optional[str]]:
    """(trace_id, parent_span_id) from an explicit parent, the thread-local
    stack, or a fresh root trace."""
    if isinstance(parent, _Span):
        # an open span that was entered disarmed has no ids to hand on
        parent = parent.context if parent.span_id is not None else None
    if parent is not None:
        return parent[0], parent[1]
    cur = current()
    if cur is not None:
        return cur[0], cur[1]
    return "%s-%s" % (_PREFIX, _next_id()), None


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

class _Span:
    """An open span; context manager. Always a profiler TraceAnnotation
    region; where tracing was armed at entry, completed on exit into the
    ring."""

    __slots__ = ("name", "attrs", "trace_id", "span_id", "parent_id",
                 "_parent", "_t0", "_ann")

    def __init__(self, name: str, parent, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.trace_id = self.span_id = self.parent_id = None
        self._parent = parent
        self._t0 = 0.0

    @property
    def context(self) -> Tuple[Optional[str], Optional[str]]:
        return (self.trace_id, self.span_id)

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def __enter__(self) -> "_Span":
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        if _ENABLED:
            self.trace_id, self.parent_id = _resolve_parent(self._parent)
            self.span_id = _next_id()
            stack = getattr(_TLS, "stack", None)
            if stack is None:
                stack = _TLS.stack = []
            stack.append((self.trace_id, self.span_id))
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.span_id is not None:
            self._complete(time.perf_counter(), exc_type)
        self._ann.__exit__(exc_type, exc, tb)

    def _complete(self, t1: float, exc_type) -> None:
        stack = getattr(_TLS, "stack", None)
        if stack:
            stack.pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _append({"kind": "span", "name": self.name,
                 "trace_id": self.trace_id, "span_id": self.span_id,
                 "parent_id": self.parent_id, "ts": self._t0,
                 "dur": t1 - self._t0, "thread": threading.get_ident(),
                 "attrs": self.attrs})


def span(name: str, parent=None, **attrs) -> _Span:
    """The span primitive: a context manager that is always a
    ``jax.profiler.TraceAnnotation(name)`` region (inert without a profiler
    session; under one, on the host plane of the device's own trace) and,
    armed, records the span into the ring on exit. ``parent`` is an explicit
    (trace_id, span_id) tuple or open span; default is the thread-local
    current span, else a fresh root trace."""
    return _Span(name, parent, attrs)


class _Phased(_Span):
    """A span cut into contiguous phases that also leaves one always-on
    record in the ring (see :func:`phased`)."""

    __slots__ = ("kind", "prefix", "phases", "_t", "_last")

    def __init__(self, kind: str, name: str, prefix: Optional[str], parent,
                 attrs: Dict[str, Any]):
        super().__init__(name, parent, attrs)
        self.kind = kind
        self.prefix = name + "." if prefix is None else prefix
        self.phases: Dict[str, float] = {}
        self._t = 0.0
        self._last = None

    def __enter__(self) -> "_Phased":
        super().__enter__()
        self._t = self._t0
        return self

    def phase(self, name: str) -> "_Phase":
        """Child span ``<prefix><name>``. It is booked from the boundary the
        phase before it closed at (or the call's entry) to its own exit, so
        phases share their boundaries and what runs between two ``with``
        blocks belongs to the later one. A name used twice accumulates."""
        return _Phase(self, name)

    def split(self, phase: str, part: str, seconds: float) -> None:
        """Book ``seconds`` of ``phase`` under ``part`` instead (a wait
        measured inside the phase by the layer that waited)."""
        booked = self.phases.get(phase, 0.0)
        seconds = min(max(seconds, 0.0), booked)
        self.phases[phase] = booked - seconds
        self.phases[part] = self.phases.get(part, 0.0) + seconds

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        if self._last is not None:
            # the return path after the last phase, so that the phases sum
            # to the call
            self.phases[self._last] += t1 - self._t
        entry = dict(self.attrs, kind=self.kind, name=self.name,
                     ts=self._t0, dur=t1 - self._t0, phases=self.phases,
                     thread=threading.get_ident())
        if exc_type is not None:
            entry["error"] = exc_type.__name__
        _append(entry)
        if self.span_id is not None:
            self._complete(t1, exc_type)
        self._ann.__exit__(exc_type, exc, tb)


class _Phase:
    """One open phase of a :class:`_Phased` call; context manager."""

    __slots__ = ("_rec", "_name", "_ann")

    def __init__(self, rec: _Phased, name: str):
        self._rec = rec
        self._name = name

    def __enter__(self) -> "_Phase":
        self._ann = _annotation(self._rec.prefix + self._name)
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        rec, name = self._rec, self._name
        t = time.perf_counter()
        rec.phases[name] = rec.phases.get(name, 0.0) + (t - rec._t)
        if rec.span_id is not None:
            record_span(rec.prefix + name, rec._t, t, parent=rec.context)
        rec._t = t
        rec._last = name
        self._ann.__exit__(exc_type, exc, tb)


def phased(kind: str, name: str, prefix: Optional[str] = None, parent=None,
           **attrs) -> _Phased:
    """:func:`span` for a call that is cut into phases::

        with tracing.phased("step", "mx.dp.step", step=t) as rec:
            with rec.phase("rng_key"):
                ...
            with rec.phase("launch"):
                ...

    The call is the span ``name``, each phase a child span
    ``<prefix><phase>`` (``prefix`` defaults to ``name + "."``). Armed or
    not, the call also appends one record to the ring: ``{"kind": kind,
    "name": name, "ts": perf_counter at entry, "dur": seconds, "phases":
    {phase: seconds}, "thread": ident, **attrs}``, with ``"error"`` where an
    exception passed through. The phases partition the call: they sum to
    ``dur``. A call leaves its record whether or not it delivered: one that
    raised carries ``error``, and the caller may mark one it abandoned
    (``rec.set_attr``; the feed marks ``aborted``). :func:`step_records`
    reads the records back."""
    return _Phased(kind, name, prefix, parent, attrs)


def record_span(name: str, t_start: float, t_end: float, parent=None,
                ctx: Optional[Tuple[str, str]] = None,
                **attrs) -> Optional[Tuple[str, str]]:
    """Record a completed span from timestamps already in hand (no clock
    reads here — callers on measured paths reuse stamps they already took).
    ``ctx`` pre-assigns this span's own (trace_id, span_id) — used when the
    id was allocated earlier (e.g. a serving request's root span). Returns
    the span's context for chaining children."""
    if not _ENABLED:
        return None
    if ctx is not None:
        trace_id, span_id = ctx
        parent_id = parent[1] if parent is not None else None
    else:
        trace_id, parent_id = _resolve_parent(parent)
        span_id = _next_id()
    _append({"kind": "span", "name": name, "trace_id": trace_id,
             "span_id": span_id, "parent_id": parent_id, "ts": t_start,
             "dur": t_end - t_start, "thread": threading.get_ident(),
             "attrs": attrs})
    return (trace_id, span_id)


def event(name: str, parent=None, **attrs) -> Optional[Tuple[str, str]]:
    """Record an instant recorder event (fault firing, io retry, anomaly)."""
    if not _ENABLED:
        return None
    trace_id, parent_id = _resolve_parent(parent)
    span_id = _next_id()
    _append({"kind": "event", "name": name, "trace_id": trace_id,
             "span_id": span_id, "parent_id": parent_id,
             "ts": time.perf_counter(), "dur": 0.0,
             "thread": threading.get_ident(), "attrs": attrs})
    return (trace_id, span_id)


def _append(entry: Dict[str, Any]) -> None:
    # Deliberately lock-free: deque.append with maxlen is atomic under the
    # GIL, and this is the armed hot path — serving records ~6 entries per
    # request from 3+ threads, so a shared lock here turns the recorder
    # into a contention point (measured ~25% closed-loop throughput loss).
    # Readers (spans()) retry on the concurrent-mutation RuntimeError.
    _RING.append(entry)  # GIL-atomic  # mxlint: disable=lock-discipline


# ---------------------------------------------------------------------------
# Ring access
# ---------------------------------------------------------------------------

# The kinds of record the ring keeps beside spans and events. Public, so that
# a reader can tell a program from before a kind (absent: nothing to read)
# from one that lost its records (declared, none kept: an error).
#   step   a DataParallelTrainer.step / run_steps call            (phased)
#   batch  a batch the feed's producer made                       (phased)
#   setup  a boundary set-up crosses before the first step:
#          mx.block.initialize, mx.block.deferred_init, mx.dp.init (phased)
#   build  a program the process built: mx.build (engine's listener)
RECORD_KINDS = ("step", "batch", "setup", "build")


def _entries() -> List[Dict[str, Any]]:
    """Snapshot of the whole ring (oldest first). Writers are lock-free
    (see _append), so a snapshot taken mid-append can raise "deque mutated
    during iteration" — retry; the window is a single append."""
    for _ in range(64):
        try:
            return list(_RING)
        except RuntimeError:
            continue
    return []  # writer storm: the flight recorder prefers empty to hanging


def spans() -> List[Dict[str, Any]]:
    """The ring's spans and events (oldest first), without the records."""
    return [e for e in _entries() if e["kind"] not in RECORD_KINDS]


def step_records(name: Optional[str] = None, since: Optional[float] = None,
                 until: Optional[float] = None) -> List[Dict[str, Any]]:
    """The ring's records (the kinds of :data:`RECORD_KINDS`; see
    :func:`phased`), oldest first: those named ``name`` (``"mx.dp.step"``,
    ``"mx.dp.run_steps"``, ``"mx.feed.batch"``, ``"mx.dp.init"``,
    ``"mx.block.initialize"``, ``"mx.block.deferred_init"``, ``"mx.build"``;
    all if None) that began in ``[since, until]``, times on
    ``time.perf_counter``."""
    return [e for e in _entries() if e["kind"] in RECORD_KINDS
            and (name is None or e["name"] == name)
            and (since is None or e["ts"] >= since)
            and (until is None or e["ts"] <= until)]


def parent_of(entry: Dict[str, Any],
              records: Optional[List[Dict[str, Any]]] = None
              ) -> Optional[Dict[str, Any]]:
    """The call a record ran under: the innermost ``step`` or ``setup``
    record of the same thread whose ``[ts, ts + dur]`` holds ``entry``'s
    ``ts``, or None. This is how a ``build`` record is attributed: nothing
    is stored at the build and the enclosing call pays nothing; the link is
    found when read (a call appends its record when it returns, so a build
    inside a call that is still open has no parent yet). ``records``: the
    candidates (default: the ring's)."""
    if records is None:
        records = step_records()
    ts, thread, found = entry["ts"], entry["thread"], None
    for r in records:
        if r["kind"] in ("step", "setup") and r["thread"] == thread \
                and r is not entry and r["ts"] <= ts <= r["ts"] + r["dur"] \
                and (found is None or r["ts"] >= found["ts"]):
            found = r
    return found


def recent(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """The trailing ``n`` entries, records included (default
    MXNET_TPU_STATUSZ_EVENTS). A ``build`` record among them also says which
    call it ran under (``"under"``: that record's name, see
    :func:`parent_of`)."""
    if n is None:
        n = int(env.get("MXNET_TPU_STATUSZ_EVENTS"))
    entries = _entries()
    tail = entries if n <= 0 or n >= len(entries) else entries[-n:]
    if any(e["kind"] == "build" for e in tail):
        calls = [e for e in entries if e["kind"] in ("step", "setup")]
        tail = [dict(e, under=(parent_of(e, calls) or {}).get("name"))
                if e["kind"] == "build" else e for e in tail]
    return tail


def set_max_spans(n: int) -> None:
    """Re-cap the ring, keeping the newest entries (mirror of
    profiler.set_max_events — the shared bounding convention)."""
    global _RING
    with _LOCK:  # excludes concurrent re-cap/reset; appends are atomic
        _RING = deque(_entries(), maxlen=max(int(n), 0))


def reset() -> None:
    """Drop recorded entries and watchdog state (telemetry.reset() calls
    this; arming state and ids are untouched)."""
    with _LOCK:
        _RING.clear()
        _WD.clear()


# ---------------------------------------------------------------------------
# Export surfaces
# ---------------------------------------------------------------------------

def dump_chrome_trace(path: str) -> str:
    """Write the ring's spans and events as Chrome trace-event JSON
    (Perfetto-loadable). Span names are the track names, the same names the
    profiler's xplane holds; timestamps are perf_counter microseconds,
    matching ``profiler.dump()`` (the xplane, not this file, is on the
    device's clock)."""
    events = []
    for e in spans():
        out = {"name": e["name"], "cat": "mx." + e["kind"],
               "ts": e["ts"] * 1e6, "pid": 0, "tid": e["thread"],
               "args": dict(e["attrs"], trace_id=e["trace_id"],
                            span_id=e["span_id"],
                            parent_id=e["parent_id"])}
        if e["kind"] == "span":
            out["ph"] = "X"
            out["dur"] = e["dur"] * 1e6
        else:
            out["ph"] = "i"
            out["s"] = "t"
        events.append(out)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return path


def dump_flight_recorder(path: Optional[str] = None,
                         reason: str = "manual") -> str:
    """Write the ring as NDJSON: a meta line (reason, pid, wall-clock ↔
    perf_counter anchor), then one entry per line (spans, events, step and
    batch records), oldest first. This is the black-box dump that
    ``elastic.run`` (preemption, a step that raised) and the crash hooks
    take when tracing is armed; disarmed nobody calls it but the job."""
    if path is None:
        path = str(env.get("MXNET_TPU_FLIGHT_RECORDER"))
    entries = _entries()
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "reason": reason,
                            "pid": os.getpid(), "wall_time": time.time(),
                            "perf_counter": time.perf_counter(),
                            "entries": len(entries)}) + "\n")
        for e in entries:
            f.write(json.dumps(e, default=str) + "\n")
    return path


# ---------------------------------------------------------------------------
# Crash hooks (unhandled-step-exception dump)
# ---------------------------------------------------------------------------

_HOOKS_INSTALLED = [False]


def install_crash_hooks() -> None:
    """Chain sys.excepthook + threading.excepthook to dump the flight
    recorder on an unhandled exception (main thread or any worker —
    dispatcher, producer, snapshot writer). Idempotent; previous hooks
    still run."""
    with _LOCK:
        if _HOOKS_INSTALLED[0]:
            return
        _HOOKS_INSTALLED[0] = True
    prev_sys = sys.excepthook

    def _sys_hook(exc_type, exc, tb):
        _crash_dump("unhandled:%s" % getattr(exc_type, "__name__", "?"))
        prev_sys(exc_type, exc, tb)

    sys.excepthook = _sys_hook
    prev_thread = threading.excepthook

    def _thread_hook(args):
        _crash_dump("thread:%s" % getattr(args.exc_type, "__name__", "?"))
        prev_thread(args)

    threading.excepthook = _thread_hook


def _crash_dump(reason: str) -> None:
    try:
        if _ENABLED and len(_RING):
            dump_flight_recorder(reason=reason)
    except Exception:  # never let the dump mask the original failure
        pass


# ---------------------------------------------------------------------------
# Anomaly watchdog
# ---------------------------------------------------------------------------

def watch_step_time(seconds: float, source: str = "step") -> None:
    """EWMA step-time regression detector. Fed per-step host-side seconds
    from telemetry.record_step — values the metrics plane already computed,
    so no new syncs or clock reads. After MXNET_TPU_ANOMALY_WARMUP samples
    per source, a step slower than MXNET_TPU_ANOMALY_STEP_RATIO x EWMA
    books an anomaly; the sample still updates the EWMA so a genuine
    regime change (bigger batch) stops alerting after a few steps."""
    if not _ENABLED:
        return
    warmup = int(env.get("MXNET_TPU_ANOMALY_WARMUP"))
    ratio = env.get("MXNET_TPU_ANOMALY_STEP_RATIO")
    with _LOCK:
        state = _WD.get(source)
        if state is None:
            state = _WD[source] = [0.0, 0.0]
        count, ewma = state
        fire = count >= warmup and ewma > 0.0 and seconds > ratio * ewma
        state[0] = count + 1.0
        state[1] = seconds if count == 0.0 \
            else ewma + _WD_ALPHA * (seconds - ewma)
    if fire:
        _anomaly("step_time_regression", source=source,
                 seconds=seconds, ewma=ewma, ratio=ratio)


def check_loss(value: float, source: str = "step") -> None:
    """Nonfinite-loss detector. Called at PendingScalar/drain sync points
    with a host float the caller already materialised — detection piggybacks
    on syncs that were happening anyway."""
    if not _ENABLED:
        return
    try:
        if math.isfinite(value):
            return
    except (TypeError, ValueError):
        return
    _anomaly("nonfinite_loss", source=source, value=repr(value))


def _anomaly(kind: str, **attrs) -> None:
    event("mx.anomaly." + kind, kind=kind, **attrs)
    from .. import telemetry as _telem
    _telem.counter(
        "mx_anomalies_total",
        "Anomalies flagged by the tracing watchdog (EWMA step-time "
        "regression, nonfinite loss)", ("kind",)).labels(kind).inc()


if _ENABLED:
    install_crash_hooks()
