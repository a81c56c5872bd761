"""Unified telemetry: a process-wide metrics registry every subsystem
reports into, plus Prometheus-text / JSON export.

The reference framework's runtime is legible through the profiler's
aggregate-stats table and KVStore-level comms visibility; this module is the
unified layer on top of those signals (ROADMAP: "as fast as the hardware
allows" is unverifiable without them):

  - **training-step metrics** — step time, examples/sec, and an MFU/roofline
    estimate derived from ``cost_analysis()`` FLOPs captured when the engine
    builds a compiled artifact (`engine.estimate_cost`). Fed by
    ``gluon.Trainer.step``, ``Module.fit``, and the fused
    ``parallel.*Trainer`` steps.
  - **collective-comms accounting** — bytes moved / calls / wall seconds per
    kvstore push/pull/pushpull and per fused-step gradient all-reduce, with
    ``jax.profiler.TraceAnnotation`` regions so the same boundaries show up
    inside xplane traces (TensorBoard/XProf).
  - **memory watermarks** — live device-buffer bytes and the process peak,
    sampled per step while enabled.
  - **export** — ``scrape()`` (Prometheus text), ``scrape_json()``,
    ``report()`` (human table unifying the profiler aggregate table and the
    compilation-cache counters), and ``start_http_server()`` for a real
    ``GET /metrics`` endpoint.

The registry is OFF by default. Every instrumentation site guards on the
module attribute ``_ENABLED`` (the same one-check-per-call idiom as
``ops/registry.py:_profile_hook``), so the disabled path costs one dict
lookup + branch; ``BENCH_SCENARIO=telemetry_overhead`` in bench.py proves
the enabled path stays under 2% of eager step time.
"""
from __future__ import annotations

import contextlib
import functools
import json
import sys
import threading
import time
from bisect import bisect_left
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError, env

__all__ = [
    "enable", "disable", "is_enabled", "counter", "gauge", "histogram",
    "get_metric", "reset", "collect", "scrape", "scrape_json", "report",
    "record_step", "record_comm", "comm_scope", "instrument_comm",
    "record_optimizer_state", "payload_bytes", "sample_memory", "peak_flops",
    "peak_bytes_per_second", "ridge_point", "roofline", "trace_steps",
    "trace_active",
    "record_feed_depth", "record_feed_stall", "record_inflight",
    "record_dispatch_wait",
    "record_checkpoint_save", "record_resume", "record_moe_dropped",
    "set_epoch", "timed", "annotate", "start_http_server",
    "stop_http_server", "DEFAULT_LATENCY_BUCKETS", "record_serving_enqueue",
    "record_serving_queue_depth", "record_serving_dispatch",
    "record_serving_completion", "record_fault_injected", "record_io_retry",
    "record_request_shed", "record_feed_producer_leak",
    "record_feed_producer_restart", "record_serving_queue_wait",
    "record_hosts_live", "record_commit_barrier", "record_hang_watchdog",
    "statusz", "tracing", "goodput",
]

env.declare("MXNET_TELEMETRY", False, bool,
            "Enable the telemetry registry at import")
env.declare("MXNET_TELEMETRY_MAX_SERIES", 512, int,
            "Max label combinations kept per metric family; excess series "
            "are dropped and counted in mx_telemetry_dropped_series_total")
env.declare("MXNET_TELEMETRY_PEAK_FLOPS", 0.0, float,
            "Roofline peak FLOP/s used for the MFU gauge; overrides the "
            "per-device-kind table (set this on CPU, where XLA's cost model "
            "has no meaningful peak)")
env.declare("MXNET_TELEMETRY_PEAK_BYTES", 0.0, float,
            "Roofline peak memory bandwidth (bytes/s) for the per-region "
            "ledger; overrides the per-device-kind HBM table (set this on "
            "CPU, where the 50 GB/s anchor is only an A/B reference)")
env.declare("MXNET_TPU_TRACE_DIR", "", str,
            "Default logdir for telemetry.trace_steps() device-trace "
            "capture (xplane, viewable in TensorBoard/XProf)")

_LOCK = threading.RLock()
_FAMILIES: "OrderedDict[str, MetricFamily]" = OrderedDict()

# the one flag every instrumentation site checks (module-attribute lookup +
# branch while disabled — the _profile_hook None-check idiom)
_ENABLED = bool(env.get("MXNET_TELEMETRY"))


def enable():
    """Turn instrumentation on (all sites start reporting)."""
    global _ENABLED
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


def is_enabled() -> bool:
    return _ENABLED


# process-rank label for multi-host scrapes: "" (single process) leaves
# every family's label set — and therefore the exposition — byte-identical
# to the single-host build; a nonempty value is appended as a TRAILING
# "host" label, so MetricFamily.get()'s prefix aggregation keeps every
# existing reader working unchanged.
_HOST_LABEL: List[Optional[str]] = [None]


def _host_label() -> str:
    """Resolve (once) the process-rank label value. Consults jax only if
    something else already imported it — a multi-host job necessarily
    initialized jax.distributed, while pure host-side processes (the
    elastic drill's children) must never pay a jax import for a label."""
    v = _HOST_LABEL[0]
    if v is None:
        v = ""
        jx = sys.modules.get("jax")
        if jx is not None:
            try:
                if int(jx.process_count()) > 1:
                    v = str(int(jx.process_index()))
            except Exception:
                v = ""
        with _LOCK:
            _HOST_LABEL[0] = v
    return v


# ---------------------------------------------------------------------------
# Metric model: family (name + label names) -> labeled series
# ---------------------------------------------------------------------------

def _escape(v: str) -> str:
    return str(v).replace("\\", r"\\").replace("\n", r"\n").replace('"', r'\"')


def _escape_help(v: str) -> str:
    # HELP-line escaping per the exposition format: backslash and newline
    # only (quotes are legal in help text). A doc with a raw newline would
    # otherwise split the HELP line and corrupt the whole scrape.
    return str(v).replace("\\", r"\\").replace("\n", r"\n")


def _fmt_labels(names: Tuple[str, ...], values: Tuple[str, ...],
                extra: str = "") -> str:
    parts = [f'{n}="{_escape(v)}"' for n, v in zip(names, values)]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _NullSeries:
    """Returned past the cardinality cap: absorbs writes silently."""

    def inc(self, n=1):
        pass

    def dec(self, n=1):
        pass

    def set(self, v):
        pass

    def set_max(self, v):
        pass

    def observe(self, v):
        pass


_NULL = _NullSeries()


class _CounterSeries:
    __slots__ = ("label_values", "value")

    def __init__(self, label_values):
        self.label_values = label_values
        self.value = 0.0

    def inc(self, n=1):
        if n < 0:
            raise MXNetError("counters only go up; use a gauge")
        with _LOCK:
            self.value += n


class _GaugeSeries:
    __slots__ = ("label_values", "value")

    def __init__(self, label_values):
        self.label_values = label_values
        self.value = 0.0

    def set(self, v):
        with _LOCK:
            self.value = float(v)

    def set_max(self, v):
        """Watermark update: keep the running maximum."""
        with _LOCK:
            self.value = max(self.value, float(v))

    def inc(self, n=1):
        with _LOCK:
            self.value += n

    def dec(self, n=1):
        with _LOCK:
            self.value -= n


class _HistogramSeries:
    __slots__ = ("label_values", "buckets", "counts", "sum", "count")

    def __init__(self, label_values, buckets):
        self.label_values = label_values
        self.buckets = buckets            # sorted upper bounds, no +Inf
        self.counts = [0] * (len(buckets) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v):
        v = float(v)
        with _LOCK:
            self.counts[bisect_left(self.buckets, v)] += 1
            self.sum += v
            self.count += 1


class MetricFamily:
    kind = "untyped"
    _series_cls = _GaugeSeries

    def __init__(self, name: str, doc: str = "",
                 labelnames: Sequence[str] = (),
                 max_series: Optional[int] = None):
        self.name = name
        self.doc = doc
        self.labelnames = tuple(labelnames)
        self.max_series = max_series if max_series is not None \
            else int(env.get("MXNET_TELEMETRY_MAX_SERIES"))
        self._series: Dict[Tuple[str, ...], Any] = {}
        self.dropped = 0

    def labels(self, *values, **kv):
        if kv:
            if values:
                raise MXNetError("pass label values positionally OR by name")
            try:
                values = tuple(str(kv[n]) for n in self.labelnames)
            except KeyError as e:
                raise MXNetError(
                    f"metric {self.name} missing label {e}") from None
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise MXNetError(
                f"metric {self.name} takes labels {self.labelnames}, "
                f"got {values}")
        s = self._series.get(values)
        if s is None:
            with _LOCK:
                s = self._series.get(values)
                if s is None:
                    if len(self._series) >= self.max_series:
                        # cap label cardinality: drop (and count) instead of
                        # letting a runaway label explode scrape size
                        self.dropped += 1
                        return _NULL
                    s = self._make_series(values)
                    self._series[values] = s
        return s

    def _make_series(self, values):
        return self._series_cls(values)

    def _default(self):
        return self.labels(*(("",) * len(self.labelnames))) \
            if self.labelnames else self.labels()

    # family-level convenience for label-less metrics
    def inc(self, n=1):
        self._default().inc(n)

    def dec(self, n=1):
        self._default().dec(n)

    def set(self, v):
        self._default().set(v)

    def set_max(self, v):
        self._default().set_max(v)

    def observe(self, v):
        self._default().observe(v)

    def get(self, *values) -> float:
        """Exact series value — or, with FEWER label values than the family
        has labelnames, the sum over every series matching that label
        prefix (Prometheus-style aggregation over the remaining labels, so
        readers written before a family grew a label keep working)."""
        values = tuple(str(v) for v in values)
        if len(values) < len(self.labelnames):
            with _LOCK:
                series = list(self._series.items())
            return sum(getattr(s, "value", getattr(s, "sum", 0.0))
                       for lv, s in series if lv[:len(values)] == values)
        s = self._series.get(values)
        if s is None:
            return 0.0
        return getattr(s, "value", getattr(s, "sum", 0.0))

    def _render(self, out: List[str]):
        out.append(f"# HELP {self.name} {_escape_help(self.doc)}")
        out.append(f"# TYPE {self.name} {self.kind}")
        with _LOCK:
            series = list(self._series.values())
        for s in series:
            out.append(f"{self.name}"
                       f"{_fmt_labels(self.labelnames, s.label_values)}"
                       f" {s.value}")

    def _as_dict(self):
        with _LOCK:
            return {
                "type": self.kind, "doc": self.doc,
                "series": [
                    {"labels": dict(zip(self.labelnames, s.label_values)),
                     "value": s.value}
                    for s in self._series.values()],
            }


class CounterFamily(MetricFamily):
    kind = "counter"
    _series_cls = _CounterSeries


class GaugeFamily(MetricFamily):
    kind = "gauge"
    _series_cls = _GaugeSeries


# seconds-scale spacing: 50us .. ~100s
_DEFAULT_BUCKETS = tuple(5e-5 * (2.5 ** i) for i in range(13))


class HistogramFamily(MetricFamily):
    kind = "histogram"

    def __init__(self, name, doc="", labelnames=(), buckets=None,
                 max_series=None):
        super().__init__(name, doc, labelnames, max_series)
        self.buckets = sorted(float(b) for b in (buckets or _DEFAULT_BUCKETS))

    def _make_series(self, values):
        return _HistogramSeries(values, self.buckets)

    def _render(self, out: List[str]):
        out.append(f"# HELP {self.name} {_escape_help(self.doc)}")
        out.append(f"# TYPE {self.name} histogram")
        with _LOCK:
            series = [(s.label_values, list(s.counts), s.sum, s.count)
                      for s in self._series.values()]
        for lv, counts, total, count in series:
            acc = 0
            for ub, c in zip(self.buckets, counts):
                acc += c
                le = 'le="%g"' % ub
                out.append(f"{self.name}_bucket"
                           f"{_fmt_labels(self.labelnames, lv, le)} {acc}")
            inf = 'le="+Inf"'
            out.append(f"{self.name}_bucket"
                       f"{_fmt_labels(self.labelnames, lv, inf)} {count}")
            out.append(f"{self.name}_sum"
                       f"{_fmt_labels(self.labelnames, lv)} {total}")
            out.append(f"{self.name}_count"
                       f"{_fmt_labels(self.labelnames, lv)} {count}")

    def _as_dict(self):
        with _LOCK:
            return {
                "type": "histogram", "doc": self.doc,
                "buckets": self.buckets,
                "series": [
                    {"labels": dict(zip(self.labelnames, s.label_values)),
                     "counts": list(s.counts), "sum": s.sum, "count": s.count}
                    for s in self._series.values()],
            }


def _family(cls, name, doc, labelnames, **kw):
    with _LOCK:
        fam = _FAMILIES.get(name)
        if fam is None:
            fam = _FAMILIES[name] = cls(name, doc, labelnames, **kw)
        elif type(fam) is not cls:
            raise MXNetError(
                f"metric {name!r} already registered as {fam.kind}")
        return fam


def counter(name, doc="", labelnames=(), max_series=None) -> CounterFamily:
    """Get-or-create a monotonically increasing counter family."""
    return _family(CounterFamily, name, doc, labelnames,
                   max_series=max_series)


def gauge(name, doc="", labelnames=(), max_series=None) -> GaugeFamily:
    return _family(GaugeFamily, name, doc, labelnames, max_series=max_series)


def histogram(name, doc="", labelnames=(), buckets=None,
              max_series=None) -> HistogramFamily:
    with _LOCK:
        fam = _FAMILIES.get(name)
        if fam is None:
            fam = _FAMILIES[name] = HistogramFamily(
                name, doc, labelnames, buckets, max_series)
        elif not isinstance(fam, HistogramFamily):
            raise MXNetError(
                f"metric {name!r} already registered as {fam.kind}")
        return fam


def get_metric(name) -> Optional[MetricFamily]:
    return _FAMILIES.get(name)


def reset():
    """Drop every registered family and all step/memory bookkeeping,
    including the per-region roofline ledger (tests; a long-lived server
    should scrape, not reset)."""
    global _mem_peak
    with _LOCK:
        _FAMILIES.clear()
        _STEP_ANCHOR.clear()
        _mem_peak = 0.0
        _HOST_LABEL[0] = None
    from . import roofline as _roofline
    _roofline.reset()
    from . import tracing as _tracing
    _tracing.reset()
    from . import goodput as _goodput
    _goodput.reset()


# ---------------------------------------------------------------------------
# Roofline peak for the MFU gauge
# ---------------------------------------------------------------------------

# nominal bf16 peak FLOP/s by device_kind substring (BASELINE.md / bench.py)
_PEAK_TABLE = (
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 46e12), ("v6", 918e12),
)
# On a CPU platform (the unit suite) MFU is reported against this nominal
# anchor so the gauge exists and A/B deltas are comparable — the absolute
# value is NOT a hardware utilization claim (docs/observability.md, "MFU
# methodology"). An accelerator whose device_kind is not in the table is an
# error, never this anchor.
_CPU_ANCHOR_PEAK = 1e12
_peak_cache: List[Optional[float]] = [None]


def _device_peak(table, cpu_anchor: float, what: str) -> float:
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return cpu_anchor
    kind = dev.device_kind.lower()
    for sub, peak in table:
        if sub in kind:
            return peak
    raise MXNetError(
        f"no {what} known for device_kind {dev.device_kind!r} "
        f"(platform {dev.platform}); add it to the table in "
        "mxnet_tpu/telemetry/__init__.py or set MXNET_TELEMETRY_PEAK_FLOPS "
        "/ MXNET_TELEMETRY_PEAK_BYTES")


def peak_flops() -> float:
    """Peak FLOP/s the MFU gauge divides by: env override, else the
    device_kind table; on a CPU platform the documented 1 TF/s anchor."""
    ov = float(env.get("MXNET_TELEMETRY_PEAK_FLOPS"))
    if ov > 0:
        return ov
    with _LOCK:
        if _peak_cache[0] is None:
            _peak_cache[0] = _device_peak(_PEAK_TABLE, _CPU_ANCHOR_PEAK,
                                          "peak FLOP/s")
        return _peak_cache[0]


# nominal HBM bandwidth (bytes/s) by device_kind substring — the roofline
# denominator for the bytes axis (same resolution order as peak_flops)
_BW_TABLE = (
    ("v5 lite", 819e9), ("v5e", 819e9), ("v5p", 2765e9),
    ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9), ("v6", 1640e9),
)
# documented CPU anchor: ~DDR-class bandwidth so the ledger's ratios and
# ridge point stay meaningful for A/B deltas on CI hosts (with the 1 TF/s
# FLOPs anchor the ridge sits at 20 FLOP/byte; not a hardware claim —
# docs/observability.md, "Peak overrides")
_CPU_ANCHOR_BYTES_PER_S = 50e9
_peak_bw_cache: List[Optional[float]] = [None]


def peak_bytes_per_second() -> float:
    """Peak memory bandwidth the per-region roofline ledger divides by:
    ``MXNET_TELEMETRY_PEAK_BYTES`` override, else the device_kind HBM
    table; on a CPU platform the documented 50 GB/s anchor."""
    ov = float(env.get("MXNET_TELEMETRY_PEAK_BYTES"))
    if ov > 0:
        return ov
    with _LOCK:
        if _peak_bw_cache[0] is None:
            _peak_bw_cache[0] = _device_peak(
                _BW_TABLE, _CPU_ANCHOR_BYTES_PER_S, "peak bytes/s")
        return _peak_bw_cache[0]


def ridge_point() -> float:
    """Arithmetic intensity (FLOP/byte) where the roofline's bandwidth
    slope meets the compute ceiling; regions below it are memory-bound."""
    return peak_flops() / peak_bytes_per_second()


# ---------------------------------------------------------------------------
# Programmatic device-trace capture (xplane timeline)
# ---------------------------------------------------------------------------

# [steps remaining, active logdir]; armed by trace_steps(), decremented by
# record_step() so the capture stops itself after n recorded steps without
# any extra sync point in the loop
_TRACE = [0, None]


def trace_steps(n: int, logdir: Optional[str] = None) -> str:
    """Start a ``jax.profiler`` device trace (xplane; TensorBoard/XProf)
    and stop it automatically after the next ``n`` recorded training steps.
    ``logdir`` defaults to ``MXNET_TPU_TRACE_DIR``, else a temp directory.
    The existing ``TraceAnnotation`` region names (``mx.dp.step``,
    ``mx.comm.*``) land inside the captured timeline, so ledger rows map
    onto trace spans by name. Returns the logdir."""
    import tempfile

    import jax
    d = logdir or str(env.get("MXNET_TPU_TRACE_DIR")) or None
    if not d:
        d = tempfile.mkdtemp(prefix="mx_trace_")
    import os as _os
    _os.makedirs(d, exist_ok=True)
    with _LOCK:
        if _TRACE[1] is not None:
            raise MXNetError(f"a trace is already active in {_TRACE[1]}")
        jax.profiler.start_trace(d)
        _TRACE[0], _TRACE[1] = max(int(n), 1), d
    return d


def trace_active() -> Optional[str]:
    """The active capture's logdir, or None."""
    return _TRACE[1]


def _trace_tick(steps: int = 1):
    """Count recorded steps against an armed capture; stops the trace when
    the budget is spent. Host-side bookkeeping only."""
    stop = False
    with _LOCK:
        if _TRACE[1] is None:
            return
        _TRACE[0] -= steps
        if _TRACE[0] <= 0:
            _TRACE[1] = None
            stop = True
    if stop:
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Training-step recording
# ---------------------------------------------------------------------------

# source -> (last perf_counter stamp, engine flops_executed at that stamp)
_STEP_ANCHOR: Dict[str, Tuple[float, float]] = {}


def _engine_flops() -> float:
    try:
        from .. import engine as _engine
        return float(_engine.cache_stats().get("flops_executed", 0.0))
    except Exception:
        return 0.0


def record_step(examples: int, source: str = "trainer", steps: int = 1,
                seconds: Optional[float] = None,
                flops_per_step: Optional[float] = None,
                lr: Optional[float] = None,
                dispatch_wait_seconds: Optional[float] = None):
    """Record `steps` completed training steps covering `examples` examples.

    With seconds=None the duration is the wall time since the previous
    record_step for the same `source` (the first call only anchors the
    clock) — the once-per-iteration sync point measures the WHOLE loop
    (forward+backward+update), the way Speedometer does. flops_per_step
    defaults to the engine's executed-FLOPs counter delta (compiled-artifact
    cost_analysis accounting), which yields the MFU estimate.

    ``dispatch_wait_seconds`` is the caller's CUMULATIVE DispatchWindow
    block time (trainers pass ``self._window.wait_seconds``): the goodput
    ledger deltas it into the step's dispatch_backpressure category —
    a host float the window already accumulated, no extra clock read.
    """
    now = time.perf_counter()
    eng_flops = _engine_flops() if flops_per_step is None else 0.0
    with _LOCK:
        prev = _STEP_ANCHOR.get(source)
        _STEP_ANCHOR[source] = (now, eng_flops)
    if seconds is None:
        if prev is None:
            return
        seconds = now - prev[0]
    if flops_per_step is None:
        flops = eng_flops - (prev[1] if prev else eng_flops)
    else:
        flops = flops_per_step * steps

    counter("mx_train_steps_total", "Completed training steps",
            ("source",)).labels(source).inc(steps)
    counter("mx_train_examples_total", "Examples consumed by training",
            ("source",)).labels(source).inc(examples)
    histogram("mx_train_step_seconds", "Wall time per training step",
              ("source",)).labels(source).observe(seconds / max(steps, 1))
    # the SLO-ladder twin of mx_train_step_seconds: same documented
    # DEFAULT_LATENCY_BUCKETS exposition as serving, so training p50/p99
    # step latency is a real histogram_quantile() query too. Recorded at
    # the same window-admission pace (completion-paced, sync-free).
    host = _host_label()
    if host:
        histogram("mx_step_seconds",
                  "Training-step latency on the documented "
                  "DEFAULT_LATENCY_BUCKETS ladder",
                  ("source", "host"), buckets=DEFAULT_LATENCY_BUCKETS) \
            .labels(source, host).observe(seconds / max(steps, 1))
    else:
        histogram("mx_step_seconds",
                  "Training-step latency on the documented "
                  "DEFAULT_LATENCY_BUCKETS ladder",
                  ("source",), buckets=DEFAULT_LATENCY_BUCKETS) \
            .labels(source).observe(seconds / max(steps, 1))
    _trace_tick(steps)
    if tracing._ENABLED:
        # feed the anomaly watchdog the per-step seconds this function just
        # computed — host-side values only, no extra sync or clock read
        tracing.watch_step_time(seconds / max(steps, 1), source=source)
    if seconds > 0:
        gauge("mx_train_examples_per_second",
              "Training throughput over the last recorded window",
              ("source",)).labels(source).set(examples / seconds)
    if flops > 0:
        counter("mx_flops_total",
                "Estimated FLOPs executed (cost_analysis accounting)",
                ("source",)).labels(source).inc(flops)
        if seconds > 0:
            fps = flops / seconds
            gauge("mx_model_flops_per_second",
                  "Estimated achieved FLOP/s", ("source",)).labels(source) \
                .set(fps)
            gauge("mx_mfu",
                  "Estimated model FLOPs utilization vs peak_flops() "
                  "(see docs/observability.md for CPU caveats)",
                  ("source",)).labels(source).set(fps / peak_flops())
    if lr is not None:
        gauge("mx_learning_rate", "Optimizer learning rate",
              ("source",)).labels(source).set(lr)
    if goodput._ENABLED:
        # the goodput waterfall rides THIS funnel: one flag check while
        # disarmed, and armed attribution consumes only cumulative stamps
        # the layers already took (no extra syncs or clock reads)
        goodput._on_step(source, seconds, steps,
                         dispatch_wait=dispatch_wait_seconds)
    sample_memory()


def set_epoch(epoch: int, source: str = "module"):
    gauge("mx_epoch", "Current training epoch", ("source",)) \
        .labels(source).set(epoch)


@contextmanager
def timed(phase: str, source: str = ""):
    """Time a coarse phase (fit/eval/export) into mx_phase_seconds."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        histogram("mx_phase_seconds", "Coarse phase wall time",
                  ("phase", "source"),
                  buckets=tuple(1e-3 * (4 ** i) for i in range(10))) \
            .labels(phase, source).observe(time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Collective-comms accounting
# ---------------------------------------------------------------------------

_tls = threading.local()


def payload_bytes(x) -> int:
    """Bytes in an NDArray / raw array / (nested) list/tuple of them."""
    if x is None:
        return 0
    if isinstance(x, (list, tuple)):
        return sum(payload_bytes(v) for v in x)
    size = getattr(x, "size", None)
    dtype = getattr(x, "dtype", None)
    if size is None or dtype is None:
        return 0
    try:
        import numpy as _np
        return int(size) * _np.dtype(str(dtype)).itemsize
    except Exception:
        return int(size) * 4


def record_comm(op: str, nbytes: int, store: str = "",
                seconds: Optional[float] = None, calls: int = 1,
                axis: str = ""):
    """Account one collective/comm operation (bytes moved, calls, time).

    `op` labels the collective kind — "allreduce", "reduce_scatter",
    "all_gather", the pipeline schedule's "ppermute" activation hops and
    "pipeline_grad_psum", "tp_weight_all_gather", the compute-partitioned
    TP path's "tp_act_psum"/"tp_act_all_gather"/"tp_act_psum_scatter",
    kvstore "push"/"pull" — so per-kind wire accounting survives
    aggregation (the check_instrumentation gate pins the trainer paths
    that must book here). `axis` names the MESH axis the collective
    crosses ("dp"/"tp"/"sp"/"pp"/"ep") so the byte totals split per
    parallelism lane — the signal that distinguishes "the dp grad
    allreduce" from "the tp weight gather". Family.get(op, store)
    aggregates over the trailing labels, so two-label readers see totals
    unchanged. On a multi-process job the process rank rides as a trailing
    "host" label (same prefix-aggregation contract; comm_axis_bytes and
    the goodput ledger find "axis" by name and are unaffected)."""
    h = _host_label()
    names = ("op", "store", "axis", "host") if h else ("op", "store", "axis")
    vals = (op, store, axis, h) if h else (op, store, axis)
    counter("mx_comm_bytes_total", "Bytes moved by comm/collective ops",
            names).labels(*vals).inc(max(int(nbytes), 0))
    counter("mx_comm_calls_total", "Comm/collective operations",
            names).labels(*vals).inc(calls)
    if seconds is not None:
        counter("mx_comm_seconds_total", "Wall seconds inside comm ops",
                names).labels(*vals).inc(seconds)


def comm_bytes_by_axis() -> Dict[str, float]:
    """mx_comm_bytes_total summed per mesh axis ("" for traffic booked
    without one), the "axis" label found by name."""
    fam = get_metric("mx_comm_bytes_total")
    if fam is None or "axis" not in fam.labelnames:
        return {}
    at = fam.labelnames.index("axis")
    with _LOCK:
        series = list(fam._series.items())
    out: Dict[str, float] = {}
    for lv, s in series:
        out[lv[at]] = out.get(lv[at], 0.0) + getattr(s, "value", 0.0)
    return out


def comm_axis_bytes(axis: str) -> float:
    """Total mx_comm_bytes_total booked on one mesh axis's lane. The
    partitioned-TP acceptance check reads comm_axis_bytes("tp") A/B between
    the weight-sharded and partitioned steps."""
    return comm_bytes_by_axis().get(axis, 0.0)


def record_optimizer_state(nbytes: int, source: str = "trainer"):
    """Per-replica optimizer-state footprint gauge. The replicated update
    reports the full state; the ZeRO-style sharded update
    (DataParallelTrainer(zero_update=True)) reports ~1/dp of it — the
    memory-side acceptance signal of arXiv:2004.13336."""
    gauge("mx_optimizer_state_per_replica_bytes",
          "Optimizer-state bytes held per replica",
          ("source",)).labels(source).set(int(nbytes))


# ---------------------------------------------------------------------------
# Input-pipeline / dispatch-overlap instrumentation (engine/async_feed)
# ---------------------------------------------------------------------------

def record_feed_depth(depth: int, source: str = "feed"):
    """Batches currently staged on device by a DeviceFeed. A depth pinned
    at 0 while the device is busy means the producer keeps up exactly; a
    full queue means H2D is fully hidden behind compute."""
    gauge("mx_feed_queue_depth",
          "Device-resident batches staged ahead by the async feed",
          ("source",)).labels(source).set(int(depth))


def record_feed_stall(total_seconds: float, source: str = "feed"):
    """Cumulative consumer time spent waiting on an empty feed queue.
    Rendered as a counter (monotone per feed instance): nonzero growth
    means the input pipeline, not the device, bounds throughput."""
    gauge("mx_feed_stall_seconds_total",
          "Cumulative seconds the consumer stalled on an empty feed queue",
          ("source",)).labels(source).set(float(total_seconds))


def record_inflight(n: int, source: str = "step"):
    """Dispatched-but-incomplete training steps in a DispatchWindow."""
    gauge("mx_inflight_steps",
          "Training steps dispatched but not yet retired by the bounded "
          "in-flight window", ("source",)).labels(source).set(int(n))


def record_dispatch_wait(total_seconds: float, source: str = "step"):
    """Cumulative seconds a DispatchWindow blocked in admit()/drain()
    waiting on in-flight step completion (``window.wait_seconds``, a host
    float the window already accumulated — set-style like
    record_feed_stall). The goodput ledger's dispatch_backpressure
    category deltas this family when the trainer doesn't hand its window
    wait down through record_step directly."""
    gauge("mx_dispatch_wait_seconds_total",
          "Cumulative seconds the bounded in-flight window blocked on "
          "step completion", ("source",)).labels(source) \
        .set(total_seconds)


# ---------------------------------------------------------------------------
# Elastic fault tolerance (mxnet_tpu/elastic — docs/checkpointing.md)
# ---------------------------------------------------------------------------

def record_checkpoint_save(seconds: float, nbytes: int,
                           source: str = "elastic"):
    """Booked by the snapshot writer ON COMMIT (the background thread,
    never the step path): wall time from save() dispatch to manifest
    commit, and payload bytes this process wrote. save_seconds trending
    toward the snapshot interval means cadence outruns write bandwidth —
    the tuning signal docs/checkpointing.md's cadence section reads."""
    h = _host_label()
    if h:
        gauge("mx_checkpoint_save_seconds",
              "Wall seconds of the last snapshot, dispatch to manifest "
              "commit", ("source", "host")).labels(source, h) \
            .set(float(seconds))
    else:
        gauge("mx_checkpoint_save_seconds",
              "Wall seconds of the last snapshot, dispatch to manifest "
              "commit", ("source",)).labels(source).set(float(seconds))
    # the cumulative twin the goodput waterfall deltas into its
    # "snapshot" category (the last-save gauge above can't be deltaed)
    counter("mx_checkpoint_save_seconds_total",
            "Cumulative snapshot wall seconds written by this process",
            ("source",)).labels(source).inc(max(float(seconds), 0.0))
    counter("mx_checkpoint_bytes_total",
            "Cumulative snapshot payload bytes written by this process",
            ("source",)).labels(source).inc(int(nbytes))


def record_resume(outcome: str, source: str = "elastic"):
    """Boot-path outcome counter: ``fresh`` (no snapshot found),
    ``resumed`` (same mesh + step program), ``resharded`` (state was
    re-laid-out onto a different mesh). A fleet restarting after a
    preemption should show resumed/resharded, never fresh — fresh after
    a kill means snapshots are not landing."""
    counter("mx_resume_total",
            "Worker boots by restore outcome",
            ("outcome", "source")).labels(outcome, source).inc()


# ---------------------------------------------------------------------------
# MoE recipes (mxnet_tpu/recipes/moe.py — docs/large_models.md)
# ---------------------------------------------------------------------------

def record_moe_dropped(n: int, source: str = "moe"):
    """Capacity-overflow (token, choice) assignments dropped by top-k
    gating, summed over experts and devices. Booked at drain()/sync()
    from device handles the step path accumulated — never per step, so
    the counter costs no host sync on the hot path. A sustained rate
    above a few percent of tokens/step means capacity_factor is too low
    or the router collapsed (check it against the aux loss — see
    docs/large_models.md)."""
    counter("mx_moe_dropped_tokens_total",
            "Tokens dropped by MoE capacity overflow",
            ("source",)).labels(source).inc(max(int(n), 0))


# ---------------------------------------------------------------------------
# Serving SLO instrumentation (mxnet_tpu/serving — docs/serving.md)
# ---------------------------------------------------------------------------

# The documented default request-latency ladder: 1 ms .. 10 s, roughly
# log-spaced, so the cumulative `_bucket` exposition supports real
# histogram_quantile() p50/p99 queries for interactive inference. The
# serving layer records END-TO-END latency (enqueue -> result ready on
# host) into this ladder; pass ``buckets=`` to ``histogram()`` for a
# different SLO range.
DEFAULT_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                           0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def record_serving_enqueue(model: str, rows: int = 1):
    """Account one request admitted to a model's serving queue."""
    counter("mx_serving_requests_total", "Inference requests enqueued",
            ("model",)).labels(model).inc()
    counter("mx_serving_request_rows_total",
            "Rows (examples) across enqueued inference requests",
            ("model",)).labels(model).inc(max(int(rows), 0))


def record_serving_queue_depth(model: str, depth: int):
    """Requests waiting in the continuous batcher (set on every enqueue and
    every batch take, so scrapes see the live depth)."""
    gauge("mx_serving_queue_depth",
          "Requests waiting in the serving queue",
          ("model",)).labels(model).set(int(depth))


def record_serving_queue_wait(model: str, seconds: float):
    """Account one request's queue wait (enqueue -> batch take), the
    queueing share of mx_serving_request_seconds. Same SLO ladder, and
    derived from the two timestamps the batcher already stamps (t_enqueue,
    the take-time perf_counter read) — no new clock reads on the hot path.
    request_seconds p99 high while queue_wait p99 is low means the device,
    not admission, is the bottleneck; both high means queueing."""
    histogram("mx_serving_queue_wait_seconds",
              "Request queue wait (enqueue to batch take)",
              ("model",), buckets=DEFAULT_LATENCY_BUCKETS) \
        .labels(model).observe(float(seconds))


def record_serving_dispatch(model: str, bucket: int, rows: int):
    """Account one padded batch handed to the compiled per-bucket artifact:
    occupancy (real vs padded rows) is the batch-formation efficiency
    signal the bucket-set tuning loop reads (docs/serving.md)."""
    bucket = max(int(bucket), 1)
    rows = max(int(rows), 0)
    counter("mx_serving_batches_total", "Batches dispatched to the device",
            ("model", "bucket")).labels(model, str(bucket)).inc()
    counter("mx_serving_batch_rows_total",
            "Real (non-padding) rows dispatched",
            ("model", "bucket")).labels(model, str(bucket)).inc(rows)
    counter("mx_serving_padded_rows_total",
            "Padding rows dispatched (bucket size minus real rows)",
            ("model", "bucket")).labels(model, str(bucket)) \
        .inc(max(bucket - rows, 0))
    gauge("mx_serving_batch_occupancy",
          "Real-row fraction of the last dispatched bucket",
          ("model", "bucket")).labels(model, str(bucket)) \
        .set(rows / bucket)


def record_serving_completion(model: str, seconds: float, rows: int = 1,
                              status: str = "ok"):
    """Account one completed request: end-to-end latency (enqueue ->
    result on host) into the DEFAULT_LATENCY_BUCKETS histogram — p50/p99
    derive from the cumulative `_bucket` lines — plus response/row
    counters (per-model throughput = rate(mx_serving_response_rows_total))."""
    histogram("mx_serving_request_seconds",
              "End-to-end request latency (enqueue to result on host)",
              ("model",), buckets=DEFAULT_LATENCY_BUCKETS) \
        .labels(model).observe(float(seconds))
    counter("mx_serving_responses_total", "Completed inference requests",
            ("model", "status")).labels(model, status).inc()
    counter("mx_serving_response_rows_total",
            "Rows returned across completed requests",
            ("model",)).labels(model).inc(max(int(rows), 0))


# ---------------------------------------------------------------------------
# Reliability plane (mxnet_tpu/faults + hardened paths — docs/reliability.md)
# ---------------------------------------------------------------------------

def record_fault_injected(point: str):
    """Account one fault fired by the deterministic injection plane. In a
    chaos run this is the denominator every recovery metric divides by:
    mx_io_retries_total/mx_faults_injected_total ≈ 1 means every injected
    IO fault was absorbed by a retry."""
    counter("mx_faults_injected_total",
            "Faults fired by the injection plane (mxnet_tpu.faults)",
            ("point",)).labels(point).inc()


def record_io_retry(point: str):
    """Account one transient-IO retry (backoff+jitter) at a named fault
    point. A nonzero steady-state rate without armed chaos means the
    snapshot filesystem is genuinely flaky — page before it exhausts
    MXNET_TPU_IO_RETRIES and surfaces as failed snapshots."""
    counter("mx_io_retries_total",
            "Transient IO failures retried with exponential backoff",
            ("point",)).labels(point).inc()


def record_request_shed(model: str, reason: str = "queue_full"):
    """Account one serving request rejected or abandoned by admission
    control: ``queue_full`` (max_queue bound, HTTP 503), ``deadline``
    (expired while queued, HTTP 504), ``cancelled`` (caller timed out and
    reclaimed the queue slot). Shed rate vs mx_serving_requests_total is
    the overload signal the autoscaler should act on."""
    counter("mx_requests_shed_total",
            "Serving requests shed by admission control or deadlines",
            ("model", "reason")).labels(model, reason).inc()


def record_feed_producer_leak(source: str = "feed"):
    """Account one DeviceFeed producer thread abandoned after the join
    timeout (blocked inside the wrapped source). Each leak pins a thread
    until the source unblocks — a growing counter means the source needs
    an interruptible read or a larger MXNET_TPU_FEED_JOIN_TIMEOUT."""
    counter("mx_feed_producer_leaks_total",
            "DeviceFeed producer threads abandoned after join timeout",
            ("source",)).labels(source).inc()


def record_feed_producer_restart(source: str = "feed"):
    """Account one bounded DeviceFeed producer restart after a transient
    source error (supervised feed, MXNET_TPU_FEED_RESTARTS)."""
    counter("mx_feed_producer_restarts_total",
            "Bounded DeviceFeed producer restarts on transient errors",
            ("source",)).labels(source).inc()


def record_hosts_live(n: int, generation: int, source: str = "elastic"):
    """Multi-host control-plane group view (elastic/coordinator.py):
    hosts with a fresh membership lease, and the monotonic generation
    epoch. mx_hosts_live below the fleet size pages a dead host;
    mx_coordinator_generation climbing without deploys means hosts are
    flapping (lease expiry + rejoin) — check heartbeat IO latency."""
    gauge("mx_hosts_live",
          "Hosts with a fresh coordinator membership lease",
          ("source",)).labels(source).set(int(n))
    gauge("mx_coordinator_generation",
          "Monotonic group-membership generation epoch",
          ("source",)).labels(source).set(int(generation))


def record_commit_barrier(seconds: float, source: str = "elastic"):
    """Account one host's wait in the two-phase cross-host snapshot
    commit barrier (its own ready marker posted -> global manifest
    visible). p99 approaching the straggler deadline means one host's
    shard writes are outliers — the next incident is a straggler abort
    (mx_snapshot_failures_total{source="straggler"})."""
    histogram("mx_commit_barrier_seconds",
              "Cross-host snapshot commit barrier wait per host",
              ("source",), buckets=DEFAULT_LATENCY_BUCKETS) \
        .labels(source).observe(float(seconds))


def record_hang_watchdog(what: str):
    """Account one hang-watchdog firing (elastic/coordinator.py
    HangWatchdog): a wall-clock deadline expired on a blocking section
    (``drain``, ``commit``, ``heartbeat`` staleness). The process dumps
    the flight recorder and exits with a diagnosis — any increment is an
    incident; the NDJSON dump next to the job is the evidence."""
    counter("mx_hang_watchdog_fires_total",
            "Hang-watchdog firings (flight recorder dumped, process exited)",
            ("what",)).labels(what).inc()


@contextmanager
def comm_scope(op: str, nbytes: int, store: str = ""):
    """Time + count a comm region and annotate it into the device trace
    (jax.profiler.TraceAnnotation -> visible in xplane/TensorBoard).
    Re-entrant: nested scopes (pushpull -> push -> pull) count once."""
    if getattr(_tls, "in_comm", False):
        yield
        return
    _tls.in_comm = True
    ann = None
    try:
        import jax
        ann = jax.profiler.TraceAnnotation(f"mx.comm.{op}")
        ann.__enter__()
    except Exception:
        ann = None
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        if ann is not None:
            ann.__exit__(None, None, None)
        _tls.in_comm = False
        record_comm(op, nbytes, store, seconds=t1 - t0)
        try:
            from .. import profiler as _profiler
            _profiler._record(op, "comm", t0, t1)
        except Exception:
            pass


def annotate(name: str):
    """A named region of host code: ``tracing.span(name)``, the one span
    primitive. Always a ``jax.profiler.TraceAnnotation`` (inert without a
    profiler session, inside the xplane timeline under one); with tracing
    armed also a span in the flight-recorder ring."""
    return tracing.span(name)


def instrument_comm(op: str):
    """Decorator for kvstore-style entry points `fn(self, key, value, ...)`:
    bytes-moved + timing + trace annotation when telemetry is enabled, one
    wrapper call + module-flag check when disabled."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kw):
            if not _ENABLED:
                return fn(self, *args, **kw)
            # args[0] is the key; the payload is the value/out argument
            payload = args[1] if len(args) > 1 \
                else kw.get("value", kw.get("out"))
            nbytes = payload_bytes(payload) or payload_bytes(kw.get("out"))
            with comm_scope(op, nbytes, getattr(self, "type", "")):
                return fn(self, *args, **kw)
        return wrapper
    return deco


# ---------------------------------------------------------------------------
# Memory watermarks
# ---------------------------------------------------------------------------

_mem_peak = 0.0


def sample_memory():
    """Sample live device-buffer bytes (jax.live_arrays) into
    mx_device_live_bytes / mx_device_peak_bytes. Called per recorded step;
    no-op when the runtime can't enumerate arrays."""
    global _mem_peak
    try:
        import jax
        live = float(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:
        return
    with _LOCK:  # max() is a read-modify-write; _LOCK is reentrant
        _mem_peak = max(_mem_peak, live)
        peak = _mem_peak
    gauge("mx_device_live_bytes",
          "Live device-buffer bytes at the last sample").set(live)
    gauge("mx_device_peak_bytes",
          "Peak sampled device-buffer bytes").set(peak)


# ---------------------------------------------------------------------------
# Export
# ---------------------------------------------------------------------------

def _sync_engine_stats():
    """Mirror the compilation-engine counters (and donation savings) into
    gauges at scrape time, so one scrape carries the whole picture; the
    per-region roofline ledger refreshes its gauges here too."""
    from . import roofline as _roofline
    _roofline.export_metrics()
    try:
        from .. import engine as _engine
        st = _engine.cache_stats()
    except Exception:
        return
    for k, v in st.items():
        if isinstance(v, (int, float)):
            gauge(f"mx_compilation_{k}",
                  "Compilation-engine counter (engine.cache_stats)").set(v)
    total_dropped = sum(f.dropped for f in _FAMILIES.values())
    if total_dropped:
        gauge("mx_telemetry_dropped_series_total",
              "Series dropped by the per-family cardinality cap") \
            .set(total_dropped)


def collect() -> Dict[str, Any]:
    _sync_engine_stats()
    with _LOCK:
        fams = list(_FAMILIES.items())
    return {name: fam._as_dict() for name, fam in fams}


def scrape() -> str:
    """Prometheus text exposition of every registered metric, including the
    compilation-cache counters mirrored from engine.cache_stats()."""
    _sync_engine_stats()
    lines: List[str] = []
    with _LOCK:
        fams = list(_FAMILIES.values())
    for fam in fams:
        fam._render(lines)
    return "\n".join(lines) + "\n"


def scrape_json(indent=None) -> str:
    return json.dumps(collect(), indent=indent, sort_keys=True)


def report(reset_profiler: bool = False) -> str:
    """Human-readable status: telemetry summary + the profiler aggregate
    table + compilation stats — the unified `mx.telemetry.report()` view."""
    from .. import profiler as _profiler
    lines = ["=== telemetry ==="]
    for name, d in sorted(collect().items()):
        for s in d["series"]:
            lab = ",".join(f"{k}={v}" for k, v in s["labels"].items() if v)
            key = f"{name}{{{lab}}}" if lab else name
            if d["type"] == "histogram":
                cnt = s["count"]
                avg = s["sum"] / cnt if cnt else 0.0
                lines.append(f"{key:<56}count={cnt:<10}avg={avg:.6g}")
            else:
                lines.append(f"{key:<56}{s['value']:.6g}")
    lines.append("")
    lines.append("=== compilation (engine.cache_stats) ===")
    lines.append(json.dumps(_profiler.compilation_stats(), sort_keys=True,
                            default=str))
    lines.append("")
    lines.append("=== profiler aggregate stats ===")
    lines.append(_profiler.dumps(reset=reset_profiler))
    return "\n".join(lines)


def _family_snapshot(name: str) -> Dict[str, float]:
    """{joined-label-values: value} for one family (statusz rendering)."""
    fam = get_metric(name)
    if fam is None:
        return {}
    with _LOCK:
        series = list(fam._series.items())
    return {",".join(lv) or "_": getattr(s, "value", getattr(s, "sum", 0.0))
            for lv, s in series}


def statusz(extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The /statusz debug snapshot: config fingerprints (every declared
    MXNET_* knob whose live value differs from its default), compilation-
    cache stats, fault-plane arming, queue depth / in-flight gauges,
    anomaly counts, and the trailing flight-recorder entries. Served by
    both start_http_server() and serving.Server.start_http(); ``extra``
    merges caller-side sections (the serving server adds its model list)."""
    config = {}
    for name, (default, _typ, _doc) in sorted(env.items().items()):
        live = env.get(name)
        if live != default:
            config[name] = live
    try:
        from .. import engine as _engine
        compilation = {k: v for k, v in _engine.cache_stats().items()
                       if isinstance(v, (int, float, str))}
    except Exception:
        compilation = {}
    try:
        from .. import faults as _faults
        fault_plane = {"active": bool(_faults._ACTIVE),
                       "armed": _faults.armed()}
    except Exception:
        fault_plane = {}
    # group view only when the control plane is actually in use — the
    # import must not drag the coordinator in on single-host jobs
    coordinator: Dict[str, Any] = {}
    _coord_mod = sys.modules.get("mxnet_tpu.elastic.coordinator")
    if _coord_mod is not None:
        try:
            coordinator = _coord_mod.statusz_view()
        except Exception:
            coordinator = {}
    d: Dict[str, Any] = {
        "telemetry_enabled": _ENABLED,
        "tracing_enabled": tracing._ENABLED,
        "device_trace_active": trace_active(),
        "config": config,
        "compilation": compilation,
        "faults": fault_plane,
        "serving_queue_depth": _family_snapshot("mx_serving_queue_depth"),
        "inflight_steps": _family_snapshot("mx_inflight_steps"),
        "anomalies": _family_snapshot("mx_anomalies_total"),
        # compiled-HLO hazard audit (engine/hlo_audit.py): per-{kind,region}
        # hazard counts for every artifact built this process — the same
        # series Prometheus scrapes as mx_hlo_hazards_total
        "hlo_audit": _family_snapshot("mx_hlo_hazards_total"),
        "recorder_events": tracing.recent(),
        "coordinator": coordinator,
        "goodput": goodput.statusz_view(),
    }
    if extra:
        d.update(extra)
    return d


# ---------------------------------------------------------------------------
# HTTP /metrics endpoint (Prometheus scrape target)
# ---------------------------------------------------------------------------

_http_server = [None]


def start_http_server(port: int = 0, addr: str = "127.0.0.1") -> int:
    """Serve GET /metrics (Prometheus text), /metrics.json, /statusz, and
    /healthz on a daemon thread; returns the bound port (port=0 picks a
    free one)."""
    import http.server

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.startswith("/metrics.json"):
                body = scrape_json().encode()
                ctype = "application/json"
            elif self.path.startswith("/metrics"):
                body = scrape().encode()
                ctype = "text/plain; version=0.0.4"
            elif self.path.startswith("/statusz"):
                body = json.dumps(statusz(), default=str).encode()
                ctype = "application/json"
            elif self.path.startswith("/healthz"):
                body = b'{"status": "ok"}'
                ctype = "application/json"
            else:
                self.send_response(404)
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer((addr, port), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True,
                         name="mx-telemetry-http")
    t.start()
    with _LOCK:
        _http_server[0] = srv
    return srv.server_address[1]


def stop_http_server():
    with _LOCK:
        srv, _http_server[0] = _http_server[0], None
    if srv is not None:
        srv.shutdown()
        srv.server_close()


# the per-region roofline ledger (mx.telemetry.roofline.report() / rows();
# imported last — it only pulls stdlib at module scope)
from . import roofline  # noqa: E402
# the span-tracing plane + flight recorder (same stdlib-only constraint;
# record_step and statusz() above reference it at call time)
from . import tracing  # noqa: E402
# the goodput waterfall ledger (stdlib-only at module scope; record_step
# and statusz() above reference it at call time)
from . import goodput  # noqa: E402
