"""Compilation engine: process-wide executable cache + buffer-donation policy.

The reference framework funnels every training step through ONE compiled
CachedOp/GraphExecutor artifact with planned memory reuse
(src/imperative/cached_op.h, src/executor/graph_executor.cc). This module is
the jax_graft analog of that shared engine state:

  - a process-wide compilation cache keyed on (graph-structure fingerprint,
    input signature, train flag) so N instances of the same model share one
    set of XLA executables instead of compiling privately per instance
    (gluon HybridBlock and symbol Executor both publish into it);
  - ``enable_compile_cache()``: the one place an entry point turns on jax's
    persistent on-disk compilation cache. ``JAX_COMPILATION_CACHE_DIR``
    places it from outside; unset, it is ``<checkout>/.jax_cache``;
  - the buffer-donation policy used by the optimizer update kernels
    (weight/optimizer-state aliasing a la arXiv:2004.13336's weight-update
    sharding — donated inputs alias their outputs in-place on TPU);
  - hit/miss/trace/compile-time/donation counters surfaced through
    ``profiler.compilation_stats()`` so cache regressions are visible;
  - one jax.monitoring listener (``_BuildListener``) that leaves a ``build``
    record in the tracing ring for every program the process traces, lowers,
    compiles or loads, and sums their seconds into ``compile_seconds``.
"""
from __future__ import annotations

import hashlib
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

from . import hlo_audit

__all__ = ["lookup", "insert", "clear_compilation_cache", "cache_stats",
           "enable_compile_cache", "persistent_cache_dir",
           "hlo_audit",
           "reset_stats", "donation_enabled", "record_donation",
           "compile_timer", "record_trace", "record_execution",
           "estimate_cost", "structural_fingerprint", "graph_fingerprint",
           "config_fingerprint", "region_digest", "pin", "unpin",
           "pinned_count",
           "async_feed", "DeviceFeed", "DispatchWindow", "PendingScalar"]


def __getattr__(name):
    # the async feed pulls in jax/ndarray machinery; keep it off the
    # import path of the light engine counters (PEP 562, same idiom as
    # the package root)
    if name == "async_feed":
        import importlib
        mod = importlib.import_module(".async_feed", __name__)
        globals()[name] = mod
        return mod
    if name in ("DeviceFeed", "DispatchWindow", "PendingScalar"):
        from . import async_feed as _af
        val = getattr(_af, name)
        globals()[name] = val
        return val
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


_LOCK = threading.RLock()
_CACHE: Dict[Tuple, Any] = {}
# serving/predict artifacts pin their cache entries (refcounted) so a
# fingerprint-scoped invalidation — e.g. one model's clear_cache — cannot
# evict an executable another live Predictor/serving bucket depends on
_PINS: Dict[Tuple, int] = {}

_STATS = {
    "hits": 0,            # shared-cache lookups that returned an artifact
    "misses": 0,          # lookups that required a fresh build
    "traces": 0,          # python-level retraces of cached forwards
    "compiles": 0,        # artifact builds (one per miss that completed)
    "compile_seconds": 0.0,  # every stage of every build record (below):
                             # what the process spent tracing, lowering,
                             # compiling and loading programs, whoever built
    "fwd_executions": 0,  # compiled forward invocations (gluon cached path)
    "bwd_executions": 0,  # compiled pullback invocations (no fwd recompute)
    "donated_updates": 0, # optimizer update calls that donated buffers
    "step_executions": 0, # fused trainer-step artifact invocations
    "flops_executed": 0.0,  # cost_analysis FLOPs of executed artifacts
                            # (telemetry's MFU numerator; 0 when telemetry
                            # is off — costs are only captured then)
    "bytes_executed": 0.0,  # cost_analysis bytes-accessed of executed
                            # artifacts (the roofline ledger's bytes axis)
    "cost_capture_failures": 0,  # estimate_cost lowerings that failed
                                 # (mirrored to mx_cost_capture_failures_total)
}


# ---------------------------------------------------------------------------
# Persistent on-disk XLA cache
# ---------------------------------------------------------------------------

# fixed, derived from the package's own location: the directory is part of
# the cache key, so a path that moves between runs never hits
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache for this process and
    return the directory in use. Entry points (bench.py, chip_smoke.py,
    the benchmark/ scripts) call this once, before their first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and
    the directory is NOT touched here; otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    # the default 1 s floor would skip the small serving/kernel artifacts
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax.config.jax_compilation_cache_dir


def persistent_cache_dir() -> Optional[str]:
    """The directory jax's persistent compilation cache writes to, or None
    when it is off."""
    import jax
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# Shared executable cache
# ---------------------------------------------------------------------------

def lookup(key: Tuple):
    """Fetch a shared artifact; counts a hit or a miss."""
    with _LOCK:
        ent = _CACHE.get(key)
        if ent is None:
            _STATS["misses"] += 1
        else:
            _STATS["hits"] += 1
        return ent


def insert(key: Tuple, artifact):
    with _LOCK:
        _CACHE[key] = artifact
    return artifact


def clear_compilation_cache(fingerprint=None, force=False):
    """Drop shared executables — all of them, or only the entries whose key
    carries `fingerprint` (HybridBlock.clear_cache uses the latter so one
    block's invalidation doesn't flush unrelated models). Entries pinned by
    live Predictor/serving artifacts survive unless ``force=True`` (tests
    that must reset the world completely)."""
    with _LOCK:
        if fingerprint is None:
            victims = list(_CACHE)
        else:
            victims = [k for k in _CACHE if fingerprint in k]
        for k in victims:
            if not force and _PINS.get(k):
                continue
            del _CACHE[k]
        if force:
            if fingerprint is None:
                _PINS.clear()
            else:
                for k in [k for k in _PINS if fingerprint in k]:
                    del _PINS[k]


def pin(key: Tuple) -> None:
    """Refcount-pin a cache entry against non-forced invalidation. A serving
    artifact holds one pin per bucket; ``Predictor.reshape`` releases the
    old shape's pin when it rebinds (never leaks it)."""
    with _LOCK:
        if key in _CACHE:
            _PINS[key] = _PINS.get(key, 0) + 1


def unpin(key: Tuple) -> None:
    """Release one pin; the entry becomes evictable at refcount zero."""
    with _LOCK:
        n = _PINS.get(key, 0)
        if n <= 1:
            _PINS.pop(key, None)
        else:
            _PINS[key] = n - 1


def pinned_count() -> int:
    """Number of distinct pinned cache entries (serving-resident artifacts)."""
    with _LOCK:
        return len(_PINS)


def cache_size() -> int:
    with _LOCK:
        return len(_CACHE)


def cache_stats() -> Dict[str, Any]:
    with _LOCK:
        st = dict(_STATS)
        st["artifacts"] = len(_CACHE)
        st["pinned"] = len(_PINS)
        st["persistent_cache_dir"] = persistent_cache_dir()
        return st


def reset_stats():
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if isinstance(_STATS[k], float) else 0


def _bump(key, n=1):
    with _LOCK:
        _STATS[key] += n


def record_trace():
    _bump("traces")


def record_execution(kind: str, flops: float = 0.0,
                     bytes_accessed: float = 0.0, region: str = None,
                     steps: int = 1, estimated: bool = False,
                     cost: Dict[str, float] = None):
    """Account ``steps`` executions of a compiled artifact.

    This is the ONE funnel both FLOPs accounts flow through: the aggregate
    ``flops_executed``/``bytes_executed`` counters (telemetry's MFU
    numerator) and — when ``region`` is given and telemetry is enabled —
    the per-region roofline ledger (telemetry/roofline.py), so the
    ledger's per-region sum always reconciles with the aggregate.
    ``estimated`` flags heuristic costs (the gluon bwd=2x-fwd fallback) so
    ledger rows built on them render distinguishably. Host arithmetic
    only; hot-path safe."""
    with _LOCK:
        if kind == "fwd":
            _STATS["fwd_executions"] += steps
        elif kind == "step":
            _STATS["step_executions"] += steps
        else:
            _STATS["bwd_executions"] += steps
        if flops:
            _STATS["flops_executed"] += flops
        if bytes_accessed:
            _STATS["bytes_executed"] += bytes_accessed
    if region is not None:
        from .. import telemetry as _telem
        if _telem._ENABLED:
            _telem.roofline.record(region, flops=flops,
                                   bytes_accessed=bytes_accessed,
                                   steps=steps, kind=kind,
                                   estimated=estimated, cost=cost)


# cost_analysis keys -> estimate_cost fields (operand-level "bytes
# accessedN{}" keys are folded into bytes_in/bytes_out below)
_COST_KEYS = (("flops", "flops"), ("bytes accessed", "bytes_accessed"),
              ("transcendentals", "transcendentals"))


def estimate_cost(jitted, *args, kind: str = "artifact",
                  region: Optional[str] = None) -> Dict[str, float]:
    """XLA cost-model + memory estimate for a jitted callable at example
    args: ``{"flops", "bytes_accessed", "bytes_in", "bytes_out",
    "transcendentals", "peak_memory_bytes", "temp_memory_bytes"}`` (keys
    present when the backend reports them; empty dict when it has no cost
    model). Captured ONCE per artifact at build time while telemetry is
    enabled — the AOT lower+compile shares XLA's compilation caches, and
    the result feeds the MFU gauge and the per-region roofline ledger.

    Lowering failures are COUNTED, not swallowed: the engine's
    ``cost_capture_failures`` stat and the ``mx_cost_capture_failures_total``
    counter (labeled by artifact kind) both tick, so a backend that stops
    reporting costs shows up on the dashboard instead of silently zeroing
    every ledger row."""
    try:
        compiled = jitted.lower(*args).compile()
        try:
            # post-lowering hazard audit (mxcheck, docs/static_analysis.md):
            # same AOT compile, one extra text scan per artifact. Donation
            # expectation is best-effort introspection of the jit wrapper;
            # the alias-pair count lands in the fingerprint either way and
            # tools/hlo_audit_gate.py diffs it.
            donate = bool(getattr(getattr(jitted, "_jit_info", None),
                                  "donate_argnums", ()) or ())
            hlo_audit.audit_compiled(
                compiled, kind=kind, region=region or kind,
                donation_expected=donate)
        except Exception:
            pass  # the audit must never fail a cost capture
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0] if c else {}
        out = {}
        for src, dst in _COST_KEYS:
            v = c.get(src)
            if v is not None and float(v) >= 0:
                out[dst] = float(v)
        bytes_in = bytes_out = 0.0
        for k, v in c.items():
            if k.startswith("bytes accessed") and k != "bytes accessed":
                if "out" in k:
                    bytes_out += float(v)
                else:
                    bytes_in += float(v)
        if bytes_in:
            out["bytes_in"] = bytes_in
        if bytes_out:
            out["bytes_out"] = bytes_out
        try:
            m = compiled.memory_analysis()
            if m is not None:
                temp = float(getattr(m, "temp_size_in_bytes", 0) or 0)
                out["temp_memory_bytes"] = temp
                out["peak_memory_bytes"] = temp + float(
                    getattr(m, "argument_size_in_bytes", 0) or 0) + float(
                    getattr(m, "output_size_in_bytes", 0) or 0)
        except Exception:
            pass  # memory analysis is best-effort extra detail
        return out
    except Exception:
        _bump("cost_capture_failures")
        from .. import telemetry as _telem
        if _telem._ENABLED:
            _telem.counter(
                "mx_cost_capture_failures_total",
                "estimate_cost lowerings that raised (regions fall back "
                "to zero/heuristic costs — see engine.cache_stats)",
                ("kind",)).labels(kind).inc()
        return {}


@contextmanager
def compile_timer(name: str = "build"):
    """Counts an artifact build (``compiles``) and times it for the
    profiler's aggregate table (category 'compilation'). The seconds are not
    added to ``compile_seconds``: the build records hold every program the
    construction builds, and would count it twice."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        _bump("compiles")
        try:
            from .. import profiler as _profiler
            _profiler._record(name, "compilation", t0, t1)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Build records: one for every program the process builds
# ---------------------------------------------------------------------------

# jax reports each stage of a build with the function's name
# (jax/_src/dispatch.py): a scalar at its start, a duration at its end
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": (0, "trace"),
           "/jax/core/compile/jaxpr_to_mlir_module_duration": (1, "lower"),
           "/jax/core/compile/backend_compile_duration": (2, "compile")}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class _BuildListener:
    """Puts jax.monitoring's events together, by thread, into one ``build``
    record a program and appends it to the tracing ring, armed or not::

        {"kind": "build", "name": "mx.build", "fun": "step", "ts": ..,
         "dur": .., "phases": {"trace": s, "lower": s, "compile": s,
         "cache_load": s}, "cache": "hit" | "miss" | "off", "thread": ident}

    ``ts`` is ``time.perf_counter`` at the first stage's start (stamped at
    the callback, the stage's duration taken off: jax's own stamps are
    ``time.time``); ``dur`` is the sum of the stages, what jax does between
    them left out. ``compile`` is the backend's duration less the persistent
    cache's retrieval on a hit, ``cache_load`` that retrieval; a stage jax
    did not report reads 0 (a function lowered again that was traced
    before; ``jitted.lower()`` without a compile). ``cache`` is ``"off"``
    where no cache directory is set. The record enters the ring when its
    first stage ends and later stages are added to it in place: the next
    stage in order of the same function on the same thread is the same
    program, anything else a new one. A stage that runs inside another on
    the same thread (a jitted function traced into an outer trace, an eager
    op under a trace) is part of that stage and leaves nothing of its own,
    so the records' seconds never overlap and sum to ``compile_seconds``.
    Armed, each stage is also a ``record_span`` child of the current span.
    What call a build ran under is found when read
    (``tracing.parent_of``)."""

    def __init__(self):
        self._tls = threading.local()

    def _state(self) -> Dict[str, Any]:
        st = self._tls.__dict__
        if not st:
            st.update(depth=0, build=None, stage=-1, asked=False, load=None)
        return st

    def on_scalar(self, event: str, value, **kw) -> None:
        if event in _STAGES:
            st = self._state()
            st["depth"] += 1
            if st["depth"] == 1:
                st["asked"], st["load"] = False, None

    def on_event(self, event: str, **kw) -> None:
        if event == _CACHE_ASKED:
            st = self._state()
            if st["depth"] == 1:
                st["asked"] = True

    def on_duration(self, event: str, secs: float, **kw) -> None:
        order, stage = _STAGES.get(event, (None, None))
        if stage is None:
            if event == _CACHE_RETRIEVAL:
                st = self._state()
                if st["depth"] == 1:
                    st["load"] = secs
            return
        st = self._state()
        # (a listener registered inside an open stage sees its end alone)
        st["depth"] = max(st["depth"] - 1, 0)
        if st["depth"]:
            return
        now = time.perf_counter()
        from ..telemetry import tracing
        fun = str(kw.get("fun_name", ""))
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]   # lower and compile name the function jit(f)
        build = st["build"]
        if build is not None and fun == "<unknown>":
            fun = build["fun"]   # a partial's lowering does not say its name
        if build is None or build["fun"] != fun or st["stage"] >= order:
            build = st["build"] = {
                "kind": "build", "name": "mx.build", "fun": fun,
                "ts": now - secs, "dur": 0.0,
                "phases": {"trace": 0.0, "lower": 0.0, "compile": 0.0,
                           "cache_load": 0.0},
                "cache": "off", "thread": threading.get_ident()}
            tracing._append(build)
        st["stage"] = order
        phases = build["phases"]
        if stage == "compile":
            load = st["load"]
            if load is not None:
                load = min(max(load, 0.0), secs)
                phases["cache_load"] = load
                phases["compile"] = secs - load
                build["cache"] = "hit"
            else:
                phases["compile"] = secs
                if st["asked"] and persistent_cache_dir():
                    build["cache"] = "miss"
            st["build"] = None
        else:
            phases[stage] = secs
        build["dur"] += secs
        _bump("compile_seconds", secs)
        tracing.record_span("mx.build." + stage, now - secs, now, fun=fun)


_BUILDS = _BuildListener()


def _listen_to_builds() -> None:
    import jax.monitoring as monitoring
    monitoring.register_scalar_listener(_BUILDS.on_scalar)
    monitoring.register_event_listener(_BUILDS.on_event)
    monitoring.register_event_duration_secs_listener(_BUILDS.on_duration)


# once, here: the module that owns cache_stats and the persistent cache
_listen_to_builds()


# ---------------------------------------------------------------------------
# Buffer donation policy
# ---------------------------------------------------------------------------

_donation_cache = {"value": None}


def donation_enabled() -> bool:
    """True when donate_argnums should be used for optimizer updates.
    MXNET_TPU_DONATION=0/1 overrides; otherwise enabled on accelerator
    backends (CPU ignores donation and would warn on every call)."""
    ov = os.environ.get("MXNET_TPU_DONATION")
    if ov is not None:
        return ov.lower() not in ("0", "false", "off")
    with _LOCK:
        if _donation_cache["value"] is None:
            try:
                import jax
                _donation_cache["value"] = \
                    jax.default_backend() not in ("cpu",)
            except Exception:
                _donation_cache["value"] = False
        return _donation_cache["value"]


def record_donation(n: int = 1):
    _bump("donated_updates", n)


# ---------------------------------------------------------------------------
# Graph-structure fingerprints
# ---------------------------------------------------------------------------

# bookkeeping attrs that vary per instance without changing the computation
_SKIP_ATTRS = {
    "_prefix", "_params", "_children", "_reg_params", "_scope",
    "_forward_hooks", "_forward_pre_hooks", "_empty_init_guard",
    "_active", "_flags", "_fingerprint_memo",
}

_SCALARS = (int, float, bool, str, bytes, type(None))


def _stable_value(v):
    """A deterministic token for a config attribute. Scalars and containers
    of scalars hash by value; anything opaque (callables, arrays, objects)
    hashes by identity so two blocks never falsely share executables."""
    if isinstance(v, _SCALARS):
        return repr(v)
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_stable_value(x) for x in v) + ")"
    if isinstance(v, dict):
        return "{" + ",".join(
            f"{k!r}:{_stable_value(v[k])}" for k in sorted(v, key=repr)) + "}"
    return f"id:{id(v)}"


def _block_config_items(block):
    items = []
    for k in sorted(vars(block)):
        if k in _SKIP_ATTRS or k.startswith("_cached"):
            continue
        v = vars(block)[k]
        if hasattr(v, "_deferred_init") or hasattr(v, "_reg_params"):
            continue  # params/children are fingerprinted structurally below
        items.append((k, _stable_value(v)))
    return items


def structural_fingerprint(block) -> str:
    """Deterministic digest of a Block tree: class, scalar config, parameter
    shapes/dtypes, children (recursively). Two instances of the same model
    definition produce the same fingerprint and therefore share compiled
    executables; prefixes/names are deliberately excluded."""
    h = hashlib.sha1()

    def walk(b):
        h.update(f"<{type(b).__module__}.{type(b).__qualname__}".encode())
        for k, v in _block_config_items(b):
            h.update(f"|{k}={v}".encode())
        for k, p in getattr(b, "_reg_params", {}).items():
            h.update(f"|p:{k}:{tuple(p.shape or ())}:{p.dtype}".encode())
        for k, c in getattr(b, "_children", {}).items():
            h.update(f"|c:{k}".encode())
            walk(c)
        h.update(b">")

    walk(block)
    return h.hexdigest()


def graph_fingerprint(text: str) -> str:
    """Digest of an explicit graph serialization (Symbol.tojson)."""
    return hashlib.sha1(text.encode()).hexdigest()


def config_fingerprint(**config) -> Tuple:
    """Deterministic token tuple for a trainer/executor configuration.
    Values go through ``_stable_value`` (scalars and containers by value,
    opaque objects by identity). The fused-step caches key on this so two
    configurations that must compile apart — e.g. distinct
    zero-update/bucket-size/comm-dtype settings — never share an artifact,
    while N instances of one configuration share a single executable."""
    return tuple((k, _stable_value(config[k])) for k in sorted(config))


def region_digest(*parts) -> str:
    """Stable short digest of a compile-key tuple, used for roofline-ledger
    region names (parallel/step_program.py): two configurations that compile
    apart ledger apart, N same-config trainers share one row."""
    return hashlib.sha1(repr(parts).encode()).hexdigest()[:6]
