"""Async device feed + bounded in-flight step dispatch.

The reference framework's heart is its asynchronous dependency engine
(src/engine/threaded_engine.h): Python pushes operations and never blocks;
reads/writes are versioned so the device pipeline stays full. On TPU the
XLA runtime already gives us async dispatch per computation — what is
missing is the *loop around the step*: host batch assembly, `device_put`,
and eager loss/metric reads each iteration serialize the pipeline
(arXiv:2301.13062 measures the dispatch-overlap this throws away). This
module is the TPU-native analog of that engine, in three parts:

  - **DeviceFeed** — wraps any ``DataIter`` / gluon ``DataLoader`` /
    iterable of batches, runs one background producer thread, and delivers
    batches already ``jax.device_put`` with the consumer's input sharding
    (replicated, or dp-sharded to match a ``DataParallelTrainer``), so the
    host->device copy of batch i+1 overlaps the compute of batch i. Queue
    depth is ``MXNET_TPU_FEED_DEPTH`` (default 2). The ``device_put`` is
    *explicit*, so ``sanitize.guard()``'s ``transfer_guard("disallow")``
    stays clean in the dispatch path. Batch order is exactly the wrapped
    iterator's order (single producer, FIFO queue), including across
    ``reset()`` and a mid-epoch ``StopIteration``.
  - **DispatchWindow** — the bounded in-flight window: trainers ``admit()``
    each dispatched step's output handle and the window blocks
    (``block_until_ready``) on the (i-K)th step once more than
    ``MXNET_TPU_INFLIGHT_STEPS`` (default 2) are outstanding. Backpressure
    instead of unbounded queueing; ``drain()`` is the epoch/eval-boundary
    sync point.
  - **PendingScalar** — a lazy handle for per-step losses/metrics that stay
    on device: ``float()`` / ``.item()`` / ``.asnumpy()`` sync on *read*,
    so a fit loop can collect losses without a host round-trip per step and
    drain them at the boundary.

Telemetry (only while ``mx.telemetry`` is enabled): the feed exports
``mx_feed_queue_depth`` and ``mx_feed_stall_seconds_total`` (consumer time
spent waiting on an empty queue — nonzero stall means the producer, not
the device, is the bottleneck), and the window exports
``mx_inflight_steps``. Step timing in the trainers is recorded *after*
window admission, i.e. at completion pace under backpressure, so
instrumentation never re-serializes the pipeline (docs/input_pipeline.md).
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

import numpy as _np

from ..base import MXNetError, env
from ..telemetry import tracing as _tracing

__all__ = ["DeviceFeed", "DispatchWindow", "PendingScalar", "drain",
           "feed_depth", "inflight_steps", "maybe_wrap"]

env.declare("MXNET_TPU_FEED_DEPTH", 2, int,
            "DeviceFeed prefetch queue depth (batches staged on device "
            "ahead of the consumer); 0 disables the async feed wrap in "
            "fit loops")
env.declare("MXNET_TPU_INFLIGHT_STEPS", 2, int,
            "Max dispatched-but-incomplete training steps before the "
            "trainer blocks on the oldest one (0 = fully synchronous)")
env.declare("MXNET_TPU_FEED_GIL_INTERVAL", 0.001, float,
            "sys.setswitchinterval applied when a DeviceFeed producer "
            "starts (never raised, only lowered): the default 5 ms GIL "
            "switch interval makes the consumer wait up to 5 ms behind a "
            "producer mid-batch on few-core hosts; 0 leaves the "
            "interpreter setting untouched")
env.declare("MXNET_TPU_FEED_RESTARTS", 0, int,
            "Opt-in supervised DeviceFeed: bounded producer restarts on "
            "transient source errors (OSError/ConnectionError/"
            "TimeoutError + injected faults) — the producer re-opens the "
            "source iterator, fast-forwards past already-delivered "
            "batches host-side, and resumes; 0 (default) surfaces the "
            "first error at next()")
env.declare("MXNET_TPU_FEED_JOIN_TIMEOUT", 5.0, float,
            "Seconds to wait (per drain round, two rounds) for a "
            "DeviceFeed producer thread to exit at stop/reset/close "
            "before abandoning it (warned loudly + counted in "
            "mx_feed_producer_leaks_total)")


def feed_depth() -> int:
    return int(env.get("MXNET_TPU_FEED_DEPTH"))


def inflight_steps() -> int:
    return int(env.get("MXNET_TPU_INFLIGHT_STEPS"))


# ---------------------------------------------------------------------------
# Lazy scalar handles
# ---------------------------------------------------------------------------

def _raw_of(v):
    """Unwrap NDArray/PendingScalar to the underlying jax.Array."""
    if isinstance(v, PendingScalar):
        return v._raw
    data = getattr(v, "_data", None)
    return data if data is not None and hasattr(data, "block_until_ready") \
        else v


class PendingScalar:
    """A device-resident scalar (a step's loss/metric) that syncs lazily.

    Returned by the fused trainers' ``step()``: holding it costs nothing;
    ``float()`` / ``.item()`` / ``.asnumpy()`` / ``np.asarray`` block on the
    value. ``repr()`` deliberately does NOT sync, so logging a handle does
    not serialize the pipeline — read it at a drain point instead.
    """

    __slots__ = ("_raw",)

    def __init__(self, raw):
        self._raw = _raw_of(raw)

    @property
    def raw(self):
        """The underlying device array (no sync)."""
        return self._raw

    def value(self):
        return self._raw

    def block_until_ready(self):
        if hasattr(self._raw, "block_until_ready"):
            self._raw.block_until_ready()
        return self

    def __float__(self):
        v = float(self._raw)
        if _tracing._ENABLED:
            # nonfinite-loss watchdog rides the sync the caller asked for
            _tracing.check_loss(v, source="pending_scalar")
        return v

    def item(self):
        return self.__float__()

    def asnumpy(self):
        return _np.asarray(self._raw)

    def __array__(self, dtype=None):
        a = _np.asarray(self._raw)
        return a.astype(dtype) if dtype is not None else a

    @property
    def shape(self):
        return tuple(getattr(self._raw, "shape", ()))

    @property
    def dtype(self):
        return getattr(self._raw, "dtype", None)

    def __repr__(self):
        return (f"PendingScalar(shape={self.shape}, dtype={self.dtype}, "
                "pending)")


def drain(values):
    """Block on a (possibly nested) collection of pending step outputs and
    return the scalar values as floats where they are 0-d. The designated
    epoch/eval-boundary sync point for a loop that collected
    ``PendingScalar`` handles."""
    if isinstance(values, (list, tuple)):
        return type(values)(drain(v) for v in values)
    raw = _raw_of(values)
    if hasattr(raw, "block_until_ready"):
        raw.block_until_ready()
    if getattr(raw, "ndim", None) == 0 or isinstance(values, PendingScalar):
        v = float(raw)
        if _tracing._ENABLED:
            _tracing.check_loss(v, source="drain")
        return v
    return raw


# ---------------------------------------------------------------------------
# Bounded in-flight dispatch window
# ---------------------------------------------------------------------------

class DispatchWindow:
    """Backpressure for async step dispatch: keep at most ``depth`` steps in
    flight; ``admit()`` the newly dispatched step's output handle and block
    on the (i-depth)th step's outputs once the window is full — the
    TPU-native equivalent of the reference engine's bounded pending-op
    queue. ``depth=0`` degrades to a fully synchronous loop (every admit
    blocks immediately); depth defaults to ``MXNET_TPU_INFLIGHT_STEPS``.
    """

    def __init__(self, depth: Optional[int] = None, name: str = "step"):
        self.depth = inflight_steps() if depth is None else int(depth)
        self.name = name
        self._pending: "deque[Any]" = deque()
        self.retired = 0
        self.wait_seconds = 0.0
        self.max_inflight = 0

    def __len__(self):
        return len(self._pending)

    @staticmethod
    def _block(handles):
        if isinstance(handles, (list, tuple)):
            for h in handles:
                DispatchWindow._block(h)
            return
        raw = _raw_of(handles)
        if hasattr(raw, "block_until_ready"):
            raw.block_until_ready()

    def admit(self, handles):
        """Register one dispatched step; blocks on the oldest in-flight step
        when the window exceeds its depth (never on the current one)."""
        self._pending.append(handles)
        while len(self._pending) > max(self.depth, 0):
            old = self._pending.popleft()
            # the backpressure wait on the oldest step in flight
            with _tracing.span("mx.window.admit", source=self.name,
                               inflight=len(self._pending)):
                t0 = time.perf_counter()
                self._block(old)
                self.wait_seconds += time.perf_counter() - t0
            self.retired += 1
        self.max_inflight = max(self.max_inflight, len(self._pending))
        from .. import telemetry as _telem
        if _telem._ENABLED:
            _telem.record_inflight(len(self._pending), source=self.name)
            # cumulative block time for the goodput waterfall's
            # dispatch_backpressure lane — the float this window already
            # accumulated, no extra clock read
            _telem.record_dispatch_wait(self.wait_seconds, source=self.name)

    def drain(self):
        """Block until every admitted step completed (epoch/eval boundary)."""
        with _tracing.span("mx.window.drain", source=self.name) as sp:
            drained = 0
            while self._pending:
                old = self._pending.popleft()
                t0 = time.perf_counter()
                self._block(old)
                self.wait_seconds += time.perf_counter() - t0
                self.retired += 1
                drained += 1
            sp.set_attr("drained", drained)
        from .. import telemetry as _telem
        if _telem._ENABLED:
            _telem.record_inflight(0, source=self.name)
            _telem.record_dispatch_wait(self.wait_seconds, source=self.name)


# ---------------------------------------------------------------------------
# Sharding-aware background device feed
# ---------------------------------------------------------------------------

_END = object()


def _bounded_put(q: "queue.Queue", item, stop: threading.Event) -> bool:
    """put() that gives up when the consumer asked the producer to stop —
    a blocking put into a full queue with a departed consumer is exactly
    the thread leak the reference prefetcher's shutdown path avoids."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


class DeviceFeed:
    """Wrap a batch source; deliver batches already placed on device.

    ``source`` may be a ``DataIter`` (``next()``/``reset()``/
    ``provide_data``), a gluon ``DataLoader``, or any re-iterable of
    batches. Each yielded item keeps its structure (``DataBatch`` fields,
    tuples, single arrays) with every array leaf explicitly
    ``jax.device_put`` by the producer thread:

      - with ``mesh``+``data_spec`` (what ``for_trainer`` passes), leaf
        placement is ``NamedSharding(mesh, P(*spec[:arr.ndim]))`` — the
        same rule ``DataParallelTrainer._put_batch`` applies, so the
        trainer's placement check finds the batch already resident and the
        guarded dispatch is transfer-free;
      - with ``sharding``, that sharding is used for every leaf;
      - with neither, a plain ``jax.device_put`` to the default device.

    The producer starts lazily on first ``next()`` (construction has no
    side effects on the wrapped iterator), preserves source order exactly,
    propagates exceptions, and is joined by ``reset()``/``close()``/GC.
    Only single-process meshes are supported — multi-host feeds go through
    ``make_array_from_process_local_data`` in the trainer instead.
    """

    def __init__(self, source, sharding=None, mesh=None, data_spec=None,
                 depth: Optional[int] = None, name: str = "feed",
                 restarts: Optional[int] = None):
        self._source = source
        self._sharding = sharding
        self._mesh = mesh
        self._data_spec = data_spec
        if sharding is not None and mesh is not None:
            raise MXNetError("pass sharding OR mesh+data_spec, not both")
        self._depth = max(feed_depth() if depth is None else int(depth), 1)
        self._max_restarts = int(env.get("MXNET_TPU_FEED_RESTARTS")
                                 if restarts is None else restarts)
        self.name = name
        self.batch_size = getattr(source, "batch_size", 0)
        self.restarts = 0            # producer restarts taken (supervised)
        self.producer_leaks = 0      # producer threads abandoned at join
        self._q: Optional[queue.Queue] = None
        self._stop: Optional[threading.Event] = None
        self._producer: Optional[threading.Thread] = None
        self._eof = False
        self._peek = None
        self.stall_seconds = 0.0
        self.batches_delivered = 0
        # resumable-input cursor (elastic fault tolerance): epoch counts
        # reset() calls on the wrapped source, _epoch_delivered counts
        # batches handed out THIS epoch, _skip is a pending fast-forward
        # the producer consumes (host-only, no device placement) when it
        # starts after load_state_dict
        self._epoch = 0
        self._epoch_delivered = 0
        self._skip = 0

    @classmethod
    def for_trainer(cls, source, trainer, depth: Optional[int] = None,
                    name: str = "feed"):
        """A feed whose leaves land with the trainer's input sharding
        (``trainer.mesh`` + ``trainer.data_spec`` — replicated, dp-sharded,
        or context-parallel, whatever the trainer was configured with)."""
        if getattr(trainer, "_is_multiprocess", lambda: False)():
            raise MXNetError(
                "DeviceFeed targets single-process meshes; multi-host "
                "batch feeding stays on the trainer's "
                "make_array_from_process_local_data path")
        return cls(source, mesh=trainer.mesh,
                   data_spec=getattr(trainer, "data_spec", None),
                   depth=depth, name=name)

    # -- placement -----------------------------------------------------------
    @staticmethod
    def _already_placed(raw, sharding) -> bool:
        """Whether the array already satisfies the target placement — same
        rule as DataParallelTrainer._put_batch, so a leaf placed once is
        handed on as the SAME array (no second dispatch, and nothing for
        sanitize mode's transfer guard to see)."""
        import jax
        if not isinstance(raw, jax.Array):
            return False
        cur = getattr(raw, "sharding", None)
        if cur is None:
            return False
        dev = set(cur.device_set)
        want = set(sharding.device_set)
        return dev == want and (
            len(want) == 1 or cur.is_equivalent_to(sharding, raw.ndim))

    def _put_raw(self, raw):
        import jax
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            spec = self._data_spec if self._data_spec is not None \
                else PartitionSpec()
            ndim = getattr(raw, "ndim", None)
            if ndim is None:
                ndim = _np.asarray(raw).ndim
            clipped = PartitionSpec(*tuple(spec)[:ndim])
            target = NamedSharding(self._mesh, clipped)
            if self._already_placed(raw, target):
                return raw
            return jax.device_put(raw, target)
        if self._sharding is not None:
            if self._already_placed(raw, self._sharding):
                return raw
            return jax.device_put(raw, self._sharding)
        return jax.device_put(raw)

    def _place_leaf(self, v):
        from ..ndarray import NDArray
        if isinstance(v, NDArray):
            if type(v) is not NDArray:
                # sparse (CSR/row-sparse) and other subclasses carry their
                # own payload layout — pass through unplaced
                return v
            return NDArray(self._put_raw(v._data), v.ctx)
        if v is None or isinstance(v, (int, float, str, bytes)):
            return v
        return self._put_raw(v)

    def _place(self, item):
        from ..io.io import DataBatch
        if isinstance(item, DataBatch):
            out = DataBatch(
                [self._place_leaf(d) for d in (item.data or [])] or None,
                [self._place_leaf(l) for l in (item.label or [])] or None,
                pad=item.pad, index=item.index, bucket_key=item.bucket_key,
                provide_data=item.provide_data,
                provide_label=item.provide_label)
            return out
        if isinstance(item, (list, tuple)):
            return type(item)(self._place_leaf(v) for v in item)
        return self._place_leaf(item)

    # -- producer ------------------------------------------------------------
    _TRANSIENT = (OSError, ConnectionError, TimeoutError)

    def _produce(self, stop: threading.Event, q: "queue.Queue"):
        """Producer body, optionally supervised: with restarts budgeted
        (``restarts=``/``MXNET_TPU_FEED_RESTARTS``) a TRANSIENT source
        error re-opens the iterator and fast-forwards host-side past
        everything already queued — batches are delivered exactly once,
        in order, and ``mx_feed_producer_restarts_total`` is booked. A
        non-transient error (or an exhausted budget) still surfaces at
        the consumer's next()."""
        from .. import faults as _faults
        restarts_left = self._max_restarts
        produced = 0
        skip, self._skip = self._skip, 0
        # all of this producer's spans group under one root context so a
        # trace viewer shows the feed as a single causal track
        root = _tracing.new_root(self.name) if _tracing._ENABLED else None
        while True:
            try:
                it = iter(self._source)
                # resume/restart fast-forward: replay the source up to the
                # restored cursor plus already-produced batches on this
                # thread, host-side only — skipped batches are never
                # placed on device, so rewind costs no transfers
                for _ in range(skip + produced):
                    next(it)
                while not stop.is_set():
                    if _faults._ACTIVE:
                        _faults.check("feed.produce")
                    # one record a batch the producer began, always on;
                    # the phases are this thread's spans (queue_wait is the
                    # feed's slack: time blocked on a full queue, the
                    # consumer being slower). A batch that was not handed
                    # over says why: `error` where the source ended or
                    # failed, `aborted` where stop cut the wait
                    with _tracing.phased("batch", "mx.feed.batch",
                                         prefix="mx.feed.", parent=root,
                                         source=self.name,
                                         batch=produced) as rec:
                        with rec.phase("produce"):
                            item = next(it)
                        with rec.phase("put"):
                            placed = self._place(item)
                        with rec.phase("queue_wait"):
                            queued = _bounded_put(q, placed, stop)
                            if not queued or stop.is_set():
                                # a put that got in behind the stop is
                                # drained, not delivered
                                rec.set_attr("aborted", True)
                    if not queued:
                        return
                    produced += 1
                return
            except StopIteration:
                _bounded_put(q, _END, stop)
                return
            except Exception as e:
                if restarts_left > 0 and not stop.is_set() and \
                        isinstance(e, self._TRANSIENT
                                   + (_faults.FaultInjected,)):
                    restarts_left -= 1
                    self.restarts += 1
                    from .. import telemetry as _telem
                    if _telem._ENABLED:
                        _telem.record_feed_producer_restart(self.name)
                    continue
                _bounded_put(q, e, stop)  # surfaced at the consumer's next()
                return

    def _ensure_producer(self):
        if self._producer is not None and self._producer.is_alive():
            return
        if self._q is None or self._producer is None:
            import sys
            iv = float(env.get("MXNET_TPU_FEED_GIL_INTERVAL"))
            if iv > 0 and sys.getswitchinterval() > iv:
                # producer and consumer interleave on the GIL; the default
                # 5 ms switch interval stalls the dispatch loop behind a
                # producer mid-batch (measured ~2 ms/step on a 1-core
                # host). Lowered once, process-wide, documented in
                # docs/input_pipeline.md; MXNET_TPU_FEED_GIL_INTERVAL=0
                # opts out.
                sys.setswitchinterval(iv)
            self._stop = threading.Event()
            self._q = queue.Queue(maxsize=self._depth)
            self._producer = threading.Thread(
                target=self._produce, args=(self._stop, self._q),
                daemon=True, name=f"mx-device-feed-{self.name}")
            self._producer.start()

    def _stop_producer(self):
        if self._producer is not None and self._stop is not None:
            self._stop.set()
            timeout = max(float(env.get("MXNET_TPU_FEED_JOIN_TIMEOUT")),
                          0.01)
            # unblock a producer stuck in put(), then join; drain again in
            # case it completed one more put before seeing the stop flag
            for _ in range(2):
                try:
                    while True:
                        self._q.get_nowait()
                except queue.Empty:
                    pass
                self._producer.join(timeout=timeout)
                if not self._producer.is_alive():
                    break
            if self._producer.is_alive():
                # blocked inside the wrapped source (not our put(), which
                # polls the stop flag) — abandoning it leaks the thread
                # until the source unblocks; say so LOUDLY and count it
                import warnings
                self.producer_leaks += 1
                warnings.warn(
                    f"DeviceFeed {self.name!r}: producer thread did not "
                    f"exit within {2 * timeout:.1f}s and was abandoned "
                    "(blocked inside the wrapped source?); the thread "
                    "leaks until the source unblocks — booked in "
                    "mx_feed_producer_leaks_total "
                    "(MXNET_TPU_FEED_JOIN_TIMEOUT tunes the wait)",
                    RuntimeWarning, stacklevel=3)
                from .. import telemetry as _telem
                if _telem._ENABLED:
                    _telem.record_feed_producer_leak(self.name)
        self._producer = None
        self._q = None
        self._stop = None

    # -- consumer protocol ---------------------------------------------------
    def next(self):
        if self._eof:
            raise StopIteration
        self._ensure_producer()
        t0 = None
        try:
            item = self._q.get_nowait()
        except queue.Empty:
            # the stall: the consumer waits for the producer
            with _tracing.span("mx.feed.next", source=self.name):
                t0 = time.perf_counter()
                while True:
                    try:
                        item = self._q.get(timeout=1.0)
                        break
                    except queue.Empty:
                        if self._producer is None or \
                                not self._producer.is_alive():
                            raise MXNetError(
                                "DeviceFeed producer thread died without "
                                "delivering a batch or an error")
                self.stall_seconds += time.perf_counter() - t0
        from .. import telemetry as _telem
        if _telem._ENABLED:
            if t0 is not None:
                _telem.record_feed_stall(self.stall_seconds, source=self.name)
            _telem.record_feed_depth(self._q.qsize(), source=self.name)
        if item is _END:
            self._eof = True
            # producer exited on its own; forget it so reset() restarts
            self._producer = None
            self._q = None
            self._stop = None
            raise StopIteration
        if isinstance(item, Exception):
            self._stop_producer()
            raise item
        self.batches_delivered += 1
        self._epoch_delivered += 1
        return item

    def __next__(self):
        return self.next()

    def __iter__(self):
        return self

    def iter_next(self):
        if self._peek is not None:
            return True
        try:
            self._peek = self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self._peek.data if self._peek is not None else None

    def getlabel(self):
        return self._peek.label if self._peek is not None else None

    def getpad(self):
        return getattr(self._peek, "pad", 0) if self._peek is not None else 0

    def reset(self):
        """Stop + join the producer, reset the wrapped source, start a fresh
        epoch. Exactly one inner ``reset()`` per call, so seeded shuffles
        advance the same way they would without the wrapper."""
        self._stop_producer()
        self._peek = None
        self._eof = False
        self._epoch += 1
        self._epoch_delivered = 0
        self._skip = 0
        if hasattr(self._source, "reset"):
            self._source.reset()

    # -- resumable input (elastic fault tolerance) ---------------------------
    def state_dict(self):
        """Durable cursor: which epoch the wrapped source is on and how
        many batches this epoch were consumed (a peeked-but-unused batch
        doesn't count). With a seeded source, ``load_state_dict`` on a
        fresh process replays the exact remaining batch sequence."""
        d = {"epoch": self._epoch,
             "cursor": self._epoch_delivered
             - (1 if self._peek is not None else 0),
             "delivered": self.batches_delivered}
        if hasattr(self._source, "state_dict"):
            d["source"] = self._source.state_dict()
        return d

    def load_state_dict(self, d):
        """Rewind to a saved cursor. A source snapshot (epoch-level state:
        shuffle order, shard assignment — anything ``reset()`` advances)
        is authoritative over the reset-replay; either way the producer
        still fast-forwards ``cursor`` batches host-side when it starts —
        the intra-epoch position is the FEED's knowledge, because the
        producer prefetches ahead of what the consumer was ever handed."""
        self._stop_producer()
        self._peek = None
        self._eof = False
        self._epoch = int(d.get("epoch", 0))
        src = d.get("source")
        if src is not None and hasattr(self._source, "load_state_dict"):
            self._source.load_state_dict(src)
        else:
            for _ in range(self._epoch):
                if hasattr(self._source, "reset"):
                    self._source.reset()
        self._skip = int(d.get("cursor", 0))
        self._epoch_delivered = int(d.get("cursor", 0))
        self.batches_delivered = int(d.get("delivered",
                                           self._epoch_delivered))

    def close(self):
        self._stop_producer()

    def __del__(self):
        try:
            self._stop_producer()
        except Exception:
            pass

    def __len__(self):
        return len(self._source)

    # -- DataIter surface passthrough ---------------------------------------
    @property
    def provide_data(self):
        return getattr(self._source, "provide_data", None)

    @property
    def provide_label(self):
        return getattr(self._source, "provide_label", None)


def maybe_wrap(source, sharding=None, mesh=None, data_spec=None,
               name: str = "feed"):
    """Wrap ``source`` in a DeviceFeed when the async feed is enabled
    (``MXNET_TPU_FEED_DEPTH`` > 0), the source is not already wrapped, and
    the process is single-controller. Used by the fit loops; returns the
    source unchanged otherwise."""
    if isinstance(source, DeviceFeed) or feed_depth() <= 0:
        return source
    try:
        import jax
        if jax.process_count() > 1:
            return source
    except Exception:
        return source
    return DeviceFeed(source, sharding=sharding, mesh=mesh,
                      data_spec=data_spec, name=name)
