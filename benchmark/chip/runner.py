"""The training runner: the system under test, driven as a training job
drives it.

    DeviceFeed.for_trainer(pool, trainer)  ->  DataParallelTrainer.step(x, y)

Set-up builds ONE trainer, drives it from the seed through its first steps
(the first of which compiles the cell's one step signature) through the same
call and feed as the window, reads what `check.py` compares, and hands that
same trainer and feed to the window. From the program the runner takes the
trainer, the feed, their counters (`DeviceFeed.stall_seconds`,
`DispatchWindow.wait_seconds`) and nothing else.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import time

import numpy as np

from traffic import Cycled

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
FIRST_STEPS = 3


class Events:
    """jax.monitoring's compile and persistent-cache events, counted by the
    phase of the run ("setup", "window", "after") in which they fall."""

    def __init__(self):
        import jax.monitoring
        self.phase = "setup"
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            self.counts["compiles", self.phase] += 1

    def _event(self, name, **kw):
        if name == CACHE_HIT_EVENT:
            self.counts["cache_hits", self.phase] += 1
        elif name == CACHE_MISS_EVENT:
            self.counts["cache_misses", self.phase] += 1

    def count(self, event, phase):
        return self.counts[event, phase]

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def claim_devices(cell, require_tpu=True):
    """The devices the cell runs on; exits non-zero where jax reports
    anything but a TPU (unless a test lifts that) or too few devices."""
    import sys
    import jax
    devices = jax.devices()
    if len(devices) < cell.chips or (require_tpu
                                     and devices[0].platform != "tpu"):
        sys.exit(f"{cell.name} needs {cell.chips} TPU chip(s); jax reports "
                 f"{len(devices)} x {devices[0].platform!r} "
                 f"({devices[0].device_kind}); nothing was run")
    return devices[:cell.chips]


def enable_compile_cache():
    """One persistent compile cache, where `JAX_COMPILATION_CACHE_DIR` says
    or else at the program's fixed default inside the checkout, with every
    program persisted, the host compiles of deferred init too: warm set-up
    of the ResNet cell fell from 118 s to 31 s (PERF.md, PR 24)."""
    import jax
    from mxnet_tpu import engine
    cache_dir = engine.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def token_loss(logits, labels):
    """Mean cross-entropy in float32: the loss chip_smoke.py, bench.py and
    the examples give the trainer."""
    import jax
    import jax.numpy as jnp
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _norm(a):
    return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))


class Program:
    """The trainer and its feed, built from a cell's files and the weights
    the benchmark made."""

    def __init__(self, cell, weights, pool, seed, devices, loss=None,
                 net=None):
        import mxnet_tpu as mx
        from mxnet_tpu.engine import DeviceFeed
        from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

        cfg, opt = cell.config, cell.config["optimizer"]
        self.cell, self.opt, self.weights, self.pool = cell, opt, weights, pool
        spec = cell.module("reference").param_spec(cfg)
        mx.random.seed(int(seed) % (1 << 31))
        # the normal path: deferred shape inference on the host, so that the
        # accelerator sees the fused step alone; then the seed's weights.
        # (`net`: a block that an earlier Program of this process built.)
        with mx.cpu():
            if net is None:
                net, sample = cell.module("programs").build(cfg, cell.traffic)
                # zeros, not the default random initializer: the seed's
                # weights replace whatever it draws (and its host programs
                # were a third of a BERT cell's compile cache)
                net.initialize(mx.init.Zero(), ctx=mx.cpu())
                if any(not p.shape or 0 in p.shape
                       for p in net.collect_params().values()):
                    net(sample)   # shapes the block could not state itself
            params = list(net.collect_params().values())
            if [tuple(p.shape) for p in params] != [tuple(s[1]) for s in spec]:
                raise RuntimeError(
                    "the program's leaves are not the reference's: "
                    f"{[(p.name, p.shape) for p in params][:4]}... against "
                    f"{[(s[0], s[1]) for s in spec][:4]}...")
            for p, (name, _, _, _) in zip(params, spec):
                p.set_data(weights[name])
        self.net = net
        self.names = [s[0] for s in spec]
        self.trainable = [s[3] for s in spec]
        mesh = make_mesh(dict(cell.traffic["mesh"]),
                         devices=devices[:cell.chips])
        self.trainer = DataParallelTrainer(
            net, loss or token_loss, optimizer=opt["name"], mesh=mesh,
            optimizer_params={k: v for k, v in opt.items() if k != "name"},
            dtype=cfg["precision"]["compute"])
        self.window = self.trainer._window
        self.feed = DeviceFeed.for_trainer(Cycled(pool), self.trainer)

    # -- set-up: the first steps, read for the comparison --------------------
    def first_steps(self):
        """Steps 1..FIRST_STEPS through the window's own call and feed.
        Returns the program's readings and the count of elements of those
        batches that differ, on the device, from the pool's."""
        from reference.steps import first_gradient
        tr, opt = self.trainer, self.opt
        losses, mismatch, grad_norms = [], 0, {}
        for i in range(FIRST_STEPS):
            x, y = self.feed.next()
            losses.append(float(tr.step(x, y)))
            tr.drain()
            for got, want in zip((x, y), self.pool[i % len(self.pool)]):
                mismatch += int(np.sum(np.asarray(got) != want))
            if i == 0:
                for n, t, s, w in zip(self.names, self.trainable,
                                      tr._opt_state, tr._params_raw):
                    if t:
                        grad_norms[n] = _norm(first_gradient(opt, s, w))
        change = {n: _norm(np.asarray(w, np.float32) - self.weights[n])
                  for n, t, w in zip(self.names, self.trainable,
                                     tr._params_raw) if t}
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change}, mismatch

    # -- the window -----------------------------------------------------------
    def stretch(self, seconds, annotate=False):
        """Drive the trainer from the feed for `seconds`, then drain.

        A step's completion is stamped when its loss is ready, read lagging
        the dispatch by the trainer's own window depth: `trainer.step` has
        by then waited for that very step, so the stamp adds no stall."""
        import jax
        span = jax.profiler.TraceAnnotation if annotate else \
            (lambda name: contextlib.nullcontext())
        tr, feed, win = self.trainer, self.feed, self.window
        depth = max(win.depth, 0)
        pending, stamps = collections.deque(), []
        step_s = 0.0
        stall0, wait0 = feed.stall_seconds, win.wait_seconds
        t0 = now = time.perf_counter()
        while now - t0 < seconds:
            with span("bench.feed_next"):
                x, y = feed.next()
            t1 = time.perf_counter()
            with span("bench.trainer_step"):
                pending.append(tr.step(x, y))
            now = time.perf_counter()
            step_s += now - t1
            with span("bench.stamp"):
                while len(pending) > depth:
                    pending.popleft().block_until_ready()
                    stamps.append(time.perf_counter())
        in_loop_wait = win.wait_seconds - wait0
        with span("bench.drain"):
            while pending:
                pending.popleft().block_until_ready()
                stamps.append(time.perf_counter())
            tr.drain()
        return {"t0": t0, "stamps": stamps, "steps": len(stamps),
                "seconds": stamps[-1] - t0, "step_call_s": step_s,
                "dispatch_wait_s": in_loop_wait,
                "feed_stall_s": feed.stall_seconds - stall0}

    def close(self, keep_executables=False):
        """Stop the feed and free the trainer's device state (the engine's
        executable cache keeps a trainer alive through its step's closure)."""
        from mxnet_tpu import engine
        self.feed.close()
        self.trainer._params_raw = self.trainer._opt_state = None
        self.trainer = self.feed = self.window = None
        if not keep_executables:
            engine.clear_compilation_cache()
        gc.collect()


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation."""
    vs = sorted(values)
    if not vs:
        return None
    k = (len(vs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (k - lo)


def end_to_end(stretch, items_per_step):
    """The window's end-to-end numbers: every item of every step over the
    whole window; the tail over every interval between completions."""
    gaps = np.diff(stretch["stamps"])
    return {"train_items_per_s":
            stretch["steps"] * items_per_step / stretch["seconds"],
            "step_p95_ms": 1e3 * percentile(gaps.tolist(), 95)}
