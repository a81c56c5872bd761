"""The step's RNG key stays on the device (ISSUE 26).

`DataParallelTrainer.step` used to read the next key back to the host
(`np.asarray(_rng.next_key_raw())`): the key is computed on the device behind
the running step, so every step was dispatched only after the one before it
had finished. Acceptance under test:

  - the two routes of `next_step_key` (the device array of a single process,
    the host value of multi-process SPMD) give bit-equal keys, one split of
    the global stream a call, so losses, parameters and the stream's state
    agree bit for bit at a nonzero dropout, on one device and on a dp=4 mesh,
    through `step` and through `run_steps`, and over a `state_dict()` /
    `load_state_dict()` resume in the middle;
  - the device route reads nothing back: with `np.asarray` of a device array
    made to raise, `step` still runs there, and raises on the host route;
  - the dispatch window fills: with a step that is slow on the device, the
    first `depth` calls return at once, the later ones wait in `admit_wait`,
    and the phase `rng_key` (drawing the key) holds no wait;
  - the benchmark's guard holds on both routes: a wait planted where the key
    is drawn reads as `step_host_sync_ms`, not as work.
"""
import importlib.util
import json
import os
import time
import types

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu import random as mx_random
from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
from mxnet_tpu.parallel import data_parallel as dp_mod
from mxnet_tpu.telemetry import tracing

ROUTES = [False, True]      # _is_multiprocess(): device route, host route
CHIP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmark", "chip")


def _loss_fn(out, y):
    return jnp.mean((out.astype(jnp.float32) - y) ** 2)


def _trainer(seed=7, dp=1, width=16, depth=2, dropout=0.1, optimizer="adam"):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    for _ in range(depth):
        net.add(gluon.nn.Dense(width, activation="relu"))
        if dropout:
            net.add(gluon.nn.Dropout(dropout))
    net.add(gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, width)))
    mesh = make_mesh({"dp": dp}, devices=jax.devices("cpu")[:dp])
    return DataParallelTrainer(net, _loss_fn, optimizer=optimizer,
                               optimizer_params={"learning_rate": 0.01},
                               mesh=mesh)


def _batch(i=0, rows=8, width=16):
    rs = onp.random.RandomState(i)
    return (rs.uniform(-1, 1, (rows, width)).astype(onp.float32),
            rs.uniform(-1, 1, (rows, 4)).astype(onp.float32))


def _state(tr):
    tr.drain()
    return ([onp.asarray(w) for w in tr._params_raw],
            onp.asarray(mx_random.get_state_raw()))


def _run(multiprocess, steps=3, **kw):
    """Losses, parameters and the stream's state after `steps` steps."""
    tr = _trainer(**kw)
    tr._multiprocess = multiprocess
    losses = [tr.step(*_batch(i)).asnumpy() for i in range(steps)]
    return (losses,) + _state(tr)


def _same(a, b):
    (la, pa, ka), (lb, pb, kb) = a, b
    assert len(la) == len(lb) and len(pa) == len(pb)
    for x, y in zip(la + pa + [ka], lb + pb + [kb]):
        assert x.dtype == y.dtype and onp.array_equal(x, y)


# ---------------------------------------------------------------------------
# the key sequence does not change
# ---------------------------------------------------------------------------

def test_both_routes_draw_the_same_keys_one_split_a_call():
    mx.random.seed(11)
    on_device = [dp_mod.next_step_key(False) for _ in range(3)]
    after = onp.asarray(mx_random.get_state_raw())
    mx.random.seed(11)
    on_host = [dp_mod.next_step_key(True) for _ in range(3)]
    assert onp.array_equal(after, onp.asarray(mx_random.get_state_raw()))
    mx.random.seed(11)
    plain = [onp.asarray(mx_random.next_key_raw()) for _ in range(3)]
    for d, h, p in zip(on_device, on_host, plain):
        assert isinstance(d, jax.Array) and isinstance(h, onp.ndarray)
        assert d.dtype == h.dtype == onp.uint32 and d.shape == h.shape == (2,)
        assert onp.array_equal(onp.asarray(d), h) and onp.array_equal(h, p)
    assert len({tuple(h) for h in on_host}) == 3


@pytest.mark.parametrize("dp", [1, 4])
def test_step_parity_of_the_routes_at_nonzero_dropout(dp):
    device, host = _run(False, dp=dp), _run(True, dp=dp)
    _same(device, host)
    # the key is live: another seed gives other masks and other losses
    other = _run(False, dp=dp, seed=8)
    assert not onp.array_equal(device[0][0], other[0][0])


def test_run_steps_parity_of_the_routes_at_nonzero_dropout():
    out = []
    for multiprocess in ROUTES:
        tr = _trainer()
        tr._multiprocess = multiprocess
        losses = onp.asarray(tr.run_steps(*_batch(), n=3))
        out.append(([losses],) + _state(tr))
    _same(*out)


@pytest.mark.parametrize("multiprocess", ROUTES)
def test_resume_in_the_middle_keeps_the_key_sequence(multiprocess):
    """Two steps, `state_dict()`, a fresh trainer under another seed,
    `load_state_dict()`, two more: bit-equal to four steps uninterrupted on
    the device route."""
    whole = _run(False, steps=4)
    tr = _trainer()
    tr._multiprocess = multiprocess
    losses = [tr.step(*_batch(i)).asnumpy() for i in range(2)]
    snapshot = tr.state_dict()
    tr2 = _trainer(seed=999)        # leaves the global stream elsewhere
    tr2._multiprocess = multiprocess
    tr2.load_state_dict(snapshot)
    losses += [tr2.step(*_batch(i)).asnumpy() for i in (2, 3)]
    _same(whole, (losses,) + _state(tr2))


# ---------------------------------------------------------------------------
# no read-back on the device route
# ---------------------------------------------------------------------------

class _NoReadBack:
    """numpy, but `asarray` of a device array raises."""

    def __getattr__(self, name):
        return getattr(onp, name)

    @staticmethod
    def asarray(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            raise AssertionError("a device value was read back to the host")
        return onp.asarray(a, *args, **kwargs)


@pytest.mark.parametrize("call", ["step", "run_steps"])
def test_the_device_route_reads_nothing_back(monkeypatch, call):
    tr = _trainer()

    def go():
        if call == "step":
            return tr.step(*_batch())
        return tr.run_steps(*_batch(), n=2)
    go()                # compile outside the guarded stretch
    tr.drain()
    mx.random.seed(3)   # run_steps draws a key again after a reseed
    monkeypatch.setattr(dp_mod, "_np", _NoReadBack())
    # the guard is what a chip would raise on; the CPU backend reads its own
    # memory without a transfer, hence the planted `asarray` as well
    with jax.transfer_guard_device_to_host("disallow"):
        go()
        tr._multiprocess = True
        with pytest.raises(AssertionError, match="read back"):
            go()
    tr._multiprocess = False
    tr.drain()


def test_key_is_handed_to_the_step_as_a_device_array(monkeypatch):
    """What `step` places with its scalars is the array the split made."""
    tr = _trainer()
    seen = []
    real = jax.device_put

    def spy(x, *args, **kwargs):
        if isinstance(x, tuple) and len(x) == 4:
            seen.append(x[0])
        return real(x, *args, **kwargs)
    monkeypatch.setattr(dp_mod.jax, "device_put", spy)
    tr.step(*_batch())
    tr.drain()
    (key,) = seen
    assert isinstance(key, jax.Array) and key.dtype == jnp.uint32


def _benchmark_reading(metric, t0, seconds, steps):
    """`metric` as the benchmark reads it: its reader (`step_phase`) with the
    parameters of `layer_metrics/<metric>.json`, over the program's records
    of `[t0, t0 + seconds]`."""
    with open(os.path.join(CHIP, "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    path = os.path.join(CHIP, "readers", spec["reader"] + ".py")
    module_spec = importlib.util.spec_from_file_location("_reader", path)
    reader = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(reader)
    view = types.SimpleNamespace(
        window={"t0": t0, "seconds": seconds, "steps": steps,
                "step_call_s": 0.0}, say=lambda line: None)
    return reader.read(view, spec["params"])


@pytest.mark.parametrize("multiprocess", ROUTES)
def test_planted_wait_in_the_key_as_the_benchmark_reads_it(monkeypatch,
                                                           multiprocess):
    """20 ms planted where the key is drawn: on either route they are in the
    benchmark's `step_host_sync_ms` and not in `step_host_work_ms` (the phase
    keeps its name `rng_key`, which that metric sums). Without the planted
    wait the device route's sync is the dispatch of the split alone."""
    tr = _trainer()
    tr._multiprocess = multiprocess
    tr.step(*_batch())
    tr.drain()

    def read(steps=4):
        t0 = time.perf_counter()
        for i in range(steps):
            tr.step(*_batch(i))
        tr.drain()
        seconds = time.perf_counter() - t0
        return [_benchmark_reading(m, t0, seconds, steps)
                for m in ("step_host_sync_ms", "step_host_work_ms")]

    sync0, work0 = read()
    real = mx_random.next_key_raw

    def slow_key():
        time.sleep(0.02)
        return real()
    monkeypatch.setattr(mx_random, "next_key_raw", slow_key)
    sync1, work1 = read()
    assert sync0 < 20.0 <= sync1, (sync0, sync1)
    assert work1 < work0 + 5.0 and work1 < 20.0, (work0, work1)


# ---------------------------------------------------------------------------
# the window fills
# ---------------------------------------------------------------------------

def test_window_fills_behind_a_step_that_is_slow_on_the_device():
    """A chain of large products makes the step slow on the device, not on
    the host: the first `depth` calls return without a wait, every later one
    waits for the oldest step in flight (`admit_wait`), and the host never
    waits for a key (`rng_key` is the dispatch of the split, a sliver of a
    step's time)."""
    tr = _trainer(width=1024, depth=8, dropout=0.0, optimizer="sgd")
    depth = tr._window.depth
    assert depth >= 2
    x, y = _batch(rows=1024, width=1024)
    tr.step(x, y)       # compile
    tr.drain()
    alone = []
    for _ in range(5):
        t0 = time.perf_counter()
        tr.step(x, y)
        tr.drain()
        alone.append(time.perf_counter() - t0)
    one_step = sorted(alone)[len(alone) // 2]
    t0 = time.perf_counter()
    for _ in range(depth + 4):
        tr.step(x, y)
    records = tracing.step_records("mx.dp.step", since=t0)
    tr.drain()
    assert len(records) == depth + 4
    # the structure, firmly: no wait while the window has room, a wait in
    # every call after that
    assert [r["phases"]["admit_wait"] > 0.0 for r in records] \
        == [False] * depth + [True] * 4
    # the sizes, loosely (a shared CPU runner): the calls that found room
    # took less than a step, the others spent most of their time waiting,
    # and drawing the key is nowhere a wait for the step before
    for r in records[:depth]:
        assert r["dur"] < 0.5 * one_step, (r, one_step)
    assert sum(r["phases"]["admit_wait"] for r in records[depth:]) \
        > 0.5 * sum(r["dur"] for r in records[depth:]), records
    assert sum(r["phases"]["rng_key"] for r in records) \
        < 0.25 * sum(r["dur"] for r in records), records
