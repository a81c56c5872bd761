"""Runtime sanitizers over the fused train path (ISSUE 3 acceptance).

The static side (tools/mxlint host-sync rule) proves no *source-level* sync
sits on the hot path; these tests prove it DYNAMICALLY: a fused
DataParallelTrainer step runs under

  - ``jax_check_tracer_leaks`` during the trace (a tracer stashed in module
    state / a Parameter / a closure would raise at trace time), and
  - ``jax.transfer_guard("disallow")`` during dispatch (any implicit
    host<->device transfer inside the step raises).

Together they certify the step is pure and transfer-free end to end on the
CPU backend — the same interlocks MXNET_TPU_SANITIZE=1 / pytest --sanitize
arm for the whole suite.
"""
import contextlib

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd


@contextlib.contextmanager
def _jax_flag(name, value):
    prev = getattr(jax.config, name)
    jax.config.update(name, value)
    try:
        yield
    finally:
        jax.config.update(name, prev)


def _make_trainer(optimizer="sgd", **opt_params):
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    trainer_kw = opt_params.pop("trainer_kw", {})
    mx.random.seed(7)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 8)))

    def loss(pred, label):
        import jax.numpy as jnp
        return jnp.mean((pred - label) ** 2)

    mesh = make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])
    opt_params.setdefault("learning_rate", 0.05)
    return DataParallelTrainer(net, loss, optimizer=optimizer,
                               optimizer_params=opt_params, mesh=mesh,
                               **trainer_kw)


def test_fused_step_traces_under_tracer_leak_checker():
    """The first step (trace + compile) runs with jax_check_tracer_leaks:
    the parameter-swap apply_fn must restore every Parameter before the
    trace ends or this raises UnexpectedTracerError."""
    tr = _make_trainer()
    x, y = nd.ones((4, 8)), nd.ones((4, 4))
    with _jax_flag("jax_check_tracer_leaks", True):
        loss0 = tr.step(x, y)
    assert np.isfinite(float(loss0))


def test_fused_step_dispatch_under_transfer_guard():
    """After warmup, a step dispatch is transfer-free: every per-step input
    (batch, key, lr, t, scale) is either device-resident or explicitly
    device_put, so transfer_guard('disallow') passes."""
    tr = _make_trainer()
    x, y = nd.ones((4, 8)), nd.ones((4, 4))
    tr.step(x, y)  # trace+compile outside the guard
    with jax.transfer_guard("disallow"):
        lossv = tr.step(x, y)
    assert np.isfinite(float(lossv))


def test_fused_step_under_both_plus_debug_nans():
    """The full MXNET_TPU_SANITIZE=1 combination via the module API:
    tracer-leak + debug-nans global, transfer guard scoped by the trainer
    itself (sanitize.guard() inside DataParallelTrainer.step)."""
    from mxnet_tpu import sanitize
    tr = _make_trainer(optimizer="adam")
    x, y = nd.ones((4, 8)), nd.ones((4, 4))
    sanitize.enable()
    try:
        assert sanitize.enabled()
        first = tr.step(x, y)       # traced under the leak checker
        second = tr.step(x, y)      # dispatched inside the trainer's guard
    finally:
        sanitize.disable()
    assert np.isfinite(float(first)) and np.isfinite(float(second))
    assert not sanitize.enabled()


SHARD_MAP_BODIES = {"zero": dict(zero_update=True),
                    "compressed": dict(compression={"type": "2bit"})}


@pytest.mark.parametrize("body", sorted(SHARD_MAP_BODIES))
def test_shard_map_step_traces_under_tracer_leak_checker(body):
    """The zero and the 2-bit compressed steps trace their forward and
    backward inside a shard_map body; the parameter swap must restore every
    Parameter there too, or the leak checker raises."""
    tr = _make_trainer(trainer_kw=SHARD_MAP_BODIES[body])
    x, y = nd.ones((4, 8)), nd.ones((4, 4))
    with _jax_flag("jax_check_tracer_leaks", True):
        loss0 = tr.step(x, y)
    assert np.isfinite(float(loss0))


@pytest.mark.parametrize("body", sorted(SHARD_MAP_BODIES))
def test_shard_map_step_dispatch_under_transfer_guard(body):
    """Their dispatch stays transfer-free: the bucket plan and the wd
    vectors (zero), the residual carry and the threshold (compressed) are
    device-resident or baked into the trace; nothing new crosses per
    step."""
    tr = _make_trainer(trainer_kw=SHARD_MAP_BODIES[body])
    x, y = nd.ones((4, 8)), nd.ones((4, 4))
    tr.step(x, y)  # trace+compile outside the guard
    with jax.transfer_guard("disallow"):
        lossv = tr.step(x, y)
    assert np.isfinite(float(lossv))


def test_transfer_guard_catches_planted_host_sync():
    """Positive control: the guard actually fires — an implicit numpy
    upload inside the guarded region must raise."""
    tr = _make_trainer()
    x, y = nd.ones((4, 8)), nd.ones((4, 4))
    tr.step(x, y)
    f = jax.jit(lambda a: a + 1)
    f(np.zeros((3,), np.float32))  # warm outside
    with jax.transfer_guard("disallow"):
        with pytest.raises(Exception, match="[Dd]isallowed"):
            f(np.zeros((3,), np.float32))


def test_run_steps_dispatch_under_transfer_guard():
    """The on-device loop (lax.scan multi-step) also dispatches clean:
    lr/key/t/scale ride the device-resident caches."""
    tr = _make_trainer()
    x, y = nd.ones((4, 8)), nd.ones((4, 4))
    tr.run_steps(x, y, n=2)  # compile + prime the scalar caches
    with jax.transfer_guard("disallow"):
        losses = tr.run_steps(x, y, n=2)
    assert np.all(np.isfinite(np.asarray(losses)))


def test_fed_overlapped_loop_under_transfer_guard():
    """ISSUE 5 acceptance: a DeviceFeed-fed, overlapped loop dispatches
    with NO host sync between consecutive steps under
    transfer_guard('disallow') — the feed's device_put is explicit (and
    runs in the producer thread), batches arrive pre-placed with the
    trainer's input sharding so _put_batch takes the no-op path, and the
    per-step losses stay pending until the drain point after the guard."""
    from mxnet_tpu.engine.async_feed import DeviceFeed, PendingScalar
    from mxnet_tpu.io import NDArrayIter

    tr = _make_trainer()
    rs = np.random.RandomState(0)
    x = rs.uniform(-1, 1, (24, 8)).astype(np.float32)
    y = rs.uniform(-1, 1, (24, 4)).astype(np.float32)

    def fresh_feed():
        return DeviceFeed.for_trainer(
            NDArrayIter(x, y, batch_size=4, shuffle=False), tr)

    feed = fresh_feed()
    for b in feed:  # trace + compile outside the guard
        tr.step(b.data[0], b.label[0])
    tr.drain()
    feed.close()

    feed = fresh_feed()
    pend = []
    with jax.transfer_guard("disallow"):
        for b in feed:
            pend.append(tr.step(b.data[0], b.label[0]))
    tr.drain()  # the designed boundary sync point
    feed.close()
    assert len(pend) == 6
    assert all(isinstance(p, PendingScalar) for p in pend)
    assert all(np.isfinite(float(p)) for p in pend)


def test_fed_overlapped_run_steps_under_transfer_guard():
    """Same contract for the compiled multi-step path: feed-delivered,
    device-resident batches drive run_steps under the guard."""
    from mxnet_tpu.engine.async_feed import DeviceFeed
    from mxnet_tpu.io import NDArrayIter

    tr = _make_trainer()
    rs = np.random.RandomState(1)
    x = rs.uniform(-1, 1, (8, 8)).astype(np.float32)
    y = rs.uniform(-1, 1, (8, 4)).astype(np.float32)

    def fresh_feed():
        return DeviceFeed.for_trainer(
            NDArrayIter(x, y, batch_size=4, shuffle=False), tr)

    feed = fresh_feed()
    for b in feed:  # compile + prime the device-resident scalar caches
        tr.run_steps(b.data[0], b.label[0], n=2)
    tr.drain()
    feed.close()

    feed = fresh_feed()
    all_losses = []
    with jax.transfer_guard("disallow"):
        for b in feed:
            all_losses.append(tr.run_steps(b.data[0], b.label[0], n=2))
    tr.drain()
    feed.close()
    assert len(all_losses) == 2
    assert np.all(np.isfinite(np.asarray(all_losses)))


@contextlib.contextmanager
def _tracing_armed():
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import tracing
    telemetry.enable()
    tracing.enable()
    tracing.reset()
    try:
        yield tracing
    finally:
        tracing.disable()
        tracing.reset()
        telemetry.disable()


def test_fed_overlapped_loop_with_tracing_armed_under_transfer_guard():
    """ISSUE 14 acceptance: ARMED span tracing adds no host<->device
    transfers to the fed overlapped loop — spans ride perf_counter stamps
    the layers already take, and the watchdog only sees host floats at the
    designed drain point. transfer_guard('disallow') + the tracer-leak
    checker both stay green with the tracer recording."""
    from mxnet_tpu.engine.async_feed import DeviceFeed, PendingScalar
    from mxnet_tpu.io import NDArrayIter

    tr = _make_trainer()
    rs = np.random.RandomState(2)
    x = rs.uniform(-1, 1, (24, 8)).astype(np.float32)
    y = rs.uniform(-1, 1, (24, 4)).astype(np.float32)

    def fresh_feed():
        return DeviceFeed.for_trainer(
            NDArrayIter(x, y, batch_size=4, shuffle=False), tr)

    feed = fresh_feed()
    for b in feed:  # trace + compile outside the guard, tracing off
        tr.step(b.data[0], b.label[0])
    tr.drain()
    feed.close()

    with _tracing_armed() as tracing:
        feed = fresh_feed()
        pend = []
        with _jax_flag("jax_check_tracer_leaks", True), \
                jax.transfer_guard("disallow"):
            for b in feed:
                pend.append(tr.step(b.data[0], b.label[0]))
        tr.drain()  # designed boundary: watchdog sees losses here
        feed.close()
        assert all(isinstance(p, PendingScalar) for p in pend)
        assert all(np.isfinite(float(p)) for p in pend)
        names = {e["name"] for e in tracing.spans()}
        assert "mx.dp.step" in names
        assert "mx.feed.produce" in names and "mx.feed.put" in names
        assert "mx.window.admit" in names


def test_fed_overlapped_run_steps_with_tracing_armed_under_transfer_guard():
    """Compiled multi-step path with tracing armed: run_steps dispatches
    transfer-free and the dispatch-only mx.dp.run_steps span lands."""
    from mxnet_tpu.engine.async_feed import DeviceFeed
    from mxnet_tpu.io import NDArrayIter

    tr = _make_trainer()
    rs = np.random.RandomState(3)
    x = rs.uniform(-1, 1, (8, 8)).astype(np.float32)
    y = rs.uniform(-1, 1, (8, 4)).astype(np.float32)

    def fresh_feed():
        return DeviceFeed.for_trainer(
            NDArrayIter(x, y, batch_size=4, shuffle=False), tr)

    feed = fresh_feed()
    for b in feed:  # compile + prime outside the guard
        tr.run_steps(b.data[0], b.label[0], n=2)
    tr.drain()
    feed.close()

    with _tracing_armed() as tracing:
        feed = fresh_feed()
        all_losses = []
        with jax.transfer_guard("disallow"):
            for b in feed:
                all_losses.append(tr.run_steps(b.data[0], b.label[0], n=2))
        tr.drain()
        feed.close()
        assert np.all(np.isfinite(np.asarray(all_losses)))
        names = {e["name"] for e in tracing.spans()}
        assert "mx.dp.run_steps" in names
