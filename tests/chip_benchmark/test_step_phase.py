"""The `step_phase` reader and the four per-layer metrics that are its data
(`step_host_sync_ms`, `step_host_work_ms`, `feed_busy_share`,
`setup_step_build_s`): on hand-made records, on a real trainer with a wait
planted in its key read-back, and in a traced toy run."""
import time
import types

import numpy as np
import pytest

import cells
import run
from mxnet_tpu.telemetry import tracing

METRICS = ("step_host_sync_ms", "step_host_work_ms", "feed_busy_share",
           "setup_step_build_s")
READER = cells.load_module("readers", "step_phase")


def _params(metric):
    spec = cells.load_json(f"{cells.HERE}/layer_metrics/{metric}.json")
    assert spec["reader"] == "step_phase"
    return spec["params"]


def _view(t0, seconds, steps, step_call_s=0.0):
    said = []
    return types.SimpleNamespace(
        window={"t0": t0, "seconds": seconds, "steps": steps,
                "step_call_s": step_call_s}, say=said.append, said=said)


def _step(ts, step, **phases):
    return {"kind": "step", "name": "mx.dp.step", "ts": ts,
            "dur": sum(phases.values()), "phases": phases, "step": step}


def _batch(ts, **phases):
    return {"kind": "batch", "name": "mx.feed.batch", "ts": ts,
            "dur": sum(phases.values()), "phases": phases, "source": "feed"}


@pytest.fixture
def ring():
    """The program's ring, emptied, for hand-made records."""
    tracing.reset()
    yield tracing._RING
    tracing.reset()


def _hand_made(ring):
    # set-up: the first step built the executable; then a window of three
    # steps from t = 100 to 100.9 and a traced stretch after it
    ring.append(_step(90.0, 1, get_step=0.5, rng_key=0.01, capture_cost=0.25,
                      launch=8.0, admit=0.001, admit_wait=0.0))
    ring.append(_batch(99.0, produce=0.5, put=0.5, queue_wait=0.0))
    for i in range(3):
        ring.append(_step(100.0 + 0.3 * i, 2 + i, get_step=0.001,
                          rng_key=0.250, put_batch=0.002, launch=0.004,
                          admit=0.001, admit_wait=0.040))
        ring.append(_batch(100.05 + 0.3 * i, produce=0.02, put=0.07,
                           queue_wait=0.2))
    ring.append(_step(101.0, 5, rng_key=7.0, launch=7.0, admit_wait=7.0))
    ring.append(_batch(101.0, produce=7.0, put=7.0, queue_wait=0.0))
    return _view(100.0, 0.9, 3, step_call_s=3 * 0.2985)


def test_each_metric_on_hand_made_records(ring):
    view = _hand_made(ring)
    got = {m: READER.read(view, _params(m)) for m in METRICS}
    # per step of the window, the records outside it left out
    assert got["step_host_sync_ms"] == pytest.approx(250.0)
    assert got["step_host_work_ms"] == pytest.approx(8.0)
    # produce + put of the window's batches over the window: 3 x 0.09 / 0.9
    assert got["feed_busy_share"] == pytest.approx(30.0)
    # the process's first step record, window or not
    assert got["setup_step_build_s"] == pytest.approx(8.75)
    # the inside and the outside reading of the call, side by side
    (line,) = view.said
    assert "3 in the window" in line and "298.000 ms a step inside" in line \
        and "298.500 ms round it" in line


def test_a_phase_that_is_gone_reads_zero(ring):
    """A later PR takes the key read-back out: 0, not nothing."""
    for i in range(3):
        ring.append(_step(100.0 + 0.3 * i, i, launch=0.004, admit=0.001,
                          admit_wait=0.290))
    view = _view(100.0, 0.9, 3)
    assert READER.read(view, _params("step_host_sync_ms")) == 0.0
    assert READER.read(view, _params("step_host_work_ms")) \
        == pytest.approx(5.0)
    assert READER.read(view, _params("setup_step_build_s")) \
        == pytest.approx(0.004)


def test_a_call_that_did_not_deliver_is_no_step_and_no_batch(ring):
    """The feed's source ends inside a `produce` (`error`), `close` cuts a
    `queue_wait` (`aborted`), a step raises (`error`): on record for the
    flight recorder, left out of every reading."""
    view = _hand_made(ring)
    ring.append(dict(_batch(100.2, produce=0.3), error="StopIteration"))
    ring.append(dict(_batch(100.3, produce=0.1, put=0.1, queue_wait=0.2),
                     aborted=True))
    ring.append(dict(_step(100.85, 9, get_step=0.001, rng_key=0.04),
                     error="RuntimeError"))
    assert READER.read(view, _params("feed_busy_share")) == pytest.approx(30.0)
    assert READER.read(view, _params("step_host_sync_ms")) \
        == pytest.approx(250.0)
    assert READER.read(view, _params("step_host_work_ms")) \
        == pytest.approx(8.0)


def test_run_steps_records_count_their_steps(ring):
    ring.append({"kind": "step", "name": "mx.dp.run_steps", "ts": 100.0,
                 "dur": 0.8, "steps": 4, "step": 0,
                 "phases": {"rng_key": 0.2, "launch": 0.2, "admit": 0.0,
                            "admit_wait": 0.4}})
    view = _view(100.0, 0.9, 4)
    assert READER.read(view, _params("step_host_sync_ms")) \
        == pytest.approx(50.0)
    assert READER.read(view, _params("step_host_work_ms")) \
        == pytest.approx(50.0)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_that_stopped_recording_is_an_error(ring, metric):
    ring.append(_step(50.0, 1, launch=1.0) if metric == "feed_busy_share"
                else _batch(100.1, produce=0.1))
    with pytest.raises(RuntimeError, match="kept no record"):
        READER.read(_view(100.0, 0.9, 3), _params(metric))


@pytest.mark.parametrize("metric", ["step_host_sync_ms", "step_host_work_ms"])
def test_a_miscounted_window_is_an_error(ring, metric):
    for i in range(2):
        ring.append(_step(100.0 + 0.3 * i, i, rng_key=0.2, launch=0.01))
    with pytest.raises(RuntimeError, match="count 2 steps, the runner drove 3"):
        READER.read(_view(100.0, 0.9, 3), _params(metric))


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_from_before_the_records_gives_nothing(monkeypatch, metric):
    """The parent commit's program has no `step_records`: the reader returns
    nothing and does not raise, and the line leaves the metric out."""
    monkeypatch.delattr(tracing, "step_records")
    assert READER.read(_view(100.0, 0.9, 3), _params(metric)) is None


def test_planted_wait_shows_in_sync_and_not_in_work(toy_root, monkeypatch):
    """A `time.sleep` in the key read-back, in a real trainer behind its
    feed: it is all in `step_host_sync_ms`, none of it in
    `step_host_work_ms`."""
    import jax
    import runner
    import traffic
    from mxnet_tpu import random as mx_random
    from reference import steps
    cell = cells.Cell("bert_toy_train", toy_root + "/BENCHMARK.json",
                      toy_root)
    spec = cell.module("reference").param_spec(cell.config)
    pool = traffic.make_pool(cell.traffic, cell.config, 9)
    prog = runner.Program(cell, steps.make_weights(spec, 9), pool, 9,
                          jax.devices()[:1])
    prog.first_steps()

    def read(window):
        view = types.SimpleNamespace(window=window, say=lambda line: None)
        return [READER.read(view, _params(m))
                for m in ("step_host_sync_ms", "step_host_work_ms")]

    sync0, work0 = read(prog.stretch(0.3))
    real = mx_random.next_key_raw

    def slow_key():
        time.sleep(0.02)
        return real()
    monkeypatch.setattr(mx_random, "next_key_raw", slow_key)
    sync1, work1 = read(prog.stretch(0.3))
    prog.close()
    # (the sleep hides the wait for the device that the read-back held
    # before it, so the planted 20 ms are a floor and not an addition)
    assert sync1 >= 20.0, (sync0, sync1)
    assert work1 < work0 + 5.0 and work1 < 20.0, (work0, work1)


def test_traced_toy_run_reports_the_four(toy_root):
    """A whole `--trace 1` run on the CPU: the four metrics are on the line
    beside the host counters, and the inside reading of the call agrees
    with the benchmark's outside one."""
    result = run.run_cell("bert_toy_train", (1 << 31) + 5, 0.5, 1,
                          root=toy_root,
                          bench_json=toy_root + "/BENCHMARK.json",
                          require_tpu=False)
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(METRICS) <= set(m) and "dispatch_ms" in m
    assert all(np.isfinite(m[k]) and m[k] >= 0 for k in METRICS)
    assert result["metrics"]["feed_busy_share"]["unit"] == "%"
    assert 0 < m["feed_busy_share"] < 100
    assert m["setup_step_build_s"] > 0
    # sync + work is the call less the window's wait, read from inside;
    # `dispatch_ms` is the same, read from outside
    assert m["step_host_sync_ms"] + m["step_host_work_ms"] == pytest.approx(
        m["dispatch_ms"], rel=0.05)
