"""The step's phases on record inside the program (ISSUE 25).

Acceptance under test:

  - `tracing.phased` cuts a call into phases that partition it: all >= 0,
    contiguous, summing to `dur`;
  - `DataParallelTrainer.step` / `run_steps` and the feed's producer leave one
    record a call / a batch in the ring with tracing DISARMED; `spans()` does
    not see them and the ring stays bounded;
  - the one span primitive always lands in a profiler trace: with
    MXNET_TELEMETRY and MXNET_TPU_TRACING unset, the host plane of a CPU trace
    holds `mx.dp.step` with every phase nested inside it, and `mx.feed.put` on
    another thread's line;
  - a wait planted where the key is drawn shows in `rng_key` on both routes
    (ISSUE 26: in one process the key stays on the device and the phase is its
    dispatch; multi-process SPMD reads it back), one planted in the
    window's block in `admit_wait` and in a real `mx.window.admit` span;
  - `telemetry.annotate` has no gate of its own.
"""
import glob
import json
import os
import threading
import time

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu import random as mx_random
from mxnet_tpu.engine.async_feed import DeviceFeed, DispatchWindow
from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
from mxnet_tpu.telemetry import tracing

STEP_PHASES = ("get_step", "rng_key", "put_batch", "put_scalars",
               "capture_cost", "launch", "admit", "admit_wait")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    monkeypatch.delenv("MXNET_TPU_TRACING", raising=False)
    telemetry.disable()
    tracing.disable()
    telemetry.reset()
    yield
    tracing.disable()
    telemetry.disable()
    telemetry.reset()


def _loss_fn(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _trainer():
    mx.random.seed(7)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 8)))
    mesh = make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])
    return DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                               optimizer_params={"learning_rate": 0.01},
                               mesh=mesh)


def _batch(rows=8):
    rs = onp.random.RandomState(0)
    return (rs.uniform(-1, 1, (rows, 8)).astype(onp.float32),
            rs.randint(0, 4, (rows,)).astype(onp.int32))


class _Source:
    """A re-iterable of `n` host batches."""

    def __init__(self, n):
        self.n = n

    def __iter__(self):
        return iter([_batch()] * self.n)


def _partition(record):
    assert all(s >= 0.0 for s in record["phases"].values()), record
    assert sum(record["phases"].values()) == pytest.approx(
        record["dur"], abs=1e-9)


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_phases_are_contiguous_and_sum_to_the_call():
    tracing.enable()    # armed, so that each phase's own span can be read
    with tracing.phased("step", "call", step=3) as rec:
        with rec.phase("a"):
            time.sleep(0.002)
        time.sleep(0.001)            # between two phases: the later one's
        with rec.phase("b"):
            pass
        with rec.phase("a"):         # a name used twice accumulates
            time.sleep(0.001)
        time.sleep(0.001)            # the return path: the last phase's
    (record,) = tracing.step_records("call")
    assert record["kind"] == "step" and record["step"] == 3
    assert set(record["phases"]) == {"a", "b"}
    assert record["phases"]["a"] >= 0.004 and record["phases"]["b"] >= 0.001
    _partition(record)
    call, = [e for e in tracing.spans() if e["name"] == "call"]
    kids = [e for e in tracing.spans() if e["name"].startswith("call.")]
    assert [e["name"] for e in kids] == ["call.a", "call.b", "call.a"]
    assert all(e["parent_id"] == call["span_id"] for e in kids)
    # each phase begins where the one before it ended, the first at entry
    assert kids[0]["ts"] == call["ts"] == record["ts"]
    for prev, nxt in zip(kids, kids[1:]):
        assert nxt["ts"] == pytest.approx(prev["ts"] + prev["dur"], abs=1e-9)


def test_split_books_a_wait_measured_inside_a_phase():
    with tracing.phased("step", "call") as rec:
        with rec.phase("admit"):
            time.sleep(0.003)
        rec.split("admit", "admit_wait", 0.002)
        rec.split("admit", "admit_wait", -1.0)      # never negative
    (record,) = tracing.step_records("call")
    assert record["phases"]["admit_wait"] == pytest.approx(0.002)
    assert record["phases"]["admit"] >= 0.001
    _partition(record)


def test_a_call_that_raises_still_leaves_its_record():
    with pytest.raises(ValueError):
        with tracing.phased("step", "call") as rec:
            with rec.phase("a"):
                pass
            with rec.phase("b"):
                raise ValueError("x")
    (record,) = tracing.step_records("call")
    assert record["error"] == "ValueError" and set(record["phases"]) == {
        "a", "b"}
    _partition(record)


def test_records_are_kept_disarmed_and_spans_does_not_see_them():
    assert not tracing.is_enabled()
    before = tracing.spans()
    t0 = time.perf_counter()
    for i in range(5):
        with tracing.phased("step", "call", step=i) as rec:
            with rec.phase("a"):
                pass
        with tracing.span("plain"):
            pass
    t1 = time.perf_counter()
    assert tracing.spans() == before == []
    assert [r["step"] for r in tracing.step_records("call")] == list(range(5))
    assert tracing.step_records("other") == []
    assert len(tracing.step_records("call", since=t0, until=t1)) == 5
    assert tracing.step_records("call", since=t1) == []
    assert tracing.step_records("call", until=t0) == []
    assert [e["kind"] for e in tracing.recent(2)] == ["step", "step"]


def test_ring_stays_bounded_with_records():
    tracing.set_max_spans(8)
    try:
        for i in range(40):
            with tracing.phased("batch", "call", i=i) as rec:
                with rec.phase("a"):
                    pass
        records = tracing.step_records()
        assert [r["i"] for r in records] == list(range(32, 40))
        # a re-cap keeps the newest records too
        tracing.set_max_spans(4)
        assert [r["i"] for r in tracing.step_records()] == [36, 37, 38, 39]
    finally:
        tracing.set_max_spans(
            telemetry.env.get("MXNET_TPU_TRACING_MAX_SPANS"))


def test_flight_recorder_holds_the_last_steps_without_arming(tmp_path):
    tr = _trainer()
    for _ in range(3):
        tr.step(*_batch())
    tr.drain()
    path = tracing.dump_flight_recorder(str(tmp_path / "box.ndjson"),
                                        reason="test")
    lines = [json.loads(ln) for ln in open(path).read().splitlines()]
    assert lines[0]["entries"] == len(lines) - 1 >= 3
    steps = [e for e in lines[1:] if e["kind"] == "step"]
    assert [e["step"] for e in steps] == [1, 2, 3]
    assert all(e["phases"]["launch"] > 0 for e in steps)


def test_annotate_has_no_gate_of_its_own():
    assert not telemetry.is_enabled() and not tracing.is_enabled()
    region = telemetry.annotate("mx.test.region")
    assert isinstance(region, tracing._Span)
    with region:
        pass
    assert tracing.spans() == []
    tracing.enable()
    with telemetry.annotate("mx.test.region"):
        pass
    assert [e["name"] for e in tracing.spans()] == ["mx.test.region"]


# ---------------------------------------------------------------------------
# the trainer and the feed, tracing disarmed
# ---------------------------------------------------------------------------

def test_one_record_per_step_call_disarmed():
    tr = _trainer()
    t0 = time.perf_counter()
    for _ in range(4):
        tr.step(*_batch(rows=6))    # a signature no other test compiles
    tr.drain()
    assert tracing.spans() == []
    records = tracing.step_records("mx.dp.step", since=t0)
    assert [r["step"] for r in records] == [1, 2, 3, 4]
    for r in records:
        assert r["kind"] == "step" and r["source"] == "data_parallel"
        assert r["thread"] == threading.get_ident()
        assert set(r["phases"]) == set(STEP_PHASES)     # no scaler: no sync
        _partition(r)
    # the first call traced, lowered and compiled the step (jit does so at
    # its first launch); the later ones looked it up
    build = [r["phases"]["get_step"] + r["phases"]["capture_cost"]
             + r["phases"]["launch"] for r in records]
    assert build[0] > 10 * max(build[1:])
    # records follow one another on the clock
    for prev, nxt in zip(records, records[1:]):
        assert nxt["ts"] >= prev["ts"] + prev["dur"]


def test_one_record_per_run_steps_call_disarmed():
    tr = _trainer()
    x, y = _batch()
    for _ in range(3):
        tr.run_steps(x, y, n=2)
    tr.drain()
    assert tracing.spans() == []
    records = tracing.step_records("mx.dp.run_steps")
    assert [(r["step"], r["steps"]) for r in records] == [(0, 2), (2, 2),
                                                          (4, 2)]
    # the key is drawn for the first call alone; afterwards it rides the
    # donated carry on the device
    assert "rng_key" in records[0]["phases"]
    assert all("rng_key" not in r["phases"] for r in records[1:])
    for r in records:
        assert {"get_step", "put_scalars", "put_batch", "capture_cost",
                "launch", "admit", "admit_wait"} <= set(r["phases"])
        _partition(r)
    assert tracing.step_records("mx.dp.step") == []


def test_one_record_per_produced_batch_from_the_producers_thread():
    tr = _trainer()
    feed = DeviceFeed.for_trainer(_Source(5), tr, name="toy")
    got = list(feed)
    feed.close()
    assert len(got) == 5 and tracing.spans() == []
    records = tracing.step_records("mx.feed.batch")
    made = [r for r in records if "error" not in r]
    assert [r["batch"] for r in made] == [0, 1, 2, 3, 4]
    for r in made:
        assert r["kind"] == "batch" and r["source"] == "toy"
        assert r["thread"] != threading.get_ident()
        assert set(r["phases"]) == {"produce", "put", "queue_wait"}
        _partition(r)
    assert len({r["thread"] for r in records}) == 1
    # the source ran out inside the sixth `produce`: on record as that
    (ended,) = [r for r in records if "error" in r]
    assert ended["error"] == "StopIteration" and set(ended["phases"]) == {
        "produce"}


def test_a_batch_the_feed_was_stopped_under_says_so():
    """`close` while the producer waits on a full queue: that batch was made
    and placed but never handed over, and its record is marked `aborted`."""
    tr = _trainer()
    feed = DeviceFeed.for_trainer(_Source(50), tr, depth=1)
    next(iter(feed))
    deadline = time.time() + 10
    while time.time() < deadline and len(
            tracing.step_records("mx.feed.batch")) < 2:
        time.sleep(0.01)        # batch 1 queued, batch 2 waiting on the queue
    time.sleep(0.05)
    feed.close()
    records = tracing.step_records("mx.feed.batch")
    cut = [r for r in records if r.get("aborted")]
    assert len(cut) == 1 and cut[0] is records[-1]
    assert "error" not in cut[0] and "queue_wait" in cut[0]["phases"]
    assert all("aborted" not in r for r in records[:-1])


def test_queue_wait_is_the_feeds_slack():
    """A consumer slower than the producer: the producer's time goes to
    `queue_wait`, not to `produce` or `put`."""
    tr = _trainer()
    feed = DeviceFeed.for_trainer(_Source(6), tr, depth=1)
    for _ in feed:
        time.sleep(0.02)
    feed.close()
    made = [r for r in tracing.step_records("mx.feed.batch")
            if "error" not in r]
    waited = sum(r["phases"]["queue_wait"] for r in made)
    busy = sum(r["phases"]["produce"] + r["phases"]["put"] for r in made)
    assert waited > 0.05 and waited > busy


@pytest.mark.parametrize("multiprocess", [False, True])
def test_planted_wait_in_the_key_shows_in_rng_key(monkeypatch, multiprocess):
    tr = _trainer()
    tr._multiprocess = multiprocess
    tr.step(*_batch())
    tr.drain()
    real = mx_random.next_key_raw

    def slow_key():
        time.sleep(0.03)
        return real()
    monkeypatch.setattr(mx_random, "next_key_raw", slow_key)
    t0 = time.perf_counter()
    tr.step(*_batch())
    tr.drain()
    (r,) = tracing.step_records("mx.dp.step", since=t0)
    assert r["phases"]["rng_key"] >= 0.03
    assert r["dur"] - r["phases"]["rng_key"] < 0.03
    _partition(r)


def test_window_wait_is_a_real_span_and_the_records_admit_wait():
    """`mx.window.admit` is measured round the blocking wait (it used to be
    rebuilt by arithmetic), and the step's record books that wait under
    `admit_wait`, not under `admit`."""
    class Slow:
        def block_until_ready(self):
            time.sleep(0.02)

    tracing.enable()
    win = DispatchWindow(depth=1, name="toy")
    win.admit(Slow())
    assert win.wait_seconds == 0.0
    assert not [e for e in tracing.spans() if e["name"] == "mx.window.admit"]
    win.admit(Slow())
    (sp,) = [e for e in tracing.spans() if e["name"] == "mx.window.admit"]
    assert sp["dur"] >= 0.02 and sp["dur"] >= win.wait_seconds >= 0.02
    assert sp["attrs"]["source"] == "toy"
    win.drain()
    (dr,) = [e for e in tracing.spans() if e["name"] == "mx.window.drain"]
    assert dr["attrs"]["drained"] == 1 and dr["dur"] >= 0.02
    tracing.disable()

    tr = _trainer()
    for _ in range(3):
        tr.step(*_batch())
    tr.drain()
    real = tr._window._block

    def slow_block(handles):
        time.sleep(0.03)
        real(handles)
    tr._window._block = slow_block
    t0 = time.perf_counter()
    for _ in range(3):            # the window is two deep: the third waits
        tr.step(*_batch())
    records = tracing.step_records("mx.dp.step", since=t0)
    tr._window._block = real
    tr.drain()
    assert records[-1]["phases"]["admit_wait"] >= 0.03
    assert records[-1]["phases"]["admit"] < 0.03
    assert records[0]["phases"]["admit_wait"] == 0.0


# ---------------------------------------------------------------------------
# one clock with the device: the profiler's own trace
# ---------------------------------------------------------------------------

def _host_lines(trace_dir):
    """{line name: [(event name, start_ns, end_ns)]} of the host plane."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events if e.name.startswith("mx.")]
            if events:
                lines[f"{line.name}#{i}"] = events
    return lines


def test_spans_land_in_a_profiler_trace_with_nothing_armed(tmp_path):
    assert "MXNET_TELEMETRY" not in os.environ
    assert "MXNET_TPU_TRACING" not in os.environ
    assert not telemetry.is_enabled() and not tracing.is_enabled()
    tr = _trainer()
    tr.step(*_batch())      # compile outside the trace
    tr.drain()
    feed = DeviceFeed.for_trainer(_Source(4), tr)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for x, y in feed:
            tr.step(x, y)
        tr.drain()
    finally:
        jax.profiler.stop_trace()
        feed.close()
    assert tracing.spans() == []          # the ring's spans stayed disarmed
    lines = _host_lines(str(tmp_path))
    (main,) = [n for n, evs in lines.items()
               if any(e[0] == "mx.dp.step" for e in evs)]
    steps = [e for e in lines[main] if e[0] == "mx.dp.step"]
    assert len(steps) == 4
    for phase in STEP_PHASES:
        if phase == "admit_wait":
            continue        # a field of the record; its span: mx.window.admit
        inside = [e for e in lines[main] if e[0] == "mx.dp.step." + phase]
        assert len(inside) == 4, phase
        for (_, s0, s1), (_, p0, p1) in zip(steps, inside):
            assert s0 <= p0 <= p1 <= s1, phase
    assert any(e[0] == "mx.window.drain" for e in lines[main])
    # the producer's spans are on another thread's line
    (producer,) = [n for n, evs in lines.items()
                   if any(e[0] == "mx.feed.put" for e in evs)]
    assert producer != main
    names = {e[0] for e in lines[producer]}
    assert {"mx.feed.batch", "mx.feed.produce", "mx.feed.put",
            "mx.feed.queue_wait"} <= names
    assert not any(e[0].startswith("mx.feed.") and e[0] != "mx.feed.next"
                   for e in lines[main])


# ---------------------------------------------------------------------------
# set-up on record (ISSUE 36): a `build` record for every program the process
# builds, `setup` records round net init and the trainer's placement
# ---------------------------------------------------------------------------

STAGES = ("trace", "lower", "compile", "cache_load")


def _builds(fun=None, since=None):
    return [b for b in tracing.step_records("mx.build", since=since)
            if fun is None or b["fun"] == fun]


def test_record_kinds_are_public_and_read_through_step_records():
    assert tracing.RECORD_KINDS == ("step", "batch", "setup", "build")
    with tracing.phased("setup", "mx.test.setup", leaves=3) as rec:
        with rec.phase("a"):
            pass
    (r,) = tracing.step_records("mx.test.setup")
    assert r["kind"] == "setup" and r["leaves"] == 3
    _partition(r)
    assert tracing.spans() == []        # records, not spans


def test_a_jitted_function_leaves_one_build_record_and_a_second_call_none():
    @jax.jit
    def once_built_fn(x):
        return jnp.tanh(x) * 3 + 1

    x = jnp.ones((5, 7))
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    once_built_fn(x)
    (b,) = _builds("once_built_fn", since=t0)
    assert b["kind"] == "build" and b["name"] == "mx.build"
    assert b["thread"] == threading.get_ident()
    assert set(b["phases"]) == set(STAGES)
    assert all(v >= 0.0 for v in b["phases"].values())
    assert b["phases"]["trace"] > 0 and b["phases"]["lower"] > 0 \
        and b["phases"]["compile"] > 0
    assert sum(b["phases"].values()) == pytest.approx(b["dur"], rel=1e-9)
    assert b["cache"] in ("off", "miss")    # nothing to load it from
    assert b["phases"]["cache_load"] == 0.0
    assert t0 <= b["ts"] <= time.perf_counter()
    once_built_fn(x)
    once_built_fn(x + 1)
    assert len(_builds("once_built_fn", since=t0)) == 1
    assert tracing.spans() == []            # disarmed: records alone


def test_a_function_traced_into_another_leaves_no_record_of_its_own():
    """A stage inside a stage of the same thread is part of it: the records'
    seconds do not overlap, so they sum to `compile_seconds`."""
    @jax.jit
    def inner_of_nested(x):
        return x * 2

    @jax.jit
    def outer_of_nested(x):
        return inner_of_nested(x) + 1

    from mxnet_tpu import engine
    x = jnp.ones(3)
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    before = engine.cache_stats()["compile_seconds"]
    outer_of_nested(x)
    spent = engine.cache_stats()["compile_seconds"] - before
    built = _builds(since=t0)
    assert [b["fun"] for b in built] == ["outer_of_nested"]
    assert spent == pytest.approx(built[0]["dur"], rel=1e-6)


def test_lower_without_compile_is_a_build_of_the_stages_it_ran():
    g = jax.jit(lambda x: x - 4)
    t0 = time.perf_counter()
    lowered = g.lower(jnp.ones(3))
    (b,) = _builds("<lambda>", since=t0)
    assert b["phases"]["trace"] > 0 and b["phases"]["lower"] > 0
    assert b["phases"]["compile"] == 0.0
    lowered.compile()                      # the same program: the same record
    (b2,) = _builds("<lambda>", since=t0)
    assert b2 is b and b["phases"]["compile"] > 0
    assert sum(b["phases"].values()) == pytest.approx(b["dur"], rel=1e-9)


def test_armed_each_stage_is_a_child_span():
    tracing.enable()
    with tracing.span("around_a_build") as sp:
        jax.jit(lambda x: x * 5 - 1)(jnp.ones(2))
    stages = [s for s in tracing.spans()
              if s["name"].startswith("mx.build.")
              and s["attrs"]["fun"] == "<lambda>"]
    assert [s["name"] for s in stages] == ["mx.build.trace", "mx.build.lower",
                                           "mx.build.compile"]
    assert all(s["parent_id"] == sp.span_id for s in stages)


_CACHE_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from mxnet_tpu.telemetry import tracing
f = jax.jit(lambda x: jnp.tanh(x) @ x.T + 2, )
f(jnp.ones((16, 16)))
(b,) = [b for b in tracing.step_records("mx.build") if b["fun"] == "<lambda>"]
print(json.dumps(b))
"""


def test_a_process_fresh_build_reads_miss_then_hit(tmp_path):
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    got = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env,
                             cwd=root, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        got.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = got
    assert first["cache"] == "miss" and first["phases"]["cache_load"] == 0.0
    assert second["cache"] == "hit" and second["phases"]["cache_load"] > 0
    for b in got:
        assert all(v >= 0 for v in b["phases"].values())
        assert sum(b["phases"].values()) == pytest.approx(b["dur"], rel=1e-9)


def test_a_build_is_attributed_to_its_own_threads_call():
    started, built = threading.Event(), threading.Event()

    def other():
        started.wait(10)
        jax.jit(lambda x: x * 7 + 2)(jnp.ones(4))     # no call round it
        built.set()

    t = threading.Thread(target=other)
    t.start()
    t0 = time.perf_counter()
    with tracing.phased("step", "mx.test.call") as rec:
        with rec.phase("wait"):
            started.set()
            assert built.wait(60)
        with rec.phase("build"):
            jax.jit(lambda x: x * 9 - 2)(jnp.ones(4))
    t.join(10)
    assert not t.is_alive()
    (call,) = tracing.step_records("mx.test.call")
    mine, theirs = [], []
    for b in _builds("<lambda>", since=t0):
        (mine if b["thread"] == threading.get_ident() else theirs).append(b)
    assert len(mine) == 1 and len(theirs) == 1
    assert call["ts"] <= theirs[0]["ts"] <= call["ts"] + call["dur"]
    assert tracing.parent_of(theirs[0]) is None
    assert tracing.parent_of(mine[0]) is call
    # /statusz's view says the same
    under = {b["thread"]: b["under"] for b in tracing.recent(64)
             if b["kind"] == "build" and b["fun"] == "<lambda>"}
    assert under == {mine[0]["thread"]: "mx.test.call",
                     theirs[0]["thread"]: None}


def test_parent_of_is_the_innermost_call():
    tid = threading.get_ident()

    def rec(kind, name, ts, dur, thread=tid):
        return {"kind": kind, "name": name, "ts": ts, "dur": dur,
                "phases": {}, "thread": thread}
    outer, inner = rec("setup", "outer", 10.0, 5.0), rec("step", "in", 11.0, 2.0)
    batch, away = rec("batch", "b", 11.0, 2.0), rec("step", "x", 11.0, 2.0, 1)
    calls = [outer, inner, batch, away]
    build = rec("build", "mx.build", 11.5, 0.1)
    assert tracing.parent_of(build, calls) is inner
    assert tracing.parent_of(dict(build, ts=13.5), calls) is outer
    assert tracing.parent_of(dict(build, ts=15.5), calls) is None
    assert tracing.parent_of(inner, calls) is outer


def test_the_steady_state_builds_nothing():
    tr = _trainer()
    x, y = _batch(rows=10)
    tr.step(x, y)
    tr.drain()
    t1 = time.perf_counter()
    for _ in range(20):
        tr.step(x, y)
    tr.drain()
    assert len(tracing.step_records("mx.dp.step", since=t1)) == 20
    assert _builds(since=t1) == []


def test_the_first_step_holds_its_build_and_compile_seconds_counts_it():
    from mxnet_tpu import engine
    tr = _trainer()
    engine.reset_stats()
    t0 = time.perf_counter()
    tr.step(*_batch(rows=12))       # a signature no other test compiles
    tr.drain()
    (first,) = tracing.step_records("mx.dp.step", since=t0)
    (step,) = [b for b in _builds("step", since=t0)
               if tracing.parent_of(b) is first]
    launch = first["phases"]["launch"]
    assert first["ts"] <= step["ts"] and step["dur"] <= launch
    assert step["dur"] > 0.5 * launch      # the launch IS the build
    spent = engine.cache_stats()["compile_seconds"]
    assert spent >= step["dur"]
    assert spent == pytest.approx(sum(b["dur"] for b in _builds(since=t0)),
                                  rel=1e-6)
    engine.reset_stats()
    assert engine.cache_stats()["compile_seconds"] == 0.0


def test_compile_timer_counts_the_artifact_and_not_its_seconds():
    from mxnet_tpu import engine
    engine.reset_stats()
    with engine.compile_timer("test:artifact"):
        time.sleep(0.02)
    st = engine.cache_stats()
    assert st["compiles"] == 1 and st["compile_seconds"] == 0.0


def test_dp_init_is_on_record_with_the_nets_leaves_and_bytes():
    t0 = time.perf_counter()
    tr = _trainer()
    (r,) = tracing.step_records("mx.dp.init", since=t0)
    assert r["kind"] == "setup" and r["source"] == "data_parallel"
    assert list(r["phases"]) == ["collect", "place_params", "init_opt_state",
                                 "compression", "program"]
    _partition(r)
    params = tr.net.collect_params().values()
    assert r["leaves"] == len(params) == 4
    assert r["bytes"] == sum(
        int(onp.prod(p.shape)) * 4 for p in params) == (8 * 16 + 16
                                                        + 16 * 4 + 4) * 4
    # the placement's programs ran under it
    assert all(tracing.parent_of(b) is r for b in _builds(since=r["ts"])
               if b["ts"] <= r["ts"] + r["dur"]
               and b["thread"] == r["thread"])


def _dense_net(in_units):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=in_units[0]),
            gluon.nn.Dense(4, in_units=in_units[1]))
    return net


@pytest.mark.parametrize("hybridize", [True, False])
def test_deferred_init_is_on_record_once_and_only_when_cold(hybridize):
    x = nd.zeros((2, 8))
    net = _dense_net((0, 16))       # the first layer's input width unknown
    t0 = time.perf_counter()
    net.initialize()
    (init,) = tracing.step_records("mx.block.initialize", since=t0)
    assert init["kind"] == "setup" and init["params"] == 4
    if hybridize:
        net.hybridize()
    net(x)
    (cold,) = tracing.step_records("mx.block.deferred_init", since=t0)
    assert cold["kind"] == "setup" and cold["params"] == 1
    assert list(cold["phases"]) == ["probe", "finish", "forward"]
    _partition(cold)
    assert cold["ts"] >= init["ts"] + init["dur"]
    if hybridize:
        # the cached graph is a function of its own: built under the record
        # (the eager ops of the plain net were built by earlier tests)
        assert any(tracing.parent_of(b) is cold for b in _builds(since=t0))
    net(x)
    net(x)
    assert len(tracing.step_records("mx.block.deferred_init", since=t0)) == 1


@pytest.mark.parametrize("hybridize", [True, False])
def test_a_net_whose_shapes_were_given_is_never_cold(hybridize):
    t0 = time.perf_counter()
    net = _dense_net((8, 16))
    net.initialize()
    if hybridize:
        net.hybridize()
    net(nd.zeros((2, 8)))
    assert len(tracing.step_records("mx.block.initialize", since=t0)) == 1
    assert tracing.step_records("mx.block.deferred_init", since=t0) == []


def test_a_nested_initialize_leaves_the_outermost_record_alone():
    from mxnet_tpu.gluon import block as block_mod

    class Outer(gluon.nn.HybridSequential):
        def initialize(self, *a, **kw):
            with block_mod._outermost("mx.block.initialize",
                                      params=len(self.collect_params())):
                for child in self._children.values():
                    child.initialize(*a, **kw)

    net = Outer()
    net.add(gluon.nn.Dense(3, in_units=2), gluon.nn.Dense(3, in_units=3))
    t0 = time.perf_counter()
    net.initialize()
    (only,) = tracing.step_records("mx.block.initialize", since=t0)
    assert only["params"] == 4
    net[0].initialize(force_reinit=True)    # the name is free again
    assert len(tracing.step_records("mx.block.initialize", since=t0)) == 2
