"""Cross-layer span tracing, flight recorder, statusz (ISSUE 14).

Acceptance under test:

  - disarmed: span() is a profiler TraceAnnotation only, spans and events
    write nothing to the ring;
  - armed: process-unique trace/span ids, parent propagation within and
    across threads (attach/new_root/explicit parent);
  - one serving request's trace id observable END TO END: the submit-side
    future exposes it, every lifecycle span carries it, and the HTTP
    front door echoes it as X-MX-Trace-Id;
  - dump_chrome_trace emits structurally valid Perfetto/Chrome trace-event
    JSON whose track names include the TraceAnnotation region names;
  - the flight-recorder NDJSON lands on SIGTERM preemption with the final
    steps' spans (kill-and-dump), and on unhandled step exceptions;
  - /statusz + /healthz on both the serving Server and
    telemetry.start_http_server();
  - the anomaly watchdog books mx_anomalies_total{kind} for EWMA step-time
    regressions and nonfinite losses.
"""
import contextlib
import json
import os
import signal
import urllib.request

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import elastic, gluon, nd, serving, telemetry
from mxnet_tpu.engine.async_feed import DeviceFeed
from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
from mxnet_tpu.telemetry import tracing


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    yield
    tracing.disable()
    telemetry.disable()
    telemetry.reset()


@contextlib.contextmanager
def _armed():
    telemetry.enable()
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------

def test_disarmed_span_writes_nothing_to_the_ring():
    """Disarmed, a span is a profiler TraceAnnotation and nothing else: no
    ids, no thread-local stack, no ring write (ISSUE 25: the one primitive
    has no flag in front of the annotation)."""
    assert not tracing.is_enabled()
    with tracing.span("x") as a:
        assert a.context == (None, None)
        assert tracing.current() is None
        with tracing.span("y", rows=3):
            pass
    assert tracing.spans() == []
    assert tracing.record_span("x", 0.0, 1.0) is None
    assert tracing.event("x") is None
    assert tracing.spans() == []


def test_armed_span_ids_nesting_and_attrs():
    with _armed():
        with tracing.span("outer", step=1) as s_out:
            assert tracing.current() == s_out.context
            with tracing.span("inner") as s_in:
                s_in.set_attr("rows", 8)
        assert tracing.current() is None
        entries = tracing.spans()
        assert [e["name"] for e in entries] == ["inner", "outer"]
        inner, outer = entries
        assert inner["trace_id"] == outer["trace_id"]
        assert inner["parent_id"] == outer["span_id"]
        assert inner["attrs"]["rows"] == 8
        assert outer["attrs"]["step"] == 1
        assert outer["parent_id"] is None
        assert outer["dur"] >= inner["dur"] >= 0.0
        # process-unique prefix on the trace id
        assert outer["trace_id"].startswith(tracing._PREFIX)


def test_span_records_error_attr_on_exception():
    with _armed():
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("x")
        (e,) = tracing.spans()
        assert e["attrs"]["error"] == "ValueError"


def test_cross_thread_attach_parents_under_captured_ctx():
    import threading
    with _armed():
        ctx = tracing.new_root("producer")
        done = threading.Event()

        def worker():
            with tracing.attach(ctx):
                with tracing.span("work"):
                    pass
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5)
        (e,) = [x for x in tracing.spans() if x["name"] == "work"]
        assert e["trace_id"] == ctx[0] and e["parent_id"] == ctx[1]


def test_ring_bound_and_set_max_spans():
    with _armed():
        tracing.set_max_spans(8)
        try:
            for i in range(32):
                tracing.event("e", i=i)
            entries = tracing.spans()
            assert len(entries) == 8
            assert [e["attrs"]["i"] for e in entries] == list(range(24, 32))
            assert [e["attrs"]["i"] for e in tracing.recent(3)] \
                == [29, 30, 31]
        finally:
            tracing.set_max_spans(
                telemetry.env.get("MXNET_TPU_TRACING_MAX_SPANS"))


def test_record_span_with_preallocated_ctx():
    with _armed():
        ctx = tracing.new_root("req")
        got = tracing.record_span("root", 1.0, 2.0, ctx=ctx, status="ok")
        assert got == ctx
        (e,) = tracing.spans()
        assert (e["trace_id"], e["span_id"]) == ctx
        assert e["dur"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# export surfaces
# ---------------------------------------------------------------------------

def test_chrome_trace_structure(tmp_path):
    with _armed():
        with tracing.span("mx.dp.step", step=1):
            tracing.event("mx.fault", point="p")
        path = str(tmp_path / "trace.json")
        assert tracing.dump_chrome_trace(path) == path
        data = json.loads((tmp_path / "trace.json").read_text())
        assert data["displayTimeUnit"] == "ms"
        evs = data["traceEvents"]
        by_name = {e["name"]: e for e in evs}
        step = by_name["mx.dp.step"]
        assert step["ph"] == "X" and step["dur"] >= 0
        assert {"ts", "pid", "tid"} <= set(step)
        assert step["args"]["trace_id"]
        fault = by_name["mx.fault"]
        assert fault["ph"] == "i" and fault["s"] == "t"
        # event parents inside the open span
        assert fault["args"]["parent_id"] == step["args"]["span_id"]


def test_flight_recorder_ndjson(tmp_path):
    with _armed():
        with tracing.span("s1"):
            pass
        tracing.event("e1", k=1)
        path = str(tmp_path / "fr.ndjson")
        tracing.dump_flight_recorder(path, reason="test")
        lines = [json.loads(ln) for ln in
                 (tmp_path / "fr.ndjson").read_text().splitlines()]
        meta, entries = lines[0], lines[1:]
        assert meta["kind"] == "meta" and meta["reason"] == "test"
        assert meta["pid"] == os.getpid()
        assert meta["entries"] == len(entries) == 2
        assert {e["name"] for e in entries} == {"s1", "e1"}


# ---------------------------------------------------------------------------
# serving: end-to-end trace id
# ---------------------------------------------------------------------------

class _SoftmaxMLP(gluon.HybridBlock):
    def __init__(self, classes=5, **kw):
        super().__init__(**kw)
        self.body = gluon.nn.HybridSequential()
        self.body.add(gluon.nn.Dense(16, activation="relu"),
                      gluon.nn.Dense(classes))

    def hybrid_forward(self, F, x):
        return self.body(x).softmax()


ROW_MLP = (6,)


@pytest.fixture
def mlp_prefix(tmp_path):
    mx.random.seed(4)
    net = _SoftmaxMLP()
    net.initialize()
    net.hybridize()
    net(nd.zeros((1,) + ROW_MLP))
    prefix = str(tmp_path / "mlp")
    net.export(prefix)
    return prefix


def _mlp_server(prefix, **kw):
    srv = serving.Server(max_wait_ms=1.0, **kw)
    srv.register("mlp", prefix + "-symbol.json", prefix + "-0000.params",
                 input_shapes={"data": ROW_MLP}, buckets=(1, 4))
    return srv


def test_serving_request_trace_end_to_end(mlp_prefix):
    x = onp.random.RandomState(0).uniform(-1, 1, (2, 6)).astype(onp.float32)
    srv = _mlp_server(mlp_prefix)
    try:
        srv.predict("mlp", data=x)  # warm outside tracing
        with _armed():
            fut = srv.submit("mlp", data=x)
            fut.result(30)
            tid = fut.trace_id
            assert tid and tid.startswith(tracing._PREFIX)
            mine = [e for e in tracing.spans() if e["trace_id"] == tid]
            names = {e["name"] for e in mine}
            # the full lifecycle funnel, all under ONE trace id
            assert {"mx.serving.enqueue", "mx.serving.queue_wait",
                    "mx.serving.dispatch", "mx.serving.complete",
                    "mx.serving.request"} <= names
            root = [e for e in mine if e["name"] == "mx.serving.request"]
            assert root and root[0]["attrs"]["status"] == "ok"
            # queue-wait histogram rode the same stamps
            text = telemetry.scrape()
            assert "mx_serving_queue_wait_seconds_bucket" in text
    finally:
        srv.close()


def test_http_front_door_echoes_trace_id_header(mlp_prefix):
    x = onp.random.RandomState(1).uniform(-1, 1, (2, 6)).astype(onp.float32)
    srv = _mlp_server(mlp_prefix)
    try:
        port = srv.start_http(0)
        srv.predict("mlp", data=x)  # warm
        body = json.dumps({"inputs": {"data": x.tolist()}}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/mlp:predict", data=body,
            headers={"Content-Type": "application/json"})
        with _armed():
            with urllib.request.urlopen(req, timeout=30) as r:
                hdr = r.headers.get("X-MX-Trace-Id")
                json.loads(r.read())
            assert hdr and hdr.startswith(tracing._PREFIX)
            mine = [e for e in tracing.spans() if e["trace_id"] == hdr]
            assert "mx.serving.request" in {e["name"] for e in mine}
        # disarmed requests carry no header
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers.get("X-MX-Trace-Id") is None
    finally:
        srv.close()


def test_serving_statusz_and_healthz(mlp_prefix):
    srv = _mlp_server(mlp_prefix)
    try:
        port = srv.start_http(0)
        with _armed():
            tracing.event("marker", k=1)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/statusz", timeout=30) as r:
                st = json.loads(r.read())
            assert st["tracing_enabled"] is True
            assert st["serving"]["models"][0]["name"] == "mlp"
            assert "mlp" in st["serving"]["queue_depth"]
            assert "compilation" in st and "faults" in st
            assert any(e["name"] == "marker" for e in st["recorder_events"])
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        srv.close()


def test_telemetry_http_server_statusz_and_healthz():
    port = telemetry.start_http_server(0)
    with _armed():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statusz", timeout=30) as r:
            st = json.loads(r.read())
        assert st["telemetry_enabled"] is True
        assert st["tracing_enabled"] is True
        assert "config" in st and "compilation" in st
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
        assert json.loads(r.read())["status"] == "ok"


# ---------------------------------------------------------------------------
# training + elastic: kill-and-dump
# ---------------------------------------------------------------------------

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


class _Feed:
    def __init__(self, n=64):
        self.n = n

    def __iter__(self):
        rs = onp.random.RandomState(0)
        x = rs.uniform(-1, 1, (8, 8)).astype(onp.float32)
        y = rs.randint(0, 4, (8,)).astype(onp.int32)
        return iter([(x, y)] * self.n)

    def reset(self):
        pass


def test_sigterm_kill_dumps_flight_recorder(tmp_path, monkeypatch):
    """ISSUE 14 acceptance: a SIGTERMed elastic.run writes the NDJSON
    black box automatically, and the dump recovers the final steps'
    mx.dp.step spans plus the preemption marker."""
    fr = tmp_path / "black_box.ndjson"
    monkeypatch.setenv("MXNET_TPU_FLIGHT_RECORDER", str(fr))
    tr = _trainer()

    def _kill_at_3(step, loss):
        if step == 3:
            os.kill(os.getpid(), signal.SIGTERM)

    with _armed():
        out = elastic.run(tr, _Feed(), num_steps=10,
                          directory=str(tmp_path / "ck"), save_every=100,
                          on_step=_kill_at_3)
        assert out["preempted"] and out["step"] == 3
    assert fr.exists()
    lines = [json.loads(ln) for ln in fr.read_text().splitlines()]
    meta, entries = lines[0], lines[1:]
    assert meta["reason"] == "preemption"
    assert meta["entries"] == len(entries)
    steps = [e for e in entries
             if e["name"] == "mx.dp.step" and e["kind"] == "span"]
    assert {e["attrs"]["step"] for e in steps} == {1, 2, 3}
    # each step's always-on record is in the dump beside its span
    records = [e for e in entries
               if e["name"] == "mx.dp.step" and e["kind"] == "step"]
    assert [e["step"] for e in records] == [1, 2, 3]
    assert any(e["name"] == "mx.preemption" for e in entries)
    # the final snapshot's writer spans land in the ring too (post-dump),
    # proving the elastic write/commit funnel records
    names = {e["name"] for e in tracing.spans()}
    assert {"mx.elastic.snapshot_write", "mx.elastic.commit"} <= names


def test_unhandled_step_exception_dumps_flight_recorder(tmp_path,
                                                        monkeypatch):
    fr = tmp_path / "crash.ndjson"
    monkeypatch.setenv("MXNET_TPU_FLIGHT_RECORDER", str(fr))
    tr = _trainer()

    class _BadFeed(_Feed):
        def __iter__(self):
            def gen():
                for i, b in enumerate(super(_BadFeed, self).__iter__()):
                    if i == 2:
                        raise RuntimeError("poisoned batch")
                    yield b
            return gen()

        def reset(self):
            raise RuntimeError("poisoned batch")

    with _armed():
        with pytest.raises(RuntimeError):
            with tracing.span("train"):
                it = iter(_BadFeed())
                for x, y in it:
                    tr.step(x, y)
        # the loop body raised outside elastic.run; simulate its hook
        tracing.dump_flight_recorder(reason="step_exception")
    assert fr.exists()
    lines = [json.loads(ln) for ln in fr.read_text().splitlines()]
    assert lines[0]["reason"] == "step_exception"
    assert any(e["name"] == "mx.dp.step" for e in lines[1:])


def test_elastic_run_step_exception_hook(tmp_path, monkeypatch):
    """elastic.run's own unhandled-step-exception hook dumps before the
    error unwinds to the caller."""
    fr = tmp_path / "hook.ndjson"
    monkeypatch.setenv("MXNET_TPU_FLIGHT_RECORDER", str(fr))
    tr = _trainer()
    tr.step(*next(iter(_Feed())))  # warm

    boom = {"n": 0}
    orig_step = tr.step

    def bad_step(x, y):
        boom["n"] += 1
        if boom["n"] >= 2:
            raise RuntimeError("device fell over")
        return orig_step(x, y)

    tr.step = bad_step
    with _armed():
        with pytest.raises(RuntimeError):
            elastic.run(tr, _Feed(), num_steps=10,
                        directory=str(tmp_path / "ck"), save_every=100)
    assert fr.exists()
    assert json.loads(fr.read_text().splitlines()[0])["reason"] \
        == "step_exception"


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def _anomaly_count(kind):
    fam = telemetry._FAMILIES.get("mx_anomalies_total")
    if fam is None or (kind,) not in fam._series:
        return 0.0
    return fam._series[(kind,)].value


def test_watchdog_step_time_regression():
    with _armed():
        for _ in range(15):
            tracing.watch_step_time(0.01, source="t")
        assert _anomaly_count("step_time_regression") == 0.0
        tracing.watch_step_time(0.2, source="t")  # 20x the EWMA
        assert _anomaly_count("step_time_regression") == 1.0
        evs = [e for e in tracing.spans()
               if e["name"] == "mx.anomaly.step_time_regression"]
        assert evs and evs[0]["attrs"]["source"] == "t"


def test_watchdog_warmup_suppresses_early_fires():
    with _armed():
        tracing.watch_step_time(5.0, source="w")   # compile step
        tracing.watch_step_time(0.01, source="w")
        assert _anomaly_count("step_time_regression") == 0.0


def test_watchdog_nonfinite_loss():
    with _armed():
        tracing.check_loss(1.25, source="drain")
        assert _anomaly_count("nonfinite_loss") == 0.0
        tracing.check_loss(float("nan"), source="drain")
        tracing.check_loss(float("inf"), source="drain")
        assert _anomaly_count("nonfinite_loss") == 2.0
        evs = [e for e in tracing.spans()
               if e["name"] == "mx.anomaly.nonfinite_loss"]
        assert len(evs) == 2


def test_pending_scalar_sync_feeds_loss_watchdog():
    """A nonfinite loss surfacing at the PendingScalar sync point books the
    anomaly without any extra device sync (the float() was the caller's)."""
    tr = _trainer()
    x = onp.full((8, 8), onp.nan, onp.float32)
    y = onp.zeros((8,), onp.int32)
    with _armed():
        v = float(tr.step(x, y))
        tr.drain()
        assert not onp.isfinite(v)
        assert _anomaly_count("nonfinite_loss") >= 1.0


# ---------------------------------------------------------------------------
# satellite bridges
# ---------------------------------------------------------------------------

def test_profiler_dumps_includes_tracing_rows():
    from mxnet_tpu import profiler
    with _armed():
        with tracing.span("mx.demo.region"):
            pass
        rows = json.loads(profiler.dumps(format="json"))
        mine = [r for r in rows if r["category"] == "tracing"
                and r["name"] == "mx.demo.region"]
        assert mine and mine[0]["count"] == 1
        assert mine[0]["max_us"] >= mine[0]["min_us"] >= 0.0


def test_telemetry_reset_clears_tracing_ring():
    with _armed():
        tracing.event("x")
        assert tracing.spans()
        telemetry.reset()
        assert tracing.spans() == []


def test_faults_firing_becomes_recorder_event():
    from mxnet_tpu import faults
    with _armed():
        with faults.injected("serving.dispatch", "first_k:1"):
            with pytest.raises(faults.FaultInjected):
                faults.check("serving.dispatch")
        evs = [e for e in tracing.spans() if e["name"] == "mx.fault"]
        assert evs and evs[0]["attrs"]["point"] == "serving.dispatch"


def test_io_retry_attempt_spans_and_retry_events():
    from mxnet_tpu import faults
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    with _armed():
        assert faults.io_retry("elastic.read", flaky, backoff=0.0) == "ok"
        attempts = [e for e in tracing.spans()
                    if e["name"] == "mx.io.elastic.read"]
        assert [a["attrs"]["status"] for a in attempts] \
            == ["error", "error", "ok"]
        assert [a["attrs"]["attempt"] for a in attempts] == [0, 1, 2]
        retries = [e for e in tracing.spans() if e["name"] == "mx.io_retry"]
        assert len(retries) == 2


def test_concurrent_dump_while_recording_is_consistent(tmp_path):
    """ISSUE 17 satellite: dumping the flight recorder while another
    thread is spinning spans into the ring must never crash (deque
    mutation during iteration) and every dump must be self-consistent —
    a meta line whose `entries` count matches the NDJSON body, every
    line parseable."""
    import threading

    with _armed():
        old_cap = tracing._RING.maxlen
        tracing.set_max_spans(2000)  # keep each dump cheap: the race,
        stop = threading.Event()     # not the volume, is under test
        errs = []

        def writer():
            i = 0
            try:
                while not stop.is_set():
                    with tracing.span("w", i=i):
                        pass
                    tracing.event("we", i=i)
                    i += 1
            except Exception as e:  # surfaced below: the race under test
                errs.append(e)

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        try:
            for k in range(20):
                path = str(tmp_path / f"fr{k}.ndjson")
                tracing.dump_flight_recorder(path, reason="race")
                lines = [json.loads(ln) for ln in
                         (tmp_path / f"fr{k}.ndjson").read_text()
                         .splitlines()]
                meta, entries = lines[0], lines[1:]
                assert meta["kind"] == "meta"
                assert meta["entries"] == len(entries)
        finally:
            stop.set()
            t.join(timeout=10)
            tracing.set_max_spans(old_cap)
        assert not errs, errs
        assert not t.is_alive()
