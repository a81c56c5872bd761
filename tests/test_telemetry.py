"""Telemetry layer: registry semantics, label cardinality, Prometheus
scrape format, disabled-path no-op, end-to-end Trainer metrics (step time /
examples-sec / MFU / comm bytes / compilation counters), the Monitor
hybridized-block regression, and the tools/check_instrumentation.py lint.
"""
import json
import re
import subprocess
import sys
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, autograd
from mxnet_tpu import telemetry as telem
from mxnet_tpu.base import MXNetError

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_registry():
    telem.reset()
    telem.disable()
    yield
    telem.stop_http_server()
    telem.reset()
    telem.disable()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    c = telem.counter("mx_t_total", "doc", ("op",))
    c.labels("x").inc()
    c.labels(op="x").inc(2)
    assert c.get("x") == 3
    assert telem.counter("mx_t_total") is c  # get-or-create
    with pytest.raises(MXNetError):
        c.labels("x").inc(-1)  # counters only go up
    with pytest.raises(MXNetError):
        telem.gauge("mx_t_total")  # type conflict

    g = telem.gauge("mx_g", "doc")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert g.get() == 3.0
    g.set_max(1.0)
    assert g.get() == 3.0  # watermark keeps the max

    h = telem.histogram("mx_h", "doc", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    s = h._default()
    assert s.count == 3 and s.counts == [1, 1, 1]
    assert abs(s.sum - 5.55) < 1e-9


def test_label_validation():
    c = telem.counter("mx_l_total", "doc", ("a", "b"))
    with pytest.raises(MXNetError):
        c.labels("only-one")
    with pytest.raises(MXNetError):
        c.labels(a="x")  # missing b
    c.labels(b="2", a="1").inc()
    assert c.get("1", "2") == 1


def test_label_cardinality_cap():
    c = telem.counter("mx_card_total", "doc", ("k",), max_series=2)
    for i in range(5):
        c.labels(str(i)).inc()  # past the cap: dropped, not stored
    assert len(c._series) == 2
    assert c.dropped == 3
    text = telem.scrape()
    assert "mx_telemetry_dropped_series_total" in text


# ---------------------------------------------------------------------------
# export formats
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+="[^"]*"'
    r'(,[a-zA-Z0-9_+]+="[^"]*")*\})? [-+]?[0-9.eE+-]+(inf|nan)?$')


def test_scrape_is_parseable_prometheus_text():
    telem.counter("mx_a_total", "a counter", ("op",)).labels("x").inc(2)
    telem.gauge("mx_b", "a gauge").set(1.5)
    telem.histogram("mx_c", "a histogram", buckets=(0.1, 1.0)).observe(0.5)
    text = telem.scrape()
    assert text.endswith("\n")
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE "))
        else:
            assert _PROM_LINE.match(line), line
    # histogram invariants: cumulative buckets, +Inf == count
    assert 'mx_c_bucket{le="+Inf"} 1' in text
    assert "mx_c_sum 0.5" in text
    assert "mx_c_count 1" in text


def test_scrape_json_and_collect():
    telem.counter("mx_j_total", "doc").inc(4)
    d = json.loads(telem.scrape_json())
    assert d["mx_j_total"]["type"] == "counter"
    assert d["mx_j_total"]["series"][0]["value"] == 4


def test_report_unifies_profiler_and_compilation():
    telem.gauge("mx_r", "doc").set(1)
    rep = telem.report()
    assert "=== telemetry ===" in rep
    assert "=== compilation (engine.cache_stats) ===" in rep
    assert "=== profiler aggregate stats ===" in rep
    assert "mx_r" in rep


def test_http_metrics_endpoint():
    telem.counter("mx_http_total", "doc").inc()
    port = telem.start_http_server(0)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    assert "mx_http_total 1" in body
    js = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics.json", timeout=5).read()
    assert json.loads(js)["mx_http_total"]["series"][0]["value"] == 1


# ---------------------------------------------------------------------------
# disabled path is a no-op; comm scopes are re-entrant
# ---------------------------------------------------------------------------

def test_disabled_instrumentation_records_nothing():
    assert not telem.is_enabled()
    kv = mx.kv.create("local")
    kv.init(0, nd.ones((4, 4)))
    kv.push(0, nd.ones((4, 4)))
    out = nd.zeros((4, 4))
    kv.pull(0, out=out)
    assert telem.get_metric("mx_comm_bytes_total") is None
    assert telem.get_metric("mx_train_steps_total") is None


def test_comm_bytes_and_reentrancy():
    telem.enable()
    kv = mx.kv.create("local")
    kv.init(0, nd.ones((4, 4)))
    kv.push(0, nd.ones((4, 4)))  # 64 bytes of f32
    fam = telem.get_metric("mx_comm_bytes_total")
    assert fam.get("push", "local") == 64
    # nested scopes count once (pushpull must not double-bill its push/pull)
    with telem.comm_scope("outer", 100):
        with telem.comm_scope("inner", 50):
            pass
    assert fam.get("outer", "") == 100
    assert fam.get("inner", "") == 0
    calls = telem.get_metric("mx_comm_calls_total")
    assert calls.get("push", "local") == 1


def test_record_step_explicit_values():
    telem.enable()
    telem.record_step(32, source="unit", seconds=0.5, flops_per_step=1e9,
                      lr=0.1)
    assert telem.get_metric("mx_train_examples_per_second").get("unit") == 64
    mfu = telem.get_metric("mx_mfu").get("unit")
    assert mfu == pytest.approx(2e9 / telem.peak_flops())
    assert telem.get_metric("mx_learning_rate").get("unit") == \
        pytest.approx(0.1)


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_PEAK_FLOPS", "123.0")
    assert telem.peak_flops() == 123.0


def test_unknown_accelerator_kind_is_an_error(monkeypatch):
    """The 1 TF/s / 50 GB/s anchors are for CPU platforms only: a chip the
    tables do not know raises instead of quietly reporting MFU against
    them; a known one (v5e reports 'TPU v5 lite') reads the table."""
    import jax
    from mxnet_tpu.base import MXNetError

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    monkeypatch.setattr(telem, "_peak_cache", [None])
    monkeypatch.setattr(telem, "_peak_bw_cache", [None])
    assert telem.peak_flops() == 197e12
    assert telem.peak_bytes_per_second() == 819e9
    Dev.device_kind = "TPU v99"
    monkeypatch.setattr(telem, "_peak_cache", [None])
    monkeypatch.setattr(telem, "_peak_bw_cache", [None])
    with pytest.raises(MXNetError, match="TPU v99"):
        telem.peak_flops()
    with pytest.raises(MXNetError, match="TPU v99"):
        telem.peak_bytes_per_second()


# ---------------------------------------------------------------------------
# acceptance: short Trainer run -> full scrape
# ---------------------------------------------------------------------------

def test_trainer_run_scrape_has_all_signals():
    telem.enable()
    net = gluon.nn.Dense(8)
    net.initialize()
    x = nd.array(np.random.uniform(-1, 1, (16, 4)).astype(np.float32))
    y = nd.zeros((16,))
    net(x)
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    for _ in range(4):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(16)
    text = telem.scrape()
    for needle in ("mx_train_step_seconds", "mx_train_examples_per_second",
                   "mx_mfu", "mx_comm_bytes_total", "mx_compilation_hits",
                   "mx_compilation_compiles", "mx_train_steps_total",
                   "mx_learning_rate", "mx_device_live_bytes"):
        assert needle in text, needle
    steps = telem.get_metric("mx_train_steps_total").get("trainer")
    assert steps >= 3  # first step() anchors the interval clock
    assert telem.get_metric("mx_mfu").get("trainer") > 0
    assert telem.get_metric("mx_comm_bytes_total").get("push", "device") > 0
    # every sample line still parses as Prometheus text
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert _PROM_LINE.match(line), line


def test_telemetry_callback_exports(tmp_path):
    from mxnet_tpu.callback import TelemetryCallback
    from mxnet_tpu.module.base_module import BatchEndParam
    from mxnet_tpu import metric as metric_mod

    path = tmp_path / "metrics.prom"
    cb = TelemetryCallback(frequent=2, scrape_path=str(path))
    assert telem.is_enabled()  # the callback opts the process in
    m = metric_mod.create("acc")
    m.update([nd.array([1, 0])], [nd.array([[0.1, 0.9], [0.2, 0.8]])])
    cb(BatchEndParam(epoch=0, nbatch=1, eval_metric=m))
    cb(BatchEndParam(epoch=0, nbatch=2, eval_metric=m))  # 2nd batch: export
    assert path.exists()
    assert "mx_train_metric" in path.read_text()
    cb.epoch_end(0)
    assert telem.get_metric("mx_epoch").get("module") == 1


# ---------------------------------------------------------------------------
# engine cost capture
# ---------------------------------------------------------------------------

def test_estimate_cost_reports_flops():
    import jax
    from mxnet_tpu import engine
    f = jax.jit(lambda a, b: a @ b)
    x = np.ones((32, 32), np.float32)
    cost = engine.estimate_cost(f, x, x)
    assert cost.get("flops", 0) > 0


# ---------------------------------------------------------------------------
# Monitor on hybridized blocks (satellite regression)
# ---------------------------------------------------------------------------

def test_monitor_hybridized_block_warns_and_survives():
    from mxnet_tpu.monitor import Monitor
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    x = nd.ones((2, 3))
    net(x)
    net.hybridize()
    mon = Monitor(interval=1)
    with pytest.warns(UserWarning, match="hybridized"):
        mon.install_block(net)
        mon.tic()
    net(x)  # fused path: taps see nothing, but nothing leaks/crashes
    res = mon.toc()
    assert res == []


def test_monitor_unhybridized_block_still_taps():
    from mxnet_tpu.monitor import Monitor
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    x = nd.ones((2, 3))
    net(x)
    mon = Monitor(interval=1)
    mon.install_block(net)
    mon.tic()
    net(x)
    res = mon.toc()
    assert res, "eager taps must record per-child stats"


# ---------------------------------------------------------------------------
# static lint: no entry point escapes observability
# ---------------------------------------------------------------------------

def test_check_instrumentation_lint_is_clean():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_instrumentation.py")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_check_instrumentation_catches_regression(tmp_path):
    """Strip a decorator from a copied tree: the lint must fail on it."""
    import shutil
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ci", REPO / "tools" / "check_instrumentation.py")
    ci = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ci)

    pkg = tmp_path / "mxnet_tpu"
    for rel in {c[0] for c in ci.METHOD_CHECKS} | \
               {c[0] for c in ci.TEXT_CHECKS}:
        dst = pkg / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / "mxnet_tpu" / rel, dst)
    assert ci.check(pkg) == []
    kv = pkg / "kvstore" / "kvstore.py"
    kv.write_text(kv.read_text().replace(
        '@_telem.instrument_comm("push")', "", 1))
    violations = ci.check(pkg)
    assert any("push" in v for v in violations)


# ---------------------------------------------------------------------------
# hostile exposition inputs (ISSUE 17 satellite): escaping must keep the
# scrape parseable no matter what lands in a label value or a HELP doc
# ---------------------------------------------------------------------------

def test_scrape_escapes_hostile_label_values():
    telem.counter("mx_hostile_total", "doc", ("k",)) \
        .labels('a"b\\c\nd').inc()
    text = telem.scrape()
    lines = [ln for ln in text.splitlines()
             if ln.startswith("mx_hostile_total{")]
    # one physical line: the raw newline in the value must not split it
    assert len(lines) == 1, lines
    ln = lines[0]
    assert '\\"' in ln and "\\\\" in ln and "\\n" in ln
    assert ln.endswith(" 1.0")


def test_scrape_escapes_hostile_help_docs():
    """A metric doc with newlines/backslashes must render as ONE escaped
    HELP line — a raw newline would truncate the HELP comment and leave
    the doc's tail as garbage samples, corrupting the whole scrape."""
    telem.counter("mx_hostile_help_total",
                  'line1\nline2 has "quotes" and a \\backslash').inc()
    telem.histogram("mx_hostile_help_h", "histo doc\nwith newline",
                    buckets=(1.0,)).observe(0.5)
    text = telem.scrape()
    for name in ("mx_hostile_help_total", "mx_hostile_help_h"):
        helps = [ln for ln in text.splitlines()
                 if ln.startswith(f"# HELP {name} ")]
        assert len(helps) == 1, (name, helps)
        assert "\\n" in helps[0]
    assert "\\\\backslash" in text
    # every comment line in the scrape is still a well-formed comment
    for ln in text.strip().splitlines():
        if ln.startswith("#"):
            assert ln.startswith(("# HELP ", "# TYPE ")), ln


# ---------------------------------------------------------------------------
# multi-host `host` label (ISSUE 17 satellite): single-process exposition
# stays byte-identical; multi-process rides a TRAILING label
# ---------------------------------------------------------------------------

def test_single_process_exposition_has_no_host_label_pinned():
    """jax.process_count() == 1 in the unit suite: the label sets — and
    therefore the exposition bytes — must match the single-host build
    exactly. These pinned series strings ARE the compatibility contract
    for existing scrape configs."""
    assert telem._host_label() == ""
    telem.record_step(8, source="t", seconds=0.01)
    telem.record_step(8, source="t", seconds=0.01)
    telem.record_comm("allreduce", 1024, store="mesh")
    telem.record_checkpoint_save(0.5, 100)
    text = telem.scrape()
    assert "host=" not in text
    assert ('mx_comm_bytes_total{op="allreduce",store="mesh",'
            'axis=""} 1024') in text
    assert 'mx_step_seconds_count{source="t"} 2' in text
    assert 'mx_checkpoint_save_seconds{source="elastic"} 0.5' in text


def test_multi_process_host_label_is_trailing_and_aggregates():
    """Simulated rank 3 (the resolver caches its answer in _HOST_LABEL):
    host rides as the TRAILING label so MetricFamily.get()'s
    prefix-aggregation keeps every existing reader working unchanged."""
    telem._HOST_LABEL[0] = "3"
    telem.record_step(8, source="t", seconds=0.01)
    telem.record_step(8, source="t", seconds=0.01)
    telem.record_comm("allreduce", 2048, store="mesh", axis="dp")
    telem.record_checkpoint_save(0.5, 100)
    text = telem.scrape()
    assert ('mx_comm_bytes_total{op="allreduce",store="mesh",'
            'axis="dp",host="3"} 2048') in text
    assert 'mx_step_seconds_count{source="t",host="3"} 2' in text
    assert 'mx_checkpoint_save_seconds{source="elastic",host="3"} 0.5' \
        in text
    # prefix aggregation: two-label readers see the same totals
    assert telem.get_metric("mx_comm_bytes_total") \
        .get("allreduce", "mesh") == 2048
    # the per-axis readers find "axis" by name, whatever trails it
    assert telem.comm_axis_bytes("dp") == 2048
    assert telem.comm_bytes_by_axis() == {"dp": 2048}


def test_record_dispatch_wait_is_set_style():
    telem.record_dispatch_wait(1.5, source="step")
    telem.record_dispatch_wait(2.25, source="step")  # cumulative, not +=
    fam = telem.get_metric("mx_dispatch_wait_seconds_total")
    assert fam.get("step") == 2.25
