"""Goodput ledger (ISSUE 17 acceptance): step-time waterfall attribution.

Pins the reconciliation invariant (compute + sum(badput) - other == wall,
exactly, with every term >= 0 and other <= 5% of wall) on a 20-step fused
DP run with an injected feed stall and on a pp x dp 1F1B run; the on-disk
NDJSON time-series ring (rotation, torn-tail tolerance); fleet
aggregation with straggler scoring; run-level restart downtime; the
eviction hook; and the Prometheus / statusz surfaces.
"""
import json
import os
import time

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu import telemetry as telem
from mxnet_tpu.engine.async_feed import DeviceFeed
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.models.bert import BertModel
from mxnet_tpu.parallel import (DataParallelTrainer, PipelineTrainer,
                                make_mesh)
from mxnet_tpu.telemetry import goodput


@pytest.fixture(autouse=True)
def _fresh():
    telem.reset()          # also resets the goodput ledger
    telem.disable()
    yield
    telem.reset()
    telem.disable()


def _assert_reconciles(totals, max_other_frac=0.05):
    """The reconciliation rule: compute + sum(badput) - other == wall
    exactly, every term >= 0, and the double-count residual (`other`)
    bounded — it IS the attribution error bar."""
    wall = totals["wall_seconds"]
    cats = totals["categories"]
    assert set(cats) == set(goodput.CATEGORIES)
    for c, v in cats.items():
        assert v >= 0.0, (c, v)
    badput = sum(v for c, v in cats.items() if c not in ("compute", "other"))
    assert abs(cats["compute"] + badput - cats["other"] - wall) < 1e-9
    if wall > 0:
        assert cats["other"] <= max_other_frac * wall, \
            (cats["other"], wall, cats)


# ---------------------------------------------------------------------------
# fused DP run: injected feed stall must land in the feed_stall category
# ---------------------------------------------------------------------------

class _SlowIter:
    """NDArrayIter wrapper whose producer-side next() sleeps: the
    DeviceFeed queue stays empty, so every consumer next() stalls."""

    def __init__(self, inner, delay):
        self.inner, self.delay = inner, delay

    def __iter__(self):
        for b in self.inner:
            time.sleep(self.delay)
            yield b

    def reset(self):
        self.inner.reset()


def test_fused_dp_20step_waterfall_attributes_injected_feed_stall(tmp_path):
    """20 recorded steps with a 50 ms producer sleep per batch: the
    waterfall must reconcile exactly, keep other <= 5% of wall, and
    attribute the injected stall to feed_stall within 20%."""
    delay = 0.05
    n_batches = 21  # first record_step only anchors -> 20 recorded
    telem.enable()
    goodput.enable(root=str(tmp_path), rank=0)

    mx.random.seed(11)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 8)))

    def loss(pred, label):
        return jnp.mean((pred - label) ** 2)

    tr = DataParallelTrainer(
        net, loss, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05},
        mesh=make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1]))

    x = onp.arange(n_batches * 4 * 8, dtype="float32").reshape(-1, 8)
    y = onp.zeros((n_batches * 4, 4), dtype="float32")
    it = NDArrayIter(x, y, batch_size=4, shuffle=False)
    # warm the compile OUTSIDE the armed window so `compile` seconds
    # don't dominate the tiny net's waterfall
    b0 = next(iter(NDArrayIter(x[:4], y[:4], batch_size=4)))
    goodput.disable()
    tr.step(b0.data[0], b0.label[0])
    tr.drain()
    goodput.enable(root=str(tmp_path), rank=0)

    feed = DeviceFeed(_SlowIter(it, delay))
    for b in feed:
        tr.step(b.data[0], b.label[0])
    tr.drain()
    feed.close()

    totals = goodput.totals()
    # the warmup step consumed record_step's clock anchor, so all
    # n_batches armed steps are recorded ...
    assert totals["steps"] == n_batches
    _assert_reconciles(totals)

    # ... but the first armed step only anchors the ledger's stamp
    # snapshot, so n_batches - 1 steps carry the injected stall
    fs = totals["categories"]["feed_stall"]
    expected = delay * (n_batches - 1)
    assert fs >= 0.8 * expected, (fs, expected)
    # the high side includes genuine sleep overrun on a loaded box, but
    # attribution must never invent stall time out of thin air
    assert fs <= 1.6 * expected, (fs, expected)
    # a stall-dominated run is badput-dominated by construction
    assert totals["goodput_ratio"] < 0.5, totals

    # the armed run left an on-disk series that aggregates to the same
    # per-category sums (the offline twin of totals())
    summary = goodput.aggregate(str(tmp_path), book_metrics=False)
    assert 0 in summary["hosts"]
    h = summary["hosts"][0]
    assert h["steps"] == totals["steps"]
    assert abs(h["categories"]["feed_stall"] - fs) < 1e-6
    goodput.disable()


# ---------------------------------------------------------------------------
# pipeline 1F1B run: analytic bubble + exact reconciliation
# ---------------------------------------------------------------------------

def test_pipeline_1f1b_ppxdp_waterfall_reconciles():
    V, B, T = 64, 8, 8
    rs = onp.random.RandomState(0)
    x = nd.array(rs.randint(0, V, (B, T)), dtype="int32")
    y = nd.array(rs.randint(0, V, (B, T)), dtype="int32")

    mx.random.seed(3)
    net = BertModel(vocab_size=V, num_layers=4, units=32, hidden_size=64,
                    num_heads=2, max_length=T, dropout=0.0)
    net.initialize()
    net(x)

    def loss_fn(logits, labels):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    telem.enable()
    goodput.enable()
    tr = PipelineTrainer(
        net, loss_fn, optimizer="sgd",
        optimizer_params={"learning_rate": 0.5, "wd": 0.0},
        mesh=make_mesh({"pp": 2, "dp": 2}, devices=jax.devices("cpu")[:4]),
        num_microbatch=4, schedule="1f1b")
    for _ in range(6):
        tr.step(x, y)
    tr.sync()

    totals = goodput.totals()
    assert totals["steps"] == 5  # first record_step anchors
    _assert_reconciles(totals)
    # the analytic 1F1B bubble fraction must be registered and charged:
    # nv=2 stages, M=4 -> ticks = 4 + 2(2-1) = 6, fraction = 2/6
    assert totals["categories"]["pipeline_bubble"] > 0.0, totals
    frac = totals["categories"]["pipeline_bubble"] / totals["wall_seconds"]
    assert frac <= 2.0 / 6.0 + 1e-9, totals  # never more than the schedule
    goodput.disable()


# ---------------------------------------------------------------------------
# on-disk time-series ring
# ---------------------------------------------------------------------------

def test_ring_rotation_keeps_two_bounded_segments(tmp_path):
    goodput.enable(root=str(tmp_path), rank=0, ring_bytes=2000)
    for i in range(200):
        goodput.note_step("toy", seconds=0.001)
    path = goodput.ring_path()
    assert path is not None and os.path.exists(path)
    assert os.path.exists(path + ".old")
    assert os.path.getsize(path) <= 2000 + 512       # one record of slack
    assert os.path.getsize(path + ".old") <= 2000 + 512
    # every surviving segment re-anchors with a meta header line
    for p in (path, path + ".old"):
        with open(p) as f:
            first = json.loads(f.readline())
        assert first["k"] == "meta" and first["rank"] == 0

    # aggregation merges both segments into the one per-rank bucket
    summary = goodput.aggregate(str(tmp_path), book_metrics=False)
    assert summary["hosts"][0]["steps"] > 0
    assert summary["hosts"][0]["steps"] < 200  # rotation dropped the head
    goodput.disable()


def test_aggregate_tolerates_torn_tail_line(tmp_path):
    goodput.enable(root=str(tmp_path), rank=3)
    for _ in range(5):
        goodput.note_step("toy", seconds=0.002)
    path = goodput.ring_path()
    goodput.disable()
    with open(path, "a") as f:
        f.write('{"k":"step","t":12.3,"wall":0.0')  # killed mid-append
    summary = goodput.aggregate(str(tmp_path), book_metrics=False)
    assert summary["hosts"][3]["steps"] == 5


# ---------------------------------------------------------------------------
# fleet aggregation + straggler detection
# ---------------------------------------------------------------------------

def _simulate_host(root, rank, n, step_seconds, generation=0):
    telem.reset()
    goodput.enable(root=root, rank=rank)
    if generation:
        goodput.set_generation(generation)
    for _ in range(n):
        goodput.note_step("toy", seconds=step_seconds)
    goodput.disable()


def test_aggregate_scores_and_flags_straggler(tmp_path):
    root = str(tmp_path)
    _simulate_host(root, 0, 10, 0.010, generation=1)
    _simulate_host(root, 1, 10, 0.011, generation=1)
    _simulate_host(root, 2, 10, 0.050, generation=2)  # 5x the fleet median

    telem.reset()
    telem.enable()
    summary = goodput.aggregate(root)
    assert sorted(summary["hosts"]) == [0, 1, 2]
    assert summary["straggler"]["flagged"] == [2]
    s = summary["straggler"]["scores"]
    assert s["2"] > 3.0 and 0.5 < s["0"] <= 1.5, s
    assert summary["generation"] == 2  # max over the records' stamps
    assert summary["fleet"]["steps"] == 30
    # book_metrics=True lands the per-rank scores on the gauge
    fam = telem.get_metric("mx_straggler_score")
    assert fam is not None and fam.get("2") > 3.0

    # the scores ride into report()'s fleet table
    text = goodput.report(summary)
    assert "STRAGGLER" in text and "compute" in text


def test_aggregate_empty_root_is_well_formed(tmp_path):
    summary = goodput.aggregate(str(tmp_path), book_metrics=False)
    assert summary["hosts"] == {}
    assert summary["straggler"]["flagged"] == []


# ---------------------------------------------------------------------------
# restart downtime + eviction hook
# ---------------------------------------------------------------------------

def test_restart_downtime_is_run_level(tmp_path):
    telem.enable()
    goodput.enable(root=str(tmp_path), rank=0)
    goodput.record_restart_downtime("resumed", seconds=2.5)
    goodput.note_step("toy", seconds=0.01)
    goodput.note_step("toy", seconds=0.01)
    totals = goodput.totals()
    # run-level: in the totals, never folded into a step's waterfall
    assert totals["categories"]["restart_downtime"] == 2.5
    per_step_wall = totals["wall_seconds"]
    assert per_step_wall < 0.1  # downtime did not inflate step wall
    goodput.disable()
    summary = goodput.aggregate(str(tmp_path), book_metrics=False)
    assert summary["hosts"][0]["restarts"] == 1
    assert summary["hosts"][0]["categories"]["restart_downtime"] == 2.5


def test_on_eviction_aggregates_and_stamps_recorder(tmp_path):
    from mxnet_tpu.telemetry import tracing
    root = str(tmp_path)
    _simulate_host(root, 0, 8, 0.010)
    _simulate_host(root, 1, 8, 0.011)
    _simulate_host(root, 2, 8, 0.060)
    telem.reset()
    telem.enable()
    goodput.enable()  # the eviction hook is a no-op disarmed
    tracing.enable()
    try:
        goodput.on_eviction([2], root=root)
        ev = [s for s in tracing.spans()
              if s.get("name") == "mx.goodput.eviction"]
        assert ev, "eviction must stamp the flight recorder"
    finally:
        tracing.disable()
        tracing.reset()
    fam = telem.get_metric("mx_straggler_score")
    assert fam is not None and fam.get("2") > 1.75


# ---------------------------------------------------------------------------
# surfaces: prometheus, statusz, report, dump_json, disarmed path
# ---------------------------------------------------------------------------

def test_comm_exposed_books_every_byte_per_axis():
    """No step body hides a collective behind compute, so every byte
    booked on mx_comm_bytes_total counts as exposed, split by the "axis"
    label wherever it sits among the labels (traffic without one under
    "none")."""
    telem.enable()
    goodput.enable()
    telem.record_step(8, source="t", seconds=0.01)   # the first snapshot
    telem.record_comm("allreduce", 4 << 20, store="mesh", axis="dp")
    telem.record_comm("tp_act_psum", 2 << 20, store="mesh", axis="tp")
    telem.record_comm("push", 1 << 20, store="local")
    telem.record_step(8, source="t", seconds=0.01)
    bw = telem.peak_bytes_per_second()
    axes = goodput.totals()["comm_exposed_axes"]
    assert axes == pytest.approx({"dp": (4 << 20) / bw, "tp": (2 << 20) / bw,
                                  "none": (1 << 20) / bw})
    assert goodput.totals()["categories"]["comm_exposed"] == \
        pytest.approx(sum(axes.values()))


def test_prometheus_and_statusz_surfaces():
    telem.enable()
    goodput.enable()
    goodput.note_step("toy", seconds=0.02)
    goodput.note_step("toy", seconds=0.02)
    text = telem.scrape()
    assert "mx_goodput_seconds_total" in text
    assert 'category="compute"' in text
    assert "mx_goodput_ratio" in text
    view = telem.statusz()["goodput"]
    assert view["enabled"] is True
    assert view["steps"] == 2
    assert "compute" in view["categories"]
    goodput.disable()


def test_report_and_dump_json(tmp_path):
    goodput.enable()
    goodput.note_step("toy", seconds=0.01)
    goodput.note_step("toy", seconds=0.01)
    text = goodput.report()
    assert "compute" in text and "goodput" in text.lower()
    out = tmp_path / "goodput.json"
    goodput.dump_json(str(out))
    d = json.loads(out.read_text())
    assert d["steps"] == 2
    _assert_reconciles(d)
    goodput.disable()


def test_disarmed_is_a_noop():
    telem.enable()
    assert not goodput.is_enabled()
    telem.record_step(8, source="toy", seconds=0.01)
    telem.record_step(8, source="toy", seconds=0.01)
    assert goodput.totals()["steps"] == 0
    assert telem.get_metric("mx_goodput_seconds_total") is None


# ---------------------------------------------------------------------------
# offline CLI
# ---------------------------------------------------------------------------

def test_goodput_report_cli(tmp_path):
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cli = os.path.join(repo, "tools", "goodput_report.py")
    root = str(tmp_path)

    # no series yet -> exit 2
    p = subprocess.run([sys.executable, cli, root],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 2, p.stderr

    _simulate_host(root, 0, 10, 0.010)
    _simulate_host(root, 1, 10, 0.050)
    _simulate_host(root, 2, 10, 0.010)
    p = subprocess.run([sys.executable, cli, root, "--per-host"],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    assert "compute" in p.stdout and "host 1" in p.stdout

    p = subprocess.run([sys.executable, cli, root, "--json"],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    d = json.loads(p.stdout)
    assert d["straggler"]["flagged"] == [1]

    p = subprocess.run([sys.executable, cli, root, "--fail-on-straggler"],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 3, (p.stdout, p.stderr)
