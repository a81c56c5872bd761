"""Backward-overlapped gradient collectives (ISSUE 10) on the 8-virtual-
device CPU mesh: chunked-vjp segment planning, segment-aligned fusion
buckets (plan_buckets ``boundaries=``), 10-step trajectory parity of the
overlapped step against the baseline across sgd/adam × zero on/off ×
compressed wire × frozen params, the K>=2 interleaved-collectives HLO
structure the acceptance demands, the async-collective XLA flag helper,
overlap telemetry (labels + mx_comm_overlap_ratio), compile-cache keying,
and the gluon Trainer per-bucket allreduce split."""
import os
import warnings

import numpy as onp
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon
from mxnet_tpu.base import MXNetError, env
from mxnet_tpu import engine as _engine
from mxnet_tpu import telemetry as telem
from mxnet_tpu.engine import xla_flags as xf
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.parallel import DataParallelTrainer, make_mesh, P
from mxnet_tpu.parallel import overlap as ov
from mxnet_tpu.parallel import zero as zero_mod


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telem.reset()
    telem.disable()
    yield
    telem.reset()
    telem.disable()


def _loss_fn(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def _mlp(width=32, depth=3):
    net = gluon.nn.HybridSequential()
    for _ in range(depth):
        net.add(gluon.nn.Dense(width, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 16)))
    return net


def _batch(seed=0, n=16):
    rs = onp.random.RandomState(seed)
    x = nd.array(rs.uniform(-1, 1, (n, 16)).astype(onp.float32))
    y = nd.array(rs.randint(0, 4, (n,)), dtype="int32")
    return x, y


def _trainer(mesh, optimizer="adam", lr=0.01, freeze=(), **kw):
    mx.random.seed(7)
    net = _mlp()
    for i, p in enumerate(net.collect_params().values()):
        if i in freeze:
            p.grad_req = "null"
    tr = DataParallelTrainer(net, _loss_fn, optimizer=optimizer,
                             optimizer_params={"learning_rate": lr},
                             mesh=mesh, **kw)
    return net, tr


class _Zoo(HybridBlock):
    """Model-zoo features+output shape (chain_blocks' third recipe)."""

    def __init__(self):
        super().__init__()
        self.features = gluon.nn.HybridSequential()
        self.features.add(gluon.nn.Dense(16, activation="relu"),
                          gluon.nn.Dense(16, activation="relu"))
        self.output = gluon.nn.Dense(4)

    def hybrid_forward(self, F, x):
        return self.output(self.features(x))


class _NoChain(HybridBlock):
    """A residual-style block chain_blocks cannot linearize."""

    def __init__(self):
        super().__init__()
        self.a = gluon.nn.Dense(16)
        self.b = gluon.nn.Dense(16)

    def hybrid_forward(self, F, x):
        return self.a(x) + self.b(x)


# ---------------------------------------------------------------------------
# trajectory parity: overlapped step == baseline step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
@pytest.mark.parametrize("zero", [False, True])
def test_overlap_matches_baseline_trajectory(host_mesh8, optimizer, zero):
    """Acceptance: 10 steps, loss AND synced parameters of the overlapped
    step match the unoverlapped baseline with the same zero setting — the
    chunked backward + per-segment collectives reorder the schedule, not
    the math."""
    x, y = _batch()
    results = {}
    for overlap in (False, True):
        net, tr = _trainer(host_mesh8, optimizer=optimizer,
                           zero_update=zero, overlap_grads=overlap,
                           bucket_bytes=1024)
        if overlap:
            assert tr._overlap and len(tr._overlap_plan) >= 2
        losses = [float(tr.step(x, y)) for _ in range(10)]
        tr.sync()
        params = [p.data().asnumpy()
                  for p in net.collect_params().values()]
        results[overlap] = (losses, params)
    onp.testing.assert_allclose(results[False][0], results[True][0],
                                rtol=1e-4, atol=1e-5)
    assert results[True][0][-1] < results[True][0][0]
    for i, (ref, got) in enumerate(zip(results[False][1],
                                       results[True][1])):
        onp.testing.assert_allclose(ref, got, rtol=1e-4, atol=1e-5,
                                    err_msg=f"param {i}")


@pytest.mark.parametrize("zero", [False, True])
def test_overlap_bf16_wire_tracks_baseline(host_mesh8, zero):
    """The compressed wire composes with overlap: per-bucket collectives
    ride the bf16 reduce phase (fp32 accumulation), so the trajectory
    stays within the same tolerance the zero bf16 path holds."""
    x, y = _batch()
    _, tr_ref = _trainer(host_mesh8, zero_update=zero)
    ref = [float(tr_ref.step(x, y)) for _ in range(8)]
    _, tr_c = _trainer(host_mesh8, zero_update=zero, overlap_grads=True,
                       comm_dtype="bfloat16", bucket_bytes=1024)
    got = [float(tr_c.step(x, y)) for _ in range(8)]
    onp.testing.assert_allclose(ref, got, rtol=0.02, atol=0.02)
    assert got[-1] < got[0]


@pytest.mark.parametrize("zero", [False, True])
def test_overlap_frozen_params(host_mesh8, zero):
    """grad_req='null' slots stay out of the fusion buckets; their values
    are bit-stable across overlapped steps and the live params still track
    the baseline with the same freeze mask."""
    x, y = _batch()
    freeze = (1,)  # second declared parameter (first Dense bias)
    results = {}
    for overlap in (False, True):
        net, tr = _trainer(host_mesh8, optimizer="sgd", lr=0.1,
                           freeze=freeze, zero_update=zero,
                           overlap_grads=overlap, bucket_bytes=1024)
        plist = list(net.collect_params().values())
        frozen_before = [plist[i].data().asnumpy() for i in freeze]
        losses = [float(tr.step(x, y)) for _ in range(6)]
        tr.sync()
        for i, before in zip(freeze, frozen_before):
            onp.testing.assert_array_equal(before,
                                           plist[i].data().asnumpy())
        results[overlap] = (losses,
                            [p.data().asnumpy() for p in plist])
    onp.testing.assert_allclose(results[False][0], results[True][0],
                                rtol=1e-4, atol=1e-5)
    for ref, got in zip(results[False][1], results[True][1]):
        onp.testing.assert_allclose(ref, got, rtol=1e-4, atol=1e-5)


def test_overlap_run_steps_and_dispatch_window(host_mesh8):
    """The scanned multi-step path reuses the overlapped body and agrees
    with the single-step baseline; the DispatchWindow drain contract is
    unchanged."""
    x, y = _batch()
    _, tr_ref = _trainer(host_mesh8, optimizer="sgd", lr=0.1)
    ref = [float(tr_ref.step(x, y)) for _ in range(6)]
    _, tr = _trainer(host_mesh8, optimizer="sgd", lr=0.1,
                     overlap_grads=True, bucket_bytes=1024)
    got = onp.asarray(tr.run_steps(x, y, 6))
    onp.testing.assert_allclose(ref, got, rtol=1e-4, atol=1e-5)
    tr.drain()


# ---------------------------------------------------------------------------
# acceptance: the optimized HLO interleaves per-bucket collectives with
# backward dots instead of one tail-fused collective block
# ---------------------------------------------------------------------------

def _optimized_hlo(tr, x, y):
    from jax.sharding import NamedSharding
    xr = jax.device_put(x._data, NamedSharding(tr.mesh, P("dp")))
    yr = jax.device_put(y._data, NamedSharding(tr.mesh, P("dp")))
    rep = NamedSharding(tr.mesh, P())
    from mxnet_tpu import random as _rng
    key = jax.device_put(onp.asarray(_rng.next_key_raw()), rep)
    lr = jax.device_put(onp.float32(0.01), rep)
    t = jax.device_put(onp.float32(1.0), rep)
    sc = jax.device_put(onp.float32(1.0), rep)
    fn = tr._get_step((xr.shape, str(xr.dtype), yr.shape, str(yr.dtype)))
    return fn.lower(tr._params_raw, tr._opt_state, key, xr, yr,
                    lr, t, sc).compile().as_text()


@pytest.mark.parametrize("zero,needle", [(False, "all-reduce"),
                                         (True, "reduce-scatter")])
def test_overlap_hlo_interleaves_collectives(host_mesh8, zero, needle):
    """Acceptance: the overlapped step's optimized HLO holds K>=2 separate
    per-bucket gradient collectives with backward dots scheduled BETWEEN
    them — proof the collectives issue mid-backward, where the async-
    collective scheduler can hide them, rather than in one tail block."""
    x, y = _batch()
    _, tr = _trainer(host_mesh8, optimizer="sgd", zero_update=zero,
                     overlap_grads=True, bucket_bytes=1024)
    buckets = tr._zero_plan if zero else tr._overlap_buckets
    assert len(buckets) >= 2
    lines = _optimized_hlo(tr, x, y).splitlines()
    colls = [i for i, l in enumerate(lines)
             if needle + "(" in l or needle + "-start(" in l]
    dots = [i for i, l in enumerate(lines) if "dot(" in l]
    assert len(colls) >= 2, "expected >=2 per-bucket collectives"
    between = [d for d in dots if colls[0] < d < colls[-1]]
    assert between, ("no backward dot scheduled between the first and "
                     "last gradient collective — tail-fused block")


# ---------------------------------------------------------------------------
# segment planner
# ---------------------------------------------------------------------------

def test_chain_blocks_recipes():
    seq = _mlp(depth=2)
    chain = ov.chain_blocks(seq)
    assert [n for n, _ in chain] == ["[0]", "[1]", "[2]"]
    zoo = _Zoo()
    zoo.initialize()
    zoo(nd.zeros((1, 8)))
    names = [n for n, _ in ov.chain_blocks(zoo)]
    assert names == ["features[0]", "features[1]", "output"]
    assert ov.chain_blocks(_NoChain()) is None


def test_plan_segments_partitions_and_owns():
    net = _mlp(depth=3)
    plist = list(net.collect_params().values())
    plan = ov.plan_segments(net, plist, 2)
    assert len(plan) == 2
    owned = [i for s in plan.segments for i in s.owned]
    assert sorted(owned) == list(range(len(plist)))
    # boundaries = each later segment's first owned slot, increasing
    assert list(plan.boundaries) == [min(s.owned)
                                     for s in plan.segments[1:]]
    assert all(b > 0 for b in plan.boundaries)
    # clamped to chain length; floor of 2 (cut thresholds may merge light
    # leading blocks, so the count lands in [2, chain length])
    assert 2 <= len(ov.plan_segments(net, plist, 100)) <= 4
    assert len(ov.plan_segments(net, plist, 0)) == 2
    # fingerprints separate different segmentations
    assert ov.plan_segments(net, plist, 2).fingerprint != \
        ov.plan_segments(net, plist, 4).fingerprint


def test_plan_segments_rejects_unchainable():
    net = _NoChain()
    net.initialize()
    net(nd.zeros((1, 8)))
    with pytest.raises(MXNetError, match="linear block chain"):
        ov.plan_segments(net, list(net.collect_params().values()), 2)


def test_overlap_explicit_raises_env_falls_back(host_mesh8, monkeypatch):
    """overlap_grads=True on an unsegmentable net is a hard error; the
    MXNET_TPU_OVERLAP_GRADS=1 fleet default degrades to the plain fused
    step with a warning instead of breaking unrelated nets."""
    def make(**kw):
        mx.random.seed(7)
        net = _NoChain()
        net.initialize()
        net(nd.zeros((1, 8)))
        return DataParallelTrainer(
            net, lambda p, t: jnp.mean((p - t.astype(jnp.float32)
                                        [:, None]) ** 2),
            optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            mesh=host_mesh8, **kw)

    with pytest.raises(MXNetError, match="linear block chain"):
        make(overlap_grads=True)
    monkeypatch.setenv("MXNET_TPU_OVERLAP_GRADS", "1")
    with pytest.warns(UserWarning, match="falling back"):
        tr = make()
    assert not tr._overlap
    # and the env default does arm overlap on a chainable net
    _, tr2 = _trainer(host_mesh8)
    assert tr2._overlap


def test_overlap_rejects_compression(host_mesh8):
    with pytest.raises(MXNetError, match="compression"):
        _trainer(host_mesh8, overlap_grads=True,
                 compression={"type": "2bit"})


# ---------------------------------------------------------------------------
# bucket planner boundaries
# ---------------------------------------------------------------------------

def test_plan_buckets_boundaries_cut():
    entries = [(0, (4,), jnp.float32), (1, (4,), jnp.float32),
               (2, (4,), jnp.float32), (3, (4,), jnp.float32)]
    plan = zero_mod.plan_buckets(entries, ndp=2, bucket_bytes=1 << 20,
                                 boundaries=(2,))
    assert [b.indices for b in plan] == [(0, 1), (2, 3)]
    # boundary + cap interact: the cap still splits within a side
    plan = zero_mod.plan_buckets(entries, ndp=2, bucket_bytes=4 * 4,
                                 boundaries=(3,))
    assert [b.indices for b in plan] == [(0,), (1,), (2,), (3,)]
    # a boundary between every entry degenerates to one bucket each
    plan = zero_mod.plan_buckets(entries, ndp=2, bucket_bytes=1 << 20,
                                 boundaries=(1, 2, 3))
    assert [b.indices for b in plan] == [(0,), (1,), (2,), (3,)]


def test_plan_buckets_boundaries_respect_dtype_groups():
    entries = [(0, (4,), jnp.float32), (1, (4,), jnp.bfloat16),
               (2, (4,), jnp.float32), (3, (4,), jnp.bfloat16)]
    plan = zero_mod.plan_buckets(entries, ndp=2, bucket_bytes=1 << 20,
                                 boundaries=(2,))
    assert [b.indices for b in plan] == [(0,), (2,), (1,), (3,)]


def test_plan_buckets_no_boundaries_byte_identical():
    """Regression the kvstore bucketed pushpull relies on: omitting the
    hint, None, and () all produce the exact same plan as before the
    parameter existed (BucketSpec is a frozen dataclass — == is deep)."""
    entries = [(0, (4, 3), jnp.float32), (1, (5,), jnp.float32),
               (2, (2, 2), jnp.bfloat16), (3, (100,), jnp.float32)]
    base = zero_mod.plan_buckets(entries, ndp=8, bucket_bytes=64 * 4)
    assert zero_mod.plan_buckets(entries, 8, 64 * 4,
                                 boundaries=None) == base
    assert zero_mod.plan_buckets(entries, 8, 64 * 4,
                                 boundaries=()) == base


def test_zero_buckets_align_to_segments(host_mesh8):
    """Under overlap + zero, every planned bucket's slots belong to exactly
    one vjp segment (the invariant the step body asserts at build time)."""
    _, tr = _trainer(host_mesh8, zero_update=True, overlap_grads=True,
                     bucket_bytes=1024)
    seg_of = tr._overlap_plan.segment_of_slot
    for b in tr._zero_plan:
        assert len({seg_of[i] for i in b.indices}) == 1


# ---------------------------------------------------------------------------
# XLA flag helper
# ---------------------------------------------------------------------------

def test_xla_flags_never_touch_XLA_FLAGS(monkeypatch):
    """jaxlib's XLA_FLAGS parser aborts the process on the --xla_tpu_*
    spellings (seen on the v5e: 'Unknown flag in XLA_FLAGS'); libtpu reads
    them from LIBTPU_INIT_ARGS, which nothing parses on a CPU process."""
    monkeypatch.setattr(xf, "backend_initialized", lambda: False)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
    assert xf.ensure_overlap_flags() is True
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=8"
    assert not any("gpu" in f for f in xf.OVERLAP_XLA_FLAGS)


def test_xla_flags_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_OVERLAP_XLA_FLAGS", "off")
    assert xf.overlap_flags() == ()
    assert xf.ensure_overlap_flags() is False  # disabled, no warning
    monkeypatch.setenv("MXNET_TPU_OVERLAP_XLA_FLAGS",
                       "--xla_foo=1 --xla_bar=2")
    assert xf.overlap_flags() == ("--xla_foo=1", "--xla_bar=2")


def test_xla_flags_append_before_init(monkeypatch):
    monkeypatch.setattr(xf, "backend_initialized", lambda: False)
    monkeypatch.setenv("LIBTPU_INIT_ARGS",
                       "--xla_tpu_overlap_compute_collective_tc=false")
    assert xf.ensure_overlap_flags() is True
    got = os.environ["LIBTPU_INIT_ARGS"].split()
    # operator's value survives; missing flags appended once
    assert "--xla_tpu_overlap_compute_collective_tc=false" in got
    assert "--xla_tpu_overlap_compute_collective_tc=true" not in got
    for f in xf.OVERLAP_XLA_FLAGS:
        assert f in got or f.startswith(
            "--xla_tpu_overlap_compute_collective_tc")
    before = os.environ["LIBTPU_INIT_ARGS"]
    assert xf.ensure_overlap_flags() is True  # idempotent
    assert os.environ["LIBTPU_INIT_ARGS"] == before


def test_xla_flags_warns_once_when_late(monkeypatch):
    monkeypatch.setattr(xf, "backend_initialized", lambda: True)
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
    monkeypatch.setattr(xf, "_WARNED", [False])
    with pytest.warns(UserWarning, match="already initialized"):
        assert xf.ensure_overlap_flags() is False
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert xf.ensure_overlap_flags() is False  # latched: no rewarn


# ---------------------------------------------------------------------------
# telemetry: overlap label + mx_comm_overlap_ratio
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("zero", [False, True])
def test_overlap_telemetry_ratio(host_mesh8, zero):
    """Overlapped steps book their collective bytes under overlap='1';
    the derived ratio is 1.0 for the pure all-reduce schedule and strictly
    between 0 and 1 under zero (the gather-back stays in the tail)."""
    x, y = _batch()
    telem.enable()
    _, tr = _trainer(host_mesh8, zero_update=zero, overlap_grads=True,
                     bucket_bytes=1024)
    tr.step(x, y)
    ratio = telem.comm_overlap_ratio()
    if zero:
        assert 0.0 < ratio < 1.0
    else:
        assert ratio == pytest.approx(1.0)
    # the gauge materializes at scrape time via _sync_engine_stats
    text = telem.scrape()
    assert "mx_comm_overlap_ratio" in text
    g = telem.get_metric("mx_comm_overlap_ratio")
    assert g.get() == pytest.approx(ratio)
    # prefix-sum get: readers using the old (op, store) arity still see
    # the family's totals after the overlap label grew
    fam = telem.get_metric("mx_comm_bytes_total")
    tot = sum(getattr(s, "value", 0.0) for s in fam._series.values())
    assert fam.get("allreduce" if not zero else "reduce_scatter",
                   "mesh") > 0
    assert sum(fam.get(op, "mesh") for op in
               ("allreduce", "reduce_scatter", "all_gather")) \
        == pytest.approx(tot)


def test_baseline_telemetry_unoverlapped(host_mesh8):
    """The plain fused step's collectives book overlap='0' and the ratio
    stays 0 — the gauge separates schedules, not configs."""
    x, y = _batch()
    telem.enable()
    _, tr = _trainer(host_mesh8)
    tr.step(x, y)
    assert telem.comm_overlap_ratio() == 0.0


# ---------------------------------------------------------------------------
# compile-cache keying
# ---------------------------------------------------------------------------

def test_compile_cache_distinct_per_overlap_config(host_mesh8):
    """Each (overlap, segments, zero) combination keys its own compiled
    artifact; identical configurations share one."""
    configs = [dict(), dict(overlap_grads=True),
               dict(overlap_grads=True, overlap_segments=2),
               dict(overlap_grads=True, zero_update=True)]
    keys = set()
    for kw in configs:
        _, tr = _trainer(host_mesh8, bucket_bytes=1024, **dict(kw))
        keys.add(tr._step_key_base)
        _, tr2 = _trainer(host_mesh8, bucket_bytes=1024, **dict(kw))
        assert tr2._step_key_base == tr._step_key_base
    assert len(keys) == len(configs)


# ---------------------------------------------------------------------------
# gluon Trainer: per-bucket allreduce split
# ---------------------------------------------------------------------------

def _gluon_run(kvstore, bucket_env, monkeypatch, record=None):
    monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", str(bucket_env))
    rs = onp.random.RandomState(0)
    x = nd.array(rs.uniform(-1, 1, (8, 16)).astype(onp.float32))
    mx.random.seed(11)
    net = _mlp(depth=2)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=kvstore,
                            update_on_kvstore=False)
    losses = []
    for step in range(3):
        with mx.autograd.record():
            out = net(x)
            loss = nd.mean(nd.square(out))
        loss.backward()
        if step == 0 and record is not None:
            trainer._init_kvstore()
            orig = trainer._kvstore.pushpull

            def spy(key, value, out=None, priority=0):
                record.append(list(key) if isinstance(key, (list, tuple))
                              else [key])
                return orig(key, value, out=out, priority=priority)
            trainer._kvstore.pushpull = spy
        trainer.step(8)
        losses.append(float(loss.asnumpy()))
    return losses, [p.data().asnumpy()
                    for p in net.collect_params().values()]


def test_gluon_trainer_bucket_split_parity(monkeypatch):
    """The per-bucket pushpull split (reverse declaration order) must be
    byte-equivalent to the single fused call: same losses, same params."""
    calls = []
    # tiny cap: every parameter becomes its own bucket -> several calls
    split = _gluon_run("tpu", 64, monkeypatch, record=calls)
    fused = _gluon_run("tpu", 1 << 30, monkeypatch)
    onp.testing.assert_allclose(split[0], fused[0], rtol=0, atol=0)
    for a, b in zip(split[1], fused[1]):
        onp.testing.assert_array_equal(a, b)
    # 3 identical steps -> calls divide evenly into per-step runs
    assert len(calls) % 3 == 0
    per_step = len(calls) // 3
    assert per_step > 2  # the split really split
    # reverse declaration order within a step: later-declared (higher-key)
    # buckets dispatch first, matching backward finalization order
    run = calls[:per_step]
    for prev, nxt in zip(run, run[1:]):
        assert max(nxt) < min(prev)
