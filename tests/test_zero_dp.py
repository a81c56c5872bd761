"""ZeRO-style cross-replica sharded weight update (arXiv:2004.13336) on the
8-virtual-device CPU mesh: trajectory parity against the replicated update,
1/N optimizer-state footprint via the telemetry gauge, per-kind collective
accounting, compile-cache keying per zero config, the compressed-wire
reduce-scatter paths, and the bucket-planner / kvstore bucketed-pushpull
mechanics the fused step shares with gluon Trainer. Also what the three
step bodies (plain, zero, 2-bit compressed) must each hold: frozen leaves
stay bit-equal, and the compiled step carries the collectives its body
says it does."""
import numpy as onp
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon
from mxnet_tpu.base import MXNetError
from mxnet_tpu import engine as _engine
from mxnet_tpu import telemetry as telem
from mxnet_tpu.parallel import make_mesh, P, DataParallelTrainer
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


def _mlp(bn=False):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32))
    if bn:
        net.add(gluon.nn.BatchNorm())
    net.add(gluon.nn.Activation("relu"), gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 16)))
    return net


def _batch(seed=0, n=16):
    rs = onp.random.RandomState(seed)
    x = nd.array(rs.uniform(-1, 1, (n, 16)).astype(onp.float32))
    y = nd.array(rs.randint(0, 4, (n,)), dtype="int32")
    return x, y


def _trainer(mesh, optimizer="adam", lr=0.01, wd=None, **kw):
    mx.random.seed(7)
    net = _mlp(bn=kw.pop("bn", False))
    opt_params = {"learning_rate": lr}
    if wd is not None:
        opt_params["wd"] = wd
    tr = DataParallelTrainer(net, _loss_fn, optimizer=optimizer,
                             optimizer_params=opt_params,
                             mesh=mesh, **kw)
    return net, tr


# ---------------------------------------------------------------------------
# trajectory parity: sharded update == replicated update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer,wd", [("adam", None), ("sgd", None),
                                          ("adam", 0.01)])
def test_zero_matches_replicated_trajectory(host_mesh8, optimizer, wd):
    """Acceptance: 10 steps, loss AND synced parameters match the
    replicated update to fp32 tolerance — including nonzero weight decay,
    which the sharded update applies through the per-bucket wd vector."""
    x, y = _batch()
    results = {}
    for zero in (False, True):
        net, tr = _trainer(host_mesh8, optimizer=optimizer, wd=wd,
                           zero_update=zero)
        losses = [float(tr.step(x, y)) for _ in range(10)]
        tr.sync()
        # block names are auto-suffixed per instance: compare positionally
        params = [p.data().asnumpy()
                  for p in net.collect_params().values()]
        results[zero] = (losses, params)
    onp.testing.assert_allclose(results[False][0], results[True][0],
                                rtol=1e-4, atol=1e-5)
    assert results[True][0][-1] < results[True][0][0]
    for i, (ref, got) in enumerate(zip(*[results[z][1]
                                         for z in (False, True)])):
        onp.testing.assert_allclose(ref, got, rtol=1e-4, atol=1e-5,
                                    err_msg=f"param {i}")


def test_zero_multi_bucket_and_run_steps(host_mesh8):
    """A tiny bucket cap forces multiple fusion buckets, and the scanned
    run_steps path must agree with the replicated single-step path."""
    x, y = _batch()
    _, tr_rep = _trainer(host_mesh8, optimizer="sgd", lr=0.1)
    ref = [float(tr_rep.step(x, y)) for _ in range(6)]

    _, tr_zero = _trainer(host_mesh8, optimizer="sgd", lr=0.1,
                          zero_update=True, bucket_bytes=1024)
    assert len(tr_zero._zero_plan) > 1
    got = onp.asarray(tr_zero.run_steps(x, y, 6))
    onp.testing.assert_allclose(ref, got, rtol=1e-4, atol=1e-5)


def test_zero_with_batchnorm_aux(host_mesh8):
    """BN running stats ride the aux carry in the sharded step. Note the
    shard_map body normalizes over each replica's LOCAL batch tile
    (classic per-device DP BatchNorm, like the compressed path and the
    reference's device-local BN) — so no parity with the replicated jit's
    global-batch statistics; the carry mechanics are what's under test."""
    x, y = _batch()
    net, tr = _trainer(host_mesh8, optimizer="sgd", lr=0.1,
                       zero_update=True, bn=True)
    losses = [float(tr.step(x, y)) for _ in range(4)]
    assert losses[-1] < losses[0]
    tr.sync()
    stats = {n: p.data().asnumpy()
             for n, p in net.collect_params().items() if "running" in n}
    assert stats, "expected BN running stats"
    for n, v in stats.items():
        assert onp.all(onp.isfinite(v)), n
        # cross-device-averaged stats accumulated across steps: off init
        if "mean" in n:
            assert onp.abs(v).max() > 0, n
        else:
            assert onp.abs(v - 1.0).max() > 1e-6, n


@pytest.mark.parametrize("comm_dtype,rtol", [("bfloat16", 0.02),
                                             ("int8", 0.05)])
def test_compressed_wire_tracks_replicated(host_mesh8, comm_dtype, rtol):
    """EQuARX-style compressed reduce-scatter: lossy on the wire, fp32
    accumulation — the trajectory stays close to the exact update."""
    x, y = _batch()
    _, tr_rep = _trainer(host_mesh8)
    ref = [float(tr_rep.step(x, y)) for _ in range(8)]
    _, tr_c = _trainer(host_mesh8, zero_update=True, comm_dtype=comm_dtype)
    got = [float(tr_c.step(x, y)) for _ in range(8)]
    onp.testing.assert_allclose(ref, got, rtol=rtol, atol=rtol)
    assert got[-1] < got[0]


# ---------------------------------------------------------------------------
# memory: per-replica optimizer state shrinks ~1/N (telemetry gauge)
# ---------------------------------------------------------------------------

def test_per_replica_state_bytes_gauge(host_mesh8):
    """Acceptance: the mx_optimizer_state_per_replica_bytes gauge reports
    <= (1/8 + epsilon) of the replicated footprint under zero_update."""
    x, y = _batch()
    telem.enable()
    sizes = {}
    for zero in (False, True):
        telem.reset()
        _, tr = _trainer(host_mesh8, zero_update=zero)
        tr.step(x, y)
        g = telem.get_metric("mx_optimizer_state_per_replica_bytes")
        assert g is not None
        sizes[zero] = g.get("data_parallel")
    assert sizes[False] > 0
    # epsilon: the tail bucket pads to a multiple of 8 elements
    pad = 8 * 2 * 4  # elements * adam (m, v) * fp32
    assert sizes[True] <= sizes[False] / 8 + pad, sizes
    # the gauge matches what the sharded state actually holds
    _, tr = _trainer(host_mesh8, zero_update=True)
    assert tr._opt_state_replica_bytes() == sizes[True]


def test_collective_kind_counters(host_mesh8):
    """Zero mode books reduce_scatter + all_gather bytes (NOT allreduce);
    the replicated step books allreduce — distinct per-kind labels."""
    x, y = _batch()
    telem.enable()
    for zero, present, absent in (
            (False, ("allreduce",), ("reduce_scatter", "all_gather")),
            (True, ("reduce_scatter", "all_gather"), ("allreduce",))):
        telem.reset()
        _, tr = _trainer(host_mesh8, zero_update=zero)
        tr.step(x, y)
        c = telem.get_metric("mx_comm_bytes_total")
        assert c is not None
        for op in present:
            assert c.get(op, "mesh") > 0, (zero, op)
        for op in absent:
            assert c.get(op, "mesh") == 0, (zero, op)
    # wire estimate sanity: the sharded update moves ~the all-reduce bytes
    # (reduce-scatter + all-gather IS the ring all-reduce decomposition)
    _, tr = _trainer(host_mesh8, zero_update=True)
    rs = zero_mod.reduce_scatter_wire_bytes(tr._zero_plan, 8)
    ag = zero_mod.all_gather_wire_bytes(tr._zero_plan, 8)
    ar = tr._grad_allreduce_bytes()
    assert abs((rs + ag) - ar) <= ar * 0.02 + 256
    # the bf16 wire halves the reduce-scatter bytes
    rs_bf16 = zero_mod.reduce_scatter_wire_bytes(tr._zero_plan, 8,
                                                 "bfloat16")
    assert rs_bf16 == rs // 2


def test_plain_step_books_one_allreduce_a_step(host_mesh8):
    """The plain fused step books one all-reduce a step on the dp lane,
    with the ring estimate's bytes, and nothing on any other lane."""
    x, y = _batch()
    telem.enable()
    _, tr = _trainer(host_mesh8)
    for steps in (1, 2, 3):
        tr.step(x, y)
        calls = telem.get_metric("mx_comm_calls_total")
        assert calls.get("allreduce", "mesh") == steps
        assert telem.comm_bytes_by_axis() == {
            "dp": tr._grad_allreduce_bytes() * steps}
    assert telem.comm_axis_bytes("tp") == 0


# ---------------------------------------------------------------------------
# the three step bodies: frozen leaves, and the collectives compiled in
# ---------------------------------------------------------------------------

BODIES = {"plain": {},
          "zero": dict(zero_update=True, bucket_bytes=1024),
          "compressed": dict(compression={"type": "2bit",
                                          "threshold": 1e-3})}


@pytest.mark.parametrize("body", sorted(BODIES))
def test_frozen_params(host_mesh8, body):
    """A grad_req='null' leaf is bit-equal after three steps of each step
    body (under zero it stays out of every bucket and of the replicated
    `extra` updates), and every other leaf moves."""
    x, y = _batch()
    mx.random.seed(7)
    net = _mlp()
    plist = list(net.collect_params().values())
    frozen = 1  # the first Dense's bias
    plist[frozen].grad_req = "null"
    tr = DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1},
                             mesh=host_mesh8, **BODIES[body])
    if body == "zero":
        assert len(tr._zero_plan) > 1
        assert all(frozen not in b.indices for b in tr._zero_plan)
    before = [p.data().asnumpy() for p in plist]
    for _ in range(3):
        tr.step(x, y)
    tr.sync()
    after = [p.data().asnumpy() for p in plist]
    for i, (b, a) in enumerate(zip(before, after)):
        if i == frozen:
            onp.testing.assert_array_equal(b, a)
        else:
            assert onp.abs(a - b).max() > 0, f"leaf {i} did not move"


def _optimized_hlo(tr, x, y):
    from jax.sharding import NamedSharding
    from mxnet_tpu import random as _rng
    xr = jax.device_put(x._data, NamedSharding(tr.mesh, P("dp")))
    yr = jax.device_put(y._data, NamedSharding(tr.mesh, P("dp")))
    rep = NamedSharding(tr.mesh, P())
    key = jax.device_put(onp.asarray(_rng.next_key_raw()), rep)
    lr, t, sc = (jax.device_put(onp.float32(v), rep)
                 for v in (0.01, 1.0, 1.0))
    fn = tr._get_step((xr.shape, str(xr.dtype), yr.shape, str(yr.dtype)))
    return fn.lower(tr._params_raw, tr._opt_state, key, xr, yr,
                    lr, t, sc).compile().as_text()


@pytest.mark.parametrize("body,present,absent", [
    ("plain", ("all-reduce",), ("reduce-scatter",)),
    ("zero", ("reduce-scatter", "all-gather"), ())],
    ids=["plain", "zero"])
def test_step_hlo_collectives(host_mesh8, body, present, absent):
    """The compiled step holds the exchange its body is built around: the
    plain step the all-reduce XLA forms from the replicated gradients and
    no reduce-scatter; the zero step a reduce-scatter and the gather-back
    of the updated shards."""
    x, y = _batch()
    _, tr = _trainer(host_mesh8, optimizer="sgd", **BODIES[body])
    text = _optimized_hlo(tr, x, y)

    def count(op):
        return text.count(op + "(") + text.count(op + "-start(")
    for op in present:
        assert count(op) >= 1, (body, op)
    for op in absent:
        assert count(op) == 0, (body, op)


# ---------------------------------------------------------------------------
# compile cache: distinct artifacts per zero configuration
# ---------------------------------------------------------------------------

def test_compile_cache_distinct_per_zero_config(host_mesh8):
    """Acceptance: each (zero, bucket_bytes, comm_dtype) configuration
    compiles its own artifact; identical configurations share one."""
    x, y = _batch()
    configs = [dict(), dict(zero_update=True),
               dict(zero_update=True, bucket_bytes=1024),
               dict(zero_update=True, comm_dtype="bfloat16")]
    keys = set()
    for kw in configs:
        _, tr = _trainer(host_mesh8, **dict(kw))
        keys.add(tr._step_key_base)
        _, tr2 = _trainer(host_mesh8, **dict(kw))
        assert tr2._step_key_base == tr._step_key_base
    assert len(keys) == len(configs)
    # a config not stepped anywhere else in the suite: the first step
    # publishes one artifact, a second trainer with the same config
    # reuses it (no growth)
    probe = dict(zero_update=True, bucket_bytes=4096,
                 comm_dtype="bfloat16")
    baseline = _engine.cache_stats()["artifacts"]
    _, tr_a = _trainer(host_mesh8, **dict(probe))
    tr_a.step(x, y)
    grown = _engine.cache_stats()["artifacts"] - baseline
    assert grown >= 1
    before = _engine.cache_stats()["artifacts"]
    _, tr_b = _trainer(host_mesh8, **dict(probe))
    tr_b.step(x, y)
    assert _engine.cache_stats()["artifacts"] == before


# ---------------------------------------------------------------------------
# configuration validation
# ---------------------------------------------------------------------------

def test_zero_rejects_incompatible_configs(host_mesh8):
    with pytest.raises(MXNetError, match="compression"):
        _trainer(host_mesh8, zero_update=True,
                 compression={"type": "2bit"})
    with pytest.raises(MXNetError, match="LAMB"):
        _trainer(host_mesh8, optimizer="lamb", zero_update=True)
    with pytest.raises(MXNetError, match="comm dtype"):
        _trainer(host_mesh8, zero_update=True, comm_dtype="float8")
    # env-var opt-in reaches the constructor default
    net = _mlp()
    import os
    os.environ["MXNET_TPU_ZERO"] = "1"
    try:
        tr = DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                                 optimizer_params={"learning_rate": 0.1},
                                 mesh=host_mesh8)
        assert tr._zero
    finally:
        del os.environ["MXNET_TPU_ZERO"]


# ---------------------------------------------------------------------------
# donation / host-feed regressions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("zero", [False, True])
def test_param_buffers_survive_donated_step(host_mesh8, zero):
    """The step jit donates the trainer's master weights; the gluon
    Parameters' own arrays must never alias them. Regression: device_put
    onto the 8-device replicated sharding shares the source device's
    buffer, so placement must copy exactly when device sets overlap."""
    x, y = _batch()
    net, tr = _trainer(host_mesh8, zero_update=zero)
    before = {n: p.data().asnumpy().copy()
              for n, p in net.collect_params().items()}
    tr.step(x, y)
    after = {n: p.data().asnumpy()
             for n, p in net.collect_params().items()}  # must not raise
    assert set(before) == set(after)


def test_batch_refeed_no_retransfer(host_mesh8):
    """Feeding a batch already resident with the right sharding must NOT
    re-transfer: _put_batch passes it through untouched (batches are not
    donated, so reuse is safe)."""
    from jax.sharding import NamedSharding
    x, y = _batch()
    _, tr = _trainer(host_mesh8, zero_update=True)
    sh = NamedSharding(host_mesh8, P("dp"))
    placed = jax.device_put(jnp.asarray(x._data), sh)
    assert tr._put_batch(placed, sh) is placed
    # and the step itself keeps the buffer alive for a second feed
    xb, yb = nd.NDArray(placed), y
    tr.step(xb, yb)
    assert tr._put_batch(xb._data, sh) is xb._data
    tr.step(xb, yb)


# ---------------------------------------------------------------------------
# bucket planner unit mechanics
# ---------------------------------------------------------------------------

def test_bucket_planner_mechanics():
    entries = [(0, (4, 3), jnp.float32), (1, (5,), jnp.float32),
               (2, (2, 2), jnp.bfloat16), (3, (100,), jnp.float32)]
    # cap of 64 fp32 elements: [w0(12)+w1(5)] then [w3(100) alone]
    plan = zero_mod.plan_buckets(entries, ndp=8, bucket_bytes=64 * 4)
    assert [b.indices for b in plan] == [(0, 1), (3,)] + [(2,)]
    for b in plan:
        assert b.padded_size % 8 == 0
        assert b.padded_size - b.pad == sum(b.sizes)
    arrays = {i: jnp.arange(onp.prod(shp), dtype=dt).reshape(shp)
              for i, shp, dt in entries}
    b0 = plan[0]
    flat = zero_mod.flatten_bucket(b0, arrays)
    assert flat.shape == (b0.padded_size,)
    back = dict(zero_mod.unflatten_bucket(b0, flat))
    for i in b0.indices:
        onp.testing.assert_array_equal(onp.asarray(back[i]),
                                       onp.asarray(arrays[i]))
    wd = zero_mod.wd_vector(b0, {0: 0.5, 1: 0.0, 2: 0.1, 3: 0.2})
    assert wd.shape == (b0.padded_size,)
    assert (wd[:12] == 0.5).all() and (wd[12:17] == 0.0).all()
    assert (wd[17:] == 0.0).all()  # pad decays nothing


def test_bucket_planner_oversize_tensor_gets_own_bucket():
    entries = [(0, (1000,), jnp.float32), (1, (2,), jnp.float32)]
    plan = zero_mod.plan_buckets(entries, ndp=4, bucket_bytes=128)
    assert [b.indices for b in plan] == [(0,), (1,)]


def test_canonical_comm_dtype():
    assert zero_mod.canonical_comm_dtype(None) is None
    assert zero_mod.canonical_comm_dtype("") is None
    assert zero_mod.canonical_comm_dtype("float32") is None
    assert zero_mod.canonical_comm_dtype("bf16") == "bfloat16"
    assert zero_mod.canonical_comm_dtype(jnp.bfloat16) == "bfloat16"
    assert zero_mod.canonical_comm_dtype("int8") == "int8"
    with pytest.raises(MXNetError):
        zero_mod.canonical_comm_dtype("int4")


# ---------------------------------------------------------------------------
# kvstore: bucketed pushpull (the eager sibling of the fused zero step)
# ---------------------------------------------------------------------------

def test_kvstore_bucketed_pushpull_matches_per_key():
    kv_b = mx.kv.create("local")
    kv_ref = mx.kv.create("local")
    rs = onp.random.RandomState(3)
    keys = [0, 1, 2]
    shapes = [(4, 3), (7,), (2, 5)]
    for kv in (kv_b, kv_ref):
        for k, shp in zip(keys, shapes):
            kv.init(k, nd.zeros(shp))
    vals = [[nd.array(rs.uniform(-1, 1, shp).astype(onp.float32))
             for _ in range(2)] for shp in shapes]
    outs_b = [nd.zeros(shp) for shp in shapes]
    outs_ref = [nd.zeros(shp) for shp in shapes]
    # list form rides the bucketed path; per-key calls are the reference
    kv_b.pushpull(keys, vals, out=outs_b)
    for k, v, o in zip(keys, vals, outs_ref):
        kv_ref.pushpull(k, v, out=o)
    for k, ob, oref in zip(keys, outs_b, outs_ref):
        onp.testing.assert_allclose(ob.asnumpy(), oref.asnumpy(),
                                    rtol=1e-6, err_msg=str(k))
        # the store persisted the merged value on both paths
        pb, pref = nd.zeros(ob.shape), nd.zeros(ob.shape)
        kv_b.pull(k, out=pb)
        kv_ref.pull(k, out=pref)
        onp.testing.assert_allclose(pb.asnumpy(), pref.asnumpy(), rtol=1e-6)


def test_kvstore_bucketed_ragged_contributors():
    """Keys with different per-key device counts take the per-key local
    reduce but still share the bucketed cross reduction."""
    kv_b = mx.kv.create("local")
    kv_ref = mx.kv.create("local")
    for kv in (kv_b, kv_ref):
        kv.init(0, nd.zeros((3,)))
        kv.init(1, nd.zeros((4,)))
    vals = [[nd.ones((3,)) * 2, nd.ones((3,))], [nd.ones((4,)) * 5]]
    outs_b = [nd.zeros((3,)), nd.zeros((4,))]
    outs_ref = [nd.zeros((3,)), nd.zeros((4,))]
    kv_b.pushpull([0, 1], vals, out=outs_b)
    for k, v, o in zip([0, 1], vals, outs_ref):
        kv_ref.pushpull(k, v, out=o)
    for ob, oref in zip(outs_b, outs_ref):
        onp.testing.assert_allclose(ob.asnumpy(), oref.asnumpy())


def test_kvstore_bucketed_falls_back_on_int_values():
    kv = mx.kv.create("local")
    kv.init(0, nd.zeros((3,)))
    kv.init(1, nd.zeros((3,), dtype="int32"))
    outs = [nd.zeros((3,)), nd.zeros((3,), dtype="int32")]
    kv.pushpull([0, 1], [nd.ones((3,)), nd.ones((3,), dtype="int32")],
                out=outs)
    onp.testing.assert_allclose(outs[0].asnumpy(), onp.ones(3))
    onp.testing.assert_array_equal(outs[1].asnumpy(),
                                   onp.ones(3, onp.int32))


def test_gluon_trainer_batched_allreduce_path():
    """gluon Trainer on a collective store with local updates routes grads
    through ONE batched pushpull (the kvstore bucketed reduce) and must
    track the plain no-kvstore trajectory."""
    rs = onp.random.RandomState(0)
    x = nd.array(rs.uniform(-1, 1, (8, 16)).astype(onp.float32))
    traj = {}
    for kvstore in (None, "tpu"):
        mx.random.seed(11)
        net = _mlp()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1}, kvstore=kvstore,
                                update_on_kvstore=False)
        losses = []
        for _ in range(3):
            with mx.autograd.record():
                out = net(x)
                loss = nd.mean(nd.square(out))
            loss.backward()
            trainer.step(8)
            losses.append(float(loss.asnumpy()))
        traj[kvstore] = losses
    onp.testing.assert_allclose(traj[None], traj["tpu"], rtol=1e-5)


def _gluon_run(kvstore, bucket_env, monkeypatch, record=None):
    monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", str(bucket_env))
    rs = onp.random.RandomState(0)
    x = nd.array(rs.uniform(-1, 1, (8, 16)).astype(onp.float32))
    mx.random.seed(11)
    net = _mlp()
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
