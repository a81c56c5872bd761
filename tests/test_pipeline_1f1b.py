"""1F1B / interleaved pipeline schedule (ISSUE 9).

The hand-scheduled 1F1B program must reproduce GPipe's and the fused
single-device trainer's math exactly (loss trajectory AND updated params)
while keeping its peak temp memory FLAT in the microbatch count — the
bounded-activation-memory property the tentpole claims. Also pinned here:
the 3D composition lanes (dp / zero-over-dp / weight-sharded tp), frozen
parameters, engine-cache compile sharing across same-config trainers, and
the ppermute comm telemetry.
"""
import numpy as onp
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import engine as _engine
from mxnet_tpu import nd
from mxnet_tpu import telemetry as telem
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.bert import BertModel
from mxnet_tpu.parallel import (make_mesh, DataParallelTrainer,
                                PipelineTrainer, shard_params_megatron)

V, B, T = 64, 8, 8


def _devices(n):
    d = jax.devices("cpu")
    assert len(d) >= n, f"need {n} cpu devices"
    return d[:n]


def _loss_fn(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _data(batch=B):
    rs = onp.random.RandomState(0)
    x = nd.array(rs.randint(0, V, (batch, T)), dtype="int32")
    y = nd.array(rs.randint(0, V, (batch, T)), dtype="int32")
    return x, y


def _bert(x):
    mx.random.seed(3)
    net = BertModel(vocab_size=V, num_layers=4, units=32, hidden_size=64,
                    num_heads=2, max_length=T, dropout=0.0)
    net.initialize()
    net(x)
    return net


def _params(net):
    return [onp.asarray(p._data._data).copy()
            for p in net.collect_params().values()]


def _dp_oracle(x, y, steps, optimizer="sgd", opt_params=None):
    net = _bert(x)
    tr = DataParallelTrainer(net, _loss_fn, optimizer=optimizer,
                             optimizer_params=opt_params or
                             {"learning_rate": 0.5, "wd": 0.0},
                             mesh=make_mesh({"dp": 1}, devices=_devices(1)))
    losses = [float(tr.step(x, y)) for _ in range(steps)]
    tr.sync()
    return net, losses


def _pp_run(x, y, steps, optimizer="sgd", opt_params=None, **kw):
    net = _bert(x)
    if kw.pop("_megatron", False):
        shard_params_megatron(net, axis="tp")
    tr = PipelineTrainer(net, _loss_fn, optimizer=optimizer,
                         optimizer_params=opt_params or
                         {"learning_rate": 0.5, "wd": 0.0}, **kw)
    losses = [float(tr.step(x, y)) for _ in range(steps)]
    tr.sync()
    return net, tr, losses


def _assert_params_close(net_a, net_b, rtol=1e-4, atol=1e-5):
    for a, b, pname in zip(_params(net_a), _params(net_b),
                           net_a.collect_params().keys()):
        onp.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                    err_msg=pname)


# ---------------------------------------------------------------------------
# 10-step loss/param parity: 1F1B vs GPipe vs dp-only
# ---------------------------------------------------------------------------

def test_1f1b_10step_parity_sgd_pp4():
    """10 SGD steps at pp=4: the 1F1B trajectory must track both GPipe and
    the single-device oracle — losses stepwise and final params."""
    x, y = _data()
    net1, l1 = _dp_oracle(x, y, 10)
    mesh = make_mesh({"pp": 4}, devices=_devices(4))
    net_g, _, lg = _pp_run(x, y, 10, mesh=mesh, num_microbatch=4,
                           schedule="gpipe")
    net_f, _, lf = _pp_run(x, y, 10, mesh=mesh, num_microbatch=4,
                           schedule="1f1b")
    onp.testing.assert_allclose(l1, lf, rtol=5e-4, atol=5e-5)
    onp.testing.assert_allclose(lg, lf, rtol=5e-4, atol=5e-5)
    _assert_params_close(net1, net_f, rtol=1e-3, atol=1e-5)
    _assert_params_close(net_g, net_f, rtol=1e-3, atol=1e-5)
    assert lf[-1] < lf[0]


@pytest.mark.slow  # adam + pp lanes are both covered by the zero test above
def test_1f1b_10step_parity_adam_pp2():
    x, y = _data()
    net1, l1 = _dp_oracle(x, y, 10, optimizer="adam",
                          opt_params={"learning_rate": 1e-2})
    net_f, _, lf = _pp_run(x, y, 10, optimizer="adam",
                           opt_params={"learning_rate": 1e-2},
                           mesh=make_mesh({"pp": 2}, devices=_devices(2)),
                           num_microbatch=4, schedule="1f1b")
    onp.testing.assert_allclose(l1, lf, rtol=2e-3, atol=2e-4)
    _assert_params_close(net1, net_f, rtol=5e-3, atol=1e-4)
    assert lf[-1] < lf[0]


@pytest.mark.slow  # pp x dp composition is covered by the zero parity test
def test_1f1b_10step_parity_sgd_pp2_dp2():
    """pp=2 x dp=2 under 1F1B == single-device math for 10 steps."""
    x, y = _data()
    net1, l1 = _dp_oracle(x, y, 10)
    net_f, _, lf = _pp_run(
        x, y, 10, mesh=make_mesh({"pp": 2, "dp": 2}, devices=_devices(4)),
        dp_axis="dp", num_microbatch=2, schedule="1f1b")
    onp.testing.assert_allclose(l1, lf, rtol=5e-4, atol=5e-5)
    _assert_params_close(net1, net_f, rtol=1e-3, atol=1e-5)


def test_interleaved_virtual_stages_parity():
    """virtual_stages=2 at pp=2 (4 layers -> 1 layer per chunk, logical
    stage order 0,2 | 1,3): same math as the single-device oracle."""
    x, y = _data()
    net1, l1 = _dp_oracle(x, y, 3)
    net_f, tr, lf = _pp_run(x, y, 3,
                            mesh=make_mesh({"pp": 2}, devices=_devices(2)),
                            num_microbatch=4, virtual_stages=2)
    assert tr._stack_order == [0, 2, 1, 3]
    onp.testing.assert_allclose(l1, lf, rtol=5e-4, atol=5e-5)
    _assert_params_close(net1, net_f, rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# bounded activation memory (the tentpole acceptance)
# ---------------------------------------------------------------------------

def test_1f1b_temp_memory_flat_in_microbatches():
    """Weak scaling in M at FIXED microbatch size: GPipe's transposed scan
    stashes one residual per (stage, microbatch), so its temp allocation
    grows with M; the 1F1B ring stash holds 2*pp*v-1 slots regardless of M,
    so its temp stays flat. Read from XLA's compiled memory_analysis."""
    telem.enable()
    mesh = make_mesh({"pp": 2}, devices=_devices(2))
    temp = {}
    for sched in ("1f1b", "gpipe"):
        for M in (4, 12):
            x, y = _data(batch=2 * M)   # microbatch stays 2 rows
            _, tr, _ = _pp_run(x, y, 1, mesh=mesh, num_microbatch=M,
                               schedule=sched)
            cost = next(iter(tr._program._costs.values()))
            temp[(sched, M)] = cost.get("temp_memory_bytes", 0.0)
    if not all(temp.values()):
        pytest.skip("backend reports no memory_analysis temp sizes")
    # 3x the microbatches: 1F1B's ring buffer does not scale at all, while
    # GPipe's residual stash grows with every extra microbatch. A constant
    # temp floor (e.g. undonated update double-buffers) is common to both and
    # is most of either at this size, so the control is held to what growth
    # it shows over that floor (1.16x and 1.22x on jax 0.9.0), not to a ratio
    # of the stash alone
    assert temp[("1f1b", 12)] == temp[("1f1b", 4)], temp
    assert temp[("gpipe", 12)] > 1.1 * temp[("gpipe", 4)], temp
    assert temp[("gpipe", 12)] > 1.1 * temp[("1f1b", 12)], temp


# ---------------------------------------------------------------------------
# fused-step compile sharing through the engine cache
# ---------------------------------------------------------------------------

def test_same_config_trainers_share_compiles():
    """Acceptance: two trainers with identical configuration resolve to ONE
    engine-cache artifact — the second construction+step adds no compile."""
    x, y = _data()
    mesh = make_mesh({"pp": 2}, devices=_devices(2))
    conf = dict(mesh=mesh, num_microbatch=8, schedule="1f1b",
                opt_params={"learning_rate": 0.3, "wd": 0.0})
    net_a = _bert(x)
    tr_a = PipelineTrainer(net_a, _loss_fn, optimizer="sgd",
                           optimizer_params=conf["opt_params"],
                           mesh=conf["mesh"],
                           num_microbatch=conf["num_microbatch"],
                           schedule=conf["schedule"])
    baseline = _engine.cache_stats()["artifacts"]
    tr_a.step(x, y)
    tr_a.drain()
    assert _engine.cache_stats()["artifacts"] - baseline >= 1
    net_b = _bert(x)
    tr_b = PipelineTrainer(net_b, _loss_fn, optimizer="sgd",
                           optimizer_params=conf["opt_params"],
                           mesh=conf["mesh"],
                           num_microbatch=conf["num_microbatch"],
                           schedule=conf["schedule"])
    assert tr_b._step_key_base == tr_a._step_key_base
    before = _engine.cache_stats()["artifacts"]
    hits0 = _engine.cache_stats()["hits"]
    tr_b.step(x, y)
    tr_b.drain()
    assert _engine.cache_stats()["artifacts"] == before
    assert _engine.cache_stats()["hits"] > hits0
    # shared fingerprint => shared roofline region name
    sig = next(iter(tr_b._program._regions))
    assert tr_b._program.region(sig) == tr_a._program.region(sig)


# ---------------------------------------------------------------------------
# ZeRO-over-dp and weight-sharded tp composition
# ---------------------------------------------------------------------------

def test_1f1b_zero_update_parity_pp2_dp2():
    """zero_update over the dp axis of the stacked stage params: same adam
    math as the single-device oracle, with the (n_stages, padded) stage
    bucket state sharded P(pp, dp)."""
    x, y = _data()
    net1, l1 = _dp_oracle(x, y, 3, optimizer="adam",
                          opt_params={"learning_rate": 1e-2})
    net_f, tr, lf = _pp_run(
        x, y, 3, optimizer="adam", opt_params={"learning_rate": 1e-2},
        mesh=make_mesh({"pp": 2, "dp": 2}, devices=_devices(4)),
        dp_axis="dp", num_microbatch=2, zero_update=True)
    onp.testing.assert_allclose(l1, lf, rtol=2e-3, atol=2e-4)
    _assert_params_close(net1, net_f, rtol=5e-3, atol=1e-4)
    # per-stage bucket state is globally (n_stages, padded)
    for _, st in tr._opt_s:
        for leaf in jax.tree_util.tree_leaves(st):
            assert leaf.shape[0] == 2


def test_1f1b_weight_sharded_tp_parity():
    """pp=2 x tp=2 with Megatron specs on the Parameters: weights stored
    tp-sharded, gathered once per step, grads sliced back — identical math
    to the unsharded oracle."""
    x, y = _data()
    net1, l1 = _dp_oracle(x, y, 3)
    net_f, tr, lf = _pp_run(
        x, y, 3, mesh=make_mesh({"pp": 2, "tp": 2}, devices=_devices(4)),
        tp_axis="tp", num_microbatch=2, _megatron=True)
    assert any(d is not None for d in tr._tp_s), "no cell leaf tp-sharded"
    onp.testing.assert_allclose(l1, lf, rtol=5e-4, atol=5e-5)
    _assert_params_close(net1, net_f, rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# frozen (grad_req='null') parameters
# ---------------------------------------------------------------------------

def test_frozen_embedding_skips_update():
    """Regression for the old hard error: frozen embed params must ride the
    schedule untouched while everything else trains to the oracle's values
    (the dp trainer with the same frozen mask)."""
    x, y = _data()

    def freeze(net):
        embed, _, _ = net.pipeline_split()
        for p in embed.collect_params().values():
            p.grad_req = "null"
        return net

    net1 = freeze(_bert(x))
    frozen_before = _params(net1)
    tr1 = DataParallelTrainer(net1, _loss_fn, optimizer="sgd",
                              optimizer_params={"learning_rate": 0.5,
                                                "wd": 0.0},
                              mesh=make_mesh({"dp": 1},
                                             devices=_devices(1)))
    l1 = [float(tr1.step(x, y)) for _ in range(3)]
    tr1.sync()

    net2 = freeze(_bert(x))
    tr2 = PipelineTrainer(net2, _loss_fn, optimizer="sgd",
                          optimizer_params={"learning_rate": 0.5, "wd": 0.0},
                          mesh=make_mesh({"pp": 2}, devices=_devices(2)),
                          num_microbatch=4)
    assert not any(tr2._tr_e)
    l2 = [float(tr2.step(x, y)) for _ in range(3)]
    tr2.sync()
    onp.testing.assert_allclose(l1, l2, rtol=5e-4, atol=5e-5)
    _assert_params_close(net1, net2, rtol=1e-3, atol=1e-5)
    # the frozen leaves are bitwise untouched
    embed_names = set(net2.pipeline_split()[0].collect_params().keys())
    for (pname, p), before in zip(net2.collect_params().items(),
                                  frozen_before):
        if pname in embed_names:
            onp.testing.assert_array_equal(onp.asarray(p._data._data),
                                           before, err_msg=pname)


# ---------------------------------------------------------------------------
# ppermute comm telemetry
# ---------------------------------------------------------------------------

def test_ppermute_comm_telemetry():
    """Each schedule books its activation-hop ppermute volume under its own
    comm kind: M + 2(pp*v - 1) combined ticks for 1F1B, M + pp*v - 1 for
    GPipe, two rings (fwd activations + bwd cotangents) each."""
    x, y = _data()
    telem.enable()
    mesh = make_mesh({"pp": 2}, devices=_devices(2))
    M, n = 4, 2
    for sched, hops in (("1f1b", M + 2 * (n - 1)), ("gpipe", M + n - 1)):
        telem.reset()
        _, tr, _ = _pp_run(x, y, 1, mesh=mesh, num_microbatch=M,
                           schedule=sched)
        bytes_c = telem.get_metric("mx_comm_bytes_total")
        calls_c = telem.get_metric("mx_comm_calls_total")
        assert bytes_c.get("ppermute", "mesh") > 0, sched
        assert calls_c.get("ppermute", "mesh") == 2 * hops, sched
        assert bytes_c.get("pipeline_grad_psum", "mesh") > 0, sched
        # act bytes per hop: one (B/M, T, units) f32 microbatch activation
        act = (B // M) * T * 32 * 4
        assert bytes_c.get("ppermute", "mesh") == act * 2 * hops, sched


# ---------------------------------------------------------------------------
# configuration validation
# ---------------------------------------------------------------------------

def test_rejects_incompatible_configs():
    x, _ = _data()
    net = _bert(x)
    mesh2 = make_mesh({"pp": 2}, devices=_devices(2))
    with pytest.raises(MXNetError, match="schedule"):
        PipelineTrainer(net, _loss_fn, mesh=mesh2, schedule="pipedream")
    with pytest.raises(MXNetError, match="1f1b"):
        PipelineTrainer(net, _loss_fn, mesh=mesh2, schedule="gpipe",
                        virtual_stages=2)
    with pytest.raises(MXNetError, match="dp_axis"):
        PipelineTrainer(net, _loss_fn, mesh=mesh2, zero_update=True)
    mesh_tp = make_mesh({"pp": 2, "dp": 2}, devices=_devices(4))
    with pytest.raises(MXNetError, match="tp_axis"):
        PipelineTrainer(net, _loss_fn, mesh=make_mesh(
            {"pp": 2, "dp": 1, "tp": 2}, devices=_devices(4)),
            dp_axis="dp", tp_axis="tp", zero_update=True)
    with pytest.raises(MXNetError, match="LAMB"):
        PipelineTrainer(net, _loss_fn, optimizer="lamb", mesh=mesh_tp,
                        dp_axis="dp", zero_update=True)
    # 4 layers cannot split into pp=2 x v=4 chunks
    with pytest.raises(MXNetError, match="divide"):
        PipelineTrainer(net, _loss_fn, mesh=mesh2, virtual_stages=4)
