"""Pipeline parallelism: the circular GPipe schedule (forward + transposed
backward) must reproduce single-device math exactly — loss AND gradients —
on the 8-virtual-device CPU mesh, alone and composed with data parallelism.

Capability uplift over the reference (SURVEY.md §2.4: no PP in reference);
the equivalence oracle is the fused single-device trainer."""
import numpy as onp
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.bert import BertModel
from mxnet_tpu.parallel import (make_mesh, P, DataParallelTrainer,
                                PipelineTrainer, pipeline_apply)
from jax import shard_map


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


V, B, T = 64, 8, 8


def _data():
    rs = onp.random.RandomState(0)
    x = nd.array(rs.randint(0, V, (B, T)), dtype="int32")
    y = nd.array(rs.randint(0, V, (B, T)), dtype="int32")
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


def test_pipeline_apply_matches_sequential():
    """The schedule itself: stacked stages over 'pp' == sequential chain."""
    n, M, D = 4, 4, 8
    mesh = make_mesh({"pp": n}, devices=_devices(n))
    rs = onp.random.RandomState(1)
    w = jnp.asarray(rs.normal(0, 0.5, (n, D, D)).astype(onp.float32))
    x = jnp.asarray(rs.normal(0, 1, (M, 2, D)).astype(onp.float32))

    def stage(wi, h):
        return jnp.tanh(h @ wi)

    ref = x
    for i in range(n):
        ref = stage(w[i], ref)

    # output is valid on the LAST stage; replicated out_spec would check
    # cross-device agreement, which by design does not hold — fetch the
    # last stage's shard instead
    from mxnet_tpu.parallel.zero import shard_map_compat
    out = jax.jit(shard_map_compat(
        lambda wi, xs: pipeline_apply(lambda p, h, t: stage(p[0], h), wi, xs,
                                      axis_name="pp")[None],
        mesh=mesh, in_specs=(P("pp"), P(None)), out_specs=P("pp")))(w, x)
    onp.testing.assert_allclose(onp.asarray(out[-1]), onp.asarray(ref),
                                rtol=1e-5, atol=1e-6)


def test_pipeline_grads_match_single_device():
    """One SGD step at wd=0: updated params are a pure gradient comparison
    (loss AND grads must match, VERDICT round-4 ask)."""
    x, y = _data()
    net1 = _bert(x)
    tr1 = DataParallelTrainer(net1, _loss_fn, optimizer="sgd",
                              optimizer_params={"learning_rate": 1.0, "wd": 0.0},
                              mesh=make_mesh({"dp": 1}, devices=_devices(1)))
    l1 = float(tr1.step(x, y))
    tr1.sync()

    net2 = _bert(x)
    tr2 = PipelineTrainer(net2, _loss_fn, optimizer="sgd",
                          optimizer_params={"learning_rate": 1.0, "wd": 0.0},
                          mesh=make_mesh({"pp": 4}, devices=_devices(4)),
                          num_microbatch=4)
    l2 = float(tr2.step(x, y))
    tr2.sync()

    onp.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b, pname in zip(_params(net1), _params(net2),
                           net1.collect_params().keys()):
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6,
                                    err_msg=pname)


def test_pipeline_adam_tracks_single_device():
    x, y = _data()
    net1 = _bert(x)
    tr1 = DataParallelTrainer(net1, _loss_fn, optimizer="adam",
                              optimizer_params={"learning_rate": 1e-2},
                              mesh=make_mesh({"dp": 1}, devices=_devices(1)))
    l1 = [float(tr1.step(x, y)) for _ in range(3)]

    net2 = _bert(x)
    tr2 = PipelineTrainer(net2, _loss_fn, optimizer="adam",
                          optimizer_params={"learning_rate": 1e-2},
                          mesh=make_mesh({"pp": 4}, devices=_devices(4)),
                          num_microbatch=4)
    l2 = [float(tr2.step(x, y)) for _ in range(3)]
    onp.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-5)
    assert l2[-1] < l2[0]


def test_pipeline_composes_with_dp():
    """pp=2 x dp=2 on 4 devices == single device math."""
    x, y = _data()
    net1 = _bert(x)
    tr1 = DataParallelTrainer(net1, _loss_fn, optimizer="sgd",
                              optimizer_params={"learning_rate": 0.5, "wd": 0.0},
                              mesh=make_mesh({"dp": 1}, devices=_devices(1)))
    l1 = [float(tr1.step(x, y)) for _ in range(2)]
    tr1.sync()

    net2 = _bert(x)
    tr2 = PipelineTrainer(net2, _loss_fn, optimizer="sgd",
                          optimizer_params={"learning_rate": 0.5, "wd": 0.0},
                          mesh=make_mesh({"pp": 2, "dp": 2},
                                         devices=_devices(4)),
                          dp_axis="dp", num_microbatch=2)
    l2 = [float(tr2.step(x, y)) for _ in range(2)]
    tr2.sync()
    onp.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-5)
    for a, b, pname in zip(_params(net1), _params(net2),
                           net1.collect_params().keys()):
        onp.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5,
                                    err_msg=pname)


def test_pipeline_multiple_layers_per_stage():
    """4 layers on pp=2 -> 2 layers/stage through the local lax.scan."""
    x, y = _data()
    net1 = _bert(x)
    tr1 = DataParallelTrainer(net1, _loss_fn, optimizer="sgd",
                              optimizer_params={"learning_rate": 1.0, "wd": 0.0},
                              mesh=make_mesh({"dp": 1}, devices=_devices(1)))
    l1 = float(tr1.step(x, y))
    tr1.sync()

    net2 = _bert(x)
    tr2 = PipelineTrainer(net2, _loss_fn, optimizer="sgd",
                          optimizer_params={"learning_rate": 1.0, "wd": 0.0},
                          mesh=make_mesh({"pp": 2}, devices=_devices(2)),
                          num_microbatch=4)
    l2 = float(tr2.step(x, y))
    tr2.sync()
    onp.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b, pname in zip(_params(net1), _params(net2),
                           net1.collect_params().keys()):
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6,
                                    err_msg=pname)


def test_pipeline_rejects_bad_configs():
    x, y = _data()
    net = _bert(x)
    # 4 layers on pp=3 does not divide
    with pytest.raises(MXNetError, match="divide"):
        PipelineTrainer(net, _loss_fn,
                        mesh=make_mesh({"pp": 3}, devices=_devices(3)))
    # batch not divisible by microbatches
    tr = PipelineTrainer(net, _loss_fn, optimizer="sgd",
                         mesh=make_mesh({"pp": 2}, devices=_devices(2)),
                         num_microbatch=3)
    with pytest.raises(MXNetError, match="divide"):
        tr.step(x, y)
    # net without pipeline_split
    mlp = mx.gluon.nn.Dense(4, in_units=4)
    mlp.initialize()
    with pytest.raises(MXNetError, match="pipeline_split"):
        PipelineTrainer(mlp, _loss_fn,
                        mesh=make_mesh({"pp": 2}, devices=_devices(2)))
