"""Parallelism tests on the 8-virtual-device CPU mesh (SURVEY.md §4's
N-process local pod pattern realized as N virtual devices): data parallel
consistency vs single device, tensor-parallel sharding, ring/Ulysses
sequence parallelism, expert-parallel MoE vs its dense reference, and the
ulysses/pipeline helpers."""
import numpy as onp
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax import shard_map

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon
from mxnet_tpu.parallel import (make_mesh, P, DataParallelTrainer,
                                ring_attention, blockwise_attention,
                                shard_params_megatron, moe_ffn,
                                expert_parallel_moe, topk_gating,
                                load_balancing_loss)
from mxnet_tpu.ops.attention import ulysses_attention


def _devices(n):
    d = jax.devices("cpu")
    assert len(d) >= n, f"need {n} cpu devices"
    return d[:n]


def _loss_fn(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def _mlp():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32), gluon.nn.Activation("relu"),
            gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 16)))
    return net


def test_dp8_matches_dp1():
    """Same data, same init: 8-way data parallel must track 1-device."""
    rs = onp.random.RandomState(0)
    x = nd.array(rs.uniform(-1, 1, (16, 16)).astype(onp.float32))
    y = nd.array(rs.randint(0, 4, (16,)), dtype="int32")

    losses = {}
    for ndev in (1, 8):
        mx.random.seed(7)
        net = _mlp()
        mesh = make_mesh({"dp": ndev}, devices=_devices(ndev))
        tr = DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                                 optimizer_params={"learning_rate": 0.1},
                                 mesh=mesh)
        losses[ndev] = [float(tr.step(x, y)) for _ in range(4)]
    onp.testing.assert_allclose(losses[1], losses[8], rtol=1e-4, atol=1e-5)
    assert losses[1][-1] < losses[1][0]


def test_tensor_parallel_training_matches_replicated():
    rs = onp.random.RandomState(1)
    x = nd.array(rs.uniform(-1, 1, (8, 16)).astype(onp.float32))
    y = nd.array(rs.randint(0, 4, (8,)), dtype="int32")

    losses = {}
    for mode in ("rep", "tp"):
        mx.random.seed(11)
        net = _mlp()
        if mode == "tp":
            from mxnet_tpu.parallel import column_parallel_spec, row_parallel_spec
            mesh = make_mesh({"dp": 2, "tp": 4}, devices=_devices(8))
            n = shard_params_megatron(net, axis="tp", rules={
                r"0\.weight$": column_parallel_spec("tp"),
                r"0\.bias$": P("tp"),
                r"2\.weight$": row_parallel_spec("tp"),
            })
            assert n > 0
        else:
            mesh = make_mesh({"dp": 2}, devices=_devices(2))
        tr = DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                                 optimizer_params={"learning_rate": 0.1},
                                 mesh=mesh)
        losses[mode] = [float(tr.step(x, y)) for _ in range(3)]
    onp.testing.assert_allclose(losses["rep"], losses["tp"], rtol=1e-4,
                                atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_blockwise(causal):
    n = 4
    mesh = make_mesh({"sp": n}, devices=_devices(n))
    rs = onp.random.RandomState(2)
    B, H, T, D = 2, 2, 64, 16
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)).astype(onp.float32))
    k = jnp.asarray(rs.normal(0, 1, (B, H, T, D)).astype(onp.float32))
    v = jnp.asarray(rs.normal(0, 1, (B, H, T, D)).astype(onp.float32))

    ref = blockwise_attention(q, k, v, causal=causal)

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=causal),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))
    out = jax.jit(ring)(q, k, v)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


def test_ulysses_attention_matches_blockwise():
    n = 2
    mesh = make_mesh({"sp": n}, devices=_devices(n))
    rs = onp.random.RandomState(3)
    B, H, T, D = 2, 4, 32, 8
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)).astype(onp.float32))
    k = jnp.asarray(rs.normal(0, 1, (B, H, T, D)).astype(onp.float32))
    v = jnp.asarray(rs.normal(0, 1, (B, H, T, D)).astype(onp.float32))
    ref = blockwise_attention(q, k, v)
    uly = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))
    out = jax.jit(uly)(q, k, v)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_topk_gating_capacity_and_slots():
    logits = jnp.asarray([[5.0, 0.0], [4.0, 0.0], [3.0, 0.0], [0.0, 5.0]])
    dispatch, combine = topk_gating(logits, top_k=1, capacity=2)
    d = onp.asarray(dispatch)
    # tokens 0,1 fill expert 0 slots 0,1; token 2 overflows (dropped)
    assert d[0, 0, 0] == 1 and d[1, 0, 1] == 1
    assert d[2].sum() == 0
    assert d[3, 1, 0] == 1
    c = onp.asarray(combine)
    assert c[0, 0, 0] > 0.9  # softmax prob of the chosen expert


def test_moe_ffn_runs_and_differentiable():
    rs = onp.random.RandomState(4)
    N, D, E, Hh = 32, 8, 4, 16
    x = jnp.asarray(rs.normal(0, 1, (N, D)).astype(onp.float32))
    gw = jnp.asarray(rs.normal(0, 0.5, (D, E)).astype(onp.float32))
    w1 = jnp.asarray(rs.normal(0, 0.5, (E, D, Hh)).astype(onp.float32))
    w2 = jnp.asarray(rs.normal(0, 0.5, (E, Hh, D)).astype(onp.float32))
    out = moe_ffn(x, gw, w1, w2, top_k=2, capacity_factor=4.0)
    assert out.shape == (N, D)
    g = jax.grad(lambda a, b, c, d: jnp.sum(moe_ffn(a, b, c, d, top_k=2,
                                                    capacity_factor=4.0) ** 2),
                 argnums=(0, 2, 3))(x, gw, w1, w2)
    assert all(float(jnp.abs(t).sum()) > 0 for t in g)


def test_expert_parallel_matches_dense():
    n = 4
    mesh = make_mesh({"ep": n}, devices=_devices(n))
    rs = onp.random.RandomState(5)
    N, D, E, Hh = 64, 8, 4, 16          # E == n -> 1 expert per device
    x = jnp.asarray(rs.normal(0, 1, (N, D)).astype(onp.float32))
    gw = jnp.asarray(rs.normal(0, 0.5, (D, E)).astype(onp.float32))
    w1 = jnp.asarray(rs.normal(0, 0.5, (E, D, Hh)).astype(onp.float32))
    w2 = jnp.asarray(rs.normal(0, 0.5, (E, Hh, D)).astype(onp.float32))

    # dense reference computed per token shard (same local capacity math)
    Nl = N // n
    ref_parts = [moe_ffn(x[i * Nl:(i + 1) * Nl], gw, w1, w2, top_k=1,
                         capacity_factor=float(E))  # capacity = Nl
                 for i in range(n)]
    ref = jnp.concatenate(ref_parts, axis=0)

    ep = shard_map(
        lambda x, gw, w1, w2: expert_parallel_moe(
            x, gw, w1, w2, axis_name="ep", top_k=1,
            capacity_factor=float(E)),
        mesh=mesh,
        in_specs=(P("ep", None), P(None, None), P("ep", None, None),
                  P("ep", None, None)),
        out_specs=P("ep", None))
    out = jax.jit(ep)(x, gw, w1, w2)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


def test_load_balancing_loss_bounds():
    rs = onp.random.RandomState(6)
    logits = jnp.asarray(rs.normal(0, 1, (128, 8)).astype(onp.float32))
    lb = float(load_balancing_loss(logits))
    assert lb >= 0.9  # >= 1 at perfect balance, higher when skewed
    skewed = jnp.zeros((128, 8)).at[:, 0].set(10.0)
    assert float(load_balancing_loss(skewed)) > lb


def test_dp_sp_combined_trainer_step():
    """dp x sp mesh: batch AND sequence sharded in the fused step."""
    from mxnet_tpu.models import bert_tiny
    mesh = make_mesh({"dp": 2, "sp": 2}, devices=_devices(4))
    net = bert_tiny(vocab_size=64)
    net.initialize()

    def loss_fn(logits, labels):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    tr = DataParallelTrainer(net, loss_fn, optimizer="adam",
                             optimizer_params={"learning_rate": 1e-3},
                             mesh=mesh, data_spec=P("dp", "sp"))
    rs = onp.random.RandomState(7)
    x = nd.array(rs.randint(0, 64, (4, 32)), dtype="int32")
    y = nd.array(rs.randint(0, 64, (4, 32)), dtype="int32")
    l0 = float(tr.step(x, y))
    l1 = float(tr.step(x, y))
    assert onp.isfinite([l0, l1]).all()


def test_run_steps_matches_single_steps():
    """On-device scan training loop == n sequential fused steps."""
    mesh = make_mesh({"dp": 1}, devices=_devices(1))
    rs = onp.random.RandomState(9)
    x = nd.array(rs.uniform(-1, 1, (8, 16)).astype(onp.float32))
    y = nd.array(rs.randint(0, 4, (8,)), dtype="int32")

    mx.random.seed(21)
    net1 = _mlp()
    tr1 = DataParallelTrainer(net1, _loss_fn, optimizer="adam",
                              optimizer_params={"learning_rate": 1e-2},
                              mesh=mesh)
    singles = [float(tr1.step(x, y)) for _ in range(4)]

    mx.random.seed(21)
    net2 = _mlp()
    tr2 = DataParallelTrainer(net2, _loss_fn, optimizer="adam",
                              optimizer_params={"learning_rate": 1e-2},
                              mesh=mesh)
    multi = tr2.run_steps(x, y, 4)
    onp.testing.assert_allclose(singles, onp.asarray(multi), rtol=1e-4,
                                atol=1e-5)
    assert tr2._t == 4
    # stacked per-step batches also run
    xs = nd.array(rs.uniform(-1, 1, (2, 8, 16)).astype(onp.float32))
    ys = nd.array(rs.randint(0, 4, (2, 8)), dtype="int32")
    out = tr2.run_steps(xs, ys, 2, stacked=True)
    assert out.shape == (2,) and onp.isfinite(onp.asarray(out)).all()


def test_compressed_dp_tracks_uncompressed():
    """2-bit gradient compression + error feedback inside the fused step
    (reference src/kvstore/gradient_compression.cc:60): compressed training
    must converge and track the uncompressed loss curve within tolerance."""
    rs = onp.random.RandomState(3)
    w_true = rs.uniform(-1, 1, (16, 4)).astype(onp.float32)
    xs = rs.uniform(-1, 1, (32, 16)).astype(onp.float32)
    ys = onp.argmax(xs @ w_true + 0.05 * rs.randn(32, 4), axis=1)
    x = nd.array(xs)
    y = nd.array(ys.astype(onp.int64), dtype="int32")

    curves = {}
    for mode in ("plain", "compressed"):
        mx.random.seed(21)
        net = _mlp()
        mesh = make_mesh({"dp": 8}, devices=_devices(8))
        comp = {"type": "2bit", "threshold": 0.01} \
            if mode == "compressed" else None
        tr = DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                                 optimizer_params={"learning_rate": 0.5},
                                 mesh=mesh, compression=comp)
        curves[mode] = [float(tr.step(x, y)) for _ in range(80)]

    plain, comp = curves["plain"], curves["compressed"]
    assert comp[-1] < comp[0] * 0.45, f"compressed did not converge: {comp}"
    # error feedback keeps the compressed curve near the exact one
    assert abs(comp[-1] - plain[-1]) < 0.4 * plain[0], (plain, comp)


def test_compressed_dp_quantizes_gradients():
    """With a huge threshold every quantized gradient is 0 — weights must
    stay exactly unchanged while residuals accumulate (proves the collective
    carries the quantized tensor, not the raw gradient)."""
    rs = onp.random.RandomState(4)
    x = nd.array(rs.uniform(-1, 1, (16, 16)).astype(onp.float32))
    y = nd.array(rs.randint(0, 4, (16,)), dtype="int32")
    mx.random.seed(5)
    net = _mlp()
    mesh = make_mesh({"dp": 8}, devices=_devices(8))
    tr = DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.5},
                             mesh=mesh,
                             compression={"type": "2bit", "threshold": 1e6})
    before = [onp.asarray(w) for w in tr._params_raw]
    tr.step(x, y)
    tr.step(x, y)
    for b, a in zip(before, tr._params_raw):
        onp.testing.assert_allclose(onp.asarray(a), b)
    assert any(float(jnp.abs(r).max()) > 0 for r in tr._comp_resid)


def test_compression_rejects_tensor_parallel():
    mx.random.seed(6)
    net = _mlp()
    from mxnet_tpu.parallel import column_parallel_spec, row_parallel_spec
    mesh = make_mesh({"dp": 2, "tp": 4}, devices=_devices(8))
    n = shard_params_megatron(net, axis="tp", rules={
        r"0\.weight$": column_parallel_spec("tp"),
        r"0\.bias$": P("tp"),
        r"2\.weight$": row_parallel_spec("tp"),
    })
    assert n > 0
    with pytest.raises(mx.MXNetError):
        DataParallelTrainer(net, _loss_fn, mesh=mesh,
                            compression={"type": "2bit", "threshold": 0.5})


def test_fused_trainer_updates_bn_running_stats():
    """BN running stats (aux) must accumulate through the fused step's
    param carry and reach the gluon Parameters on sync() — otherwise any
    eval after fused training uses init stats and is garbage."""
    rs = onp.random.RandomState(9)
    mx.random.seed(31)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8), gluon.nn.BatchNorm(), gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 6)))
    mesh = make_mesh({"dp": 1}, devices=_devices(1))
    tr = DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.05},
                             mesh=mesh)
    # input with a strongly nonzero mean so running_mean must move
    x = nd.array((rs.randn(16, 6) + 5.0).astype(onp.float32))
    y = nd.array(rs.randint(0, 4, (16,)), dtype="int32")
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()
              if "running" in k}
    assert before, "net has no BN running stats?"
    for _ in range(5):
        tr.step(x, y)
    tr.sync()
    moved = False
    for k, p in net.collect_params().items():
        if "running" in k:
            moved = moved or not onp.allclose(p.data().asnumpy(), before[k])
    assert moved, "running stats never updated through the fused trainer"


def test_compressed_fp16_overflow_does_not_poison_residuals():
    """An overflow (non-finite) step under float16 loss scaling must roll
    back the error-feedback residuals too: a NaN residual would make the
    quantizer emit 0 forever, silently freezing that parameter (advisor
    round-2 medium finding)."""
    rs = onp.random.RandomState(11)
    xs = rs.uniform(-1, 1, (16, 16)).astype(onp.float32)
    ys = rs.randint(0, 4, (16,))
    x = nd.array(xs)
    y = nd.array(ys, dtype="int32")
    x_bad = nd.array(onp.where(onp.arange(16)[:, None] == 0, onp.nan,
                               xs).astype(onp.float32))
    mx.random.seed(13)
    net = _mlp()
    mesh = make_mesh({"dp": 8}, devices=_devices(8))
    tr = DataParallelTrainer(net, _loss_fn, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.2},
                             mesh=mesh, dtype="float16",
                             compression={"type": "2bit", "threshold": 0.01})
    tr.step(x, y)
    resid_before = [onp.asarray(r).copy() for r in tr._comp_resid]
    w_before = [onp.asarray(w).copy() for w in tr._params_raw]
    tr.step(x_bad, y)  # overflow step: grads are NaN
    # weights AND residuals rolled back — nothing NaN anywhere
    for b, a in zip(w_before, tr._params_raw):
        onp.testing.assert_array_equal(onp.asarray(a), b)
    for b, a in zip(resid_before, tr._comp_resid):
        arr = onp.asarray(a)
        assert onp.isfinite(arr).all(), "residual poisoned by overflow step"
        onp.testing.assert_array_equal(arr, b)
    # training continues to make progress afterwards
    losses = [float(tr.step(x, y)) for _ in range(30)]
    assert onp.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_over_attention_heads_matches_replicated(tp):
    """Head-sharded attention (VERDICT r4 ask #3): BertModel with
    head-major fused QKV, column-sharded by head groups + row-sharded
    output projection under 'tp', must track replicated training."""
    from mxnet_tpu.models.bert import BertModel

    V, B, T = 64, 8, 16
    rs = onp.random.RandomState(5)
    x = nd.array(rs.randint(0, V, (B, T)), dtype="int32")
    y = nd.array(rs.randint(0, V, (B, T)), dtype="int32")

    def loss_fn(logits, labels):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    losses = {}
    for mode in ("rep", "tp"):
        mx.random.seed(21)
        net = BertModel(vocab_size=V, num_layers=2, units=32, hidden_size=64,
                        num_heads=4, max_length=T, dropout=0.0,
                        head_major_qkv=True)
        net.initialize()
        net(x)
        if mode == "tp":
            mesh = make_mesh({"dp": 8 // tp, "tp": tp}, devices=_devices(8))
            n = shard_params_megatron(net, axis="tp")
            assert n > 0
        else:
            mesh = make_mesh({"dp": 2}, devices=_devices(2))
        tr = DataParallelTrainer(net, loss_fn, optimizer="sgd",
                                 optimizer_params={"learning_rate": 0.2,
                                                   "wd": 0.0},
                                 mesh=mesh)
        losses[mode] = [float(tr.step(x, y)) for _ in range(3)]
    onp.testing.assert_allclose(losses["rep"], losses["tp"], rtol=2e-4,
                                atol=2e-5)
    assert losses["rep"][-1] < losses["rep"][0]
