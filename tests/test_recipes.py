"""Large-model recipes (mxnet_tpu/recipes): expert-parallel MoE and
long-context training as first-class parity-tested workloads.

Every trainer test runs real cross-device collectives on the 8 virtual CPU
devices (conftest XLA_FLAGS); the parity oracles pin the recipes' central
claims — E=1 MoE == dense FFN, ep4 == ep1, ring attention == dense
attention — as 10-step loss trajectories, not single forwards.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry as telem
from mxnet_tpu.parallel import moe as pmoe
from mxnet_tpu.parallel import zero as pzero
from mxnet_tpu.parallel.mesh import make_mesh, P
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
from mxnet_tpu.recipes import get_recipe, list_recipes, Recipe
from mxnet_tpu.recipes import moe as rmoe
from mxnet_tpu.recipes import long_context as rlc


def _mesh(axes):
    return make_mesh(axes, devices=jax.devices("cpu")[:8])


def _lm_batch(seed, bs=16, T=8, vocab=64):
    rs = np.random.RandomState(seed)
    x = rs.randint(0, vocab, size=(bs, T)).astype(np.int32)
    y = rs.randint(0, vocab, size=(bs, T)).astype(np.int32)
    return x, y


def _losses(trainer, x, y, n):
    return [float(trainer.step(mx.nd.array(x), mx.nd.array(y)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_recipe_registry():
    assert sorted(list_recipes()) == ["long_context", "moe"]
    for name in list_recipes():
        r = get_recipe(name)
        assert isinstance(r, Recipe) and r.name == name
        assert callable(r.build_model) and callable(r.build_trainer) \
            and callable(r.build_oracle)
    with pytest.raises(KeyError):
        get_recipe("nope")


# ---------------------------------------------------------------------------
# gating semantics (satellite: capacity overflow + deterministic tie-break)
# ---------------------------------------------------------------------------

def test_topk_gating_overflow_exact_slots():
    """Capacity slots are claimed in TOKEN order; overflow tokens get an
    all-zero dispatch row AND zero combine weight."""
    logits = jnp.asarray([[9.0, 0.0]] * 5)  # all 5 tokens pick expert 0
    dispatch, combine = pmoe.topk_gating(logits, top_k=1, capacity=3)
    d, c = np.asarray(dispatch), np.asarray(combine)
    for n in range(3):                       # first three tokens, slots 0..2
        assert d[n, 0, n] == 1.0 and d[n].sum() == 1.0
    for n in (3, 4):                         # overflow: dropped entirely
        assert d[n].sum() == 0.0 and c[n].sum() == 0.0
    assert int(pmoe.dropped_tokens(dispatch, 5, 1)) == 2


def test_moe_ffn_drops_overflow_rows():
    """Dropped tokens produce exact-zero output rows in moe_ffn (combine
    weight 0), and the reported count matches the zero-row count."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(np.abs(rs.normal(size=(5, 4))).astype(np.float32) + 0.1)
    gate_w = jnp.zeros((4, 2), jnp.float32).at[:, 0].set(1.0)
    w1 = jnp.asarray(rs.normal(size=(2, 4, 8)).astype(np.float32))
    w2 = jnp.asarray(rs.normal(size=(2, 8, 4)).astype(np.float32))
    # all-positive x routes every token to expert 0; capacity is
    # max(1, int(0.6 * 5 * 1 / 2)) = 1 slot, so 4 of 5 tokens drop
    y, aux = pmoe.moe_ffn(x, gate_w, w1, w2, top_k=1, capacity_factor=0.6,
                          return_aux=True)
    y = np.asarray(y)
    assert int(aux["dropped"]) == 4
    zero_rows = [n for n in range(5) if np.all(y[n] == 0.0)]
    assert len(zero_rows) == 4 and 0 not in zero_rows


def test_topk_gating_tie_break_deterministic():
    """Documented contract: lax.top_k resolves ties to the LOWER expert
    index, and repeated evaluation is bitwise identical."""
    logits = jnp.zeros((6, 4), jnp.float32)   # all-tied logits
    d1, c1 = pmoe.topk_gating(logits, top_k=2, capacity=6)
    d2, c2 = pmoe.topk_gating(logits, top_k=2, capacity=6)
    assert np.array_equal(np.asarray(d1), np.asarray(d2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    # every token lands on experts 0 and 1 (lowest indices win the tie)
    assigned = np.asarray(jnp.sum(d1, axis=2))  # (N, E)
    assert np.all(assigned[:, :2] == 1.0) and np.all(assigned[:, 2:] == 0.0)


def test_load_balance_loss_uniform_minimum():
    """Switch aux loss: E * sum(f * p) == 1 exactly at perfectly uniform
    routing, ~E when fully skewed; gradient flows through probs only."""
    E, N = 4, 16
    probs_u = jnp.full((N, E), 1.0 / E)
    disp_u, _ = pmoe.topk_gating(jnp.tile(jnp.eye(E), (N // E, 1)) * 5.0,
                                 1, N)
    assert abs(float(pmoe.load_balance_loss(probs_u, disp_u)) - 1.0) < 1e-6
    logits_skew = jnp.zeros((N, E)).at[:, 0].set(20.0)
    probs_s = jax.nn.softmax(logits_skew, axis=-1)
    disp_s, _ = pmoe.topk_gating(logits_skew, 1, N)
    assert float(pmoe.load_balance_loss(probs_s, disp_s)) > 3.0
    g = jax.grad(lambda p: pmoe.load_balance_loss(p, disp_s))(probs_s)
    assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# wire all_to_all (satellite: round-trip permutation + byte accounting)
# ---------------------------------------------------------------------------

def _shard_map_ep8(fn, *args):
    mesh = _mesh({"ep": 8})
    sm = pzero.shard_map_compat(fn, mesh, in_specs=(P("ep"),) * len(args),
                                out_specs=P("ep"))
    return sm(*args)


@pytest.mark.parametrize("comm", [None, "bfloat16", "int8"])
def test_wire_all_to_all_roundtrip_permutation(comm):
    """a2a twice over the same axis is the identity permutation — every
    row returns home (bf16/int8 wires round-trip within quantization)."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.normal(0, 1, (64, 16)).astype(np.float32))

    def body(xl):
        once = pmoe.wire_all_to_all(xl, "ep", comm)
        return pmoe.wire_all_to_all(once, "ep", comm)

    back = np.asarray(_shard_map_ep8(body, x))
    tol = 0.0 if comm is None else (0.08 if comm == "int8" else 0.04)
    np.testing.assert_allclose(back, np.asarray(x), atol=tol)


def test_wire_all_to_all_is_permutation_of_rows():
    """One a2a conserves the multiset of rows (bytes conserved, only
    placement changes): sorted rows before == sorted rows after."""
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.normal(0, 1, (64, 8)).astype(np.float32))
    once = np.asarray(_shard_map_ep8(
        lambda xl: pmoe.wire_all_to_all(xl, "ep", None), x))
    np.testing.assert_array_equal(np.sort(np.asarray(x), axis=0),
                                  np.sort(once.reshape(64, 8), axis=0))


def test_wire_all_to_all_vjp_is_transpose():
    """The custom VJP routes cotangents back through the inverse exchange:
    grad of <a2a(x), c> w.r.t. x equals a2a(c) (self-transpose block
    permutation)."""
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.normal(0, 1, (64, 4)).astype(np.float32))
    c = jnp.asarray(rs.normal(0, 1, (64, 4)).astype(np.float32))

    def body(xl, cl):
        g = jax.grad(
            lambda t: jnp.sum(pmoe.wire_all_to_all(t, "ep", None) * cl))(xl)
        return g - pmoe.wire_all_to_all(cl, "ep", None)

    diff = np.asarray(_shard_map_ep8(body, x, c))
    np.testing.assert_allclose(diff, 0.0, atol=1e-6)


def test_all_to_all_wire_bytes_accounting():
    cap = pmoe.moe_capacity(64, 2, 1.5, 8)            # int(1.5*64*2/8) = 24
    assert cap == 24
    elems = 8 * cap * 16                              # E * C * D
    common = dict(n_experts=8, top_k=2, capacity_factor=1.5)
    # f32: 4 B/elem, (ep-1)/ep of the payload crosses the wire
    assert pmoe.all_to_all_wire_bytes(64, 16, ep=4, **common) \
        == elems * 4 * 3 // 4
    assert pmoe.all_to_all_wire_bytes(64, 16, ep=4, comm_dtype="bfloat16",
                                      **common) == elems * 2 * 3 // 4
    # int8: 1 B/elem plus one f32 scale per outbound row
    assert pmoe.all_to_all_wire_bytes(64, 16, ep=4, comm_dtype="int8",
                                      **common) == elems * 3 // 4 + 4 * 4
    # no expert parallelism, no wire
    assert pmoe.all_to_all_wire_bytes(64, 16, ep=1, **common) == 0


def test_expert_sharded_moe_matches_single_device():
    """ep-sharded expert_parallel_moe == single-device moe_ffn on the same
    token shard: distributing the experts over 8 devices must not change
    any token's output."""
    rs = np.random.RandomState(6)
    E, D, H = 8, 16, 32
    x = jnp.asarray(rs.normal(0, 1, (64, D)).astype(np.float32))
    gate_w = jnp.asarray(rs.normal(0, 0.3, (D, E)).astype(np.float32))
    w1 = jnp.asarray(rs.normal(0, 0.3, (E, D, H)).astype(np.float32))
    w2 = jnp.asarray(rs.normal(0, 0.3, (E, H, D)).astype(np.float32))
    mesh = _mesh({"ep": 8})
    sm = pzero.shard_map_compat(
        lambda xl, w1l, w2l: pmoe.expert_parallel_moe(
            xl, gate_w, w1l, w2l, axis_name="ep", top_k=2,
            capacity_factor=2.0),
        mesh, in_specs=(P("ep"), P("ep"), P("ep")), out_specs=P("ep"))
    y_ep = np.asarray(sm(x, w1, w2))
    for d in range(8):                       # each device's 8-token shard
        xs = x[d * 8:(d + 1) * 8]
        y_ref = np.asarray(pmoe.moe_ffn(xs, gate_w, w1, w2, top_k=2,
                                        capacity_factor=2.0))
        np.testing.assert_allclose(y_ep[d * 8:(d + 1) * 8], y_ref,
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# MoE recipe trainer parity
# ---------------------------------------------------------------------------

def test_moe_e1_matches_dense_oracle_10_steps():
    """E=1/top_k=1 degenerate gating: normalize_gates makes the combine
    weight exactly 1 (g/g), so the MoE recipe must track the dense-FFN
    oracle's full 10-step loss trajectory (aux weight 0 — the E=1 aux
    loss is the constant 1)."""
    r = get_recipe("moe")
    mx.random.seed(101)
    net_moe = r.build_model(vocab_size=64, num_experts=1, top_k=1)
    mx.random.seed(101)
    net_dense = r.build_oracle(vocab_size=64, num_experts=1, top_k=1)
    tr_moe = rmoe.MoETrainer(net_moe, rmoe.token_cross_entropy,
                             optimizer="adam",
                             optimizer_params={"learning_rate": 1e-2},
                             mesh=_mesh({"dp": 8, "ep": 1}),
                             aux_loss_weight=0.0)
    tr_dense = DataParallelTrainer(
        net_dense, rmoe.token_cross_entropy, optimizer="adam",
        optimizer_params={"learning_rate": 1e-2},
        mesh=_mesh({"dp": 8}), zero_update=True)
    x, y = _lm_batch(7)
    la = _losses(tr_moe, x, y, 10)
    lb = _losses(tr_dense, x, y, 10)
    np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-4)
    assert la[-1] < la[0]                     # and it actually learns


def test_moe_ep4_matches_ep1_trajectory():
    """Expert parallelism is a layout, not a model change: ep4 and ep1
    runs of the same net/seed/batch produce the same loss trajectory."""
    r = get_recipe("moe")
    mx.random.seed(55)
    net_a = r.build_model(vocab_size=64, num_experts=4, top_k=1)
    mx.random.seed(55)
    net_b = r.build_model(vocab_size=64, num_experts=4, top_k=1)
    tr_a = r.build_trainer(net_a, _mesh({"dp": 2, "ep": 4}))
    tr_b = r.build_trainer(net_b, _mesh({"dp": 8, "ep": 1}))
    x, y = _lm_batch(8)
    la = _losses(tr_a, x, y, 10)
    lb = _losses(tr_b, x, y, 10)
    np.testing.assert_allclose(la, lb, rtol=2e-4, atol=2e-4)


def test_moe_dropped_tokens_and_comm_telemetry():
    """Dropped-token counts ride device handles to drain() (no per-step
    sync) and land on mx_moe_dropped_tokens_total; ep>1 steps book the
    all_to_all dispatch/combine wire bytes exactly."""
    telem.reset()
    telem.enable()
    try:
        r = get_recipe("moe")
        net = r.build_model(vocab_size=64, num_experts=4, top_k=1,
                            capacity_factor=0.25)   # starved capacity
        tr = r.build_trainer(net, _mesh({"dp": 2, "ep": 4}))
        x, y = _lm_batch(9)
        _losses(tr, x, y, 2)
        tr.drain()
        assert telem.counter("mx_moe_dropped_tokens_total").get("moe") > 0
        a2a = telem.counter("mx_comm_bytes_total").get(
            "all_to_all", "mesh")
        per_step = sum(
            4 * pmoe.all_to_all_wire_bytes(
                x.size // 8, cell._units, n_experts=cell._num_experts,
                top_k=cell._top_k, capacity_factor=cell._capacity_factor,
                ep=4, comm_dtype=tr._comm_dtype)
            for cell in rmoe._moe_cells(net))
        assert per_step > 0 and a2a == 2 * per_step
    finally:
        telem.reset()
        telem.disable()


def test_moe_program_captures_step_cost():
    """The fused step is a StepProgram artifact with cost_analysis FLOPs
    captured for the roofline ledger."""
    telem.reset()
    telem.enable()
    try:
        r = get_recipe("moe")
        net = r.build_model(vocab_size=64, num_experts=4, top_k=1)
        tr = r.build_trainer(net, _mesh({"dp": 2, "ep": 4}))
        x, y = _lm_batch(10)
        _losses(tr, x, y, 1)
        tr.drain()
        costs = list(tr._program._costs.values())
        assert costs and any(c.get("flops", 0) > 0 for c in costs)
    finally:
        telem.reset()
        telem.disable()


def test_moe_elastic_kill_and_resume_with_ep_reshard():
    """Snapshot at step 3, resume on (a) the same dp2xep4 mesh and (b) a
    resharded dp4xep2 mesh: both must continue with the interrupted run's
    exact losses (expert leaves re-laid-out across ep degrees)."""
    from mxnet_tpu.elastic import state as es
    r = get_recipe("moe")
    mx.random.seed(77)
    net = r.build_model(vocab_size=64, num_experts=4, top_k=1)
    tr = r.build_trainer(net, _mesh({"dp": 2, "ep": 4}))
    x, y = _lm_batch(11)
    _losses(tr, x, y, 3)
    tr.drain()
    snap = es.capture(tr)
    host = {k: np.asarray(v) for k, v in snap["leaves"].items()}
    baseline = _losses(tr, x, y, 3)          # the uninterrupted run
    for axes in ({"dp": 2, "ep": 4}, {"dp": 4, "ep": 2}):
        mx.random.seed(999)                  # resume must NOT depend on this
        net2 = r.build_model(vocab_size=64, num_experts=4, top_k=1)
        tr2 = r.build_trainer(net2, _mesh(axes))
        es.install(tr2, snap["meta"], lambda n: host[n], set(host))
        assert tr2._t == 3
        resumed = _losses(tr2, x, y, 3)
        np.testing.assert_allclose(resumed, baseline, rtol=2e-4, atol=2e-4,
                                   err_msg=f"resume diverged on {axes}")


def test_moe_trainer_rejects_unsuitable_nets():
    from mxnet_tpu.base import MXNetError
    net = mx.models.mlp()
    net.initialize(ctx=mx.cpu())
    with pytest.raises(MXNetError, match="_is_moe_expert"):
        rmoe.MoETrainer(net, rmoe.token_cross_entropy,
                        mesh=_mesh({"dp": 4, "ep": 2}))
    r = get_recipe("moe")
    moe_net = r.build_model(vocab_size=64, num_experts=4)
    with pytest.raises(MXNetError, match="divisible"):
        rmoe.MoETrainer(moe_net, rmoe.token_cross_entropy,
                        mesh=_mesh({"dp": 1, "ep": 8}))  # 4 experts, ep=8


# ---------------------------------------------------------------------------
# long-context recipe
# ---------------------------------------------------------------------------

def test_long_context_env_default(monkeypatch):
    assert rlc.default_seq_len() == 32768
    monkeypatch.setenv("MXNET_TPU_LONG_CONTEXT_SEQ", "4096")
    assert rlc.default_seq_len() == 4096
    net = rlc.LongContextLM(32, num_layers=1, units=16, hidden_size=32,
                            num_heads=1)
    assert net._max_length == 4096


def test_token_windows_chunking():
    toks = np.arange(0, 1000, dtype=np.int32)
    src = rlc.TokenWindows(toks, batch_size=3, seq_len=8)
    assert len(src) == (1000 - 1) // 24
    batches = list(src)
    assert len(batches) == len(src)           # re-iterable, exact count
    x0, y0 = batches[0]
    assert x0.shape == (3, 8) and y0.shape == (3, 8)
    np.testing.assert_array_equal(y0.ravel(), x0.ravel() + 1)  # next-token
    with pytest.raises(Exception):
        rlc.TokenWindows(np.arange(5), batch_size=4, seq_len=8)


def test_long_context_flash_matches_dense_oracle():
    """Model-level parity: the flash/blockwise attention path vs the dense
    O(T^2) oracle, identical weights."""
    r = get_recipe("long_context")
    mx.random.seed(13)
    flash_net = r.build_model(vocab_size=64, seq_len=256, num_layers=1,
                              units=32, hidden_size=64, num_heads=2)
    oracle = r.build_oracle(vocab_size=64, seq_len=256, num_layers=1,
                            units=32, hidden_size=64, num_heads=2)
    src, dst = flash_net.collect_params(), oracle.collect_params()
    assert len(src.keys()) == len(dst.keys())
    for a, b in zip(src.keys(), dst.keys()):
        dst[b]._data._set_data(np.asarray(src[a].data()._data))
    x, _ = _lm_batch(14, bs=2, T=256)
    out_f = flash_net(mx.nd.array(x)).asnumpy()
    out_d = oracle(mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(out_f, out_d, rtol=2e-4, atol=2e-4)


def test_long_context_sp4_matches_sp1_trajectory():
    """Ring attention + sequence sharding is a layout, not a model change:
    dp2xsp4 and dp8xsp1 trajectories agree (global causal positions, fused
    grad normalization across both axes)."""
    r = get_recipe("long_context")
    mx.random.seed(21)
    net_a = r.build_model(vocab_size=64, seq_len=64, num_layers=1, units=32,
                          hidden_size=64, num_heads=2)
    mx.random.seed(21)
    net_b = r.build_model(vocab_size=64, seq_len=64, num_layers=1, units=32,
                          hidden_size=64, num_heads=2)
    tr_a = r.build_trainer(net_a, _mesh({"dp": 2, "sp": 4}))
    tr_b = r.build_trainer(net_b, _mesh({"dp": 8, "sp": 1}))
    x, y = _lm_batch(22, bs=8, T=32)
    la = _losses(tr_a, x, y, 10)
    lb = _losses(tr_b, x, y, 10)
    np.testing.assert_allclose(la, lb, rtol=2e-4, atol=2e-4)
    assert la[-1] < la[0]


def test_long_context_feed_and_ring_telemetry():
    """TokenWindows -> DeviceFeed -> trainer end to end; sp>1 books the
    ring ppermute wire bytes."""
    telem.reset()
    telem.enable()
    try:
        r = get_recipe("long_context")
        net = r.build_model(vocab_size=64, seq_len=64, num_layers=1,
                            units=32, hidden_size=64, num_heads=2)
        tr = r.build_trainer(net, _mesh({"dp": 2, "sp": 4}))
        toks = np.random.RandomState(23).randint(
            0, 64, size=4 * 32 * 3 + 1).astype(np.int32)
        feed = rlc.make_feed(rlc.TokenWindows(toks, 4, 32), tr)
        try:
            for _, (xb, yb) in zip(range(2), feed):
                tr.step(xb, yb)
        finally:
            feed.close()
        tr.drain()
        assert telem.counter("mx_comm_bytes_total").get(
            "ppermute", "mesh") > 0
    finally:
        telem.reset()
        telem.disable()


def test_long_context_32k_blockwise_no_oom():
    """The >=32k enabler: blockwise attention at the recipe's default
    sequence length runs on CPU in O(T*block) memory (the dense T^2
    scores tensor would be 4 GiB in f32)."""
    T = rlc.default_seq_len()
    assert T >= 32768
    rs = np.random.RandomState(31)
    q = jnp.asarray(rs.normal(0, 1, (1, 1, T, 8)).astype(np.float32))
    k = jnp.asarray(rs.normal(0, 1, (1, 1, T, 8)).astype(np.float32))
    v = jnp.asarray(rs.normal(0, 1, (1, 1, T, 8)).astype(np.float32))
    from mxnet_tpu.ops.attention import blockwise_attention
    out = blockwise_attention(q, k, v, causal=True, block_size=1024)
    out.block_until_ready()
    assert out.shape == (1, 1, T, 8)
    assert np.isfinite(np.asarray(out[0, 0, ::4096])).all()
