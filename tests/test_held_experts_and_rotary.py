"""PR 32's two new ops, on the CPU at toy sizes: the dropless expert layer
of a chip that holds a share of a layer's experts (`parallel/moe.py:
held_moe_ffn`, the op `_contrib_held_moe_ffn`) and rotary positions
(`ops/rotary.py`), each against its equations written out here; and the
attention block that uses them, on its two routes."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import moe

N, D, F, E, HELD, K = 96, 32, 16, 32, 4, 5


def _layer(seed, n=N, held=HELD):
    rs = np.random.RandomState(seed)
    arr = lambda scale, *s: jnp.asarray(rs.normal(0, scale, s)
                                        .astype(np.float32))
    return (arr(1, n, D), arr(1, E, D), arr(0.2, held, D, 2 * F),
            arr(0.2, held, F, D))


def dense_over_held(x, router_w, w_gate_up, w_down, first=0, scaling=2.5,
                    k=K):
    """The equations, expert by expert over every row."""
    p = jax.nn.softmax(x @ router_w.T, axis=-1)
    vals, idx = jax.lax.top_k(p, k)
    w = scaling * vals / vals.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(w_gate_up.shape[0]):
        c = jnp.sum(jnp.where(idx == e + first, w, 0.0), axis=-1)
        gu = x @ w_gate_up[e]
        out = out + c[:, None] * ((jax.nn.silu(gu[:, :F]) * gu[:, F:])
                                  @ w_down[e])
    return out


def _held(*a, **kw):
    kw = dict(dict(top_k=K, published_experts=E, scaling=2.5), **kw)
    return moe.held_moe_ffn(*a, **kw)


def _planted(seed, bias):
    """A layer whose routing leans towards the held experts by `bias`: the
    tokens share a direction (as they do at a random draw already) and the
    held experts' router rows point along it."""
    x, router_w, w_gate_up, w_down = _layer(seed)
    return (jnp.abs(x), router_w.at[:HELD].add(bias / D), w_gate_up, w_down)


@pytest.mark.parametrize("bias,exact", [(0.0, False), (1.5, False),
                                        (3.0, False), (8.0, True),
                                        (40.0, True)])
def test_held_experts_against_the_equations(bias, exact):
    """Whether the row buffer holds the step's assignments (the sorted
    path) or the routing sends more (the dense path): the same sums and the
    same gradients."""
    args = _planted(0, bias)
    y, aux = _held(*args, return_aux=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(dense_over_held(*args)),
                               rtol=2e-5, atol=2e-6)
    assert bool(aux["exact"]) is exact
    assert int(aux["kept"]) == round(float(aux["mean_load"]) * HELD)
    assert int(aux["max_load"]) >= float(aux["mean_load"])
    grads = jax.grad(lambda *a: jnp.sum(_held(*a) ** 2),
                     argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense_over_held(*a) ** 2),
                    argnums=(0, 1, 2, 3))(*args)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("bias,kept,exact", [(0.0, 28, False),
                                             (1.5, 43, False),
                                             (3.0, 66, False),
                                             (8.0, 157, True)])
def test_the_buffer_follows_the_routing(bias, kept, exact):
    """Uniform routing sends 96 x 5 x 4 / 32 = 60 assignments here; the
    buffer is half again that, rounded up to a power of two (128 rows of a
    bound of 384). It takes what it holds, the dense path the rest."""
    args = _planted(5, bias)
    assert moe.held_rows(N, K, HELD, E) == (128, N * HELD)
    _, aux = _held(*args, return_aux=True)
    assert abs(int(aux["kept"]) - kept) <= 2 and bool(aux["exact"]) is exact


# -- the grouped products stop at `kept` (PR 35) ----------------------------------------

def _swiglu_rows(rows, w_gate_up, w_down, counts, w_row):
    """`grouped_swiglu`'s equations, expert by expert, over the rows that
    belong to one; zeros behind them."""
    f32 = jnp.float32
    out, lo = jnp.zeros(rows.shape, f32), 0
    for e, n in enumerate(counts):
        gu = rows[lo:lo + n].astype(f32) @ w_gate_up[e].astype(f32)
        f = gu.shape[1] // 2
        y = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down[e].astype(f32)
        out = out.at[lo:lo + n].set(w_row[lo:lo + n, None] * y)
        lo += n
    return out


_GROUPS = {                      # 64 rows, 4 experts, row tiles of 16
    "well_under_the_buffer": (5, 9, 3, 7),
    "an_expert_with_no_row": (20, 0, 17, 0),
    "no_row_at_all": (0, 0, 0, 0),
    "one_expert_with_every_row": (0, 0, 41, 0),
    "edges_off_the_tile": (17, 13, 1, 30),
    "edges_on_the_tile": (16, 0, 32, 16),
    "the_buffer_full": (23, 9, 31, 1),
    "one_tile_for_all": (2, 1, 1, 3),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("counts", _GROUPS.values(), ids=_GROUPS.keys())
def test_grouped_swiglu_against_its_equations(monkeypatch, counts, dtype):
    """The kernels, in interpret mode, on a buffer whose rows past `kept`
    are NaN going in: the kept rows and every gradient as the equations
    give them, the weights' gradients of an expert without a row zero, and
    no NaN in anything that is summed over rows."""
    from mxnet_tpu.ops.pallas import grouped_matmul as gm
    monkeypatch.setattr(gm, "_ROW_TILE", 16)
    rows, d, f, kept = 64, 32, 16, sum(counts)
    rs = np.random.RandomState(kept)
    arr = lambda scale, *s: jnp.asarray(rs.normal(0, scale, s), dtype)
    x, w_gu, w_d = arr(1, rows, d), arr(0.3, 4, d, 2 * f), arr(0.3, 4, f, d)
    w_row = jnp.asarray(rs.uniform(0.2, 1, rows), jnp.float32)
    t = jnp.asarray(rs.normal(0, 1, (rows, d)), jnp.float32)
    live = (jnp.arange(rows) < kept)
    poison = lambda a: jnp.where(live.reshape((-1,) + (1,) * (a.ndim - 1)),
                                 a, jnp.nan)

    def got(x, w_gu, w_d, w_row):
        z = gm.grouped_swiglu(poison(x), w_gu, w_d,
                              jnp.asarray(counts, jnp.int32), poison(w_row),
                              True)
        return jnp.where(live[:, None], z, 0).astype(jnp.float32)

    def want(x, w_gu, w_d, w_row):
        return _swiglu_rows(x, w_gu, w_d, counts, w_row)

    args = (x, w_gu, w_d, w_row)
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(got(*args)),
                               np.asarray(want(*args)), **tol)
    grads = jax.grad(lambda *a: jnp.sum(got(*a) * t), argnums=(0, 1, 2, 3))
    wants = jax.grad(lambda *a: jnp.sum(want(*a) * t), argnums=(0, 1, 2, 3))
    for g, w in zip(grads(*args), wants(*args)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(
            g, w, rtol=tol["rtol"],
            atol=tol["atol"] * max(float(np.abs(w).max()), 1e-3))
    # the poison was there: what the kernels leave behind `kept` is not zeros
    h, = gm._gate_up(poison(x), w_gu, jnp.asarray(counts, jnp.int32), False,
                     True)
    assert kept == rows or bool(jnp.isnan(h[kept:].astype(jnp.float32)).all())


def test_the_visits_of_a_buffer():
    """Tiles of 16 rows, groups of 17, 0, 13, 30 in a buffer of 96: the
    first expert's rows lie in tiles 0 and 1, the third's in 1, the
    fourth's in 1 to 3; tiles 4 and 5 are never visited."""
    from mxnet_tpu.ops.pallas import grouped_matmul as gm
    counts = jnp.asarray([17, 0, 13, 30], jnp.int32)
    gid, tid, n, starts, ends = gm.visits(counts, 96, 16)
    assert int(n[0]) == 6 and gid.shape == tid.shape == (6 + 4 - 1,)
    assert gid.tolist()[:6] == [0, 0, 2, 3, 3, 3]
    assert tid.tolist()[:6] == [0, 1, 1, 1, 2, 3]
    assert gid.tolist()[6:] == [3] * 3 and tid.tolist()[6:] == [3] * 3
    assert starts.tolist() == [0, 17, 17, 30] \
        and ends.tolist() == [17, 17, 30, 60]
    gid, tid, n, _, _ = gm.visits(counts, 96, 16, empty=True)
    assert int(n[0]) == 7 and gid.tolist()[:7] == [0, 0, 1, 2, 3, 3, 3]
    assert tid.tolist()[:7] == [0, 1, 1, 1, 1, 2, 3]


def _routed(kind):
    """Layers whose routing is planted: which held experts get rows."""
    x, router_w, w_gate_up, w_down = _planted(5, 3.0)
    if kind == "one_expert_with_every_row":     # 96 rows on expert 0
        router_w = router_w.at[0].set(1.0).at[1:HELD].set(-1.0)
    elif kind == "an_expert_with_no_row":
        router_w = router_w.at[2].set(-1.0)
    elif kind == "the_buffer_full":     # 32 tokens x 4 held = the 128 rows
        x = x.at[32:].multiply(-1.0)
        router_w = router_w.at[:HELD].set(1.0)
    return x, router_w, w_gate_up, w_down


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tile", [16, 64])
@pytest.mark.parametrize("kind,kept,most", [
    ("well_under_the_buffer", 66, 29),
    ("one_expert_with_every_row", N, N),
    ("an_expert_with_no_row", 55, 29),
    ("the_buffer_full", 128, 32)])
def test_the_sorted_path_stops_at_kept(monkeypatch, kind, kept, most, tile,
                                       dtype):
    """The sorted path with its products stopping at `kept`, the rows
    behind left as the kernels leave them (NaN in interpret mode): the
    layer's output and the gradient of every operand are the equations',
    and its report is what the routing says."""
    from mxnet_tpu.ops.pallas import grouped_matmul as gm
    monkeypatch.setattr(gm, "_ROW_TILE", tile)
    args = tuple(a.astype(dtype) for a in _routed(kind))
    wide = tuple(a.astype(jnp.float32) for a in args)
    y, aux = _held(*args, return_aux=True)
    assert y.dtype == args[0].dtype and not bool(aux["exact"])
    assert [float(aux[k]) for k in ("kept", "max_load", "mean_load")] \
        == [kept, most, kept / HELD]
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(dense_over_held(*wide)), **tol)
    t = jnp.asarray(np.random.RandomState(8).normal(0, 1, y.shape), "f")
    grads = jax.grad(lambda *a: jnp.sum(_held(*a).astype("f") * t),
                     argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense_over_held(*a) * t),
                    argnums=(0, 1, 2, 3))(*wide)
    for g, w in zip(grads, want):
        g = np.asarray(g, np.float32)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(
            g, np.asarray(w), rtol=10 * tol["rtol"],
            atol=tol["atol"] * float(jnp.abs(w).max()))


def test_the_cells_buffer():
    """The cell: 8,192 tokens, 10 of 256, 8 held: 2,560 expected, 4,096
    rows, of a bound of 65,536."""
    assert moe.held_rows(8192, 10, 8, 256) == (4096, 65536)


def test_no_assignment_is_dropped_when_every_row_goes_to_held_experts():
    """A planted routing: the router's rows of the held experts are large,
    so every token sends min(k, held) = 4 assignments here, 6.4 times what
    uniform routing would. The row buffer is too small, the exact dense
    path runs and says so, and nothing is left out."""
    x, router_w, w_gate_up, w_down = _layer(1)
    x = jnp.abs(x)
    router_w = router_w.at[:HELD].set(5.0)       # held: experts 0..3
    n_rows, most = moe.held_rows(N, K, HELD, E)
    assert n_rows < most == N * HELD
    y, aux = _held(x, router_w, w_gate_up, w_down, return_aux=True)
    assert bool(aux["exact"]) and int(aux["kept"]) == most
    assert int(aux["max_load"]) == N
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(dense_over_held(x, router_w, w_gate_up,
                                                  w_down)),
        rtol=2e-5, atol=2e-6)
    # under jit, inside a recomputed region, differentiated: the same
    f = jax.jit(jax.grad(lambda w: jnp.sum(jax.checkpoint(
        lambda w: _held(x, router_w, w, w_down))(w))))
    want = jax.grad(lambda w: jnp.sum(dense_over_held(x, router_w, w,
                                                      w_down)))(w_gate_up)
    np.testing.assert_allclose(np.asarray(f(w_gate_up)), np.asarray(want),
                               rtol=2e-4, atol=1e-5)


def _layer_conds(jaxpr):
    """The layer's own `lax.cond`s (a kernel's `pl.when` is one too, inside
    its call)."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        found += eqn.primitive.name == "cond"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _layer_conds(sub)
    return found


def test_a_buffer_that_holds_the_bound_has_no_second_path():
    """A chip that holds every expert: the buffer is the bound."""
    x, router_w, w_gate_up, w_down = _layer(2, held=E)
    assert moe.held_rows(N, K, E, E) == (N * K, N * K)
    whole = jax.make_jaxpr(_held)(x, router_w, w_gate_up, w_down)
    assert _layer_conds(whole.jaxpr) == 0
    assert _layer_conds(jax.make_jaxpr(_held)(*_layer(2)).jaxpr) == 1


def test_the_shares_add_up_to_the_whole_layer():
    """Every chip's part of the routed sum, each computed by a layer that is
    told which experts it holds, adds up to the layer that holds them all."""
    x, router_w, w_gate_up, w_down = _layer(3, held=E)
    whole = dense_over_held(x, router_w, w_gate_up, w_down)
    parts = sum(_held(x, router_w, w_gate_up[s:s + HELD],
                      w_down[s:s + HELD], first_held=s)
                for s in range(0, E, HELD))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)


def test_the_op_reports_its_loads_and_leaves_the_old_callers_alone():
    """The op's second output is the call's report; the collectors of the
    capacity-gated layers hear nothing of it."""
    x, router_w, w_gate_up, w_down = _layer(4)
    with moe.collect_metrics() as seen, moe.expert_axis("ep"):
        y, report = mx.nd._contrib_held_moe_ffn(
            mx.nd.array(x).reshape((2, N // 2, D)), mx.nd.array(router_w),
            mx.nd.array(w_gate_up), mx.nd.array(w_down), top_k=K,
            published_experts=E, scaling=2.5)
        assert not seen.aux_losses
        old = moe.moe_ffn(x, router_w[:HELD].T, w_gate_up[:, :, :F], w_down,
                          return_aux=True)[1]
        moe.report_metrics(old)
    assert y.shape == (2, N // 2, D)
    np.testing.assert_allclose(
        y.asnumpy().reshape(N, D),
        np.asarray(dense_over_held(x, router_w, w_gate_up, w_down)),
        rtol=2e-5, atol=2e-6)
    _, aux = _held(x, router_w, w_gate_up, w_down, return_aux=True)
    from mxnet_tpu.ops.moe import HELD_REPORT
    np.testing.assert_allclose(report.asnumpy(),
                               [float(aux[k]) for k in HELD_REPORT])
    assert len(seen.aux_losses) == len(seen.dropped) == 1


@pytest.mark.parametrize("recompute", [False, True])
def test_the_block_keeps_the_last_steps_report_as_state(recompute):
    """`HeldExpertsFFN.routing` after a fused trainer's step: what the
    layer reported in that step, handed on with the step's aux outputs as
    BatchNorm's statistics are, from a recomputed block too; no gradient,
    no update of the optimizer's."""
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models.hybrid_decoder import HeldExpertsFFN
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    net = HeldExpertsFFN(D, F, F, HELD, E, K, scaling=2.5)
    net.initialize(mx.init.Normal(1.0), ctx=mx.cpu())
    if recompute:
        net.recompute()
    assert net.routing.grad_req == "null"
    trainer = DataParallelTrainer(
        net, gloss.L2Loss(), optimizer="sgd",
        optimizer_params={"learning_rate": 0.0},
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    x = np.abs(np.random.RandomState(7).normal(0, 1, (2, N // 2, D))
               ).astype(np.float32)
    trainer.step(mx.nd.array(x), mx.nd.zeros((2, N // 2, D)))
    trainer.sync()
    _, aux = _held(jnp.asarray(x).reshape(N, D),
                   net.router_weight.data()._data,
                   net.experts_gate_up.data()._data,
                   net.experts_down.data()._data, return_aux=True)
    np.testing.assert_allclose(
        net.routing.data().asnumpy(),
        [float(aux["kept"]), float(aux["max_load"]), float(aux["mean_load"]),
         float(aux["exact"])])
    assert float(aux["kept"]) > 0


# -- rotary positions ---------------------------------------------------------------

def written_rope(x, base, r, yarn=None, factor=1.0):
    """The formulas as HF writes them (`_compute_yarn_parameters`,
    `apply_rotary_pos_emb`), in float64."""
    x = np.asarray(x, np.float64)
    t = x.shape[-2]
    pos_freqs = base ** (np.arange(0, r, 2) / r)
    inv = 1.0 / pos_freqs
    if yarn:
        s, length, fast, slow = yarn

        def dim_of(rot):
            return r * math.log(length / (rot * 2 * math.pi)) \
                / (2 * math.log(base))
        low, high = max(math.floor(dim_of(fast)), 0), \
            min(math.ceil(dim_of(slow)), r - 1)
        ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0, 1)
        extrapolation = 1 - ramp
        inv = inv / s * (1 - extrapolation) + inv * extrapolation
    freqs = np.outer(np.arange(t), inv)
    emb = np.concatenate([freqs, freqs], axis=-1)
    cos, sin = np.cos(emb) * factor, np.sin(emb) * factor
    rot, rest = x[..., :r], x[..., r:]
    half = np.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], axis=-1)
    return np.concatenate([rot * cos + half * sin, rest], axis=-1)


@pytest.mark.parametrize("kw,written", [
    (dict(base=10000.0), dict(base=10000.0, r=128)),
    (dict(base=500000.0, rotary_dim=64, yarn_factor=128.0,
          yarn_original_length=8192, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
          attention_factor=1.4852030263919618),
     dict(base=500000.0, r=64, yarn=(128.0, 8192, 32.0, 1.0),
          factor=1.4852030263919618)),
    (dict(base=100.0, rotary_dim=32), dict(base=100.0, r=32)),
], ids=["laguna_sliding", "laguna_full_yarn", "partial"])
def test_rotary_op_against_the_written_formula(kw, written):
    rs = np.random.RandomState(0)
    x = rs.normal(0, 1, (2, 3, 300, 128)).astype(np.float32)
    got = mx.nd._contrib_rotary_embedding(mx.nd.array(x), **kw).asnumpy()
    np.testing.assert_allclose(got, written_rope(x, **written), rtol=1e-4,
                               atol=2e-4)
    # a rotation: the turned pairs keep their length (times the factor)
    r, f = written["r"], written.get("factor", 1.0)
    pairs = lambda a: a[..., :r // 2] ** 2 + a[..., r // 2:r] ** 2
    np.testing.assert_allclose(pairs(got), f * f * pairs(x), rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_array_equal(got[..., r:], x[..., r:])
    np.testing.assert_allclose(got[:, :, 0], np.concatenate(
        [f * x[:, :, 0, :r], x[:, :, 0, r:]], axis=-1), rtol=1e-6)


def test_yarn_frequencies_keep_the_fast_pairs_and_stretch_the_slow():
    from mxnet_tpu.ops.rotary import rotary_inv_freq
    plain = rotary_inv_freq(64, 500000.0)
    yarn = rotary_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    assert yarn.dtype == np.float32 and yarn.shape == (32,)
    np.testing.assert_allclose(yarn[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(yarn[-4:], plain[-4:] / 128.0, rtol=1e-6)
    assert np.all(yarn <= plain * (1 + 1e-6)) and np.all(np.diff(yarn) < 0)


def test_rotary_scores_depend_on_the_distance_alone():
    rs = np.random.RandomState(1)
    q = np.tile(rs.normal(0, 1, (1, 1, 1, 32)), (1, 1, 40, 1)).astype("f")
    k = np.tile(rs.normal(0, 1, (1, 1, 1, 32)), (1, 1, 40, 1)).astype("f")
    rope = lambda a: mx.nd._contrib_rotary_embedding(mx.nd.array(a),
                                                     base=50.0).asnumpy()
    s = rope(q)[0, 0] @ rope(k)[0, 0].T
    for dist in (0, 3, 17):
        np.testing.assert_allclose(np.diag(s, -dist), s[dist, 0], rtol=2e-4,
                                   atol=2e-4)
    assert abs(s[3, 0] - s[0, 0]) > 1e-3


# -- the attention block on its two routes --------------------------------------------

@pytest.mark.parametrize("window", [None, 8, 100])
def test_gated_windowed_attention_routes_agree(monkeypatch, window):
    from mxnet_tpu.models.hybrid_decoder import GroupedQueryAttention
    T = 48
    layer = GroupedQueryAttention(
        32, 6, 2, head_dim=16, window=window, gate=True,
        rope=dict(base=100.0, rotary_dim=8), kernel_scope="mx.flash.window")
    layer.initialize(mx.init.Normal(0.3))
    x = mx.nd.array(np.random.RandomState(2).normal(0, 1, (2, T, 32)))
    plain = layer(x).asnumpy()
    monkeypatch.setenv("MXNET_FLASH_ATTENTION_MIN_SEQ", "16")
    flash = layer(x).asnumpy()
    np.testing.assert_allclose(flash, plain, rtol=2e-4, atol=2e-5)
    if window == 8:     # a later position's output ignores the far keys
        x2 = x.asnumpy().copy()
        x2[:, :20] += 1.0
        moved = layer(mx.nd.array(x2)).asnumpy()
        np.testing.assert_allclose(moved[:, 30:], flash[:, 30:], rtol=1e-4,
                                   atol=1e-5)
        assert np.abs(moved[:, :27] - flash[:, :27]).max() > 1e-3


def test_granites_arguments_build_granites_leaves():
    from mxnet_tpu.models.hybrid_decoder import (hybrid_decoder_tiny,
                                                 windowed_moe_decoder_tiny)
    old = hybrid_decoder_tiny()
    old.initialize(mx.init.Normal(0.1))
    ids = mx.nd.array(np.zeros((1, 16)), dtype="int32")
    assert old(ids).shape == (1, 16, 256)
    names = list(old.collect_params())
    assert not any("head_weight" in n or "router" in n for n in names)
    assert sum("dense" in n for n in names) == 2 * 2 + 4 + 3 * 2
    new = windowed_moe_decoder_tiny()
    new.initialize(mx.init.Normal(0.1))
    assert new(ids).shape == (1, 16, 256)
    shapes = [tuple(p.shape) for p in new.collect_params().values()]
    assert shapes[:2] == [(256, 32), (256, 32)]            # table and head
    assert (6, 32) in shapes and (4, 32) in shapes         # the gates
    assert shapes.count((16, 32)) == 2                     # two routers
    assert shapes.count((4, 32, 32)) == 2                  # held experts
