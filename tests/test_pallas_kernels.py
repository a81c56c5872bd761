"""Pallas kernel tests: flash attention fwd/bwd vs naive reference, fused
optimizer vs eager kernels.

Runs the real kernels in interpret mode on CPU (MXNET_PALLAS_INTERPRET=1 via
monkeypatch) — the same kernel code the TPU executes, minus the hardware.
Mirrors reference test style: check_consistency across implementations
(python/mxnet/test_utils.py:1422).
"""
import contextlib
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas.flash_attention import flash_attention, _fwd, _bwd
from mxnet_tpu.ops.pallas import fused_optimizer as fo
from mxnet_tpu.ops.attention import blockwise_attention


def naive_attention(q, k, v, causal=False):
    B, H, T, D = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((T, k.shape[2]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _rand_qkv(seed, B=2, H=2, T=160, Tk=None, D=64, dtype=np.float32):
    rs = np.random.RandomState(seed)
    Tk = Tk or T
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)).astype(dtype))
    k = jnp.asarray(rs.normal(0, 1, (B, H, Tk, D)).astype(dtype))
    v = jnp.asarray(rs.normal(0, 1, (B, H, Tk, D)).astype(dtype))
    return q, k, v


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_naive(interpret_mode, causal):
    q, k, v = _rand_qkv(0, T=160, D=64)  # non-multiple of block => padding path
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_forward_cross_attention(interpret_mode):
    q, k, v = _rand_qkv(1, T=96, Tk=224, D=32)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_naive(interpret_mode, causal):
    q, k, v = _rand_qkv(2, B=1, H=2, T=128, D=32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=128) ** 2)

    def f_naive(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_naive):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_backward_padded_shapes(interpret_mode):
    # T not a multiple of the block: exercises the padded-row masking in bwd
    q, k, v = _rand_qkv(3, B=1, H=1, T=100, Tk=150, D=32)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=128, block_k=128))

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def f_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v))

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_matches_blockwise_fallback():
    # without interpret mode on CPU, flash_attention routes to lax.scan path
    q, k, v = _rand_qkv(4, T=128, D=32)
    out = flash_attention(q, k, v)
    ref = blockwise_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_bf16(interpret_mode):
    q, k, v = _rand_qkv(5, T=128, D=64)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, block_q=128, block_k=128)
    assert out.dtype == jnp.bfloat16
    ref = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# the schedule's branches: resident heads (several a grid step, more than one
# grid step), loops that stop at the diagonal, padded lengths, T != Tk, and
# the chunk pairs (`tiled`) that long or wide sequences are cut into
# ---------------------------------------------------------------------------

def _fa():
    # the package re-exports the flash_attention FUNCTION under the module's
    # name: load the module itself
    import importlib
    return importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")


def _plans(BH, T, Tk, D, dtype, causal, limit=512):
    """The forward's and the backward's (q chunks, k chunks, heads a step)
    as `flash_attention` would plan them: one chunk a side is the resident
    schedule."""
    fa = _fa()
    bq, bk = fa._block(T, limit), fa._block(Tk, limit)
    return [(p.n_qc, p.n_kc, p.G) for p in (
        fa._plan(backward, BH, T, Tk, D, jnp.dtype(dtype), causal, bq, bk)
        for backward in (False, True))]


# (B*H, T, Tk, D, causal, dtype, chunks a side (q, k), least grid steps of
# several heads)
_SCHEDULE_CASES = [
    pytest.param(8, 1024, 1024, 64, False, np.float32, (1, 1), 2,
                 id="bert_cell_shape"),
    pytest.param(4, 2048, 2048, 64, True, np.float32, (1, 1), 0,
                 id="granite_cell_shape"),
    pytest.param(8, 1024, 1024, 64, False, jnp.bfloat16, (1, 1), 2,
                 id="bert_cell_shape_bf16"),
    pytest.param(4, 2048, 2048, 64, True, jnp.bfloat16, (1, 1), 2,
                 id="granite_cell_shape_bf16"),
    pytest.param(2, 1000, 1000, 64, True, np.float32, (1, 1), 0,
                 id="padded_causal"),
    pytest.param(2, 300, 700, 32, False, np.float32, (1, 1), 0,
                 id="cross_attention"),
    # 64 block pairs, causal: two chunks of 2,048 a side, three live pairs
    pytest.param(2, 4096, 4096, 64, True, jnp.bfloat16, (2, 2), 0,
                 id="causal_too_long_to_unroll"),
    # chunks of 1,536: a diagonal, a below-diagonal and a dead pair, T padded
    pytest.param(1, 2400, 2400, 128, True, np.float32, (2, 2), 0,
                 id="tiled_causal"),
    pytest.param(1, 640, 2400, 128, False, np.float32, (1, 2), 0,
                 id="tiled_keys_cross"),
    pytest.param(1, 2400, 640, 128, False, np.float32, (2, 1), 0,
                 id="tiled_queries_cross"),
    # not causal, resident and of 25 block pairs: rolled, its bounds static
    pytest.param(2, 2400, 2400, 64, False, jnp.bfloat16, (1, 1), 0,
                 id="resident_rolled"),
]


@pytest.mark.parametrize("BH,T,Tk,D,causal,dtype,chunks,min_steps",
                         _SCHEDULE_CASES)
def test_flash_schedule_branches(interpret_mode, BH, T, Tk, D, causal, dtype,
                                 chunks, min_steps):
    """Forward and backward of every branch of the schedule against the
    dense softmax in float32; bfloat16 inputs at test_flash_bf16's
    tolerance."""
    (n_q, n_k, G), _ = _plans(BH, T, Tk, D, dtype, causal)
    assert (n_q, n_k) == chunks
    if min_steps:
        # several heads a forward step, and more than one grid step
        assert G >= 2 and BH // G >= min_steps, (BH, G)
    q, k, v = _rand_qkv(31 + T, B=1, H=BH, T=T, Tk=Tk, D=D)
    rs = np.random.RandomState(T)
    co = jnp.asarray(rs.normal(0, 1, q.shape).astype(np.float32))
    qd, kd, vd = (x.astype(dtype) for x in (q, k, v))

    def f_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal)
        return jnp.vdot(out.astype(jnp.float32), co), out

    def f_naive(q, k, v):
        out = naive_attention(q, k, v, causal=causal)
        return jnp.vdot(out, co), out

    g, out = jax.grad(f_flash, argnums=(0, 1, 2), has_aux=True)(qd, kd, vd)
    g_ref, ref = jax.grad(f_naive, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out.dtype == dtype
    tol = dict(rtol=2e-4, atol=2e-4) if dtype == np.float32 \
        else dict(rtol=0.05, atol=0.05)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), **tol)
    for name, a, b in zip("qkv", g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b),
            err_msg=f"d{name}", **tol)


def test_flash_residual_statistics_are_lane_dense(interpret_mode):
    """What the forward hands the backward has T on its last axis: as a
    column (BH, T, 1) every statistic would take a 128-lane tile in memory
    (100 MB where 0.8 MB is needed at the t1024 cell's shapes)."""
    fa = _fa()
    q, k, v = (x.reshape(4, 384, 64) for x in _rand_qkv(9, T=384, D=64))
    out, res = fa._flash_fwd(q, k, v, False, 0.125, 128, 128, True)
    lse = res[-1]
    assert lse.shape == (4, 1, 384) and lse.dtype == jnp.float32
    s = jnp.einsum("bqd,bkd->bqk", q, k) * 0.125
    np.testing.assert_allclose(np.asarray(lse[:, 0]),
                               np.asarray(jax.nn.logsumexp(s, axis=-1)),
                               rtol=1e-5, atol=1e-5)
    # a padded length pads the statistics to whole blocks, still on lanes
    _, res = fa._flash_fwd(q[:, :300], k, v, True, 0.125, 128, 128, True)
    assert res[-1].shape == (4, 1, 384)


def test_flash_plan_at_the_cells_shapes():
    fa = _fa()
    bf16 = jnp.bfloat16
    # resident while a head's rows fit and both walks unroll (up to 16 block
    # pairs under `causal`), in chunk pairs of 4 x 4 blocks beyond; the
    # cells: four heads a forward step and two a backward step (t1024), two
    # and one under the causal mask at T = 2048 (granite)
    assert _plans(192, 1024, 1024, 64, bf16, False) == [(1, 1, 4), (1, 1, 2)]
    assert _plans(64, 2048, 2048, 64, bf16, True) == [(1, 1, 2), (1, 1, 1)]
    assert _plans(16, 4096, 4096, 64, bf16, False)[1][:2] == (1, 1)
    assert _plans(16, 4096, 4096, 64, bf16, True) == [(2, 2, 1), (2, 2, 1)]
    assert _plans(8, 8192, 8192, 64, bf16, True) == [(4, 4, 1), (4, 4, 1)]
    assert _plans(8, 8192, 8192, 64, bf16, False) == [(4, 4, 1), (4, 4, 1)]
    for BH in (192, 64, 7, 1):
        for flops in (1e6, 3e8, 1e10):
            G = fa._heads_per_step(BH, 2 << 20, flops)
            assert BH % G == 0 and G * (2 << 20) <= fa._VMEM_BLOCK_BYTES
    assert fa._heads_per_step(192, 1 << 30, 1e6) == 1
    # blocks: whole lane tiles under the limit, the cheapest walk of L
    assert [fa._block(L, 512) for L in (100, 1000, 1024, 1100, 2400)] \
        == [128, 512, 512, 384, 512]
    assert fa._block(1000, 128) == 128 and fa._block(50, 64) == 128


# ---------------------------------------------------------------------------
# a head whose scores are one tile computes its own delta (T, Tk <= 512: the
# published BERT length); longer heads read sum(dO * o) as they did
# ---------------------------------------------------------------------------

def plain_attention(q, k, v, causal=False):
    """The plain path as models/bert.py writes it, in the inputs' dtype."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(np.tril(np.ones(s.shape[-2:], bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("BH,T,causal", [(8, 512, False), (4, 384, True)],
                         ids=["bert_t512", "causal_t384"])
def test_one_tile_head_computes_its_own_delta(interpret_mode, BH, T, causal):
    """bfloat16, the cells' precision. The key third of BERT's fused bias has
    no gradient under softmax: sum_j dk_j is rounding alone, and what AdamW
    makes of it failed `correct` at T = 512 while delta came from the rounded
    o (1.3 x the plain path's here). From the tile's own p and dp every row
    of ds sums to zero before it is rounded, as autodiff's does."""
    assert _plans(BH, T, T, 64, jnp.bfloat16, causal)[1][:2] == (1, 1)
    rs = np.random.RandomState(T)
    q, k, v, co = (jnp.asarray(rs.normal(0, 1, (1, BH, T, 64)), jnp.bfloat16)
                   for _ in range(4))

    def grads(attn, *args):
        return jax.grad(lambda q, k, v: jnp.vdot(
            attn(q, k, v, causal=causal).astype(jnp.float32),
            co.astype(jnp.float32)), argnums=(0, 1, 2))(*args)

    g = grads(flash_attention, q, k, v)
    g_plain = grads(plain_attention, q, k, v)
    g_ref = grads(naive_attention,
                  *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, b in zip("qkv", g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b),
            rtol=0.05, atol=0.05, err_msg=f"d{name}")

    def key_bias_grad(dk):
        return float(jnp.linalg.norm(jnp.sum(dk.astype(jnp.float32), axis=2)))

    assert key_bias_grad(g[1]) <= 1.15 * key_bias_grad(g_plain[1])


def _backward_eqns(T):
    """(operands of the backward's Mosaic call, number of reduce_sums over a
    (BH, T, d) product outside it) in the jaxpr of flash_attention's grad."""
    x = jax.ShapeDtypeStruct((1, 2, T, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2)))(x, x, x)
    operands, row_sums = [], 0

    def walk(j):
        nonlocal row_sums
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                if e.params["name"] == "mx_flash_bwd":
                    operands.append(len(e.invars))
                continue
            if e.primitive.name == "reduce_sum" \
                    and e.invars[0].aval.shape == (2, T, 64):
                row_sums += 1
            for p in e.params.values():
                inner = getattr(p, "jaxpr", p)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jaxpr.jaxpr)
    return operands, row_sums


def test_delta_source_follows_the_plan(interpret_mode):
    """T = 512 hands the backward q, k, v, dO and lse, computes no
    sum(dO * o) in XLA and keeps no o between the passes; T = 1024 (two
    blocks a head) is handed delta as before."""
    fa = _fa()
    assert _backward_eqns(512) == ([5], 0)
    assert _backward_eqns(1024) == ([6], 1)
    q, k, v = (x.reshape(4, 512, 64) for x in _rand_qkv(9, T=512, D=64))
    out, res = fa._flash_fwd(q, k, v, False, 0.125, 512, 512, True)
    assert [None if r is None else r.shape for r in res] \
        == [(4, 512, 64)] * 3 + [None, (4, 1, 512)]
    q, k, v = (x.reshape(4, 1024, 64) for x in _rand_qkv(9, T=1024, D=64))
    out, res = fa._flash_fwd(q, k, v, False, 0.125, 512, 512, True)
    assert res[3].shape == out.shape == (4, 1024, 64)


# ---------------------------------------------------------------------------
# non-Pallas fallback gradient path (NO interpret fixture: on CPU
# flash_attention routes to the blockwise lax.scan — the path every
# CPU-trained model differentiates through)
# ---------------------------------------------------------------------------

def _grad_pair(fn_a, fn_b, q, k, v, seed):
    """Cotangent-contracted grads of both implementations."""
    rs = np.random.RandomState(seed)
    co = jnp.asarray(rs.normal(0, 1, q.shape).astype(np.float32))
    ga = jax.grad(lambda *a: jnp.vdot(fn_a(*a), co), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(lambda *a: jnp.vdot(fn_b(*a), co), argnums=(0, 1, 2))(q, k, v)
    return ga, gb


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T,block", [(100, 256), (257, 256), (128, 32)])
def test_fallback_grad_matches_naive_vjp(causal, T, block):
    """The CPU fallback's gradient must equal the dense-softmax VJP,
    including sequence lengths that are NOT a multiple of the block (the
    padded key rows must contribute exactly zero cotangent)."""
    q, k, v = _rand_qkv(11 + T, B=1, H=2, T=T, D=16)
    g_fb, g_ref = _grad_pair(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        block_q=block, block_k=block),
        lambda q, k, v: naive_attention(q, k, v, causal=causal),
        q, k, v, seed=T)
    for name, a, b in zip("qkv", g_fb, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5,
            err_msg=f"fallback d{name} diverges at T={T} causal={causal}")


def test_fallback_grad_cross_attention():
    # Tk != T and Tk not a block multiple: key-padding mask in the bwd
    q, k, v = _rand_qkv(21, B=1, H=1, T=96, Tk=200, D=16)
    g_fb, g_ref = _grad_pair(
        lambda q, k, v: flash_attention(q, k, v, block_k=128),
        lambda q, k, v: naive_attention(q, k, v),
        q, k, v, seed=21)
    for a, b in zip(g_fb, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_fallback_grad_matches_blockwise_direct():
    """flash_attention's fallback and blockwise_attention called directly
    must be the SAME differentiable function (routing adds no wrapper that
    detaches or rescales gradients)."""
    q, k, v = _rand_qkv(22, B=1, H=2, T=100, D=16)
    g_fb, g_bw = _grad_pair(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_k=64),
        lambda q, k, v: blockwise_attention(q, k, v, causal=True,
                                            block_size=64),
        q, k, v, seed=22)
    for a, b in zip(g_fb, g_bw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# fused optimizer
# ---------------------------------------------------------------------------

def test_fused_sgd_matches_reference():
    rs = np.random.RandomState(6)
    shapes = [(7, 5), (128,), (3, 4, 5)]
    ws = [jnp.asarray(rs.normal(size=s).astype(np.float32)) for s in shapes]
    gs = [jnp.asarray(rs.normal(size=s).astype(np.float32)) for s in shapes]
    ms = [jnp.zeros(s, jnp.float32) for s in shapes]
    w2, m2 = fo.fused_sgd_apply(ws, gs, ms, lr=0.1, momentum=0.9, wd=0.01)
    for w, g, m, wn, mn in zip(ws, gs, ms, w2, m2):
        gref = g + 0.01 * w
        mref = 0.9 * m + gref
        np.testing.assert_allclose(np.asarray(mn), np.asarray(mref), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(wn), np.asarray(w - 0.1 * mref),
                                   rtol=1e-6)


def test_fused_adam_matches_reference():
    rs = np.random.RandomState(7)
    shapes = [(33,), (16, 16)]
    ws = [jnp.asarray(rs.normal(size=s).astype(np.float32)) for s in shapes]
    gs = [jnp.asarray(rs.normal(size=s).astype(np.float32)) for s in shapes]
    ms = [jnp.zeros(s, jnp.float32) for s in shapes]
    vs = [jnp.zeros(s, jnp.float32) for s in shapes]
    w2, m2, v2 = fo.fused_adam_apply(ws, gs, ms, vs, lr=1e-3, t=1)
    for w, g, wn in zip(ws, gs, w2):
        m = 0.1 * g
        v = 0.001 * g * g
        mhat = m / 0.1
        vhat = v / 0.001
        ref = w - 1e-3 * mhat / (jnp.sqrt(vhat) + 1e-8)
        np.testing.assert_allclose(np.asarray(wn), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def test_flash_dispatch_respects_exec_platform():
    """Regression: under a trace, the Pallas-vs-fallback decision must use
    the execution platform recorded by the surrounding invoke/compile, not
    jax.default_backend() (which says 'tpu' on a TPU machine even while
    compiling for CPU arrays — that crashed CPU deferred-init of models
    containing flash attention)."""
    from mxnet_tpu.ops import registry
    fa = _fa()

    class TracerLike:
        def devices(self):
            raise AttributeError("tracers have no concrete placement")

    tok = registry.exec_platform.set("cpu")
    try:
        assert fa._on_tpu(TracerLike()) is False
    finally:
        registry.exec_platform.reset(tok)
    tok = registry.exec_platform.set("tpu")
    try:
        assert fa._on_tpu(TracerLike()) is True
    finally:
        registry.exec_platform.reset(tok)


# ---------------------------------------------------------------------------
# TPU cross-lowering: Pallas itself accepts the kernels (no chip needed). A
# kernel edit that Pallas rejects fails here, not on the chip budget; what
# only libtpu can say (the Mosaic compile, the numbers) is chip_smoke.py's.
# ---------------------------------------------------------------------------

def _tpu_module(fn, *args):
    from jax import export
    return export.export(jax.jit(fn), platforms=["tpu"])(*args).mlir_module()


@pytest.fixture()
def building_for_tpu():
    from mxnet_tpu.ops import registry
    tok = registry.exec_platform.set("tpu")
    yield
    registry.exec_platform.reset(tok)


# (B*H, T, causal): a T that is no multiple of the block (the padded path),
# then the two cells that run the kernels at their own shapes
@pytest.mark.parametrize("BH,T,causal", [
    (2, 1000, False), (2, 1000, True),
    pytest.param(192, 1024, False, id="bert_base_train_t1024"),
    pytest.param(64, 2048, True, id="granite4_h_micro_train_t2048"),
])
def test_flash_kernels_lower_for_tpu(building_for_tpu, BH, T, causal):
    rs = np.random.RandomState(8)
    q, k, v = (jnp.asarray(rs.normal(0, 1, (1, BH, T, 64)), jnp.bfloat16)
               for _ in range(3))

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal)

    assert _tpu_module(f, q, k, v).count("tpu_custom_call") == 1
    grad = jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
                    argnums=(0, 1, 2))
    # forward (for the residuals) + the one backward call
    assert _tpu_module(grad, q, k, v).count("tpu_custom_call") == 2


# What only the chip's compiler can say, said off the chip: Mosaic compiles
# the kernels at the cells' shapes for a described v5e (no device attached).
# An unaligned slice or a step that passes the VMEM limit fails here. The
# topology is described inside the fixture, never at import (one process at
# a time may load the TPU's library, and every xdist worker imports this file).

@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.mark.parametrize("BH,T,causal", [
    pytest.param(192, 1024, False, id="bert_base_train_t1024"),
    pytest.param(64, 2048, True, id="granite4_h_micro_train_t2048"),
])
def test_flash_kernels_compile_for_v5e(building_for_tpu, one_chip, BH, T,
                                       causal):
    x = jax.ShapeDtypeStruct((1, BH, T, 64), jnp.bfloat16, sharding=one_chip)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal).astype(jnp.float32)), argnums=(0, 1, 2))
    compiled = jax.jit(grad).lower(x, x, x).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    # the statistics between the two calls: T on lanes, under 1 MB for all
    # heads where the column layout took 100 MB
    assert f"f32[{BH},1,{T}]" in text and f"f32[{BH},{T},1]" not in text


@pytest.mark.parametrize("BH,window", [
    pytest.param(72, 512, id="laguna_sliding_layer"),
    pytest.param(48, None, id="laguna_full_layer"),
])
def test_flash_kernels_compile_for_v5e_at_t8192(building_for_tpu, one_chip,
                                                BH, window):
    """laguna_s_2_1_train_t8192's two kinds of layer: heads of 128 over
    8,192 positions, the window of 512 as a band in two chunks, the full
    causal head in 4 x 4 chunk pairs."""
    x = jax.ShapeDtypeStruct((1, BH, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=window).astype(jnp.float32)),
        argnums=(0, 1, 2))
    text = jax.jit(grad).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert f"f32[{BH},1,8192]" in text


@pytest.mark.parametrize("rows,D,F,experts,tokens", [
    pytest.param(32768, 2048, 768, 16, 16384,
                 id="keye_vl2_30b_a3b_train_t16384"),
    pytest.param(4096, 3072, 1024, 8, 8192, id="laguna_s_2_1_train_t8192"),
])
def test_grouped_swiglu_compiles_for_v5e(one_chip, rows, D, F, experts,
                                         tokens):
    """The held-experts layer's sorted path at its two cells' shapes: the
    five kernels of `ops/pallas/grouped_matmul.py` (a whole expert's weights
    resident, row tiles of 512) fit Mosaic's VMEM and its tiling, forward
    and backward."""
    from mxnet_tpu.ops.pallas.grouped_matmul import routed_swiglu
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    args = (shaped((tokens, D)), shaped((experts, D, 2 * F)),
            shaped((experts, F, D)), shaped((rows,), jnp.float32),
            shaped((rows,), jnp.int32), shaped((experts,), jnp.int32))
    fwd = jax.jit(lambda *a: routed_swiglu(*a, False)).lower(*args)
    # gate-and-up, down, and the rows summed by token (a grouped product too)
    assert fwd.compile().as_text().count("tpu_custom_call") == 3
    grad = jax.grad(lambda *a: jnp.sum(routed_swiglu(*a, False)
                                       .astype(jnp.float32)),
                    argnums=(0, 1, 2, 3))
    text = jax.jit(grad).lower(*args).compile().as_text()
    # gate-and-up with g and u kept, the two backward products, two
    # gradients of weights and the rows' gradient summed by token; the down
    # product's output is read by nothing
    for name in ("mx_moe_gate_up", "mx_moe_down_bwd", "mx_moe_gate_up_bwd",
                 "mx_moe_dweights"):
        assert name in text
    assert text.count("tpu_custom_call") >= 6


@contextlib.contextmanager
def trainer_context(mesh, axis="dp"):
    """What DataParallelTrainer._build_step sets round its traced body."""
    from mxnet_tpu.ops import registry
    tok = registry.batch_partition.set((mesh, axis))
    try:
        yield
    finally:
        registry.batch_partition.reset(tok)


def _flash_grad(q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)


def test_flash_kernels_compile_partitioned_for_v5e(building_for_tpu, v5e_2x2):
    """bert_base_train_dp4's attention: the global batch of 128 sharded over
    dp = 4 in the trainer's GSPMD step. XLA cannot partition a Mosaic call;
    under the trainer's context the call is a shard_map over the batch axis
    and each chip runs t512's own two calls, with no collective round them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(4), ("dp",))
    x = jax.ShapeDtypeStruct((128, 12, 512, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))

    def in_context(q, k, v):
        with trainer_context(mesh):
            return _flash_grad(q, k, v)

    text = jax.jit(in_context).lower(x, x, x).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2, calls
    assert all("bf16[384,512,64]" in c and "bf16[1536," not in c
               for c in calls)
    for collective in ("all-gather", "all-to-all", "collective-permute",
                       "all-reduce"):
        assert collective not in text, collective
    # with no context the multi-device trace keeps jax's own refusal
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(_flash_grad).lower(x, x, x).compile()


def test_partitioned_batch_must_divide(building_for_tpu):
    from jax.sharding import Mesh
    from mxnet_tpu.base import MXNetError
    mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("dp",))
    x = jax.ShapeDtypeStruct((6, 2, 512, 64), jnp.bfloat16)
    with trainer_context(mesh), \
            pytest.raises(MXNetError, match="6 rows.*4 devices"):
        jax.eval_shape(_flash_grad, x, x, x)
    # a mesh of one device partitions nothing
    with trainer_context(Mesh(np.array(jax.devices("cpu")[:1]), ("dp",))):
        assert "shard_map" not in str(jax.make_jaxpr(_flash_grad)(x, x, x))


def test_fused_optimizer_kernels_lower_for_tpu(monkeypatch):
    monkeypatch.setattr(fo, "_available", lambda x=None: True)
    ws = [jnp.ones((33,)), jnp.ones((16, 16))]
    sgd = _tpu_module(lambda w, g, m: fo.fused_sgd_apply(w, g, m, 0.1, 0.9),
                      ws, ws, ws)
    adam = _tpu_module(lambda w, g, m, v: fo.fused_adam_apply(w, g, m, v, 1e-3),
                       ws, ws, ws, ws)
    assert sgd.count("tpu_custom_call") == 1
    assert adam.count("tpu_custom_call") == 1
