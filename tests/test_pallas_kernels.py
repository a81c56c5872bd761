"""Pallas kernel tests: flash attention fwd/bwd vs naive reference, fused
optimizer vs eager kernels.

Runs the real kernels in interpret mode on CPU (MXNET_PALLAS_INTERPRET=1 via
monkeypatch) — the same kernel code the TPU executes, minus the hardware.
Mirrors reference test style: check_consistency across implementations
(python/mxnet/test_utils.py:1422).
"""
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
    import importlib
    from mxnet_tpu.ops import registry
    # the package __init__ re-exports the flash_attention FUNCTION under the
    # same name — load the module itself
    fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")

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


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernels_lower_for_tpu(building_for_tpu, causal):
    # T=1000: not a multiple of the block, so the padded path lowers too
    q, k, v = (x.astype(jnp.bfloat16)
               for x in _rand_qkv(8, B=1, H=2, T=1000, D=64))

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal)

    assert _tpu_module(f, q, k, v).count("tpu_custom_call") == 1
    grad = jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
                    argnums=(0, 1, 2))
    # forward (for the residuals) + dq + dk/dv
    assert _tpu_module(grad, q, k, v).count("tpu_custom_call") == 3


def test_fused_optimizer_kernels_lower_for_tpu(monkeypatch):
    monkeypatch.setattr(fo, "_available", lambda x=None: True)
    ws = [jnp.ones((33,)), jnp.ones((16, 16))]
    sgd = _tpu_module(lambda w, g, m: fo.fused_sgd_apply(w, g, m, 0.1, 0.9),
                      ws, ws, ws)
    adam = _tpu_module(lambda w, g, m, v: fo.fused_adam_apply(w, g, m, v, 1e-3),
                       ws, ws, ws, ws)
    assert sgd.count("tpu_custom_call") == 1
    assert adam.count("tpu_custom_call") == 1
