"""The window of the flash kernels (PR 32): query t sees the keys
t - window < t' <= t. The kernels run in interpret mode on the CPU against
masked plain attention, forward and backward, on resident and on streamed
heads; `window=None` traces to the parent's kernels, operation for operation
(the parent's file is kept beside the tests as text)."""
import importlib
import importlib.util
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu.ops.attention import blockwise_attention

flash = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
PARENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                      "flash_attention_pr31.py.txt")


def masked_attention(q, k, v, window, scale=None):
    T, d = q.shape[2], q.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) \
        * (scale or d ** -0.5)
    t = np.arange(T)
    seen = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < window)
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _qkv(seed, H, T, D):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.normal(0, 1, (1, H, T, D)).astype(np.float32))
                 for _ in range(3)) + (
        jnp.asarray(rs.normal(0, 1, (1, H, T, D)).astype(np.float32)),)


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


# (H, T, D, window, block limit, VMEM budget or None, streamed)
_CASES = [
    pytest.param(2, 300, 128, 70, 128, None, False, id="T_and_W_off_block"),
    pytest.param(2, 512, 128, 128, 128, None, False, id="W_one_block"),
    pytest.param(1, 640, 128, 300, 128, None, False, id="W_spans_blocks"),
    pytest.param(2, 200, 128, 200, 128, None, False, id="W_equals_T"),
    pytest.param(2, 200, 128, 1000, 128, None, False, id="W_over_T"),
    pytest.param(2, 160, 64, 1, 128, None, False, id="W_one_key"),
    # one tile a head, padded rows whose band holds no real key: the toy cell
    pytest.param(6, 32, 16, 8, 512, None, False, id="one_tile_padded"),
    pytest.param(2, 200, 64, 30, 512, None, False, id="one_tile_padded_d64"),
    pytest.param(1, 1920, 64, 512, 128, None, False, id="too_long_to_unroll"),
    pytest.param(1, 1000, 128, 200, 128, 256 * 4096, True,
                 id="streamed_W_off_block"),
    pytest.param(1, 1024, 128, 512, 256, 512 * 4096, True,
                 id="streamed_W_two_blocks"),
    pytest.param(1, 768, 128, 2000, 128, 256 * 4096, True,
                 id="streamed_W_over_T"),
]


@pytest.mark.parametrize("H,T,D,window,limit,budget,streamed", _CASES)
def test_window_kernels_against_masked_attention(
        interpret_mode, monkeypatch, H, T, D, window, limit, budget, streamed):
    if budget:
        monkeypatch.setattr(flash, "_VMEM_BLOCK_BYTES", budget)
        jax.clear_caches()
    blk = flash._block(T, limit)
    _, n_q, _, n_k, _, _ = flash._plan(False, H, T, T, D, np.float32, True,
                                       blk, blk)
    assert (n_q > 1 and n_k > 1) == streamed, (n_q, n_k)
    q, k, v, co = _qkv(T + window, H, T, D)

    def f(fn):
        def g(q, k, v):
            out = fn(q, k, v)
            return jnp.vdot(out.astype(jnp.float32), co), out
        return jax.grad(g, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    got, out = f(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True, window=window, block_q=limit, block_k=limit))
    want, ref = f(lambda q, k, v: masked_attention(q, k, v, window))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")
    if budget:
        jax.clear_caches()


def test_window_visits_only_the_bands_blocks(interpret_mode):
    """The kernels' work follows the band, not T x T: with keys and values
    outside the band of every query set to NaN the result is finite only if
    no dead block is ever read into a product that is kept."""
    H, T, D, W = 1, 1024, 128, 128
    q, k, v, _ = _qkv(5, H, T, D)
    base = flash.flash_attention(q, k, v, causal=True, window=W,
                                 block_q=128, block_k=128)
    # the last query block sees keys 768.. alone: poison all before them
    bad = jnp.where(jnp.arange(T)[None, None, :, None] < 768, jnp.nan, 1.0)
    out = flash.flash_attention(q, k * bad, v * bad, causal=True, window=W,
                                block_q=128, block_k=128)
    np.testing.assert_array_equal(np.asarray(out[:, :, 896:]),
                                  np.asarray(base[:, :, 896:]))


@pytest.mark.parametrize("T,window", [(200, 70), (300, 256), (64, 500)])
def test_fallback_and_blockwise_take_the_same_mask(T, window):
    q, k, v, co = _qkv(T, 2, T, 32)
    ref = masked_attention(q, k, v, window)
    for fn in (lambda *a: blockwise_attention(*a, block_size=64, causal=True,
                                              window=window),
               lambda *a: flash.flash_attention(*a, causal=True,
                                                window=window)):
        np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda k: jnp.vdot(flash.flash_attention(
        q, k, v, causal=True, window=window), co))(k)
    g_ref = jax.grad(lambda k: jnp.vdot(masked_attention(q, k, v, window),
                                        co))(k)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=2e-4,
                               atol=2e-4)


def test_a_window_needs_causal_self_attention(interpret_mode):
    from mxnet_tpu.base import MXNetError
    q, k, v, _ = _qkv(0, 1, 128, 64)
    with pytest.raises(MXNetError):
        flash.flash_attention(q, k, v, causal=False, window=16)
    with pytest.raises(MXNetError):
        flash.flash_attention(q, k[:, :, :64], v[:, :, :64], causal=True,
                              window=16)


def test_window_route_is_counted(interpret_mode):
    from mxnet_tpu import telemetry
    was_on = telemetry.is_enabled()
    telemetry.enable()
    family = telemetry.counter("mx_attention_route_total",
                               labelnames=("route",))
    try:
        q, k, v, _ = _qkv(0, 1, 128, 64)
        before = {r: int(family.get(r)) for r in ("flash", "flash_window")}
        flash.flash_attention(q, k, v, causal=True, window=16)
        flash.flash_attention(q, k, v, causal=True)
        assert {r: int(family.get(r)) - n for r, n in before.items()} == {
            "flash": 1, "flash_window": 1}
    finally:
        if not was_on:
            telemetry.disable()


# -- `window=None` is the parent's kernels ---------------------------------------

def _parent():
    """PR 31's flash_attention.py, loaded beside the package's own."""
    name = "mxnet_tpu.ops.pallas._flash_attention_pr31"
    loader = importlib.machinery.SourceFileLoader(name, PARENT)
    spec = importlib.util.spec_from_loader(name, loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


def _traced(mod, BH, T, D, causal, dtype):
    """The jaxpr of forward and backward of the Mosaic calls (not
    interpreted), as text with source locations stripped."""
    sds = jax.ShapeDtypeStruct((BH, T, D), dtype)
    blk = mod._block(T, 512)
    extra = (None,) if mod is flash else ()

    def f(q, k, v):
        return jax.grad(lambda *a: jnp.sum(mod._flash(
            *a, causal, D ** -0.5, blk, blk, False, *extra).astype(
                jnp.float32)), argnums=(0, 1, 2))(q, k, v)
    text = str(jax.make_jaxpr(f)(sds, sds, sds))
    return re.sub(r" at [^\s\]]+\.py:\d+", "", text).replace(
        "_flash_attention_pr31", "flash_attention")


@pytest.mark.parametrize("BH,T,D,causal,dtype", [
    (384, 512, 64, False, jnp.bfloat16),     # the BERT cells at t512
    (192, 1024, 64, False, jnp.bfloat16),    # t1024
    (64, 2048, 64, True, jnp.bfloat16),      # granite
    (2, 8192, 128, True, jnp.bfloat16),      # streamed, causal
    (2, 300, 128, True, jnp.float32),
])
def test_no_window_traces_to_the_parents_kernels(BH, T, D, causal, dtype):
    mine = _traced(flash, BH, T, D, causal, dtype)
    theirs = _traced(_parent(), BH, T, D, causal, dtype)
    assert "mx_flash_fwd" in mine and "mx_flash_bwd" in mine
    assert mine == theirs
