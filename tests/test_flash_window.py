"""The window of the flash kernels (PR 32): query t sees the keys
t - window < t' <= t. The kernels run in interpret mode on the CPU against
masked plain attention, forward and backward, on resident and on streamed
heads; `window=None` traces to the parent's kernels, operation for operation
(the parent's file is kept beside the tests as text). Since PR 33 a head too
long to be resident and unrolled is cut into a `band` (a window that reaches
few blocks back) or `tiled` into chunk pairs: their cases, the poison tests
of what neither may read, and the plan at the cells' shapes are below."""
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


# (H, T, D, window, block limit, VMEM budget or None, schedule)
_CASES = [
    pytest.param(2, 300, 128, 70, 128, None, "resident", id="T_and_W_off_block"),
    pytest.param(2, 512, 128, 128, 128, None, "resident", id="W_one_block"),
    pytest.param(1, 640, 128, 300, 128, None, "band", id="W_spans_blocks"),
    pytest.param(2, 200, 128, 200, 128, None, "resident", id="W_equals_T"),
    pytest.param(2, 200, 128, 1000, 128, None, "resident", id="W_over_T"),
    pytest.param(2, 160, 64, 1, 128, None, "resident", id="W_one_key"),
    # one tile a head, padded rows whose band holds no real key: the toy cell
    pytest.param(6, 32, 16, 8, 512, None, "resident", id="one_tile_padded"),
    pytest.param(2, 200, 64, 30, 512, None, "resident", id="one_tile_padded_d64"),
    pytest.param(1, 1920, 64, 512, 128, None, "tiled", id="too_long_to_unroll"),
    pytest.param(1, 1000, 128, 200, 128, 256 * 4096, "band",
                 id="streamed_W_off_block"),
    pytest.param(1, 1024, 128, 512, 256, 512 * 4096, "band",
                 id="streamed_W_two_blocks"),
    pytest.param(1, 768, 128, 2000, 128, 256 * 4096, "tiled",
                 id="streamed_W_over_T"),
    # PR 33. A band: chunks of whole halos, the first chunk's halo skipped
    pytest.param(1, 1280, 64, 70, 128, None, "band", id="band_W_under_a_block"),
    pytest.param(2, 1280, 64, 128, 128, None, "band", id="band_W_one_block"),
    pytest.param(1, 1024, 64, 200, 128, None, "band", id="band_W_off_block"),
    pytest.param(1, 1024, 64, 384, 128, None, "band",
                 id="band_W_three_blocks"),
    # T no multiple of the chunk (two of 640 rows): the last 180 rows are
    # padding, and the band of the last of them holds no real key
    pytest.param(1, 1100, 64, 70, 128, None, "band", id="band_padded_tail"),
    pytest.param(1, 1100, 128, 300, 128, 384 * 4096, "band",
                 id="band_padded_tail_small_chunks"),
    # too wide for a band: chunk pairs, those behind the band dead
    pytest.param(1, 1536, 64, 700, 128, None, "tiled",
                 id="tiled_W_over_a_chunk"),
    pytest.param(1, 1100, 64, 5000, 128, None, "tiled", id="tiled_W_over_T"),
]


@pytest.mark.parametrize("H,T,D,window,limit,budget,schedule", _CASES)
def test_window_kernels_against_masked_attention(
        interpret_mode, monkeypatch, H, T, D, window, limit, budget, schedule):
    if budget:
        monkeypatch.setattr(flash, "_VMEM_BLOCK_BYTES", budget)
        jax.clear_caches()
    blk = flash._block(T, limit)
    plan = flash._plan(False, H, T, T, D, np.float32, True, blk, blk, window)
    assert plan.schedule == schedule, plan
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


def _grads_of(q, k, v, co, **kw):
    def f(q, k, v):
        out = flash.flash_attention(q, k, v, block_q=128, block_k=128, **kw)
        return jnp.vdot(out.astype(jnp.float32), co), out
    return jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)


def test_a_band_reads_nothing_behind_its_halo(interpret_mode):
    """Two chunks of 1,024 rows, W = 128: the second chunk's keys are its own
    rows and one block before them. With every key and value behind that
    halo set to NaN its rows' output and dq stay what they were."""
    H, T, D, W = 1, 2048, 64, 128
    plan = flash._plan(False, H, T, T, D, np.float32, True, 128, 128, W)
    assert (plan.schedule, plan.cq, plan.n_qc, plan.halo) \
        == ("band", 1024, 2, 1)
    q, k, v, co = _qkv(7, H, T, D)
    (dq, _, _), out = _grads_of(q, k, v, co, causal=True, window=W)
    bad = jnp.where(jnp.arange(T)[None, None, :, None] < 896, jnp.nan, 1.0)
    (dq_bad, _, _), out_bad = _grads_of(q, k * bad, v * bad, co, causal=True,
                                        window=W)
    for a, b in ((out, out_bad), (dq, dq_bad)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a[:, :, 1024:]),
                                      np.asarray(b[:, :, 1024:]))


def test_a_tiled_causal_head_reads_no_dead_chunk_pair(interpret_mode):
    """Three chunks of 512 rows a side: the pairs above the diagonal are no
    grid step's work and their keys are not fetched for them. With the keys
    and values of every chunk after a query chunk's own set to NaN that
    chunk's output and dq stay what they were."""
    H, T, D = 1, 1536, 64
    plan = flash._plan(False, H, T, T, D, np.float32, True, 128, 128)
    assert (plan.schedule, plan.cq, plan.n_qc, plan.n_kc) \
        == ("tiled", 512, 3, 3)
    q, k, v, co = _qkv(11, H, T, D)
    (dq, _, _), out = _grads_of(q, k, v, co, causal=True)
    assert np.isfinite(np.asarray(out)).all()
    for chunk in range(2):
        end = 512 * (chunk + 1)
        bad = jnp.where(jnp.arange(T)[None, None, :, None] >= end, jnp.nan,
                        1.0)
        (dq_bad, _, _), out_bad = _grads_of(q, k * bad, v * bad, co,
                                            causal=True)
        for a, b in ((out, out_bad), (dq, dq_bad)):
            np.testing.assert_array_equal(np.asarray(a[:, :, :end]),
                                          np.asarray(b[:, :, :end]))


# (B*H, T, d, causal, window) of the flash calls of the six cells
_CELL_CALLS = {
    "bert_base_train_t512": (384, 512, 64, False, None),
    "bert_base_train_dp4": (384, 512, 64, False, None),
    "bert_base_train_t1024": (192, 1024, 64, False, None),
    "granite4_h_micro_train_t2048": (64, 2048, 64, True, None),
    "laguna_sliding_layer": (72, 8192, 128, True, 512),
    "laguna_full_layer": (48, 8192, 128, True, None),
}


def _kernel_jaxprs(jaxpr, out):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append((e.params["name"], str(e.params["jaxpr"])))
            continue
        for p in e.params.values():
            inner = getattr(p, "jaxpr", p)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                _kernel_jaxprs(inner, out)
    return out


@pytest.mark.parametrize("cell", sorted(_CELL_CALLS))
def test_the_plan_at_the_cells_shapes(cell):
    """laguna's two kinds of layer are cut so that every walk has bounds that
    are Python ints (no rolled loop is left in either kernel's text); the four other
    attention cells stay resident. The telemetry names the schedule beside
    the route as the call is traced."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import registry
    BH, T, D, causal, window = _CELL_CALLS[cell]
    want = "resident" if "laguna" not in cell \
        else "band" if window else "tiled"
    for backward in (False, True):
        plan = flash._plan(backward, BH, T, T, D, jnp.bfloat16, causal, 512,
                           512, window)
        assert plan.schedule == want, plan
        assert all(isinstance(x, int) for x in plan[1:]), plan
        assert plan.nbytes + (16 << 20) <= flash._VMEM_LIMIT_MAX
    was_on = telemetry.is_enabled()
    telemetry.enable()
    routes = telemetry.counter("mx_attention_route_total",
                               labelnames=("route",))
    schedules = telemetry.counter("mx_attention_schedule_total",
                                  labelnames=("schedule",))
    route = "flash_window" if window else "flash"
    before = int(routes.get(route)), int(schedules.get(want))
    tok = registry.exec_platform.set("tpu")
    try:
        x = jax.ShapeDtypeStruct((1, 2, T, D), jnp.bfloat16)
        kw = {"window": window} if window else {}
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash.flash_attention(q, k, v, causal=causal, **kw).astype(
                jnp.float32)), argnums=(0, 1, 2)))(x, x, x)
    finally:
        registry.exec_platform.reset(tok)
        if not was_on:
            telemetry.disable()
    assert (int(routes.get(route)), int(schedules.get(want))) \
        == (before[0] + 1, before[1] + 1)
    kernels = _kernel_jaxprs(jaxpr.jaxpr, [])
    assert sorted(name for name, _ in kernels) \
        == ["mx_flash_bwd", "mx_flash_fwd"]
    if want != "resident":
        # a loop with static bounds is a `scan` that unrolls; one whose
        # bounds the kernel computes, or over heads, would be a `while`
        for name, text in kernels:
            assert "while[" not in text, name
            assert re.findall(r"\blength=(\d+)", text) \
                == re.findall(r"\bunroll=(\d+)", text), name


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
    (2, 300, 128, True, jnp.float32),
])
def test_no_window_traces_to_the_parents_kernels(BH, T, D, causal, dtype):
    mine = _traced(flash, BH, T, D, causal, dtype)
    theirs = _traced(_parent(), BH, T, D, causal, dtype)
    assert "mx_flash_fwd" in mine and "mx_flash_bwd" in mine
    assert mine == theirs
