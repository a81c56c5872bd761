"""The flash kernels with v of another width than q and k (latent attention:
heads of 192 for the scores, 128 for the values). The kernels run in
interpret mode on the CPU against plain causal attention, forward and
backward, resident and tiled, with T a multiple of the block and not; the
plan of a call whose widths agree is the one an earlier version of the file
gives (its text is kept beside the tests), and the plan of two widths
counts each operand at its own width. The backward of a long head wider
than one lane tile walks blocks of 256 rows where its forward walks 512;
no other head's blocks change."""
import importlib
import importlib.machinery
import importlib.util
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops.attention import blockwise_attention

flash = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
PARENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                      "flash_attention_pr33.py.txt")


def causal_attention(q, k, v, scale):
    T = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    t = np.arange(T)
    p = jax.nn.softmax(jnp.where((t[None, :] <= t[:, None])[None, None], s,
                                 -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _qkv(seed, H, T, D, Dv):
    rs = np.random.RandomState(seed)

    def a(d):
        return jnp.asarray(rs.normal(0, 1, (1, H, T, d)).astype(np.float32))
    return a(D), a(D), a(Dv), a(Dv)


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


# (H, T, D, Dv, block limit, VMEM budget or None, schedule)
_CASES = [
    pytest.param(2, 256, 192, 128, 128, None, "resident", id="resident"),
    pytest.param(2, 300, 192, 128, 128, None, "resident",
                 id="resident_T_off_block"),
    pytest.param(1, 100, 192, 128, 512, None, "resident", id="one_tile"),
    pytest.param(1, 2560, 192, 128, 128, None, "tiled", id="tiled"),
    pytest.param(1, 1000, 192, 128, 128, 256 * 6656, "tiled",
                 id="tiled_T_off_block"),
    pytest.param(2, 200, 64, 128, 128, None, "resident", id="v_wider"),
    # the backward of a wide tiled head walks blocks of 256 where the
    # forward walks 512: chunks that pad T alike, and otherwise
    pytest.param(1, 2048, 192, 128, 512, 512 * 12288, "tiled",
                 id="wide_tiled"),
    pytest.param(1, 1300, 160, 128, 512, 1024 * 12288, "tiled",
                 id="wide_tiled_T_off_chunk"),
    pytest.param(2, 300, 160, 128, 128, None, "resident",
                 id="odd_width_resident"),
]


@pytest.mark.parametrize("H,T,D,Dv,limit,budget,schedule", _CASES)
def test_two_widths_against_plain_attention(
        interpret_mode, monkeypatch, H, T, D, Dv, limit, budget, schedule):
    if budget:
        monkeypatch.setattr(flash, "_VMEM_BLOCK_BYTES", budget)
        jax.clear_caches()
    blk = flash._block(T, limit)
    for backward in (False, True):
        plan = flash._plan(backward, H, T, T, D, np.float32, True, blk, blk,
                           Dv=Dv)
        assert plan.schedule == schedule, plan
    q, k, v, co = _qkv(T + D, H, T, D, Dv)
    scale = D ** -0.5

    def f(fn):
        def g(q, k, v):
            out = fn(q, k, v)
            return jnp.vdot(out.astype(jnp.float32), co), out
        return jax.grad(g, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    got, out = f(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True, block_q=limit, block_k=limit))
    want, ref = f(lambda q, k, v: causal_attention(q, k, v, scale))
    assert out.shape == (1, H, T, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")
    if budget:
        jax.clear_caches()


@pytest.mark.parametrize("T", [256, 300])
def test_two_widths_fallback_and_blockwise(T):
    """Off the kernels: the lax.scan path takes v's width as its own."""
    q, k, v, _ = _qkv(T, 2, T, 48, 32)
    want = causal_attention(q, k, v, 48 ** -0.5)
    got = blockwise_attention(q, k, v, block_size=128, causal=True)
    assert got.shape == (1, 2, T, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    dv = jax.grad(lambda v: jnp.sum(blockwise_attention(
        q, k, v, block_size=128, causal=True)))(v)
    dv_want = jax.grad(lambda v: jnp.sum(causal_attention(
        q, k, v, 48 ** -0.5)))(v)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_want),
                               rtol=2e-5, atol=2e-5)


def _kernel_grids(jaxpr, out):
    """(name, grid) of every Pallas call in `jaxpr` and the jaxprs in it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        tuple(eqn.params["grid_mapping"].grid)))
            continue
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                _kernel_grids(inner, out)
    return out


@pytest.mark.parametrize("D,wide", [(64, False), (128, False), (160, True),
                                    (192, True), (256, True)])
def test_wide_heads_walk_smaller_backward_blocks(D, wide):
    """A long causal head (tiled) wider than one lane tile: its backward
    call walks blocks of 256 rows in chunks of 1,024 where its forward walks
    512 in chunks of 2,048, counted once a traced layer in
    `mx_attention_wide_head_total`; a head of up to one tile keeps the
    earlier version's plan, forward and backward, and counts nothing."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import registry
    H, T = 2, 8192
    assert flash._wide_blocks(D, 512, 512) == ((256, 256) if wide
                                               else (512, 512))
    parent = _parent()
    plans = [flash._plan(back, H, T, T, D, jnp.bfloat16, True, 512, 512,
                         Dv=128) for back in (False, True)]
    assert [p.schedule for p in plans] == ["tiled", "tiled"]
    assert [(p.bk, p.cq) for p in plans] == \
        [(512, 2048), (256, 1024) if wide else (512, 2048)]
    if not wide:
        assert plans == [parent._plan(back, H, T, T, D, jnp.bfloat16, True,
                                      512, 512) for back in (False, True)]
    was_on = telemetry.is_enabled()
    telemetry.enable()
    counted = telemetry.counter("mx_attention_wide_head_total",
                                labelnames=("blocks",))
    before = int(counted.get("512->256"))
    tok = registry.exec_platform.set("tpu")
    try:
        qk = jax.ShapeDtypeStruct((1, H, T, D), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, H, T, 128), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash.flash_attention(q, k, v, causal=True).astype(
                jnp.float32)), argnums=(0, 1, 2)))(qk, qk, v)
    finally:
        registry.exec_platform.reset(tok)
        if not was_on:
            telemetry.disable()
    assert int(counted.get("512->256")) == before + wide
    assert sorted(_kernel_grids(jaxpr.jaxpr, [])) == [
        ("mx_flash_bwd", (H, 8, 8) if wide else (H, 4, 4)),
        ("mx_flash_fwd", (H, 4, 4))]


def test_latent_layer_through_the_kernels_as_through_plain_scores(
        monkeypatch):
    """A latent layer at the published widths (scores of 128 + 64, values
    of 128), tiled by a small VMEM budget, gives through the flash kernels
    what it gives through plain scores and a softmax, forward and backward;
    the layer is counted as a wide head once."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd, telemetry
    from mxnet_tpu.models import LatentAttention
    block = LatentAttention(64, 2, 32, 16, 128, 64, 128,
                            rope=dict(base=10000.0, interleaved=True))
    block.initialize(mx.init.Normal(0.2), ctx=mx.cpu())
    x = nd.array(np.random.RandomState(7).normal(0, 1, (1, 600, 64))
                 .astype(np.float32))
    x.attach_grad()

    def run():
        with mx.cpu(), autograd.record():
            out = block(x)
            loss = (out * out).sum()
        loss.backward()
        return out.asnumpy(), x.grad.asnumpy().copy()

    plain = run()
    monkeypatch.setenv("MXNET_FLASH_ATTENTION_MIN_SEQ", "16")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(flash, "_VMEM_BLOCK_BYTES", 384 * 12288)
    jax.clear_caches()
    was_on = telemetry.is_enabled()
    telemetry.enable()
    routes = telemetry.counter("mx_attention_route_total",
                               labelnames=("route",))
    counted = telemetry.counter("mx_attention_wide_head_total",
                                labelnames=("blocks",))
    before = int(routes.get("flash")), int(counted.get("384->256"))
    try:
        got = run()
    finally:
        if not was_on:
            telemetry.disable()
        jax.clear_caches()
    assert int(routes.get("flash")) - before[0] == 1
    assert int(counted.get("384->256")) - before[1] == 1
    for a, b in zip(got, plain):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_two_widths_take_no_window_or_selection(interpret_mode):
    q, k, v, _ = _qkv(0, 1, 64, 48, 32)
    with pytest.raises(MXNetError):
        flash.flash_attention(q, k, v, causal=True, window=8)
    with pytest.raises(MXNetError):
        flash.flash_attention(q, k, v, causal=True,
                              select=jnp.ones((1, 64, 64), jnp.int8))


def _parent():
    name = "mxnet_tpu.ops.pallas._flash_attention_earlier_plan"
    loader = importlib.machinery.SourceFileLoader(name, PARENT)
    spec = importlib.util.spec_from_loader(name, loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


_SHAPES = [
    # (BH, T, D, causal, window): the cells' calls and shapes round them
    (384, 512, 64, False, None), (192, 1024, 64, False, None),
    (64, 2048, 64, True, None), (48, 8192, 128, True, None),
    (72, 8192, 128, True, 512), (32, 16384, 128, True, None),
    (2, 300, 128, True, None), (4, 4096, 64, True, None),
    (8, 1100, 256, False, None), (32, 8192, 192, True, None),
]


@pytest.mark.parametrize("BH,T,D,causal,window", _SHAPES)
def test_one_width_keeps_the_parents_plan(BH, T, D, causal, window):
    """Block sizes, chunks, heads a step, VMEM bytes and schedule of a call
    whose v is as wide as q and k: as the earlier version planned them,
    whether the width is given or not; the tiled backward of a head wider
    than one lane tile as it planned blocks of 256."""
    parent = _parent()
    for backward in (False, True):
        for dtype in (jnp.bfloat16, jnp.float32):
            blk = flash._block(T, 512)
            want = parent._plan(backward, BH, T, T, D, dtype, causal, blk,
                                blk, window)
            if backward and D > 128 and want.schedule == "tiled":
                want = parent._plan(backward, BH, T, T, D, dtype, causal,
                                    256, 256, window)
            assert flash._plan(backward, BH, T, T, D, dtype, causal, blk,
                               blk, window) == want
            assert flash._plan(backward, BH, T, T, D, dtype, causal, blk,
                               blk, window, Dv=D) == want


def test_two_widths_plan_counts_each_operand_at_its_width():
    """At the latent cell's call (32 heads of 192 and 128 over 8,192
    positions, causal) the head is tiled, the forward in chunks of 2,048
    rows and the backward, a wide head's, in chunks of 1,024; its bytes
    lie between the plan of one width 128 at the same blocks and of one
    width 192, and a v padded to 192 would cost more VMEM than v at 128."""
    args = (32, 8192, 8192, 192, jnp.bfloat16, True, 512, 512)
    for backward in (False, True):
        plan = flash._plan(backward, *args, Dv=128)
        assert (plan.schedule, plan.cq, plan.n_qc) == \
            (("tiled", 1024, 8) if backward else ("tiled", 2048, 4))
        padded = flash._plan(backward, *args)
        narrow = flash._plan(backward, *args[:3], 128, *args[4:6],
                             plan.bq, plan.bk)
        assert narrow.nbytes < plan.nbytes < padded.nbytes
        assert plan.nbytes + (16 << 20) <= flash._VMEM_LIMIT_MAX


# What only the chip's compiler can say, said off the chip: Mosaic compiles
# the latent cell's kernels for a described v5e (no device attached). The
# topology is described inside the fixture, never at import.

@pytest.fixture()
def building_for_tpu():
    from mxnet_tpu.ops import registry
    tok = registry.exec_platform.set("tpu")
    yield
    registry.exec_platform.reset(tok)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_latent_kernels_compile_for_v5e_at_t8192(building_for_tpu, one_chip):
    """joyai_llm_flash_train_t8192's layer: 32 heads of 192 for the scores
    and 128 for the values over 8,192 positions, causal, tiled in 4 x 4
    chunk pairs; v, o, dO and dv stay 128 wide."""
    qk = jax.ShapeDtypeStruct((1, 32, 8192, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash.flash_attention(
        q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(qk, qk, v).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "f32[32,1,8192]" in text
    assert "bf16[32,8192,128]" in text and "bf16[32,8192,256]" not in text
