"""Learned sparse attention's pieces (PR 34), at toy sizes on the CPU: the
selection op against `lax.top_k` (a sequence that is not whole chunks, a
top-k over the whole sequence, planted ties), the selecting flash kernels in
interpret mode against masked plain attention (forward and backward, a
sequence that is not whole blocks, a block pair in which nothing is
selected), `select=None` lowering to the kernels of before the change, the
same set in the forward pass and in the recomputed backward pass, sectioned
rotary positions and the QK norm against their formulas, and the frozen
indexer through the fused trainer."""
import importlib
import importlib.machinery
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import sparse_select as S
from mxnet_tpu.ops.attention import blockwise_attention
from mxnet_tpu.ops.rotary import rotary_embedding, rotary_inv_freq

flash = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
PARENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                      "flash_attention_pr33.py.txt")


def _arr(rs, *shape):
    return jnp.asarray(rs.normal(0, 1, shape).astype(np.float32))


# -- the selection op ------------------------------------------------------------

def dense_select(q, k, w, top_k):
    """The set by `lax.top_k` on the whole T x T scores, written out
    independently of the op's chunks and bisection: bool (B, T, T)."""
    B, Hi, T, di = q.shape
    s = jnp.einsum("bhqd,bkd->bhqk", q, k)
    score = jnp.sum(jax.nn.relu(s) * jnp.moveaxis(w, 2, 1)[..., None],
                    axis=1) * (di * Hi) ** -0.5
    pos = np.arange(T)
    seen = pos[None, :] <= pos[:, None]
    _, idx = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), min(top_k, T))
    chosen = jnp.zeros((B, T, T), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None], idx
    ].set(True)
    return jnp.logical_and(chosen, seen)


def _selected(q, k, w, top_k, chunk):
    packed, report = S.indexer_select(q, k, w, top_k=top_k, chunk=chunk)
    T = q.shape[2]
    assert packed.dtype == jnp.uint8
    assert packed.shape == (q.shape[0], T, S.padded_keys(T, chunk) // 8)
    return np.asarray(S.unpack_selection(packed, keys=T)), np.asarray(report)


@pytest.mark.parametrize("B,H,T,d,top_k,chunk", [
    pytest.param(2, 2, 37, 8, 8, 8, id="T_not_whole_chunks"),
    pytest.param(1, 3, 64, 8, 8, 16, id="whole_chunks"),
    pytest.param(2, 2, 20, 8, 32, 8, id="top_k_over_T"),
    pytest.param(1, 2, 24, 8, 24, 8, id="top_k_equals_T"),
    pytest.param(1, 2, 100, 8, 5, 16, id="nine_groups_of_chunks"),
    pytest.param(1, 16, 96, 64, 16, 32, id="the_cells_heads"),
])
def test_selection_is_lax_top_ks_set(B, H, T, d, top_k, chunk):
    rs = np.random.RandomState(T)
    q, k, w = _arr(rs, B, H, T, d), _arr(rs, B, T, d), _arr(rs, B, T, H)
    got, report = _selected(q, k, w, top_k, chunk)
    want = np.asarray(dense_select(q, k, w, top_k))
    assert (got == want).all()
    rows = np.minimum(np.arange(T) + 1, top_k)
    assert (got.sum(-1) == rows).all()                  # every row's count
    assert got.dtype == np.int8 and set(np.unique(got)) <= {0, 1}
    assert not np.triu(got[0], 1).any()                 # causal
    assert report[0] == pytest.approx(rows.mean(), rel=1e-6)


@pytest.mark.parametrize("T,top_k,chunk", [(40, 8, 8), (37, 6, 16)])
def test_planted_ties_keep_the_first_keys(T, top_k, chunk):
    """All head weights zero: every score is equal, and the set of a query
    is its first `top_k` keys (the lower position first, as `lax.top_k`)."""
    rs = np.random.RandomState(3)
    q, k = _arr(rs, 2, 2, T, 8), _arr(rs, 2, T, 8)
    got, _ = _selected(q, k, jnp.zeros((2, T, 2)), top_k, chunk)
    pos = np.arange(T)
    first = (pos[None, :] < top_k) & (pos[None, :] <= pos[:, None])
    assert (got == first[None]).all()
    assert (got == np.asarray(dense_select(
        q, k, jnp.zeros((2, T, 2)), top_k))).all()


def test_partial_ties_and_signed_zeros():
    """Scores on a coarse grid (whole numbers, many equal, zeros of both
    signs from negative head weights on a ReLU's zeros): still `lax.top_k`'s
    set, -0.0 counting as 0.0."""
    rs = np.random.RandomState(5)
    q = jnp.round(_arr(rs, 2, 2, 48, 8))
    k = jnp.round(_arr(rs, 2, 48, 8))
    w = jnp.round(_arr(rs, 2, 48, 2))
    got, _ = _selected(q, k, w, 7, 8)
    assert (got == np.asarray(dense_select(q, k, w, 7))).all()


def test_empty_tiles_are_counted():
    """Ties keep the first keys, so every chunk x chunk tile past the first
    `top_k` keys and on or under the diagonal is empty."""
    T, top_k, chunk = 64, 8, 8
    rs = np.random.RandomState(0)
    got, report = _selected(_arr(rs, 1, 2, T, 8), _arr(rs, 1, T, 8),
                            jnp.zeros((1, T, 2)), top_k, chunk)
    n = T // chunk
    assert report[1] == sum(c for c in range(n))        # tiles 1..c of row c
    tiles = got[0].reshape(n, chunk, n, chunk).any(axis=(1, 3))
    assert report[1] == (~tiles & np.tril(np.ones((n, n), bool))).sum()


def test_pack_and_unpack_are_inverse():
    rs = np.random.RandomState(1)
    mask = rs.uniform(size=(2, 5, 48)) < 0.4
    packed = S.pack_selection(jnp.asarray(mask))
    assert packed.shape == (2, 5, 6) and packed.dtype == jnp.uint8
    assert (np.asarray(S.unpack_selection(packed, keys=48)) == mask).all()
    assert (np.asarray(S.unpack_selection(packed, keys=45))
            == mask[..., :45]).all()


# -- the selecting kernels --------------------------------------------------------

def masked_attention(q, k, v, select, scale=None):
    T, d = q.shape[2], q.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (scale or d ** -0.5)
    t = np.arange(T)
    live = jnp.logical_and((t[None, :] <= t[:, None])[None, None],
                           select[:, None] != 0)
    p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _case(seed, B, H, T, D, blk, empty):
    rs = np.random.RandomState(seed)
    q, k, v, g = (_arr(rs, B, H, T, D) for _ in range(4))
    sel = rs.uniform(size=(B, T, T)) < 0.3
    sel[:, :, 0] = True                 # every query keeps a key
    if empty:                           # a block pair with nothing selected
        sel[:, 2 * blk:3 * blk, blk:2 * blk] = False
    return q, k, v, g, jnp.asarray(sel.astype(np.int8))


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("B,H,T,D,blk,empty,schedule", [
    pytest.param(2, 2, 300, 128, 128, True, "resident", id="T_off_block"),
    pytest.param(1, 2, 640, 128, 128, True, "tiled", id="chunk_pairs"),
    pytest.param(1, 2, 700, 128, 128, True, "tiled",
                 id="chunk_pairs_T_off_block"),
    pytest.param(2, 4, 32, 16, 512, False, "resident", id="one_tile_toy"),
    pytest.param(1, 2, 384, 64, 128, True, "resident", id="d64"),
])
def test_selecting_kernels_against_masked_attention(
        interpret_mode, B, H, T, D, blk, empty, schedule):
    q, k, v, g, sel = _case(T, B, H, T, D, blk, empty)
    b = flash._block(T, blk)
    assert flash._plan(False, B * H, T, T, D, q.dtype, True, b, b, None,
                       True).schedule == schedule

    def mine(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, block_q=blk,
                                     block_k=blk, select=sel)

    def ref(q, k, v):
        return masked_attention(q, k, v, sel)

    np.testing.assert_allclose(mine(q, k, v), ref(q, k, v), atol=2e-5)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * g), argnums=(0, 1, 2))(
        q, k, v) for f in (mine, ref)]
    for a, b_ in zip(*grads):
        np.testing.assert_allclose(a, b_, atol=5e-5)


@pytest.mark.parametrize("T,blk", [(300, 128), (100, 32)])
def test_blockwise_route_takes_the_selection(T, blk):
    q, k, v, g, sel = _case(9, 2, 2, T, 16, blk, T > 3 * blk)

    def mine(q, k, v):
        return blockwise_attention(q, k, v, causal=True, block_size=blk,
                                   select=sel)
    np.testing.assert_allclose(mine(q, k, v), masked_attention(q, k, v, sel),
                               atol=2e-5)
    for a, b in zip(
            jax.grad(lambda *a: jnp.sum(mine(*a) * g), (0, 1, 2))(q, k, v),
            jax.grad(lambda *a: jnp.sum(masked_attention(*a, sel) * g),
                     (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_a_selection_needs_causal_self_attention_of_its_shape():
    from mxnet_tpu.base import MXNetError
    q = jnp.zeros((1, 2, 16, 8))
    sel = jnp.ones((1, 16, 16), jnp.int8)
    with pytest.raises(MXNetError):
        flash.flash_attention(q, q, q, causal=False, select=sel)
    with pytest.raises(MXNetError):
        flash.flash_attention(q, q, q, causal=True, window=4, select=sel)
    with pytest.raises(MXNetError):
        flash.flash_attention(q, q, q, causal=True, select=sel[:, :8])


def test_the_route_is_counted(monkeypatch):
    from mxnet_tpu import telemetry
    monkeypatch.setattr(telemetry, "_ENABLED", True)
    series = telemetry.counter(
        "mx_attention_route_total",
        "Attention layers traced, by the route they took",
        ("route",)).labels("flash_select")
    before = series.value
    q = jnp.zeros((1, 2, 16, 8))
    flash.flash_attention(q, q, q, causal=True,
                          select=jnp.ones((1, 16, 16), jnp.int8))
    assert series.value == before + 1


# -- `select=None` is the kernels of before the change ------------------------------

def _parent():
    """PR 33's flash_attention.py, loaded beside the package's own."""
    name = "mxnet_tpu.ops.pallas._flash_attention_pr33"
    loader = importlib.machinery.SourceFileLoader(name, PARENT)
    spec = importlib.util.spec_from_loader(name, loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


def _traced(mod, BH, T, D, causal, dtype, window):
    """The jaxpr of forward and backward of the Mosaic calls (not
    interpreted), as text with source locations stripped."""
    sds = jax.ShapeDtypeStruct((BH, T, D), dtype)
    blk = mod._block(T, 512)

    def f(q, k, v):
        return jax.grad(lambda *a: jnp.sum(mod._flash(
            *a, causal, D ** -0.5, blk, blk, False, window).astype(
                jnp.float32)), argnums=(0, 1, 2))(q, k, v)
    text = str(jax.make_jaxpr(f)(sds, sds, sds))
    return re.sub(r" at [^\s\]]+\.py:\d+", "", text).replace(
        "_flash_attention_pr33", "flash_attention")


@pytest.mark.parametrize("BH,T,D,causal,dtype,window", [
    pytest.param(384, 512, 64, False, jnp.bfloat16, None, id="t512_dp4"),
    pytest.param(192, 1024, 64, False, jnp.bfloat16, None, id="t1024"),
    pytest.param(64, 2048, 64, True, jnp.bfloat16, None, id="granite"),
    pytest.param(48, 8192, 128, True, jnp.bfloat16, None, id="laguna_full"),
    pytest.param(72, 8192, 128, True, jnp.bfloat16, 512,
                 id="laguna_window"),
    pytest.param(2, 300, 128, True, jnp.float32, None, id="f32_off_block"),
])
def test_no_selection_traces_to_the_parents_kernels(BH, T, D, causal, dtype,
                                                    window):
    mine = _traced(flash, BH, T, D, causal, dtype, window)
    theirs = _traced(_parent(), BH, T, D, causal, dtype, window)
    assert "mx_flash_fwd" in mine and "mx_flash_bwd" in mine
    assert mine == theirs


# -- the same set forward and backward ------------------------------------------------

def _tiny(**kw):
    import mxnet_tpu as mx
    from mxnet_tpu.models import indexed_moe_decoder_tiny
    net = indexed_moe_decoder_tiny(**kw)
    net.initialize(mx.init.Normal(0.05), ctx=mx.cpu())
    return net


def _loss_of(net):
    """A pure function of the net's leaves over one batch of ids."""
    from mxnet_tpu.parallel.data_parallel import _make_apply_fn
    plist = list(net.collect_params().values())
    apply_fn = _make_apply_fn(net, plist, train=True)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 32)),
                      jnp.int32)

    def loss(ps):
        out, _ = apply_fn(jax.random.PRNGKey(0), ps, ids)
        return jnp.mean(jnp.square(out.astype(jnp.float32)))
    return loss, plist, [p.data()._data for p in plist]


def test_the_backward_pass_masks_by_the_forward_passs_set():
    """A recomputed indexed layer keeps its packed selection from the
    forward pass (`recompute(keep=("mx.select",))`): it is among the values
    saved for the backward pass, once a layer, and the indexer's scores are
    not computed again."""
    from jax.ad_checkpoint import print_saved_residuals
    net = _tiny()
    loss, plist, leaves = _loss_of(net)
    import io
    import contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        print_saved_residuals(loss, leaves)
    named = [line for line in buf.getvalue().splitlines()
             if "mx.select" in line]
    assert len(named) == 2 and all("u8[2,32,4]" in n for n in named), named
    # the bisection and the packing shift bits to the left and nothing else
    # of the model does: as many of them in the gradient's program as in the
    # forward pass alone
    text = str(jax.make_jaxpr(jax.grad(loss))(leaves))
    forward = str(jax.make_jaxpr(loss)(leaves))
    loops = lambda t: t.count("shift_left")
    assert loops(forward) > 0 and loops(text) == loops(forward)
    # without the name kept, the backward pass scores and selects again
    for layer in net.layers._children.values() \
            if hasattr(net.layers, "_children") else []:
        layer.recompute(keep=())
    again = str(jax.make_jaxpr(jax.grad(_loss_of(net)[0]))(leaves))
    assert loops(again) == 2 * loops(forward)


def test_the_indexer_is_frozen_and_gets_no_gradient():
    net = _tiny()
    loss, plist, leaves = _loss_of(net)
    grads = jax.grad(loss)(leaves)
    frozen = [p.grad_req == "null" for p in plist]
    # per layer: the indexer's three projections, its norm's weight and
    # bias, and two leaves of state (selection, routing)
    assert sum(frozen) == 2 * 7
    for p, g, f in zip(plist, grads, frozen):
        if f:
            assert float(jnp.abs(g).max()) == 0.0, p.name
        elif "router" not in p.name:
            assert float(jnp.abs(g).max()) > 0.0, p.name


def test_frozen_leaves_ride_the_fused_step():
    """Through `DataParallelTrainer.step`: the indexer's leaves come back as
    they went in, bit for bit, with no optimizer state; the others move; the
    layers' `selection` state holds the step's report."""
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    net = _tiny()
    plist = list(net.collect_params().values())
    before = [np.asarray(p.data()._data) for p in plist]

    def token_loss(logits, labels):
        logits = logits.astype(jnp.float32)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)

    trainer = DataParallelTrainer(
        net, token_loss, optimizer="adamw",
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        optimizer_params={"learning_rate": 1e-3})
    rs = np.random.RandomState(1)
    x = rs.randint(0, 256, (2, 32)).astype(np.int32)
    for _ in range(2):
        trainer.step(x, x)
    trainer.drain()
    for p, w0, w1, s in zip(plist, before, trainer._params_raw,
                            trainer._opt_state):
        if p.name.endswith("selection"):
            assert np.asarray(w1)[0] == pytest.approx(7.125)    # mean kept
        elif p.name.endswith("routing"):
            assert np.asarray(w1)[0] > 0
        elif p.grad_req == "null":
            assert s == () and (np.asarray(w1) == w0).all(), p.name
        else:
            assert s != () and not (np.asarray(w1) == w0).all(), p.name


# -- sectioned rotary positions ---------------------------------------------------------

def test_sectioned_rotary_against_the_formula():
    """Three unequal rows of positions; frequency pairs 0-1 / 2-4 / 5-7 each
    turned by its own row."""
    rs = np.random.RandomState(2)
    B, H, T, d, sections, base = 2, 3, 10, 16, (2, 3, 3), 1e7
    x = _arr(rs, B, H, T, d)
    pos = rs.randint(0, 50, (3, B, T)).astype(np.int32)
    got = np.asarray(rotary_embedding(x, jnp.asarray(pos), base=base,
                                      sections=sections))
    inv = base ** (-np.arange(0, d, 2) / d)
    row = np.repeat(np.arange(3), sections)
    want = np.empty_like(got)
    for b in range(B):
        for t in range(T):
            for i in range(d // 2):
                a = pos[row[i], b, t] * inv[i]
                x1, x2 = np.asarray(x[b, :, t, i]), \
                    np.asarray(x[b, :, t, i + d // 2])
                want[b, :, t, i] = x1 * np.cos(a) - x2 * np.sin(a)
                want[b, :, t, i + d // 2] = x2 * np.cos(a) + x1 * np.sin(a)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert rotary_inv_freq(d, base) == pytest.approx(inv, rel=1e-6)


def test_sectioned_rotary_with_equal_rows_is_the_plain_op():
    rs = np.random.RandomState(4)
    x = _arr(rs, 2, 3, 12, 16)
    plain = rotary_embedding(x, base=1e7)
    rows = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (3, 2, 12))
    assert (np.asarray(rotary_embedding(x, rows, base=1e7,
                                        sections=(2, 3, 3)))
            == np.asarray(plain)).all()
    assert (np.asarray(rotary_embedding(x, base=1e7, sections=(2, 3, 3)))
            == np.asarray(plain)).all()
    with pytest.raises(ValueError):
        rotary_embedding(x, base=1e7, sections=(2, 3, 4))
    with pytest.raises(ValueError):
        rotary_embedding(x, rows, base=1e7)


# -- the QK norm ---------------------------------------------------------------------------

def test_qk_norm_against_the_formula():
    """One mixer with a QK norm and nothing else against the written-out
    attention: each head of q and of k normed over its d numbers by one
    weight a side."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import GroupedQueryAttention
    units, H, G, d, eps = 24, 4, 2, 8, 1e-6
    mixer = GroupedQueryAttention(units, H, G, head_dim=d, qk_norm=eps)
    mixer.initialize(mx.init.Normal(0.3), ctx=mx.cpu())
    rs = np.random.RandomState(6)
    wq, wk = rs.uniform(0.5, 1.5, d), rs.uniform(0.5, 1.5, d)
    mixer.query_norm.gamma.set_data(wq.astype(np.float32))
    mixer.key_norm.gamma.set_data(wk.astype(np.float32))
    x = rs.normal(0, 1, (2, 10, units)).astype(np.float32)
    got = mixer(nd.array(x)).asnumpy()

    def proj(block, heads):
        w = block.weight.data().asnumpy()
        return (x @ w.T).reshape(2, 10, heads, d).transpose(0, 2, 1, 3)

    def rms(v, w):
        return w * v / np.sqrt((v * v).mean(-1, keepdims=True) + eps)

    q, k = rms(proj(mixer.query, H), wq), rms(proj(mixer.key, G), wk)
    v = proj(mixer.value, G)
    k, v = np.repeat(k, H // G, 1), np.repeat(v, H // G, 1)
    s = q @ k.transpose(0, 1, 3, 2) / np.sqrt(d)
    s = np.where(np.tril(np.ones((10, 10), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    o = (p / p.sum(-1, keepdims=True)) @ v
    want = o.transpose(0, 2, 1, 3).reshape(2, 10, H * d) \
        @ mixer.proj.weight.data().asnumpy().T
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and without the norm the mixer is another function
    plain = GroupedQueryAttention(units, H, G, head_dim=d)
    plain.initialize(mx.init.Normal(0.3), ctx=mx.cpu())
    for name in ("query", "key", "value", "proj"):
        getattr(plain, name).weight.set_data(
            getattr(mixer, name).weight.data())
    assert np.abs(plain(nd.array(x)).asnumpy() - want).max() > 1e-3


def test_the_other_families_build_their_programs_unchanged():
    """Granite's and laguna's arguments give the leaves they gave: no QK
    norm, no indexer, no state of a selection, the shared expert there."""
    from mxnet_tpu.models import hybrid_decoder_tiny, windowed_moe_decoder_tiny
    for make, count in ((hybrid_decoder_tiny, None),
                        (windowed_moe_decoder_tiny, None)):
        names = list(make().collect_params().keys())
        assert not [n for n in names if "selection" in n or "layernorm" in n]
    net = windowed_moe_decoder_tiny()
    assert net.layers[1].ffn.shared is not None
    assert net.layers[1].mixer.query_norm is None
