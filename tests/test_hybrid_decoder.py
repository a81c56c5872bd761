"""The hybrid decoder (Mamba-2 and grouped-KV attention layers) and what it
brought: the chunked state-space scan against the step-by-step recurrence,
the causal convolution, RMSNorm and its gated form, grouped KV heads with a
stated scale, recomputed blocks in the fused step, and the vocabulary's
slices against the uncut model. Small sizes, CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import hybrid_decoder as hd
from mxnet_tpu.ops import ssm


# -- the scan -----------------------------------------------------------------

def _scan_inputs(seed, b=2, t=37, h=4, p=8, g=1, n=16):
    """Heads of long and of short memory: A spreads over four orders."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jnp.linspace(-6.0, 3.0, h))
    B = jax.random.normal(ks[2], (b, t, g, n))
    C = jax.random.normal(ks[3], (b, t, g, n))
    D = jax.random.normal(ks[4], (h,))
    return x, dt, A, B, C, D


def _recurrence(x, dt, A, B, C, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t."""
    b, t, h, p = x.shape
    rep = h // B.shape[2]
    Bh, Ch = jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * A)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)
    s0 = jnp.zeros((b, h, p, B.shape[3]))
    _, y = jax.lax.scan(step, s0, tuple(jnp.moveaxis(v, 1, 0)
                                        for v in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


@pytest.mark.parametrize("t,chunk,groups", [(37, 8, 1), (32, 8, 2), (5, 8, 1),
                                            (64, 16, 4)])
def test_chunked_scan_equals_the_recurrence(t, chunk, groups):
    args = _scan_inputs(0, t=t, g=groups)
    want = _recurrence(*args)
    got = ssm.ssd_scan(*args, chunk_size=chunk)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # the long-memory head carries state over every chunk: leaving the carry
    # out must show
    one = ssm.ssd_scan(*(a[:, :chunk] if a.ndim > 1 else a for a in args),
                       chunk_size=chunk)
    if t > chunk:
        two = ssm.ssd_scan(*(a[:, chunk:2 * chunk] if a.ndim > 1 else a
                             for a in args), chunk_size=chunk)
        assert float(jnp.max(jnp.abs(two - want[:, chunk:2 * chunk]))) > 1e-2
    np.testing.assert_allclose(one, want[:, :chunk], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("wrt", range(6), ids=["x", "dt", "A", "B", "C", "D"])
def test_chunked_scan_gradients_equal_the_recurrences(wrt):
    args = _scan_inputs(1, t=29)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def of(fn):
        return jax.grad(lambda a: jnp.sum(w * fn(*args[:wrt], a,
                                                 *args[wrt + 1:])))(args[wrt])
    got = of(lambda *a: ssm.ssd_scan(*a, chunk_size=8))
    want = of(_recurrence)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4 * scale)


def test_scan_keeps_decays_and_state_in_float32_under_bfloat16():
    args = _scan_inputs(2, t=40)
    want = _recurrence(*args)
    low = [a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
           for i, a in enumerate(args)]
    got = ssm.ssd_scan(*low, chunk_size=8)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(jnp.max(err)) < 0.05 * float(jnp.max(jnp.abs(want)))


def test_registered_ops_reach_the_scan_and_the_convolution():
    args = _scan_inputs(3, t=16)
    got = nd._contrib_ssd_scan(*(nd.array(np.asarray(a)) for a in args),
                               chunk_size=8)
    np.testing.assert_allclose(got.asnumpy(), _recurrence(*args), rtol=2e-4,
                               atol=2e-4)


# -- the convolution and the norms ---------------------------------------------

def test_causal_convolution_is_a_shifted_sum():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 9, 5).astype(np.float32)
    w = rng.randn(5, 4).astype(np.float32)
    bias = rng.randn(5).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):
            src = t - 3 + k
            if src >= 0:
                want[:, t] += w[:, k] * x[:, src]
    want += bias
    got = nd._contrib_causal_conv1d(nd.array(x), nd.array(w), nd.array(bias))
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)
    # causal: a later position changes no earlier output
    x2 = x.copy()
    x2[:, 6:] += 1.0
    got2 = ssm.causal_conv1d(jnp.asarray(x2), jnp.asarray(w), None)
    np.testing.assert_allclose(got2[:, :6], want[:, :6] - bias, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("gated", [False, True])
def test_rms_norm(gated):
    rng = np.random.RandomState(1)
    x = rng.randn(3, 7, 16).astype(np.float32)
    z = rng.randn(3, 7, 16).astype(np.float32)
    gamma = rng.rand(16).astype(np.float32) + 0.5
    layer = mx.gluon.nn.RMSNorm(epsilon=1e-5, in_channels=16)
    layer.initialize()
    layer.gamma.set_data(nd.array(gamma))
    v = x * (z / (1 + np.exp(-z))) if gated else x
    want = gamma * v / np.sqrt(np.mean(v * v, axis=-1, keepdims=True) + 1e-5)
    got = layer(nd.array(x), nd.array(z)) if gated else layer(nd.array(x))
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)
    low = nd.RMSNorm(nd.array(x).astype("bfloat16"),
                     nd.array(gamma).astype("bfloat16"))
    assert low.dtype == jnp.bfloat16      # computed in float32, returned low


# -- grouped KV heads with a stated scale --------------------------------------

def _plain_attention(x, wq, wk, wv, wo, heads, kv_heads, scale):
    b, t, c = x.shape
    d = c // heads
    q = (x @ wq.T).reshape(b, t, heads, d).transpose(0, 2, 1, 3)
    k = (x @ wk.T).reshape(b, t, kv_heads, d).transpose(0, 2, 1, 3)
    v = (x @ wv.T).reshape(b, t, kv_heads, d).transpose(0, 2, 1, 3)
    k, v = (np.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("bhqk,bhkd->bhqd", e / e.sum(-1, keepdims=True), v)
    return o.transpose(0, 2, 1, 3).reshape(b, t, c) @ wo.T


@pytest.mark.parametrize("path", ["plain", "flash_op"])
def test_grouped_kv_attention_with_a_stated_scale(path, monkeypatch):
    if path == "flash_op":      # the registered op from the first position on
        monkeypatch.setenv("MXNET_FLASH_ATTENTION_MIN_SEQ", "1")
    rng = np.random.RandomState(2)
    x = rng.randn(2, 12, 32).astype(np.float32)
    att = hd.GroupedQueryAttention(32, num_heads=4, num_kv_heads=2,
                                   scale=1 / 8)        # d = 8: not 1/sqrt(d)
    att.initialize(mx.init.Normal(0.2))
    got = att(nd.array(x)).asnumpy()
    w = [p.data().asnumpy() for p in (att.query.weight, att.key.weight,
                                      att.value.weight, att.proj.weight)]
    assert w[1].shape == (16, 32)                       # 2 KV heads of 8
    want = _plain_attention(x, *w, heads=4, kv_heads=2, scale=1 / 8)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    other = _plain_attention(x, *w, heads=4, kv_heads=2, scale=8 ** -0.5)
    assert np.max(np.abs(other - want)) > 1e-3


def test_flash_attention_op_takes_a_scale_and_defaults_as_before():
    rng = np.random.RandomState(3)
    q, k, v = (nd.array(rng.randn(1, 2, 16, 8).astype(np.float32))
               for _ in range(3))

    def plain(scale):
        s = np.einsum("bhqd,bhkd->bhqk", q.asnumpy(), k.asnumpy()) * scale
        s = np.where(np.tril(np.ones((16, 16), bool)), s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        return np.einsum("bhqk,bhkd->bhqd", e / e.sum(-1, keepdims=True),
                         v.asnumpy())
    got = nd._contrib_flash_attention(q, k, v, causal=True, scale=1 / 8)
    np.testing.assert_allclose(got.asnumpy(), plain(1 / 8), rtol=1e-4,
                               atol=1e-5)
    got = nd._contrib_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got.asnumpy(), plain(8 ** -0.5), rtol=1e-4,
                               atol=1e-5)


# -- recomputed blocks in the fused step ----------------------------------------

def _token_loss(logits, labels):
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def _trainer(recompute, weights=None, dtype="float32", **model):
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    mx.random.seed(7)
    net = hd.hybrid_decoder_tiny(recompute=recompute, **model)
    net.initialize(mx.init.Normal(0.05), ctx=mx.cpu())
    params = list(net.collect_params().values())
    if weights is not None:
        for p, w in zip(params, weights):
            p.set_data(nd.array(w))
    mesh = make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])
    tr = DataParallelTrainer(net, _token_loss, optimizer="adamw", mesh=mesh,
                             optimizer_params={"learning_rate": 1e-3},
                             dtype=dtype)
    return net, tr, [p.data().asnumpy() for p in params]


def _batch(seed, vocab=256, b=4, t=24):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, vocab, (b, t)).astype(np.int32),
            rng.randint(0, vocab, (b, t)).astype(np.int32))


def test_recomputed_layers_give_the_same_step():
    x, y = _batch(0)
    _, plain, weights = _trainer(False)
    _, again, _ = _trainer(True, weights)
    losses = [float(tr.step(x, y)) for tr in (plain, again)]
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)
    for a, b in zip(plain._params_raw, again._params_raw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def _step_program(tr, x, y):
    step = jax.jit(tr._build_any_step())
    scalar = jnp.float32(1)
    return step.lower(tr._params_raw, tr._opt_state,
                      jnp.zeros((2,), jnp.uint32), jnp.asarray(x),
                      jnp.asarray(y), scalar, scalar, scalar)


def test_recomputed_layers_keep_less_between_the_passes():
    """What lives from the forward pass to the backward one (the residuals
    of the loss's vjp) shrinks to the layers' inputs; the compiled step's
    temporaries shrink too (by how much is the backend's scheduling: the
    TPU's compiler, which orders for memory, is what PERF.md reports)."""
    from mxnet_tpu.parallel.data_parallel import _make_apply_fn
    x, y = _batch(1, b=8, t=64)
    kw = dict(layer_types=("mamba",) * 4 + ("attention",))
    kept, temps, texts = [], [], []
    for recompute in (False, True):
        net, tr, _ = _trainer(recompute, **kw)
        apply_fn = _make_apply_fn(net, tr._plist, train=True)

        def lossf(ps):
            out, _ = apply_fn(jnp.zeros((2,), jnp.uint32), ps, jnp.asarray(x))
            return _token_loss(out, jnp.asarray(y))
        _, pullback = jax.vjp(lossf, list(tr._params_raw))
        kept.append(sum(a.nbytes for a in jax.tree_util.tree_leaves(pullback)
                        if hasattr(a, "nbytes")))
        lowered = _step_program(tr, x, y)
        texts.append(lowered.as_text(debug_info=True))
        temps.append(lowered.compile().memory_analysis().temp_size_in_bytes)
    assert kept[1] < 0.25 * kept[0], kept
    assert temps[1] < temps[0], temps
    assert "checkpoint" in texts[1] and "checkpoint" not in texts[0]


def test_recompute_is_a_property_of_the_block_and_of_traces_only():
    net = hd.hybrid_decoder_tiny(recompute=True)
    layers = [net.layers[i] for i in range(len(net.layers))]
    assert all(layer._recompute for layer in layers)
    assert not net._recompute and not layers[0].mixer._recompute
    assert "_recompute" not in vars(mx.gluon.nn.Dense(3))
    plain = hd.hybrid_decoder_tiny(recompute=False)
    assert not any(plain.layers[i]._recompute for i in range(3))
    # eager calls (no enclosing trace) run the forward as it is written
    net.initialize(mx.init.Normal(0.05))
    ids = nd.array(_batch(2)[0], dtype="int32")
    assert net(ids).shape == (4, 24, 256)


def test_recomputed_block_hands_on_deferred_aux_updates():
    """A recomputed block with BatchNorm inside: the running statistics
    leave the recomputed region as outputs and still reach the trainer."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    def build(recompute):
        mx.random.seed(3)
        body = nn.HybridSequential()
        body.add(nn.Dense(8, in_units=6), nn.BatchNorm(in_channels=8),
                 nn.Dense(4, in_units=8))
        if recompute:
            body.recompute()
        body.initialize(mx.init.Normal(0.3), ctx=mx.cpu())
        mesh = make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])
        return DataParallelTrainer(body, _token_loss, optimizer="sgd",
                                   mesh=mesh,
                                   optimizer_params={"learning_rate": 0.1})
    rng = np.random.RandomState(4)
    x = rng.randn(16, 6).astype(np.float32)
    y = rng.randint(0, 4, (16,)).astype(np.int32)
    plain, again = build(False), build(True)
    assert float(plain.step(x, y)) == pytest.approx(float(again.step(x, y)),
                                                    rel=1e-6)
    moved = 0
    for a, b, p in zip(plain._params_raw, again._params_raw, plain._plist):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
        moved += "running" in p.name and bool(np.any(np.asarray(a) != 0))
    assert moved >= 1       # the running mean did move, on both sides


# -- the model -------------------------------------------------------------------

def test_scopes_name_the_groups_a_trace_is_read_by():
    x, y = _batch(3)
    _, tr, _ = _trainer(True)
    text = _step_program(tr, x, y).as_text(debug_info=True)
    for scope in ("mx.embed", "mx.mamba", "mx.ssd", "mx.conv1d", "mx.attn",
                  "mx.ffn", "mx.head"):
        assert scope in text, scope
    assert "mx.mamba/mx.ssd" in text and "mx.mamba/mx.conv1d" in text


def test_trains_in_bfloat16_with_float32_master():
    x, y = _batch(4)
    net, tr, before = _trainer(True, dtype="bfloat16")
    losses = [float(tr.step(x, y)) for _ in range(8)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert all(np.asarray(w).dtype == np.float32 for w in tr._params_raw)
    names = [p.name for p in net.collect_params().values()]
    assert names[0].endswith("embed_weight") and len(names) == len(before)
    assert mx.models.HybridDecoder is hd.HybridDecoder


def test_vocabulary_slices_add_up_to_the_uncut_model():
    """The tied table divided by rows over 8 chips: what each slice's model
    gives for ids of its own rows is the uncut model's logits over those
    rows; an id's embedding from its slice is the whole table's row; and for
    one hidden state the eight slices' logits, side by side, are the uncut
    model's."""
    vocab, parts = 64, 8
    rows = vocab // parts
    mx.random.seed(11)
    whole = hd.hybrid_decoder_tiny(vocab_size=vocab, recompute=False)
    whole.initialize(mx.init.Normal(0.05))
    weights = [p.data().asnumpy() for p in whole.collect_params().values()]
    table = weights[0]
    rng = np.random.RandomState(5)
    side_by_side = []
    ids0 = rng.randint(0, rows, (2, 10)).astype(np.int32)   # slice 0's ids
    h0 = nd.array(12.0 * table[ids0])
    for k in range(parts):
        part = hd.hybrid_decoder_tiny(vocab_size=rows, recompute=False)
        part.initialize(mx.init.Zero())
        own = table[k * rows:(k + 1) * rows]
        for p, w in zip(part.collect_params().values(), [own] + weights[1:]):
            p.set_data(nd.array(w))
        ids = rng.randint(0, rows, (2, 10)).astype(np.int32)
        want = whole(nd.array(ids + k * rows, dtype="int32")).asnumpy()
        got = part(nd.array(ids, dtype="int32")).asnumpy()
        np.testing.assert_allclose(got, want[..., k * rows:(k + 1) * rows],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(own[ids], table[ids + k * rows])
        # the same hidden state through this slice's layers, norm and rows
        h = part.norm(part.layers(h0))
        side_by_side.append(nd.FullyConnected(
            h, nd.array(own), no_bias=True, num_hidden=rows,
            flatten=False).asnumpy() / 8.0)
    np.testing.assert_allclose(
        np.concatenate(side_by_side, axis=-1),
        whole(nd.array(ids0, dtype="int32")).asnumpy(), rtol=1e-5, atol=1e-6)
