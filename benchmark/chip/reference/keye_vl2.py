"""Plain float32 reference of the decoder that `keye_vl2_30b_a3b` configures
(the language model of Kwai-Keye/Keye-VL-2.0-30B-A3B, `model_type`
`KeyeVL2`): grouped-KV attention over the keys a learned indexer selects
for each query, a QK norm, rotary positions in sections, and routed experts
without a shared one, with a head of its own. Straight `jax.numpy`; nothing
of `mxnet_tpu` is imported.

With C the hidden size, H query heads over G KV heads of d numbers,
rms(v) = w * v * rsqrt(mean(v^2) + eps), every layer alike:

    h = E[ids];  a = rms(h)
    q = a Wq (H heads), k = a Wk, v = a Wv (G heads), no bias
    QK norm: each head of q and of k is rms over its d numbers, one weight
          for all query heads and one for all key heads, before the positions
    positions: rotate-half over the whole head, inv_freq_i = theta^(-2i/d);
          pair i is turned by p_r[t] * inv_freq_i, r the section of
          `mrope_section` [s0, s1, s2] that holds i (in order, not
          interleaved); p (3, T) are the text's rows, 0 .. T - 1 each
    indexer (`sa_config`; its input is a with the gradient stopped, its
          leaves are not trained): qI = a W_qI (Hi heads of di),
          kI = layer_norm(a W_kI) (one head of di), wI = a W_wI (Hi numbers),
          the temporal positions on the whole di of qI and kI (the same
          theta), I[t,s] = di^-0.5 Hi^-0.5 sum_j wI[t,j] relu(qI[t,j] . kI[s])
          S_t = the `topk` keys s <= t of largest I[t,s] (all of them where
          t < topk; of equal scores the lower s: `lax.top_k`)
    o_j = softmax over s in S_t of (q_j . k_{j // (H/G)}[s] / sqrt(d)) v[s]
    h = h + concat_j(o_j) Wo
    b = rms(h);  p = softmax(b Wr) over all published experts, S = the
          `num_experts_per_tok` largest, w_e = p_e / sum_{e' in S} p_e',
          h = h + sum_{e in S, e held} w_e ffn_e(b),  ffn a SwiGLU
    logits = rms(h) W_head^T over the rows held

The chip's share: experts 0 .. `num_experts` - 1 of each layer's published
128 are held and the router keeps its published width; what the others
would add is left out, as in the program. Independent of the code under
test: per block of queries the float32 scores I, `jax.lax.top_k`, a boolean
mask scattered from its indices, then masked scores and a softmax against
every key (no kernel, no threshold, no bisection, no packed set); the
expert layer is **dense over the held experts** (no sort, no gather);
positions from the formulas above.

Only for memory (one row of 16,384 positions cannot be split by rows of the
batch): a layer's selection is made first, in blocks of `Q_BLOCK` queries,
as a T x T boolean; attention runs KV head by KV head (its H / G query heads
with it) in blocks of `Q_BLOCK` queries, the experts and the loss in blocks
of `ROW_BLOCK` positions, every block and every layer recomputed in the
backward pass.

`q` rounds the operands of every product and each layer's output (the
identity for the reference, FP8 for the control: reference/steps.py).

Faults that can be planted, for the tool that shows `correct` sees each
mechanism (tools/keye_trial.py): `cfg["fault"]` = `selection_ignored` (every
causal key attended), `selection_first` (the first `topk` keys in place of
the chosen), `qknorm_dropped`, `positions_dropped` (no rotation of q and k),
`routed_dropped` (the routed sum left out: the layer's feed-forward adds
nothing).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ITEMS = "tokens"
ROW_BLOCKS_OK = True   # rows are independent: a batch may be split in blocks
Q_BLOCK = 256          # queries whose scores (or indexer scores) live at once
ROW_BLOCK = 1024       # positions the experts or the loss take at once


def _sizes(cfg):
    c, d = cfg["hidden_size"], cfg["head_dim"]
    sa = cfg["sa_config"]
    return (c, d, cfg["num_attention_heads"] * d,
            cfg["num_key_value_heads"] * d, sa["indexer_num_heads"],
            sa["indexer_head_dim"])


def param_spec(cfg):
    """Ordered (name, shape, init, trainable), in the order in which
    mxnet_tpu/models/hybrid_decoder.py lists its leaves. Matrices and the
    head normal(0, initializer_range), the table
    normal(0, embedding_initializer_range); norm weights ones, the
    indexer's layer-norm bias zeros. The indexer's leaves are not trained (a
    top-k hands no gradient back); `selection` and `routing` are the
    program's state, which no equation here reads."""
    c, d, hq, kv, hi, di = _sizes(cfg)
    v, fe = cfg["vocab_size"], cfg["moe_intermediate_size"]
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    w = ("normal", cfg.get("initializer_range", 0.02))
    # the table's own draw: tokens that stay distinct (the configuration's
    # `assumed` says why)
    table = ("normal", cfg.get("embedding_initializer_range", w[1]))
    spec = [("embed", (v, c), table, True), ("head", (v, c), w, True)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        spec += [(p + "mixer_norm", (c,), "ones", True),
                 (p + "selection", (2,), "zeros", False),
                 (p + "query", (hq, c), w, True),
                 (p + "key", (kv, c), w, True),
                 (p + "value", (kv, c), w, True),
                 (p + "proj", (c, hq), w, True),
                 (p + "query_norm", (d,), "ones", True),
                 (p + "key_norm", (d,), "ones", True),
                 (p + "index_query", (hi * di, c), w, False),
                 (p + "index_key", (di, c), w, False),
                 (p + "index_key_norm", (di,), "ones", False),
                 (p + "index_key_bias", (di,), "zeros", False),
                 (p + "index_weight", (hi, c), w, False),
                 (p + "ffn_norm", (c,), "ones", True),
                 (p + "router", (routed, c), w, True),
                 (p + "experts_gate_up", (held, c, 2 * fe), w, True),
                 (p + "experts_down", (held, fe, c), w, True),
                 (p + "routing", (4,), "zeros", False)]
    spec.append(("norm", (c,), "ones", True))
    return spec


def _rms(v, w, eps, q):
    return q(w * v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1,
                                            keepdims=True) + eps))


def _dense(v, w, q):
    return q(jnp.matmul(q(v), q(w).T))


def _blocks(t, size):
    n = -(-t // size)
    return n, -(-t // n)


def _row_blocks(fn, *rows):
    """fn over blocks of `ROW_BLOCK` positions of (b, T, ..) arrays, each
    block recomputed in the backward pass; T padded to whole blocks."""
    t = rows[0].shape[1]
    n, block = _blocks(t, ROW_BLOCK)
    pad = n * block - t
    split = [jnp.moveaxis(jnp.pad(r, ((0, 0), (0, pad)) + ((0, 0),)
                                  * (r.ndim - 2))
                          .reshape((r.shape[0], n, block) + r.shape[2:]),
                          1, 0) for r in rows]
    out = jax.lax.map(jax.checkpoint(lambda a: fn(*a)), tuple(split))
    return jax.tree_util.tree_map(
        lambda o: jnp.moveaxis(o, 0, 1).reshape(
            (o.shape[1], n * block) + o.shape[3:])[:, :t], out)


# -- positions ---------------------------------------------------------------------

def _rope(x, theta, sections=None):
    """x (b, T, heads, d), rotate-half over the whole head. `sections`
    (s0, s1, s2): pair i is turned by the row of its section; for text every
    row is 0 .. T - 1 (written out all the same). None: one row."""
    d, t = x.shape[-1], x.shape[1]
    inv_freq = (theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)) \
        .astype(np.float32)
    rows = jnp.broadcast_to(jnp.arange(t, dtype=jnp.float32),
                            (len(sections or (1,)), t))
    row_of_pair = np.repeat(np.arange(rows.shape[0]),
                            sections or (d // 2,))
    angle = rows[row_of_pair].T * inv_freq              # (T, d / 2)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# -- the indexer and its selection ---------------------------------------------------

def _selection(a, p, cfg, q):
    """bool (b, T, T): the keys each query attends to."""
    b, t, _ = a.shape
    _, _, _, _, hi, di = _sizes(cfg)
    top_k, fault = cfg["sa_config"]["topk"], cfg.get("fault")
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    if fault == "selection_ignored":
        return jnp.broadcast_to(causal, (b, t, t))
    if fault == "selection_first":
        return jnp.broadcast_to(
            jnp.logical_and(causal, pos[None, :] < top_k), (b, t, t))
    a = jax.lax.stop_gradient(a)
    theta = cfg["rope_theta"]
    qi = _rope(_dense(a, p["index_query"], q).reshape(b, t, hi, di), theta)
    ki = _dense(a, p["index_key"], q)
    mean = jnp.mean(ki, axis=-1, keepdims=True)
    ki = (ki - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
        + cfg["rms_norm_eps"])
    ki = q(ki * p["index_key_norm"] + p["index_key_bias"])
    ki = _rope(ki[:, :, None, :], theta)[:, :, 0]
    wi = _dense(a, p["index_weight"], q)                 # (b, T, hi)
    n, block = _blocks(t, Q_BLOCK)
    pad = n * block - t
    qb = jnp.moveaxis(jnp.pad(qi, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      .reshape(b, n, block, hi, di), 1, 0)
    wb = jnp.moveaxis(jnp.pad(wi, ((0, 0), (0, pad), (0, 0)))
                      .reshape(b, n, block, hi), 1, 0)

    def one_block(inp):
        i, qn, wn = inp
        rows = (i * block + jnp.arange(block))[:, None]
        seen = pos[None, :] <= rows
        s = jnp.einsum("bqhd,bkd->bqhk", q(qn), q(ki))
        score = jnp.sum(wn[..., None] * jax.nn.relu(s), axis=2) \
            * (di ** -0.5 * hi ** -0.5)
        _, idx = jax.lax.top_k(jnp.where(seen, score, -jnp.inf),
                               min(top_k, t))
        chosen = jnp.zeros((b, block, t), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(block)[None, :, None],
            idx].set(True, unique_indices=True)
        return jnp.logical_and(chosen, seen)

    mask = jax.lax.map(one_block, (jnp.arange(n), qb, wb))
    return jnp.moveaxis(mask, 0, 1).reshape(b, n * block, t)[:, :t]


# -- the mixer ----------------------------------------------------------------------

def _attention(a, p, cfg, q):
    b, t, c = a.shape
    _, d, _, _, _, _ = _sizes(cfg)
    g, hq = cfg["num_key_value_heads"], cfg["num_attention_heads"]
    r = hq // g                     # query heads that share a KV head
    fault, eps = cfg.get("fault"), cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    sections = tuple(cfg["rope_scaling"]["mrope_section"])
    n, block = _blocks(t, Q_BLOCK)
    pad = n * block - t
    live = jnp.pad(_selection(a, p, cfg, q), ((0, 0), (0, pad), (0, 0)))
    live = jnp.moveaxis(live.reshape(b, n, block, t), 1, 0)

    def head_norm(x, w):
        return x if fault == "qknorm_dropped" else _rms(x, w, eps, q)

    def rope(x):
        return x if fault == "positions_dropped" else _rope(x, theta, sections)

    @jax.checkpoint
    def one_kv_head(ws):            # its r query heads: (b, t, r d)
        wq, wk, wv = ws
        qh = rope(head_norm(_dense(a, wq, q).reshape(b, t, r, d),
                            p["query_norm"]))
        kh = rope(head_norm(_dense(a, wk, q)[:, :, None, :],
                            p["key_norm"]))[:, :, 0]
        vh = _dense(a, wv, q)
        qb = jnp.pad(qh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qb = jnp.moveaxis(qb.reshape(b, n, block, r, d), 1, 0)

        @jax.checkpoint
        def one_block(inp):         # `block` queries against every key
            qn, seen = inp
            s = jnp.einsum("bqrd,bkd->brqk", q(qn), q(kh)) / math.sqrt(d)
            s = jnp.where(seen[:, None], s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            # a padded row sees nothing: keep its exp finite
            e = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
            att = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
            return q(jnp.einsum("brqk,bkd->bqrd", q(att), q(vh)))

        o = jax.lax.map(one_block, (qb, live))
        return jnp.moveaxis(o, 0, 1).reshape(b, n * block, r * d)[:, :t]

    # query head j uses KV head j // r: the leaves' rows, grouped by KV head
    o = jax.lax.map(one_kv_head, (
        p["query"].reshape(g, r * d, c), p["key"].reshape(g, d, c),
        p["value"].reshape(g, d, c)))
    return _dense(jnp.moveaxis(o, 0, 2).reshape(b, t, hq * d), p["proj"], q)


# -- the experts ----------------------------------------------------------------------

def _experts(v, p, cfg, q):
    """The held experts' part of the routed sum over one block of rows v
    (b, n, c)."""
    held, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    first = cfg.get("first_held_expert", 0)
    prob = jax.nn.softmax(_dense(v, p["router"], q), axis=-1)
    top, chosen = jax.lax.top_k(prob, k)
    weight = top / jnp.sum(top, axis=-1, keepdims=True)
    routed = jnp.zeros_like(v)
    for e in range(held):           # every held expert over every row
        combine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0),
                          axis=-1, keepdims=True)
        gu = q(jnp.matmul(q(v), q(p["experts_gate_up"][e])))
        f = gu.shape[-1] // 2
        act = q(jax.nn.silu(gu[..., :f]) * gu[..., f:])
        routed = routed + combine * q(jnp.matmul(
            act, q(p["experts_down"][e])))
    return q(routed)


def _layer(h, p, cfg, q):
    eps = cfg["rms_norm_eps"]
    h = q(h + _attention(_rms(h, p["mixer_norm"], eps, q), p, cfg, q))
    if cfg.get("fault") == "routed_dropped":
        return h
    b = _rms(h, p["ffn_norm"], eps, q)
    return q(h + _row_blocks(lambda v: _experts(v, p, cfg, q), b))


def _trunk(params, x, cfg, q):
    h = q(params["embed"][x])
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer{i}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        h = jax.checkpoint(lambda h, p: _layer(h, p, cfg, q))(h, p)
    return _rms(h, params["norm"], cfg["rms_norm_eps"], q)


def logits(params, x, cfg, q=lambda a: a):
    """(b, T) ids -> (b, T, vocab) logits over the head's rows held here."""
    return _dense(_trunk(params, x, cfg, q), params["head"], q)


def loss(params, x, y, cfg, q=lambda a: a):
    """Mean cross-entropy over every position of tokens x against y, the
    logits taken in blocks of positions."""
    def block(h, yb):
        lg = _dense(h, params["head"], q)
        m = jnp.max(lg, axis=-1, keepdims=True)
        logz = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
        gold = jnp.take_along_axis(lg, yb[..., None], axis=-1)[..., 0]
        return logz - gold
    return jnp.mean(_row_blocks(block, _trunk(params, x, cfg, q), y))
