"""Plain float32 reference of the decoder that `laguna_s_2_1` configures
(poolside Laguna-S-2.1, `model_type` `laguna`): full and sliding-window
attention layers with per-layer head counts, a per-head output gate and two
kinds of rotary positions, over a dense SwiGLU (layer 0) or routed experts
beside a shared one, with a head of its own. Straight `jax.numpy`; nothing
of `mxnet_tpu` is imported.

With C the hidden size, d the head size, G KV heads, H_l the query heads of
layer l, W the window, rms(v) = w * v * rsqrt(mean(v^2) + eps):

    h = E[ids];  per layer  a = rms(h)
    q = rope(a Wq) (H_l heads), k = rope(a Wk), v = a Wv (G heads), no bias
    rope: rotate-half over the first r d numbers of a head by the angles
          t * inv_freq_i. Sliding layers: r = 1, inv_freq_i = 1e4^(-2i/d).
          Full layers: r = 0.5, YaRN (base 5e5, factor 128 over 8,192,
          beta_fast 32, beta_slow 1: a linear ramp over the pair index from
          the extrapolated to the interpolated frequency between the two
          correction indices), cos and sin times `attention_factor`
    o_j = softmax(q_j k_{j // (H_l/G)}^T / sqrt(d) + mask) v;  key t' is seen
          by query t where t' <= t, in sliding layers also t - t' < W
    g = sigmoid(a Wg) (H_l numbers);  h = h + concat_j(g_j o_j) Wo
    b = rms(h);  layer 0:  h = h + W_down (silu(W_gate b) * (W_up b))
    layers >= 1: p = softmax(b Wr) over all 256 published experts,
          S = the ten largest, w_e = p_e / sum_{e' in S} p_e',
          h = h + 2.5 sum_{e in S, e held} w_e ffn_e(b) + ffn_shared(b)
    logits = rms(h) W_head^T over the rows held

The chip's share: experts 0 .. `num_experts` - 1 of each layer's published
256 are held and the router keeps its published width; what the others
would add is left out, as in the program. Independent of the code under
test: attention is masked scores and a softmax, in blocks of queries against
every key (no kernel, no walk over key blocks, no band); the expert layer is
**dense over the held experts**, every held expert applied to every row and
multiplied by its combine weight, zero where the row was not routed to it
(no sort, no gather); positions from the formulas above.

Only for memory (one row of 8,192 positions cannot be split by rows of the
batch): attention runs KV head by KV head (its H_l / G query heads with it)
and in blocks of `Q_BLOCK` queries, the feed-forwards and the loss in blocks
of `ROW_BLOCK` positions, every block and every layer recomputed in the
backward pass.

`q` rounds the operands of every product and each layer's output (the
identity for the reference, FP8 for the control: reference/steps.py).

Faults that can be planted, for the tool that shows `correct` sees each
mechanism (tools/laguna_trial.py): `cfg["fault"]` = `window_ignored`
(sliding layers see every earlier key), `positions_dropped` (no rotation),
`routed_dropped` (the routed sum left out, the shared expert kept),
`gate_dropped` (heads' outputs not gated).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ITEMS = "tokens"
ROW_BLOCKS_OK = True   # rows are independent: a batch may be split in blocks
Q_BLOCK = 256          # queries of one KV head's group whose scores live at once
ROW_BLOCK = 1024       # positions a feed-forward or the loss takes at once


def _sizes(cfg):
    c, d = cfg["hidden_size"], cfg["head_dim"]
    return c, d, cfg["num_key_value_heads"] * d


def param_spec(cfg):
    """Ordered (name, shape, init, trainable), in the order in which
    mxnet_tpu/models/hybrid_decoder.py lists its leaves. Matrices, the table
    and the head normal(0, initializer_range); norm weights ones. The
    per-layer lists of the published configuration (`gating_types`,
    `num_attention_heads_per_layer`) keep their 48 entries: the first
    `num_hidden_layers` are read."""
    c, d, kv = _sizes(cfg)
    v = cfg["vocab_size"]
    f, fe, fs = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                 cfg["shared_expert_intermediate_size"])
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    w = ("normal", cfg.get("initializer_range", 0.02))
    spec = [("embed", (v, c), w, True), ("head", (v, c), w, True)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        hq = cfg["num_attention_heads_per_layer"][i]
        spec += [(p + "mixer_norm", (c,), "ones", True),
                 (p + "query", (hq * d, c), w, True),
                 (p + "key", (kv, c), w, True),
                 (p + "value", (kv, c), w, True),
                 (p + "gate", (hq, c), w, True),
                 (p + "proj", (c, hq * d), w, True),
                 (p + "ffn_norm", (c,), "ones", True)]
        if cfg["mlp_layer_types"][i] == "dense":
            spec += [(p + "ffn1", (2 * f, c), w, True),
                     (p + "ffn2", (c, f), w, True)]
        else:
            spec += [(p + "router", (routed, c), w, True),
                     (p + "experts_gate_up", (held, c, 2 * fe), w, True),
                     (p + "experts_down", (held, fe, c), w, True),
                     # the program's layer keeps its last report there:
                     # state, which no equation here reads
                     (p + "routing", (4,), "zeros", False),
                     (p + "shared1", (2 * fs, c), w, True),
                     (p + "shared2", (c, fs), w, True)]
    spec.append(("norm", (c,), "ones", True))
    return spec


def _rms(v, w, eps, q):
    return q(w * v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1,
                                            keepdims=True) + eps))


def _dense(v, w, q):
    return q(jnp.matmul(q(v), q(w).T))


def _row_blocks(fn, *rows):
    """fn over blocks of `ROW_BLOCK` positions of (b, T, ..) arrays, each
    block recomputed in the backward pass; T padded to whole blocks."""
    t = rows[0].shape[1]
    n = -(-t // ROW_BLOCK)
    block = -(-t // n)
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

def _inv_freq(rope, d):
    """(rotary numbers r, (r/2,) inverse frequencies, factor on cos and sin)
    of one of the configuration's two `rope_parameters` sets."""
    r = int(d * rope.get("partial_rotary_factor", 1))
    base = rope["rope_theta"]
    freq = base ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if rope["rope_type"] == "default":
        return r, freq.astype(np.float32), 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    length = rope["original_max_position_embeddings"]

    def index_of(rotations):    # the pair that turns so often over `length`
        return r * math.log(length / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(index_of(rope["beta_fast"])), 0)
    high = min(math.ceil(index_of(rope["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    mixed = freq / rope["factor"] * ramp + freq * (1 - ramp)
    return r, mixed.astype(np.float32), rope["attention_factor"]


def _rope(x, rope, dropped):
    """x (b, T, heads, d), positions 0 .. T - 1."""
    if dropped:
        return x
    r, inv_freq, factor = _inv_freq(rope, x.shape[-1])
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = (factor * jnp.cos(angle))[None, :, None, :]
    sin = (factor * jnp.sin(angle))[None, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


# -- the mixer ----------------------------------------------------------------------

def _attention(a, p, i, cfg, q):
    b, t, c = a.shape
    _, d, _ = _sizes(cfg)
    g, hq = cfg["num_key_value_heads"], cfg["num_attention_heads_per_layer"][i]
    r = hq // g                     # query heads that share a KV head
    kind, fault = cfg["layer_types"][i], cfg.get("fault")
    rope = cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" \
        and fault != "window_ignored" else None
    dropped = fault == "positions_dropped"
    nq = -(-t // Q_BLOCK)
    block = -(-t // nq)
    keys = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one_kv_head(ws):            # its r query heads, gated: (b, t, r d)
        wq, wk, wv, wg = ws
        qh = _rope(_dense(a, wq, q).reshape(b, t, r, d), rope, dropped)
        kh = _rope(_dense(a, wk, q)[:, :, None, :], rope, dropped)[:, :, 0]
        vh = _dense(a, wv, q)
        qb = jnp.pad(qh, ((0, 0), (0, nq * block - t), (0, 0), (0, 0)))
        qb = jnp.moveaxis(qb.reshape(b, nq, block, r, d), 1, 0)

        @jax.checkpoint
        def one_block(inp):         # `block` queries against every key
            n, qn = inp
            rows = (n * block + jnp.arange(block))[:, None]
            seen = keys <= rows
            if window is not None:
                seen = jnp.logical_and(seen, rows - keys < window)
            s = jnp.einsum("bqrd,bkd->brqk", q(qn), q(kh)) / math.sqrt(d)
            s = jnp.where(seen, s, -jnp.inf)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            att = e / jnp.sum(e, axis=-1, keepdims=True)
            return q(jnp.einsum("brqk,bkd->bqrd", q(att), q(vh)))

        o = jax.lax.map(one_block, (jnp.arange(nq), qb))
        o = jnp.moveaxis(o, 0, 1).reshape(b, nq * block, r, d)[:, :t]
        if fault != "gate_dropped":
            o = q(o * jax.nn.sigmoid(_dense(a, wg, q))[..., None])
        return o.reshape(b, t, r * d)

    # query head j uses KV head j // r: the leaves' rows, grouped by KV head
    o = jax.lax.map(one_kv_head, (
        p["query"].reshape(g, r * d, c), p["key"].reshape(g, d, c),
        p["value"].reshape(g, d, c), p["gate"].reshape(g, r, c)))
    return _dense(jnp.moveaxis(o, 0, 2).reshape(b, t, hq * d), p["proj"], q)


# -- the feed-forwards ----------------------------------------------------------------

def _swiglu(v, w_in, w_out, q):
    """w_out (silu(g) * u), [g, u] = w_in v; w_in (2 f, c), w_out (c, f)."""
    gu = _dense(v, w_in, q)
    f = gu.shape[-1] // 2
    return _dense(q(jax.nn.silu(gu[..., :f]) * gu[..., f:]), w_out, q)


def _experts(v, p, cfg, q):
    """The held experts' part of the routed sum and the shared expert, over
    one block of rows v (b, n, c)."""
    held, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    first = cfg.get("first_held_expert", 0)
    shared = _swiglu(v, p["shared1"], p["shared2"], q)
    if cfg.get("fault") == "routed_dropped":
        return shared
    prob = jax.nn.softmax(_dense(v, p["router"], q), axis=-1)
    top, chosen = jax.lax.top_k(prob, k)
    weight = cfg["moe_routed_scaling_factor"] * top \
        / jnp.sum(top, axis=-1, keepdims=True)
    routed = jnp.zeros_like(v)
    for e in range(held):           # every held expert over every row
        combine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0),
                          axis=-1, keepdims=True)
        gu = q(jnp.matmul(q(v), q(p["experts_gate_up"][e])))
        f = gu.shape[-1] // 2
        act = q(jax.nn.silu(gu[..., :f]) * gu[..., f:])
        routed = routed + combine * q(jnp.matmul(
            act, q(p["experts_down"][e])))
    return q(routed) + shared


def _layer(h, p, i, cfg, q):
    eps = cfg["rms_norm_eps"]
    h = q(h + _attention(_rms(h, p["mixer_norm"], eps, q), p, i, cfg, q))
    b = _rms(h, p["ffn_norm"], eps, q)
    if cfg["mlp_layer_types"][i] == "dense":
        y = _row_blocks(lambda v: _swiglu(v, p["ffn1"], p["ffn2"], q), b)
    else:
        y = _row_blocks(lambda v: _experts(v, p, cfg, q), b)
    return q(h + y)


def _trunk(params, x, cfg, q):
    h = q(params["embed"][x])
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer{i}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        h = jax.checkpoint(lambda h, p, i=i: _layer(h, p, i, cfg, q))(h, p)
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
