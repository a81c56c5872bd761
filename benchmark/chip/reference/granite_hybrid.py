"""Plain float32 reference of the hybrid decoder that `granite4_h_micro`
configures: Mamba-2 state-space layers and grouped-KV attention layers as
the configuration's `layer_types` lists them (HF `GraniteMoeHybrid` with no
experts). Straight `jax.numpy`; nothing of `mxnet_tpu` is imported.

With e, r, s, l the embedding, residual, attention and logits multipliers:

    h0 = e * E[ids];  per layer  h = h + r * mixer(rms(h)),
                                 h = h + r * ffn(rms(h));
    logits = rms(h) @ E^T / l    (the same table E: tied; here its slice)
    rms(v) = w * v * rsqrt(mean(v^2) + eps)
    ffn(v) = W_out (silu(g) * u),  [g, u] = W_in v
    attention: softmax(s q k^T + causal mask) v, 32 query heads over 8 KV
               heads, no positions, no bias
    mamba:  [z, xBC, dt] = W_in v;  xBC = silu(conv(xBC)) (causal, depthwise,
            width 4, bias);  [x, B, C] = xBC;  dt = softplus(dt + dt_bias);
            A = -exp(A_log);  per head  S_t = exp(dt_t A) S_{t-1}
            + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t;
            W_out (w * n(y * silu(z)))

The state-space layer is **the recurrence itself**, a `lax.scan` over the
positions, and not the chunked algebra the program computes it by
(mxnet_tpu/ops/ssm.py): independent of the code under test. Only for memory
the positions run in blocks whose inner scan is recomputed in the backward
pass; the state is carried from block to block unchanged.

`q` rounds the operands of every product and each layer's output (the
identity for the reference, FP8 for the control: reference/steps.py); the
decays and the carried state stay float32, as in the program. Layers are
recomputed in the backward pass and their leaves are not stacked (a scanned
stack costs 12.75 B more a stacked parameter: PERF.md, Findings, PR 27).

One fault can be planted, for the tool that shows `correct` sees the carried
state (tools/granite_trial.py): `cfg["fault"] == "chunk_reset"` empties the
state at every boundary of `mamba_chunk_size` positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ITEMS = "tokens"
ROW_BLOCKS_OK = True   # rows are independent: a batch may be split in blocks
A_LOG_STD = 3.0        # see `param_spec`
CONV_STD = 0.29        # see `param_spec`
SCAN_BLOCK = 64        # positions whose recurrence is recomputed together


def _dims(cfg):
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    inner = h * p
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return h, p, inner, gn, inner + 2 * gn


def param_spec(cfg):
    """Ordered (name, shape, init, trainable), in the order in which
    mxnet_tpu/models/hybrid_decoder.py lists its leaves. Matrices and the
    table normal(0, initializer_range); norm weights and `D` ones; `dt_bias`
    and the convolution's bias zeros. The convolution's weight normal(0,
    CONV_STD), the standard deviation of the U(-1/2, 1/2) that the published
    model's framework draws a width-4 depthwise filter from: at 0.02 x, B
    and C would be a fiftieth of the size and the state's part of y, cubic
    in them, a thousandth of `D x`: no comparison could see the scan at all
    (PERF.md, Findings, PR 28). `A_log` normal(0,
    A_LOG_STD): A = exp(A_log) then spreads over orders of magnitude, so
    that with dt near 0.7 about a ninth of the heads (A under 0.025, A_log
    under -3.7: 11% at a standard deviation of 3) still hold over 1% of
    their state after a chunk of 256 positions and most forget within a few
    positions: `correct` has to see the state carried between chunks."""
    c, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    w = ("normal", cfg.get("initializer_range", 0.02))
    heads, _, inner, _, conv = _dims(cfg)
    d = c // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    spec = [("embed", (v, c), w, True)]
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"layer{i}."
        spec.append((p + "mixer_norm", (c,), "ones", True))
        if kind == "mamba":
            spec += [(p + "conv_weight", (conv, cfg["mamba_d_conv"]),
                      ("normal", CONV_STD), True),
                     (p + "conv_bias", (conv,), "zeros", True),
                     (p + "dt_bias", (heads,), "zeros", True),
                     (p + "A_log", (heads,), ("normal", A_LOG_STD), True),
                     (p + "D", (heads,), "ones", True),
                     (p + "ssm_in", (inner + conv + heads, c), w, True),
                     (p + "ssm_norm", (inner,), "ones", True),
                     (p + "ssm_out", (c, inner), w, True)]
        else:
            spec += [(p + "query", (c, c), w, True),
                     (p + "key", (kv, c), w, True),
                     (p + "value", (kv, c), w, True),
                     (p + "proj", (c, c), w, True)]
        spec += [(p + "ffn_norm", (c,), "ones", True),
                 (p + "ffn1", (2 * f, c), w, True),
                 (p + "ffn2", (c, f), w, True)]
    spec.append(("norm", (c,), "ones", True))
    return spec


def _rms(v, w, eps, q):
    return q(w * v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1,
                                            keepdims=True) + eps))


def _dense(v, w, q):
    return q(jnp.matmul(q(v), q(w).T))


def _ffn(v, p, f, q):
    gu = _dense(v, p["ffn1"], q)
    return _dense(q(jax.nn.silu(gu[..., :f]) * gu[..., f:]), p["ffn2"], q)


def _attention(v, p, cfg, q):
    b, t, c = v.shape
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = c // h
    qh = _dense(v, p["query"], q).reshape(b, t, g, h // g, d)
    kh = _dense(v, p["key"], q).reshape(b, t, g, d)
    vh = _dense(v, p["value"], q).reshape(b, t, g, d)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_kv_head(qkv):           # its r query heads: (b, t, r, d)
        qg, kg, vg = qkv
        s = jnp.einsum("bqrd,bkd->brqk", q(qg), q(kg)) \
            * cfg["attention_multiplier"]
        s = jnp.where(causal, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        att = e / jnp.sum(e, axis=-1, keepdims=True)
        return q(jnp.einsum("brqk,bkd->bqrd", q(att), q(vg)))

    o = jax.lax.map(one_kv_head, tuple(jnp.moveaxis(a, 2, 0)
                                       for a in (qh, kh, vh)))
    return _dense(jnp.moveaxis(o, 0, 2).reshape(b, t, c), p["proj"], q)


def _conv(x, weight, bias):
    """y_t = bias + sum_k weight[:, k] x_{t-W+1+k}: causal, depthwise."""
    t, width = x.shape[1], weight.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return bias + sum(xp[:, k:k + t] * weight[:, k] for k in range(width))


def _recurrence(x, dt, a, bm, cm, chunk, reset):
    """y_t = S_t C_t with S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T, position
    by position. x (b, T, H, P), dt (b, T, H), a (H,), bm and cm (b, T, H, N).
    `reset` (the planted fault) empties the state every `chunk` positions."""
    b, t, h, p = x.shape
    n = bm.shape[-1]
    block = min(SCAN_BLOCK, chunk)
    if chunk % block:
        raise ValueError(f"mamba_chunk_size {chunk} is no multiple of {block}")
    pad = -t % block
    if pad:     # dt = 0: such a position neither decays nor feeds the state
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (v.ndim - 2)) for v in (x, dt, bm, cm))
    blocks = [jnp.moveaxis(v.reshape((b, -1, block) + v.shape[2:]), (1, 2),
                           (0, 1)) for v in (x, dt, bm, cm)]

    def position(s, inp):
        x_t, dt_t, b_t, c_t = inp          # (b,H,P) (b,H) (b,H,N) (b,H,N)
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    @jax.checkpoint
    def one_block(s, inp):
        i, rest = inp[0], inp[1:]
        if reset:
            s = jnp.where((i * block) % chunk == 0, jnp.zeros_like(s), s)
        return jax.lax.scan(position, s, rest)

    _, y = jax.lax.scan(one_block, jnp.zeros((b, h, p, n), jnp.float32),
                        (jnp.arange(len(blocks[0])), *blocks))
    return jnp.moveaxis(y, (0, 1), (1, 2)).reshape(b, -1, h, p)[:, :t]


def _mamba(v, p, cfg, q):
    b, t, _ = v.shape
    heads, hp, inner, gn, conv = _dims(cfg)
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    zxbcdt = _dense(v, p["ssm_in"], q)
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv],
                  zxbcdt[..., inner + conv:])
    xbc = q(jax.nn.silu(_conv(xbc, p["conv_weight"], p["conv_bias"])))
    x = xbc[..., :inner].reshape(b, t, heads, hp)
    bm, cm = (jnp.repeat(xbc[..., inner + i * gn:inner + (i + 1) * gn]
                         .reshape(b, t, g, n), heads // g, axis=2)
              for i in range(2))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _recurrence(x, dt, -jnp.exp(p["A_log"]), bm, cm,
                    cfg["mamba_chunk_size"], cfg.get("fault") == "chunk_reset")
    y = q(y + p["D"][:, None] * x).reshape(b, t, inner)
    y = _rms(y * jax.nn.silu(z), p["ssm_norm"], cfg["rms_norm_eps"], q)
    return _dense(y, p["ssm_out"], q)


def _layer(h, p, kind, cfg, q):
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    mixer = _mamba if kind == "mamba" else _attention
    h = q(h + r * mixer(_rms(h, p["mixer_norm"], eps, q), p, cfg, q))
    return q(h + r * _ffn(_rms(h, p["ffn_norm"], eps, q), p,
                          cfg["intermediate_size"], q))


def logits(params, x, cfg, q=lambda a: a):
    """(b, T) ids -> (b, T, vocab) logits over the table's rows held here."""
    table = params["embed"]
    h = q(cfg["embedding_multiplier"] * table[x])
    for i, kind in enumerate(cfg["layer_types"]):
        pre = f"layer{i}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        h = jax.checkpoint(lambda h, p, kind=kind: _layer(h, p, kind, cfg,
                                                          q))(h, p)
    h = _rms(h, params["norm"], cfg["rms_norm_eps"], q)
    return _dense(h, table, q) / cfg["logits_scaling"]


def loss(params, x, y, cfg, q=lambda a: a):
    """Mean cross-entropy over every position of tokens x against y."""
    lg = logits(params, x, cfg, q)
    m = jnp.max(lg, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
