"""Plain float32 reference of the BERT encoder that `bert_base` configures.

The architecture is the one the configuration file states, departures from
the published model included: pre-LN encoder cells, a LayerNorm after the
embeddings and after the last cell, one fused (C -> 3C) QKV projection laid
out (3, heads, d), erf GELU, an MLM head (dense + GELU + LayerNorm) whose
decoder is not tied to the embedding, no dropout, no segment ids, loss over
every position. Straight `jax.numpy`; nothing of `mxnet_tpu` is imported.

`q` rounds every tensor the forward pass keeps: the operands of every matrix
product and the output of every projection, normalisation, activation and
residual sum. It is the identity for the reference and a lower precision for
the control (reference/steps.py), as the program keeps those tensors in its
compute type.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ITEMS = "tokens"
ROW_BLOCKS_OK = True   # rows are independent: a batch may be split in blocks


def param_spec(cfg):
    """Ordered (name, shape, init, trainable); init is ("normal", std),
    "ones" or "zeros". The order is the order in which the model is built."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    f = cfg["intermediate_size"]
    std = cfg.get("initializer_range", 0.02)
    w = ("normal", std)
    spec = [("word_embed", (v, c), w, True),
            ("pos_embed", (cfg["max_position_embeddings"], c), w, True),
            ("seg_embed", (cfg["type_vocab_size"], c), w, True)]

    def ln(name):
        return [(name + ".gamma", (c,), "ones", True),
                (name + ".beta", (c,), "zeros", True)]

    def dense(name, out, inp):
        return [(name + ".weight", (out, inp), w, True),
                (name + ".bias", (out,), "zeros", True)]

    spec += ln("embed_ln")
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        spec += ln(p + "ln1") + dense(p + "qkv", 3 * c, c) \
            + dense(p + "proj", c, c) + ln(p + "ln2") \
            + dense(p + "ffn1", f, c) + dense(p + "ffn2", c, f)
    spec += ln("encoder_ln") + dense("mlm_dense", c, c) + ln("mlm_ln") \
        + dense("mlm_decoder", v, c)
    return spec


def _ln(x, p, name, eps, q):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return q((x - mean) / jnp.sqrt(var + eps) * p[name + ".gamma"]
             + p[name + ".beta"])


def _dense(x, p, name, q):
    return q(jnp.matmul(q(x), q(p[name + ".weight"]).T) + p[name + ".bias"])


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _cell(x, p, cfg, q):
    b, t, c = x.shape
    h = cfg["num_attention_heads"]
    d = c // h
    eps = cfg["layer_norm_eps"]
    qkv = _dense(_ln(x, p, "ln1", eps, q), p, "qkv", q).reshape(b, t, 3, h,
                                                                d)
    qh, kh, vh = (jnp.transpose(qkv[:, :, i], (0, 2, 1, 3)) for i in range(3))
    s = jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh)) / math.sqrt(d)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s)
    att = e / jnp.sum(e, axis=-1, keepdims=True)
    o = q(jnp.einsum("bhqk,bhkd->bhqd", q(att), q(vh)))
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, t, c)
    x = q(x + _dense(o, p, "proj", q))
    y = _dense(q(_gelu(_dense(_ln(x, p, "ln2", eps, q), p, "ffn1", q))), p,
               "ffn2", q)
    return q(x + y)


def loss(params, x, y, cfg, q=lambda a: a):
    """Mean cross-entropy over every position of tokens x against y."""
    eps = cfg["layer_norm_eps"]
    t = x.shape[1]
    h = q(params["word_embed"][x] + params["pos_embed"][:t][None])
    h = _ln(h, params, "embed_ln", eps, q)
    # the cells are alike, so they run as one scanned cell over their stacked
    # leaves (a short program to compile and keep), each recomputed in the
    # backward pass so that float32 at the timed batch and length fits
    n = cfg["num_hidden_layers"]
    names = [k[len("layer0."):] for k in params if k.startswith("layer0.")]
    stacked = {k: jnp.stack([params[f"layer{i}.{k}"] for i in range(n)])
               for k in names}
    cell = jax.checkpoint(lambda h, p: _cell(h, p, cfg, q))
    h, _ = jax.lax.scan(lambda h, p: (cell(h, p), None), h, stacked)
    h = _ln(h, params, "encoder_ln", eps, q)
    h = _ln(q(_gelu(_dense(h, params, "mlm_dense", q))), params, "mlm_ln",
            eps, q)
    logits = _dense(h, params, "mlm_decoder", q)
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
