"""Plain float32 reference of ResNet v1 with bottleneck blocks (He et al.,
arXiv:1512.03385, Table 1) as the Gluon model zoo builds `resnet50_v1`:
stride on the first 1x1 convolution of a stage's first block, a bias on
both 1x1 convolutions of a block's body and none on the 3x3 or the shortcut,
batch normalisation in training mode (batch statistics, biased variance),
NCHW. Straight `jax.numpy` and `lax`; nothing of `mxnet_tpu` is imported.

`q` rounds every tensor the forward pass keeps: the operands of every
convolution and of the classifier's matrix product, and the output of every
convolution, normalisation, pooling and block. It is the identity for the
reference and a lower precision for the control (reference/steps.py), as the
program keeps those tensors in its compute type.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

ITEMS = "images"
ROW_BLOCKS_OK = False   # batch normalisation couples the rows of a batch


def _bn_spec(name, c):
    return [(name + ".gamma", (c,), "ones", True),
            (name + ".beta", (c,), "zeros", True),
            (name + ".running_mean", (c,), "zeros", False),
            (name + ".running_var", (c,), "ones", False)]


def _conv_spec(name, out, inp, k, bias):
    spec = [(name + ".weight", (out, inp, k, k),
             ("normal", math.sqrt(2.0 / (inp * k * k))), True)]
    if bias:
        spec.append((name + ".bias", (out,), "zeros", True))
    return spec


def _blocks(cfg):
    """(prefix, in, out, stride, downsample) of every bottleneck block."""
    chans = cfg["channels"]
    out = []
    for s, n in enumerate(cfg["layers"]):
        for b in range(n):
            inp = chans[s] if b == 0 else chans[s + 1]
            stride = 2 if (b == 0 and s > 0) else 1
            out.append((f"stage{s + 1}.block{b}.", inp, chans[s + 1], stride,
                        b == 0 and chans[s] != chans[s + 1]))
    return out


def param_spec(cfg):
    c0 = cfg["channels"][0]
    spec = _conv_spec("stem.conv", c0, 3, 7, False) + _bn_spec("stem.bn", c0)
    for pre, inp, out, _, down in _blocks(cfg):
        mid = out // 4
        spec += _conv_spec(pre + "conv1", mid, inp, 1, True) \
            + _bn_spec(pre + "bn1", mid) \
            + _conv_spec(pre + "conv2", mid, mid, 3, False) \
            + _bn_spec(pre + "bn2", mid) \
            + _conv_spec(pre + "conv3", out, mid, 1, True) \
            + _bn_spec(pre + "bn3", out)
        if down:
            spec += _conv_spec(pre + "down.conv", out, inp, 1, False) \
                + _bn_spec(pre + "down.bn", out)
    last = cfg["channels"][-1]
    spec += [("fc.weight", (cfg["classes"], last), ("normal", 0.01), True),
             ("fc.bias", (cfg["classes"],), "zeros", True)]
    return spec


def _conv(x, p, name, stride, pad, q):
    y = lax.conv_general_dilated(
        q(x), q(p[name + ".weight"]), (stride, stride),
        ((pad, pad), (pad, pad)), dimension_numbers=("NCHW", "OIHW", "NCHW"))
    bias = p.get(name + ".bias")
    return q(y if bias is None else y + bias[None, :, None, None])


def _bn(x, p, name, eps, q):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    g = p[name + ".gamma"][None, :, None, None]
    b = p[name + ".beta"][None, :, None, None]
    return q((x - mean) / jnp.sqrt(var + eps) * g + b)


def _block(x, p, stride, down, eps, q):
    y = jax.nn.relu(_bn(_conv(x, p, "conv1", stride, 0, q), p, "bn1", eps,
                        q))
    y = jax.nn.relu(_bn(_conv(y, p, "conv2", 1, 1, q), p, "bn2", eps, q))
    y = _bn(_conv(y, p, "conv3", 1, 0, q), p, "bn3", eps, q)
    if down:
        x = _bn(_conv(x, p, "down.conv", stride, 0, q), p, "down.bn", eps, q)
    return q(jax.nn.relu(x + y))


def loss(params, x, y, cfg, q=lambda a: a):
    """Mean cross-entropy of images x (N, 3, H, W) against labels y (N,)."""
    eps = cfg["bn_eps"]
    h = jax.nn.relu(_bn(_conv(x, params, "stem.conv", 2, 3, q), params,
                        "stem.bn", eps, q))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    for pre, _, _, stride, down in _blocks(cfg):
        blk = {k[len(pre):]: v for k, v in params.items()
               if k.startswith(pre)}
        # recompute the block in the backward pass, so that float32 at the
        # timed batch fits the chip
        h = jax.checkpoint(
            lambda h, p, s=stride, d=down: _block(h, p, s, d, eps, q))(h, blk)
    h = jnp.mean(h, axis=(2, 3))
    logits = q(jnp.matmul(q(h), q(params["fc.weight"]).T)
               + params["fc.bias"])
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
