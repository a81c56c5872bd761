"""Neural-network ops: FullyConnected, Convolution, Pooling, norms, softmax,
Dropout, Embedding, RNN, CTC.

Reference: src/operator/nn/* (convolution.cc:399, fully_connected.cc,
batch_norm.cc, layer_norm.cc, group_norm.cc, pooling.cc, softmax.cc,
dropout-inl.h, lrn.cc), src/operator/rnn-inl.h:414, src/operator/nn/ctc_loss-inl.h.

TPU-first notes:
  - Convs route through `lax.conv_general_dilated`; XLA lays them out for the
    MXU (no cuDNN-style algo autotune needed — reference nn/cudnn/cudnn_algoreg
    has no analog here by design).
  - Matmul-heavy ops accept bf16 and accumulate f32 via
    `preferred_element_type` — the MXU-native mixed-precision contract.
  - Dropout/random take an explicit key array input (counter-based RNG) so the
    same op is usable eagerly and inside jit traces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from ..base import MXNetError
from .registry import register


def _pref(x):
    """f32 accumulation for low-precision matmuls (MXU contract)."""
    return jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else None


# ---------------------------------------------------------------------------
# FullyConnected / Dense
# ---------------------------------------------------------------------------

@register("FullyConnected")
def fully_connected(data, weight, bias=None, *, num_hidden=None, no_bias=False,
                    flatten=True):
    """reference src/operator/nn/fully_connected.cc — weight is (num_hidden, in)."""
    # explicit product, not -1: reshape(0, -1) on a zero-size batch cannot
    # infer the flattened dim (0 % anything) — the reference supports
    # 0-batch forward
    flat = int(_np.prod(data.shape[1:])) if data.ndim > 1 else 1
    x = data.reshape(data.shape[0], flat) if flatten else data
    out = jnp.matmul(x, weight.T, preferred_element_type=_pref(x))
    if out.dtype != x.dtype:
        out = out.astype(x.dtype)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (1D/2D/3D, grouped)
# ---------------------------------------------------------------------------

_CONV_DNUMS = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
               3: ("NCDHW", "OIDHW", "NCDHW")}


def _match_conv_dtypes(data, weight):
    """(data', weight', restore_dtype|None): fp16 → compute f32, round back;
    mixed data/weight dtypes promote to the wider one, output keeps data's."""
    if data.dtype == jnp.float16 or weight.dtype == jnp.float16:
        return data.astype(jnp.float32), weight.astype(jnp.float32), data.dtype
    if data.dtype != weight.dtype:
        wide = jnp.result_type(data.dtype, weight.dtype)
        return data.astype(wide), weight.astype(wide), data.dtype
    return data, weight, None


def _conv_tuples(kernel, stride, dilate, pad):
    nd = len(kernel)
    stride = tuple(stride) if stride else (1,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    return nd, stride, dilate, tuple((p, p) for p in pad)


@register("Convolution")
def convolution(data, weight, bias=None, *, kernel, num_filter, stride=None,
                dilate=None, pad=None, num_group=1, no_bias=False, layout=None):
    """reference src/operator/nn/convolution.cc:399 — NCHW/OIHW semantics."""
    nd, stride, dilate, padding = _conv_tuples(kernel, stride, dilate, pad)
    # no preferred_element_type here: the MXU accumulates bf16 convs in f32
    # natively, and an explicit f32 preference breaks the transpose rule
    # (f32 cotangent vs bf16 weight) under grad-of-bf16. fp16 has no native
    # MXU mode and a 65504 max, so compute it in f32 and round back.
    data, weight, lo_dt = _match_conv_dtypes(data, weight)
    # XLA's TPU layout assignment already picks channels-last internally; an
    # explicit NHWC transpose sandwich was measured neutral at model level
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _CONV_DNUMS[nd])
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride, padding=padding,
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if lo_dt is not None:
        out = out.astype(lo_dt)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register("Deconvolution")
def deconvolution(data, weight, bias=None, *, kernel, num_filter, stride=None,
                  dilate=None, pad=None, adj=None, target_shape=None,
                  num_group=1, no_bias=False, layout=None):
    """Transposed conv (reference src/operator/nn/deconvolution.cc).
    weight layout (C_in, num_filter/group, *kernel) as in MXNet."""
    nd, stride, dilate, _ = _conv_tuples(kernel, stride, dilate, pad)
    pad_t = tuple(pad) if pad else (0,) * nd
    adj_t = tuple(adj) if adj else (0,) * nd
    # lhs-dilated conv == gradient of strided conv == deconv
    k = kernel
    padding = tuple(
        (k[i] - 1 - pad_t[i], k[i] - 1 - pad_t[i] + adj_t[i]) for i in range(nd))
    # weight (I, O/g, *k) -> flip spatial, move to (O, I/g, *k) per group
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if num_group > 1:
        ci, co_g = w.shape[0], w.shape[1]
        w = w.reshape((num_group, ci // num_group, co_g) + w.shape[2:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((num_group * co_g, ci // num_group) + w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _CONV_DNUMS[nd])
    data, w, lo_dt = _match_conv_dtypes(data, w)
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if lo_dt is not None:
        out = out.astype(lo_dt)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

@register("Pooling")
def pooling(data, *, kernel=(), pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", count_include_pad=True,
            p_value=2, layout=None):
    """reference src/operator/nn/pooling.cc — NC+spatial layout."""
    nd = data.ndim - 2
    if global_pool:
        ax = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=ax, keepdims=True)
        if pool_type in ("avg", "sum"):
            red = jnp.sum if pool_type == "sum" else jnp.mean
            return red(data, axis=ax, keepdims=True)
        if pool_type == "lp":
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value), axis=ax,
                                     keepdims=True), 1.0 / p_value)
        raise MXNetError(f"pool_type {pool_type}")
    kernel = tuple(kernel)
    stride = tuple(stride) if stride else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode output: pad on the high side so the last window fits
        pads = [(0, 0), (0, 0)]
        for i in range(nd):
            in_sz = data.shape[2 + i]
            out_sz = int(_np.ceil((in_sz + 2 * pad[i] - kernel[i]) / stride[i])) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - in_sz - pad[i]
            pads.append((pad[i], max(needed, pad[i])))
        padding = tuple(pads)
    else:
        padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)

    # init values must be Python scalars: an array init defeats jax's monoid
    # detection for reduce_window and its grad cannot linearize under jit
    if pool_type == "max":
        # typed numpy scalar for ints so the identity matches the operand
        # dtype (a weak Python int would defeat monoid detection for int8 &c)
        init = -_np.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else _np.dtype(data.dtype).type(_np.iinfo(_np.dtype(data.dtype)).min)
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0,
                              lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = float(_np.prod(kernel))
            return s / denom
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        s = lax.reduce_window(jnp.power(jnp.abs(data), p_value), 0.0,
                              lax.add, window, strides, padding)
        return jnp.power(s, 1.0 / p_value)
    raise MXNetError(f"pool_type {pool_type}")


@register("UpSampling")
def upsampling(data, *weights, scale=2, sample_type="nearest", num_filter=0,
               multi_input_mode="concat", num_args=1, workspace=None):
    """reference src/operator/nn/upsampling.cc. `bilinear` is a LEARNABLE
    depthwise deconv (upsampling-inl.h:172 GetDeconvolutionParam: kernel
    2*scale - scale%2, stride scale, pad ceil((scale-1)/2), num_group ==
    num_filter, no bias) — the weight input is trained, so it must be
    honored, not replaced by a fixed resize."""
    if sample_type == "nearest":
        # reference multi_input_mode: every input is upsampled to the
        # FIRST input's scaled size (smaller inputs get a larger integer
        # factor), then channel-concatenated ('concat', default) or
        # elementwise-summed ('sum') — upsampling-inl.h nearest path
        oh, ow = data.shape[2] * scale, data.shape[3] * scale
        outs = []
        for x in (data,) + weights:
            fh, fw = oh // x.shape[2], ow // x.shape[3]
            outs.append(jnp.repeat(jnp.repeat(x, fh, axis=2), fw, axis=3))
        if len(outs) == 1:
            return outs[0]
        if multi_input_mode == "sum":
            out = outs[0]
            for x in outs[1:]:
                out = out + x
            return out
        return jnp.concatenate(outs, axis=1)
    if sample_type == "bilinear":
        if not weights:
            raise MXNetError(
                "UpSampling bilinear needs a weight input (it is a "
                "deconvolution; initialize with init.Bilinear())")
        k = 2 * scale - scale % 2
        p = int(_np.ceil((scale - 1) / 2.0))
        nf = num_filter or data.shape[1]
        return deconvolution(data, weights[0], None, kernel=(k, k),
                             num_filter=nf, stride=(scale, scale),
                             pad=(p, p), num_group=nf, no_bias=True)
    raise MXNetError(f"UpSampling: unknown sample_type {sample_type!r}")


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

@register("BatchNorm", multi_output=True)
def batch_norm(data, gamma, beta, moving_mean, moving_var, *, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False, training=True):
    """reference src/operator/nn/batch_norm.cc.

    Pure-functional: returns (out, batch_mean, batch_var); running-stat update
    (momentum blend) is done by the caller (gluon BatchNorm layer) — the
    reference mutates aux states in-op, which is hostile to XLA.
    """
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if training and not use_global_stats:
        x32 = data.astype(jnp.float32)
        mean = jnp.mean(x32, axis=red)
        var = jnp.var(x32, axis=red)
    else:
        mean, var = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    inv = lax.rsqrt(var + eps)
    out = (data.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
    out = out * g.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype), mean, var


@register("LayerNorm")
def layer_norm(data, gamma, beta, *, axis=-1, eps=1e-5):
    """reference src/operator/nn/layer_norm.cc."""
    ax = axis % data.ndim
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=ax, keepdims=True)
    var = jnp.var(x32, axis=ax, keepdims=True)
    out = (x32 - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register("RMSNorm")
def rms_norm(data, gamma, gate=None, *, eps=1e-5):
    """gamma * v * rsqrt(mean(v^2) + eps) over the last axis, in float32;
    with `gate`, v = data * silu(gate) (the gated norm in front of a
    Mamba-2 mixer's output projection). No reference counterpart."""
    v = data.astype(jnp.float32)
    if gate is not None:
        v = v * jax.nn.silu(gate.astype(jnp.float32))
    out = v * lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + eps)
    return (out * gamma.astype(jnp.float32)).astype(data.dtype)


@register("GroupNorm")
def group_norm(data, gamma, beta, *, num_groups=1, eps=1e-5):
    """reference src/operator/nn/group_norm.cc — (N, C, ...) grouped over C."""
    n, c = data.shape[0], data.shape[1]
    rest = data.shape[2:]
    x32 = data.astype(jnp.float32).reshape((n, num_groups, c // num_groups) + rest)
    red = tuple(range(2, x32.ndim))
    mean = jnp.mean(x32, axis=red, keepdims=True)
    var = jnp.var(x32, axis=red, keepdims=True)
    out = ((x32 - mean) * lax.rsqrt(var + eps)).reshape(data.shape)
    # reference gamma/beta have shape (num_groups,) (group_norm.cc:50);
    # per-channel (C,) is also accepted for gluon-style affine params
    if gamma.shape[0] == num_groups and num_groups != c:
        gamma = jnp.repeat(gamma, c // num_groups)
        beta = jnp.repeat(beta, c // num_groups)
    shape = (1, c) + (1,) * len(rest)
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, *, eps=1e-3):
    red = tuple(range(2, data.ndim))
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=red, keepdims=True)
    var = jnp.var(x32, axis=red, keepdims=True)
    out = (x32 - mean) * lax.rsqrt(var + eps)
    shape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register("LRN")
def lrn(data, *, nsize, alpha=1e-4, beta=0.75, knorm=2.0):
    """Local response norm across channels (reference src/operator/nn/lrn.cc)."""
    sq = jnp.square(data.astype(jnp.float32))
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = jnp.zeros_like(sq)
    for i in range(nsize):
        window = window + lax.dynamic_slice_in_dim(padded, i, sq.shape[1], axis=1)
    norm = jnp.power(knorm + (alpha / nsize) * window, beta)
    return (data.astype(jnp.float32) / norm).astype(data.dtype)


# ---------------------------------------------------------------------------
# Activation / softmax
# ---------------------------------------------------------------------------

@register("Activation")
def activation(data, *, act_type):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "silu":
        return jax.nn.silu(data)
    raise MXNetError(f"Activation act_type {act_type}")


@register("softmax")
def softmax(data, length=None, *, axis=-1, temperature=None, use_length=False,
            dtype=None):
    x = data.astype(jnp.float32)
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if use_length and length is not None:
        T = data.shape[axis]
        steps = jnp.arange(T)
        mask_shape = [1] * data.ndim
        mask_shape[axis % data.ndim] = T
        mask = steps.reshape(mask_shape) < length.reshape(
            length.shape + (1,) * (data.ndim - length.ndim)).astype(jnp.int32)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        out = jnp.where(mask, out, 0.0)
    else:
        out = jax.nn.softmax(x, axis=axis)
    return out.astype(jnp.dtype(dtype) if dtype else data.dtype)


@register("log_softmax")
def log_softmax(data, *, axis=-1, temperature=None, dtype=None):
    x = data.astype(jnp.float32)
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(jnp.dtype(dtype) if dtype else data.dtype)


@register("softmin")
def softmin(data, *, axis=-1, temperature=None, dtype=None):
    return softmax(-data, axis=axis, temperature=temperature, dtype=dtype)


@register("SoftmaxActivation")
def softmax_activation(data, *, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output, normalization, smooth_alpha):
    ax = 1 if multi_output else -1
    return jax.nn.softmax(data.astype(jnp.float32), axis=ax).astype(data.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _softmax_output(data, label, grad_scale, ignore_label, use_ignore,
                    multi_output, normalization, smooth_alpha):
    return _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                               multi_output, normalization, smooth_alpha)


def _softmax_output_vjp_fwd(data, label, grad_scale, ignore_label, use_ignore,
                            multi_output, normalization, smooth_alpha):
    out = _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                              multi_output, normalization, smooth_alpha)
    return out, (out, label)


def _softmax_output_vjp_bwd(grad_scale, ignore_label, use_ignore, multi_output,
                            norm, smooth, res, g):
    out, label = res
    ax = 1 if multi_output else -1
    nclass = out.shape[ax]
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, nclass, axis=ax, dtype=jnp.float32)
    if smooth:
        onehot = onehot * (1 - smooth) + smooth / (nclass - 1)
    grad = out.astype(jnp.float32) - onehot
    if use_ignore:
        keep = (lab != int(ignore_label)).astype(jnp.float32)
        grad = grad * jnp.expand_dims(keep, ax % out.ndim)
    scale = grad_scale
    if norm == "batch":
        scale = scale / out.shape[0]
    elif norm == "valid":
        if use_ignore:
            scale = scale / jnp.maximum(jnp.sum(keep), 1.0)
        else:
            scale = scale / float(_np.prod(label.shape))
    grad = grad * scale
    return (grad.astype(out.dtype), jnp.zeros_like(label))


_softmax_output.defvjp(_softmax_output_vjp_fwd, _softmax_output_vjp_bwd)


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, *, grad_scale=1.0, ignore_label=-1.0,
                   use_ignore=False, multi_output=False, normalization="null",
                   preserve_shape=False, smooth_alpha=0.0, out_grad=False):
    """Output op whose *gradient* is softmax CE (reference softmax_output.cc)."""
    return _softmax_output(data, label, grad_scale, ignore_label, use_ignore,
                           multi_output, normalization, smooth_alpha)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    lab = label.astype(jnp.int32)
    nll = -jnp.take_along_axis(logp, lab[:, None], axis=-1)
    return jnp.sum(nll)


# ---------------------------------------------------------------------------
# Dropout / Embedding
# ---------------------------------------------------------------------------

@register("Dropout")
def dropout(data, key, *, p=0.5, mode="training", axes=(), training=True,
            cudnn_off=False):
    """reference src/operator/nn/dropout-inl.h. `key` is a (2,) uint32 RNG key
    array (counter-based RNG — the TPU-native replacement for the reference's
    per-device PRNG states)."""
    if not training or p <= 0.0:
        return data
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        k = key
    else:
        k = jax.random.wrap_key_data(key.astype(jnp.uint32), impl="threefry2x32")
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(k, keep, shape)
    return jnp.where(mask, data / keep, jnp.zeros((), data.dtype))


@register("Embedding")
def embedding(data, weight, *, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    """reference src/operator/tensor/indexing_op.cc Embedding."""
    idx = data.astype(jnp.int32)
    return jnp.take(weight, idx, axis=0)


# ---------------------------------------------------------------------------
# Fused RNN (reference src/operator/rnn-inl.h:414 RNNOp)
# ---------------------------------------------------------------------------

def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def _unpack_rnn_params(params, mode, num_layers, input_size, state_size, bidir):
    """Unpack MXNet/cuDNN flat param vector: all weights (layer-major,
    direction-minor), then all biases (two bias vectors per gate set, cuDNN
    style). Gate order: LSTM [i f g o], GRU [r z n]."""
    ng = _gates(mode)
    d = 2 if bidir else 1
    ws, bs = [], []
    off = 0
    for layer in range(num_layers):
        for _dir in range(d):
            isz = input_size if layer == 0 else state_size * d
            wx_n = ng * state_size * isz
            wh_n = ng * state_size * state_size
            wx = lax.dynamic_slice_in_dim(params, off, wx_n).reshape(ng * state_size, isz)
            off += wx_n
            wh = lax.dynamic_slice_in_dim(params, off, wh_n).reshape(ng * state_size, state_size)
            off += wh_n
            ws.append((wx, wh))
    for layer in range(num_layers):
        for _dir in range(d):
            bx = lax.dynamic_slice_in_dim(params, off, ng * state_size); off += ng * state_size
            bh = lax.dynamic_slice_in_dim(params, off, ng * state_size); off += ng * state_size
            bs.append((bx, bh))
    return ws, bs


def rnn_param_size(mode, num_layers, input_size, state_size, bidirectional=False):
    ng = _gates(mode)
    d = 2 if bidirectional else 1
    n = 0
    for layer in range(num_layers):
        isz = input_size if layer == 0 else state_size * d
        n += d * ng * state_size * (isz + state_size + 2)
    return n


def _cell_step(mode, state_size):
    if mode == "lstm":
        def step(carry, gates):
            h, c = carry
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c2 = f * c + i * g
            h2 = o * jnp.tanh(c2)
            return (h2, c2)
        return step
    if mode == "gru":
        def step(carry, pair):
            h = carry[0]
            gx, gh = pair  # each (B, 3H)
            rx, zx, nx = jnp.split(gx, 3, axis=-1)
            rh, zh, nh = jnp.split(gh, 3, axis=-1)
            r = jax.nn.sigmoid(rx + rh)
            z = jax.nn.sigmoid(zx + zh)
            n = jnp.tanh(nx + r * nh)
            h2 = (1 - z) * n + z * h
            return (h2,)
        return step

    act = jnp.tanh if mode == "rnn_tanh" else (lambda v: jnp.maximum(v, 0))

    def step(carry, gates):
        return (act(gates),)
    return step


def _run_layer(x, wx, wh, bx, bh, h0, c0, mode, reverse=False):
    """x: (T, B, I). Returns (T, B, H), final states."""
    H = wh.shape[-1]
    step = _cell_step(mode, H)
    xg = jnp.einsum("tbi,gi->tbg", x, wx) + bx  # precompute input gates: one big MXU matmul
    if reverse:
        xg = jnp.flip(xg, axis=0)

    def scan_fn(carry, xt):
        h = carry[0]
        hg = jnp.matmul(h, wh.T) + bh
        if mode == "gru":
            new = step(carry, (xt, hg))
        else:
            new = step(carry, xt + hg)
        return new, new[0]

    init = (h0,) if mode != "lstm" else (h0, c0)
    final, ys = lax.scan(scan_fn, init, xg)
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return ys, final


@register("RNN", multi_output=True)
def rnn(data, parameters, state, state_cell=None, *, state_size, num_layers,
        mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
        projection_size=None, use_sequence_length=False, lstm_state_clip_min=None,
        lstm_state_clip_max=None, lstm_state_clip_nan=False):
    """Fused multi-layer RNN. data (T, B, I); state (L*D, B, H).

    The reference dispatches to cuDNN's fused kernel; here each layer is a
    `lax.scan` whose input projection is hoisted into one large matmul per
    layer (MXU-friendly), with the recurrent matmul inside the scan.
    """
    T, B, I = data.shape
    d = 2 if bidirectional else 1
    ws, bs = _unpack_rnn_params(parameters, mode, num_layers, I, state_size, bidirectional)
    x = data
    h_finals, c_finals = [], []
    for layer in range(num_layers):
        outs = []
        for di in range(d):
            li = layer * d + di
            wx, wh = ws[li]
            bx, bh = bs[li]
            h0 = state[li]
            c0 = state_cell[li] if (mode == "lstm" and state_cell is not None) else None
            ys, final = _run_layer(x, wx, wh, bx, bh, h0, c0, mode, reverse=(di == 1))
            outs.append(ys)
            h_finals.append(final[0])
            if mode == "lstm":
                c_finals.append(final[1])
        x = outs[0] if d == 1 else jnp.concatenate(outs, axis=-1)
    outputs = (x,)
    outputs = outputs + (jnp.stack(h_finals, axis=0),)
    if mode == "lstm":
        outputs = outputs + (jnp.stack(c_finals, axis=0),)
    return outputs


# ---------------------------------------------------------------------------
# CTC loss (reference src/operator/nn/ctc_loss-inl.h / 3rdparty/ctc_include)
# ---------------------------------------------------------------------------

@register("CTCLoss", aliases=("ctc_loss",))
def ctc_loss(data, label, data_lengths=None, label_lengths=None, *,
             use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """Log-domain forward algorithm via lax.scan. data (T, B, C) activations
    (un-normalized), label (B, L) padded with -1 (or 0 when blank='first')."""
    T, B, C = data.shape
    L = label.shape[1]
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    blank = 0 if blank_label == "first" else C - 1
    lab = label.astype(jnp.int32)
    if blank_label == "last":
        pad_val = -1
    else:
        pad_val = 0
    if use_label_lengths and label_lengths is not None:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        # infer: count entries != padding
        lab_len = jnp.sum((lab != (0 if blank == 0 else -1)).astype(jnp.int32), axis=1)
    if use_data_lengths and data_lengths is not None:
        seq_len = data_lengths.astype(jnp.int32)
    else:
        seq_len = jnp.full((B,), T, dtype=jnp.int32)

    S = 2 * L + 1
    # extended label sequence: blank l1 blank l2 ... blank
    ext = jnp.full((B, S), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    neg_inf = jnp.float32(-1e30)

    # alpha recursion
    same_as_prev2 = jnp.concatenate(
        [jnp.ones((B, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)
    is_blank = ext == blank

    def step(alpha, t):
        lp = logp[t]  # (B, C)
        emit = jnp.take_along_axis(lp, ext, axis=1)  # (B, S)
        a_prev = alpha
        a_shift1 = jnp.concatenate([jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
        a_shift2 = jnp.concatenate([jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
        allow2 = jnp.logical_not(jnp.logical_or(is_blank, same_as_prev2))
        a2 = jnp.where(allow2, a_shift2, neg_inf)
        new = jnp.logaddexp(jnp.logaddexp(a_prev, a_shift1), a2) + emit
        # freeze past data length
        new = jnp.where((t < seq_len)[:, None], new, alpha)
        return new, None

    alpha0 = jnp.full((B, S), neg_inf)
    emit0 = jnp.take_along_axis(logp[0], ext, axis=1)
    alpha0 = alpha0.at[:, 0].set(emit0[:, 0])
    alpha0 = alpha0.at[:, 1].set(jnp.where(lab_len > 0, emit0[:, 1], neg_inf))
    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
    send = 2 * lab_len  # index of final blank
    a_last = jnp.take_along_axis(alpha, send[:, None], axis=1)[:, 0]
    a_last2 = jnp.take_along_axis(alpha, jnp.maximum(send - 1, 0)[:, None], axis=1)[:, 0]
    ll = jnp.logaddexp(a_last, jnp.where(lab_len > 0, a_last2, neg_inf))
    return -ll
