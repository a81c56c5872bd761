"""Fused multi-tensor optimizer updates as Pallas kernels.

TPU analog of the reference's multi-tensor optimizer kernels
(src/operator/optimizer_op.cc multi_sgd_update / multi_mp_sgd_update and
src/operator/contrib/preloaded_multi_sgd.cc): instead of launching one
update per parameter, all parameters are flattened into ONE buffer and
updated by a single elementwise kernel — one launch, sequential HBM
traffic, no per-tensor overhead. Scalars (lr, momentum, wd) ride in SMEM so
changing the learning rate does not recompile.

Off-TPU, falls back to the same math in plain jnp (XLA fuses it fine).
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_ROWS = 512  # 512*128 f32 = 256 KB per operand block in VMEM


def _available(x=None) -> bool:
    if x is not None:
        try:
            return all(d.platform == "tpu" for d in x.devices())
        except Exception:
            pass
    dev = jax.config.jax_default_device
    if dev is not None:
        return getattr(dev, "platform", str(dev)) == "tpu"
    return jax.default_backend() == "tpu"


def _flatten(arrs: Sequence[jnp.ndarray]):
    """Concatenate to one (rows, 128) f32-convertible buffer + split info."""
    sizes = [int(a.size) for a in arrs]
    flat = jnp.concatenate([a.reshape(-1) for a in arrs])
    n = flat.shape[0]
    rows = (n + _LANES - 1) // _LANES
    rows = (rows + _BLOCK_ROWS - 1) // _BLOCK_ROWS * _BLOCK_ROWS
    flat = jnp.pad(flat, (0, rows * _LANES - n))
    return flat.reshape(rows, _LANES), sizes, n


def _unflatten(buf, sizes, shapes):
    flat = buf.reshape(-1)
    outs, off = [], 0
    for sz, sh in zip(sizes, shapes):
        outs.append(flat[off:off + sz].reshape(sh))
        off += sz
    return outs


def _sgd_kernel(s_ref, w_ref, g_ref, m_ref, ow_ref, om_ref):
    lr, mom, wd = s_ref[0], s_ref[1], s_ref[2]
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) + wd * w
    m = mom * m_ref[:].astype(jnp.float32) + g
    om_ref[:] = m.astype(om_ref.dtype)
    ow_ref[:] = (w - lr * m).astype(ow_ref.dtype)


def fused_sgd_apply(weights: List, grads: List, moms: List, lr: float,
                    momentum: float = 0.0, wd: float = 0.0):
    """One-launch SGD(+momentum,+wd) over a whole parameter list.
    Returns (new_weights, new_moms)."""
    shapes = [w.shape for w in weights]
    wbuf, sizes, _ = _flatten(weights)
    gbuf, _, _ = _flatten(grads)
    mbuf, _, _ = _flatten(moms)
    scal = jnp.asarray([lr, momentum, wd], jnp.float32)
    if not _available(wbuf):
        g = gbuf + scal[2] * wbuf
        m = scal[1] * mbuf + g
        w2, m2 = wbuf - scal[0] * m, m
    else:
        rows = wbuf.shape[0]
        w2, m2 = pl.pallas_call(
            _sgd_kernel,
            grid=(rows // _BLOCK_ROWS,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(wbuf.shape, wbuf.dtype),
                jax.ShapeDtypeStruct(mbuf.shape, mbuf.dtype),
            ],
        )(scal, wbuf, gbuf, mbuf)
    return _unflatten(w2, sizes, shapes), _unflatten(m2, sizes, shapes)


def _adam_kernel(s_ref, w_ref, g_ref, m_ref, v_ref, ow_ref, om_ref, ov_ref):
    lr, b1, b2, eps, wd, c1, c2 = (s_ref[0], s_ref[1], s_ref[2], s_ref[3],
                                   s_ref[4], s_ref[5], s_ref[6])
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) + wd * w
    m = b1 * m_ref[:].astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v_ref[:].astype(jnp.float32) + (1.0 - b2) * g * g
    om_ref[:] = m.astype(om_ref.dtype)
    ov_ref[:] = v.astype(ov_ref.dtype)
    mhat = m / c1
    vhat = v / c2
    ow_ref[:] = (w - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(ow_ref.dtype)


def fused_adam_apply(weights: List, grads: List, ms: List, vs: List,
                     lr: float, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.0,
                     t: int = 1):
    """One-launch Adam over a whole parameter list.
    Returns (new_weights, new_ms, new_vs)."""
    shapes = [w.shape for w in weights]
    wbuf, sizes, _ = _flatten(weights)
    gbuf, _, _ = _flatten(grads)
    mbuf, _, _ = _flatten(ms)
    vbuf, _, _ = _flatten(vs)
    c1 = 1.0 - float(beta1) ** t
    c2 = 1.0 - float(beta2) ** t
    scal = jnp.asarray([lr, beta1, beta2, eps, wd, c1, c2], jnp.float32)
    if not _available(wbuf):
        g = gbuf + scal[4] * wbuf
        m = scal[1] * mbuf + (1.0 - scal[1]) * g
        v = scal[2] * vbuf + (1.0 - scal[2]) * g * g
        w2 = wbuf - scal[0] * (m / scal[5]) / (jnp.sqrt(v / scal[6]) + scal[3])
        m2, v2 = m, v
    else:
        rows = wbuf.shape[0]
        spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
        w2, m2, v2 = pl.pallas_call(
            _adam_kernel,
            grid=(rows // _BLOCK_ROWS,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * 4,
            out_specs=[spec] * 3,
            out_shape=[jax.ShapeDtypeStruct(wbuf.shape, wbuf.dtype)] * 3,
        )(scal, wbuf, gbuf, mbuf, vbuf)
    return (_unflatten(w2, sizes, shapes), _unflatten(m2, sizes, shapes),
            _unflatten(v2, sizes, shapes))
