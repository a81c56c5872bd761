"""Attention kernels: blockwise (flash-style) single-device and ring/Ulysses
sequence-parallel variants.

Capability uplift over the reference (SURVEY.md §2.4, §5-g: no SP/ring
attention; closest are the contrib interleaved attention matmuls,
src/operator/contrib/transformer.cc:650-819). Implemented as lax.scan over
key blocks with log-sum-exp accumulation in f32 — O(T) memory, MXU-sized
matmul blocks; the ring variant rotates kv shards with ppermute so comm
overlaps compute on the ICI ring.

A causal call may take a `window` (PR 32): query t then sees the keys
t - window < t' <= t. The Pallas kernels, the blockwise scan and the models'
plain scores-softmax route apply the same mask; a windowed layer's route is
counted as `flash_window` in `mx_attention_route_total`, and the schedule
the kernels chose for a layer in `mx_attention_schedule_total` (PR 33).
Positions are not this module's: `ops/rotary.py` turns q and k before they come here.

A causal call may instead take a selection (PR 34): `select` int8 (B, T, T),
not zero where query t keeps key t' (learned sparse attention: an indexer
scores the keys and `ops/sparse_select.py` keeps the best `top_k` a query).
A key is then live where causal and kept. The Pallas kernels mask every
block they visit by the selection's tile, the blockwise scan and the models'
plain route take the same mask; the route is counted as `flash_select`. A
QK norm, like positions, is the model's (`models/hybrid_decoder.py`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telem
from ..base import env
from .registry import register

env.declare("MXNET_FLASH_ATTENTION_MIN_SEQ", 512, int,
            "Sequence length from which the models' attention goes through "
            "_contrib_flash_attention instead of the plain scores-softmax "
            "path (ops/attention.py: use_flash)")

_NEG = -1e30  # finite mask: -inf makes exp(-inf - -inf) = nan on fully
              # masked (q-row, k-block) pairs under causal blocking


def flash_min_seq() -> int:
    """The crossover length, 512 by default: from it up attention goes to
    `flash_attention` (on the TPU the Pallas kernels, which beat the plain
    path at 512 and at 1024: PERF.md, Findings of PR 29 and PR 31; where
    `DataParallelTrainer`'s GSPMD step is traced for several devices, the
    kernels' call is partitioned over its batch axis by a shard_map, the
    route `flash_partitioned` of `mx_attention_route_total`). Below it
    nothing has been measured and the plain scores-softmax path stays."""
    return env.get("MXNET_FLASH_ATTENTION_MIN_SEQ")


def count_route(route: str, schedule: Optional[str] = None,
                wide: Optional[str] = None):
    """One count a traced (or eagerly run) attention layer in
    `mx_attention_route_total`: `plain` where `use_flash` said no, `flash`
    where the layer went to `flash_attention` (`flash_window` where it took
    a window with it), `flash_partitioned` where that wrapped its kernels in
    a shard_map over the trainer's batch axis. Where the layer took the
    Pallas kernels, `mx_attention_schedule_total` counts beside it the
    schedule their plan chose from the call's shapes (`resident`, `band`,
    `tiled`: `ops/pallas/flash_attention.py: _plan`), and
    `mx_attention_wide_head_total` the `tiled` layers whose heads are wider
    than one lane tile and whose backward therefore walks smaller blocks
    than the forward, by `wide` (`"512->256"`: `_wide_blocks`). Counted
    while tracing; nothing of it is in the step."""
    if _telem._ENABLED:
        _telem.counter(
            "mx_attention_route_total",
            "Attention layers traced, by the route they took",
            ("route",)).labels(route).inc()
        if schedule is not None:
            _telem.counter(
                "mx_attention_schedule_total",
                "Attention layers traced onto the flash kernels, by the "
                "schedule the kernels' plan chose",
                ("schedule",)).labels(schedule).inc()
        if wide is not None:
            _telem.counter(
                "mx_attention_wide_head_total",
                "Attention layers traced onto the flash kernels whose "
                "backward walks smaller blocks for heads wider than a lane "
                "tile, by the blocks",
                ("blocks",)).labels(wide).inc()


def use_flash(seq_len: int) -> bool:
    """Whether attention over `seq_len` positions takes the flash kernels:
    the one place the models (models/bert.py, models/hybrid_decoder.py,
    parallel/megatron.py) ask."""
    flash = seq_len >= flash_min_seq()
    if not flash:
        count_route("plain")
    return flash


def _block_attn(q, k, v, bias, scale):
    """One attention block in f32 LSE form. q:(B,H,Tq,D) k/v:(B,H,Tk,D)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    num = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    den = jnp.sum(p, axis=-1, keepdims=True)
    return num, den, m


def blockwise_attention(q, k, v, block_size: int = 512, causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None, select=None):
    """Flash-style attention via lax.scan over key blocks. `window` (with
    `causal`): query t sees the keys t - window < t' <= t. `select`
    (B, T, Tk): not zero where query t keeps key t' (with the other masks).
    v may be of another width than q and k: (B, H, Tk, Dv)."""
    B, H, T, D = q.shape
    Dv = v.shape[3]
    scale = scale if scale is not None else (1.0 / (D ** 0.5))
    block_size = min(block_size, k.shape[2])
    Tk = k.shape[2]
    nblk = (Tk + block_size - 1) // block_size
    pad = nblk * block_size - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = jnp.moveaxis(k.reshape(B, H, nblk, block_size, D), 2, 0)  # (n,B,H,bs,D)
    vb = jnp.moveaxis(v.reshape(B, H, nblk, block_size, Dv), 2, 0)
    qf = q.astype(jnp.float32)
    q_pos = jnp.arange(T)[:, None]

    def body(carry, inp):
        i, kblk, vblk = inp[:3]
        acc_num, acc_den, acc_max = carry
        k_pos = i * block_size + jnp.arange(block_size)[None, :]
        mask = k_pos < Tk
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
            if window is not None:
                mask = jnp.logical_and(mask, q_pos - k_pos < window)
        if select is None:
            bias = jnp.where(mask, 0.0, _NEG)[None, None]
        else:
            bias = jnp.where(jnp.logical_and(mask[None, None],
                                             inp[3][:, None]), 0.0, _NEG)
        num, den, m = _block_attn(qf, kblk.astype(jnp.float32), vblk, bias, scale)
        new_max = jnp.maximum(acc_max, m)
        corr_old = jnp.exp(acc_max - new_max)
        corr_new = jnp.exp(m - new_max)
        return (acc_num * corr_old + num * corr_new,
                acc_den * corr_old + den * corr_new, new_max), None

    # init carry derived from qf (x0 terms are no-ops XLA folds away) so it
    # carries the same device-varying type as the scanned k/v blocks when
    # this runs inside shard_map (ulysses path)
    zero_like_q = qf * 0.0
    zero_col = zero_like_q[..., :1]
    zero_num = zero_like_q if Dv == D else \
        jnp.broadcast_to(zero_col, (B, H, T, Dv))
    acc = (zero_num, zero_col, zero_col + _NEG)
    xs = (jnp.arange(nblk), kb, vb)
    if select is not None:
        kept = jnp.pad(select != 0, ((0, 0), (0, 0), (0, pad)))
        xs += (jnp.moveaxis(kept.reshape(B, T, nblk, block_size), 2, 0),)
    (num, den, _), _ = lax.scan(body, acc, xs)
    return (num / jnp.maximum(den, 1e-30)).astype(q.dtype)


@register("_contrib_flash_attention")
def flash_attention_op(q, k, v, select=None, *, causal=False, block_size=512,
                       scale=None, window=None):
    """Registered op form so the eager autograd tape records its VJP.
    Dispatches to the Pallas TPU kernel (ops/pallas/flash_attention.py)
    when on TPU; the lax.scan blockwise path elsewhere. `scale` multiplies
    q k^T (None: 1/sqrt(d)); `window` (with `causal`) keeps the keys
    t - window < t' <= t of query t; `select` int8 (B, T, T) (with `causal`)
    the keys it marks."""
    from .pallas.flash_attention import flash_attention as _pallas_flash
    return _pallas_flash(q, k, v, causal=causal, scale=scale,
                         block_q=block_size, block_k=block_size,
                         window=window, select=select)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over mesh axis `axis_name` (call inside shard_map).
    q/k/v: local sequence shards (B, H, T_local, D)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    scale = scale if scale is not None else (1.0 / (D ** 0.5))
    qf = q.astype(jnp.float32)
    q_pos_base = idx * T + jnp.arange(T)[:, None]

    def body(carry, step):
        acc_num, acc_den, acc_max, kb, vb = carry
        kv_rank = (idx - step) % n
        bias = None
        if causal:
            k_pos = kv_rank * T + jnp.arange(T)[None, :]
            bias = jnp.where(q_pos_base >= k_pos, 0.0, _NEG)[None, None]
        num, den, m = _block_attn(qf, kb.astype(jnp.float32), vb, bias, scale)
        new_max = jnp.maximum(acc_max, m)
        corr_old = jnp.exp(acc_max - new_max)
        corr_new = jnp.exp(m - new_max)
        acc_num = acc_num * corr_old + num * corr_new
        acc_den = acc_den * corr_old + den * corr_new
        perm = [(i, (i + 1) % n) for i in range(n)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (acc_num, acc_den, new_max, kb, vb), None

    # pvary: the scan carry must match the device-varying type of the
    # ppermute'd k/v shards under shard_map's varying-axis checking
    def _vary(x):
        try:
            return lax.pvary(x, (axis_name,))
        except (AttributeError, TypeError):
            return x

    acc = (_vary(jnp.zeros((B, H, T, D), jnp.float32)),
           _vary(jnp.zeros((B, H, T, 1), jnp.float32)),
           _vary(jnp.full((B, H, T, 1), _NEG, jnp.float32)), k, v)
    (num, den, _, _, _), _ = lax.scan(body, acc, jnp.arange(n))
    return (num / jnp.maximum(den, 1e-30)).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False):
    """Ulysses SP: all-to-all sequence<->head reshard, full attention per head
    group, reshard back. Inside shard_map over `axis_name`."""
    def a2a(x, split_axis, concat_axis):
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    qh = a2a(q, 1, 2)
    kh = a2a(k, 1, 2)
    vh = a2a(v, 1, 2)
    out = blockwise_attention(qh, kh, vh, causal=causal)
    return a2a(out, 2, 1)
