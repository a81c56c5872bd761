"""Mixture-of-Experts with expert parallelism (capability uplift: the
reference has no EP/MoE at all — SURVEY.md §2.4).

TPU-native design: capacity-based top-k gating builds fixed-shape dispatch/
combine tensors (no dynamic shapes — dropped tokens are the standard
capacity-overflow semantics), expert FFNs run as one batched einsum, and
expert parallelism shards the expert dimension over an 'ep' mesh axis with
two `lax.all_to_all` exchanges (token -> expert shard -> token), riding ICI.
The exchanges optionally compress onto the same bf16/int8 comm wire the
ZeRO gradient collectives use (EQuARX, arXiv:2506.17615) — see
``wire_all_to_all`` / ``MXNET_TPU_COMM_DTYPE``.

End-to-end training of these layers lives in ``mxnet_tpu.recipes.moe``
(docs/large_models.md); this module stays a pure function library.

Beside the capacity-gated layers stands ``held_moe_ffn`` (PR 32): the
dropless layer of a chip that is told which experts of a published layer it
holds. It routes over all of them, keeps every assignment that falls on its
own and computes their part of the sum by grouped products; on one chip it
has no exchange. It is an op (``_contrib_held_moe_ffn``) of gluon blocks on
the normal path (``models/hybrid_decoder.py`` through
``DataParallelTrainer``), not of ``MoETrainer``.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from ..ops.pallas.flash_attention import _on_tpu
from ..ops.pallas.grouped_matmul import routed_swiglu
from .mesh import axis_size as _axis_size


def topk_gating(logits, top_k: int, capacity: int):
    """Top-k capacity gating (Switch/GShard style).

    logits: (N, E). Returns (dispatch (N, E, C) float 0/1, combine (N, E, C)).
    Token n's k-th choice lands in expert e's slot c if fewer than C earlier
    tokens chose e; overflow tokens are dropped (their combine weight is 0).

    Determinism contract (parity tests depend on it):

      - expert ties break toward the LOWER expert index — ``lax.top_k``
        returns the first maximal index on equal probabilities, on every
        backend;
      - capacity slots are claimed in TOKEN order (the running ``cumsum``
        over axis 0), so for a fixed token ordering the overflow set is a
        pure function of the logits — two runs (or two devices gating the
        same shard) always drop the same tokens;
      - choice ranks fill sequentially: all k=0 assignments claim slots
        before any k=1 assignment of the same call (the ``counts`` carry).

    Nothing here samples or depends on iteration order of a hash map, so
    gating is bitwise-reproducible for identical inputs.
    """
    N, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = lax.top_k(probs, top_k)                     # (N, K)

    dispatch = jnp.zeros((N, E, capacity), logits.dtype)
    combine = jnp.zeros((N, E, capacity), logits.dtype)
    counts = jnp.zeros((E,), jnp.int32)
    for k in range(top_k):
        onehot = jax.nn.one_hot(idx[:, k], E, dtype=jnp.int32)   # (N, E)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot           # prior count
        pos = jnp.sum(onehot * (pos_in_e + counts[None, :]), axis=1)  # (N,)
        e_sel = idx[:, k]
        fits = pos < capacity
        slot = jax.nn.one_hot(jnp.where(fits, pos, capacity), capacity,
                              dtype=logits.dtype)                # (N, C)
        d_k = jax.nn.one_hot(e_sel, E, dtype=logits.dtype)[:, :, None] * \
            slot[:, None, :]                                     # (N, E, C)
        d_k = d_k * fits[:, None, None].astype(logits.dtype)
        dispatch = dispatch + d_k
        gate = jnp.take_along_axis(probs, e_sel[:, None], axis=1)[:, 0]
        combine = combine + d_k * gate[:, None, None]
        counts = counts + jnp.sum(onehot, axis=0)
    return dispatch, combine


def load_balance_loss(probs, dispatch):
    """Switch-style auxiliary load-balancing loss from the gate's outputs.

    probs: (N, E) router probabilities; dispatch: (N, E, C) assignment mask
    from ``topk_gating``. ``E * sum_e f_e * P_e`` where ``f_e`` is the
    fraction of realized (post-capacity) assignments that landed on expert
    e and ``P_e`` the mean router probability — minimized (= 1) at uniform
    routing, so adding ``aux_weight * load_balance_loss`` to the task loss
    pushes the router toward balance. Differentiable through ``probs``
    only (the dispatch mask is a hard assignment; its gradient is zero
    a.e., matching the Switch Transformer estimator).
    """
    E = probs.shape[1]
    assigned = jnp.sum(dispatch, axis=2)                      # (N, E) 0/1
    denom = jnp.maximum(jnp.sum(assigned), 1.0)
    f = lax.stop_gradient(jnp.sum(assigned, axis=0) / denom)  # realized share
    p = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * p)


def dropped_tokens(dispatch, n_tokens: int, top_k: int):
    """Capacity-overflow count: (token, choice) assignments that found no
    free slot. Scalar int32, ``0 <= dropped <= N * top_k``. Surfaced by the
    MoE recipe trainer on ``mx_moe_dropped_tokens_total``."""
    made = jnp.sum(dispatch.astype(jnp.float32))
    return (jnp.int32(n_tokens * top_k) - made.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Comm-wire all_to_all: the dispatch/combine exchanges ride the same
# bf16/int8 wire as the ZeRO gradient collectives (zero.py, EQuARX
# arXiv:2506.17615). all_to_all with split_axis=0/concat_axis=0 is a pure
# block permutation, so it is its own transpose: the custom VJP runs the
# SAME compressed exchange on the cotangent.
# ---------------------------------------------------------------------------

def _a2a(x, axis_name):
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)


def _wire_exchange(x, axis_name, comm_dtype):
    """One compressed all_to_all. x: (n_dev, ...) local block layout."""
    if comm_dtype is None:
        return _a2a(x, axis_name)
    if comm_dtype == "bfloat16":
        return _a2a(x.astype(jnp.bfloat16), axis_name).astype(x.dtype)
    if comm_dtype == "int8":
        # per-destination-row chunk scaling (one amax per outbound block,
        # the zero.py reduce_scatter idiom): scale rides the wire as f32
        n = x.shape[0]
        flat = x.reshape(n, -1)
        amax = jnp.max(jnp.abs(flat), axis=1, keepdims=True)
        scale = jnp.maximum(amax / 127.0, 1e-12)
        q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
        q = _a2a(q, axis_name)
        scale = _a2a(scale, axis_name)
        return (q.astype(x.dtype) * scale).reshape(x.shape)
    raise ValueError(f"unsupported comm_dtype {comm_dtype!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def wire_all_to_all(x, axis_name: str, comm_dtype: Optional[str] = None):
    """``lax.all_to_all(split_axis=0, concat_axis=0)`` over `axis_name`,
    optionally compressed on the wire (``comm_dtype`` None/'bfloat16'/
    'int8' — the ``MXNET_TPU_COMM_DTYPE`` vocabulary, canonicalized by
    ``zero.canonical_comm_dtype``). The backward exchange compresses the
    cotangent identically, so forward and backward wire volume match
    ``all_to_all_wire_bytes`` exactly."""
    return _wire_exchange(x, axis_name, comm_dtype)


def _wire_a2a_fwd(x, axis_name, comm_dtype):
    return _wire_exchange(x, axis_name, comm_dtype), None


def _wire_a2a_bwd(axis_name, comm_dtype, _res, g):
    return (_wire_exchange(g, axis_name, comm_dtype),)


wire_all_to_all.defvjp(_wire_a2a_fwd, _wire_a2a_bwd)


def moe_capacity(n_tokens_local: int, top_k: int, capacity_factor: float,
                 n_experts: int) -> int:
    """The per-expert slot count every gating call in this module uses."""
    return max(1, int(capacity_factor * n_tokens_local * top_k / n_experts))


def all_to_all_wire_bytes(n_tokens_local: int, d_model: int, *,
                          n_experts: int, top_k: int,
                          capacity_factor: float, ep: int,
                          comm_dtype: Optional[str] = None,
                          dtype="float32") -> int:
    """Exact per-device wire bytes of ONE dispatch/combine exchange.

    The exchanged tensor is (ep, El, C, D) = E*C*D elements per device; an
    all_to_all keeps 1/ep of it local, so (ep-1)/ep of the payload crosses
    the wire — the same (n-1)/n convention the ZeRO wire accounting uses
    (zero.reduce_scatter_wire_bytes). Compression changes the element size
    (bf16: 2, int8: 1 + one f32 scale per outbound row); ``comm_dtype``
    None means the payload dtype. Multiply by 4 * n_layers for a full MoE
    training step (dispatch + combine, forward + backward).
    """
    if ep <= 1:
        return 0
    cap = moe_capacity(n_tokens_local, top_k, capacity_factor, n_experts)
    elems = n_experts * cap * d_model
    if comm_dtype == "bfloat16":
        item = 2
        extra = 0
    elif comm_dtype == "int8":
        item = 1
        extra = ep * 4                      # one f32 scale per outbound row
    else:
        item = _np.dtype(dtype).itemsize
        extra = 0
    return elems * item * (ep - 1) // ep + extra


# ---------------------------------------------------------------------------
# MoE layers
# ---------------------------------------------------------------------------

def moe_ffn(x, gate_w, w1, w2, *, top_k: int = 2,
            capacity_factor: float = 1.5, activation=jax.nn.relu,
            normalize_gates: bool = True, return_aux: bool = False):
    """Dense (single-shard) MoE FFN.

    x (N, D); gate_w (D, E); w1 (E, D, H); w2 (E, H, D). Returns (N, D),
    or ``(y, {"aux_loss", "dropped"})`` with ``return_aux=True`` — the
    Switch load-balance loss and the capacity-overflow count for this call.
    """
    N, D = x.shape
    E = gate_w.shape[1]
    capacity = moe_capacity(N, top_k, capacity_factor, E)
    logits = x @ gate_w
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine = topk_gating(logits, top_k, capacity)
    if normalize_gates:
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)
    expert_in = jnp.einsum("nd,nec->ecd", x, dispatch)     # (E, C, D)
    h = activation(jnp.einsum("ecd,edh->ech", expert_in, w1))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2)         # (E, C, D)
    y = jnp.einsum("ecd,nec->nd", expert_out, combine)
    if not return_aux:
        return y
    aux = {"aux_loss": load_balance_loss(probs, dispatch),
           "dropped": dropped_tokens(dispatch, N, top_k)}
    return y, aux


def expert_parallel_moe(x, gate_w, w1_local, w2_local, *, axis_name: str,
                        top_k: int = 2, capacity_factor: float = 1.5,
                        activation=jax.nn.relu, normalize_gates: bool = True,
                        comm_dtype: Optional[str] = None,
                        return_aux: bool = False):
    """Expert-parallel MoE FFN — call inside shard_map over `axis_name`.

    Tokens are sharded over the axis (x is the LOCAL (Nl, D) shard); experts
    are sharded too (w1_local (El, D, H), El = E / axis_size). Dataflow:

      gate locally over ALL E experts
      -> all_to_all: each device collects the slots destined to ITS experts
      -> batched expert FFN on local experts
      -> all_to_all back -> combine locally

    Same math as moe_ffn on the gathered arrays (up to capacity rounding);
    with ``axis_size == 1`` the exchanges are identities and the result
    equals ``moe_ffn`` bitwise. ``comm_dtype`` compresses both exchanges on
    the wire (``wire_all_to_all``).
    """
    n_dev = _axis_size(axis_name)
    Nl, D = x.shape
    El = w1_local.shape[0]
    E = El * n_dev
    capacity = moe_capacity(Nl, top_k, capacity_factor, E)

    logits = x @ gate_w                                     # (Nl, E)
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine = topk_gating(logits, top_k, capacity)
    if normalize_gates:
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)
    expert_in = jnp.einsum("nd,nec->ecd", x, dispatch)      # (E, C, D)
    # regroup experts by owner device and exchange: after all_to_all, axis 0
    # indexes the SOURCE device and axis 1 the local expert
    expert_in = expert_in.reshape(n_dev, El, capacity, D)
    expert_in = wire_all_to_all(expert_in, axis_name, comm_dtype)
    # (n_dev_src, El, C, D) -> (El, n_dev_src * C, D)
    gathered = jnp.moveaxis(expert_in, 0, 1).reshape(El, n_dev * capacity, D)
    h = activation(jnp.einsum("ecd,edh->ech", gathered, w1_local))
    out = jnp.einsum("ech,ehd->ecd", h, w2_local)           # (El, n_dev*C, D)
    # reverse exchange: send each source device its slots back
    out = jnp.moveaxis(out.reshape(El, n_dev, capacity, D), 1, 0)
    out = wire_all_to_all(out, axis_name, comm_dtype)       # (n_dev, El, C, D)
    out = out.reshape(E, capacity, D)
    y = jnp.einsum("ecd,nec->nd", out, combine)
    if not return_aux:
        return y
    aux = {"aux_loss": load_balance_loss(probs, dispatch),
           "dropped": dropped_tokens(dispatch, Nl, top_k)}
    return y, aux


def load_balancing_loss(logits, top_k: int = 2):
    """Auxiliary load-balance loss (Switch Transformer eq. 4) from raw
    logits, pre-capacity (kept for callers that gate elsewhere; the
    post-capacity variant is ``load_balance_loss``). Scalar >= 1/E."""
    N, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = lax.top_k(probs, top_k)
    me = jnp.mean(probs, axis=0)                            # mean router prob
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)     # token fraction
    return E * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# The dropless layer of one chip's share of the experts
# ---------------------------------------------------------------------------

def held_rows(n_tokens: int, top_k: int, held: int, published_experts: int):
    """(the rows of the sorted path's buffer; the most assignments that can
    fall on the experts held). The buffer is half again what uniform routing
    sends here (n_tokens * top_k * held / published_experts), rounded up to
    a power of two; the bound is every token on as many held experts as it
    may choose."""
    most = n_tokens * min(top_k, held)
    expected = n_tokens * top_k * held / published_experts
    rows = 1 << max(math.ceil(1.5 * expected) - 1, 0).bit_length()
    return min(rows, most), most


def held_moe_ffn(x, router_w, w_gate_up, w_down, *, top_k: int,
                 published_experts: int, first_held: int = 0,
                 scaling: float = 1.0, return_aux: bool = False):
    """Dropless top-k expert layer of a chip that holds `held` of the
    `published_experts` experts of a layer, `first_held` onwards: one
    chip's part of an expert-parallel layer, without its exchange.

    x (N, D); router_w (published_experts, D); w_gate_up (held, D, 2 F);
    w_down (held, F, D): SwiGLU experts, `[g, u] = x W_gate_up`,
    `W_down (silu(g) * u)`. Returns (N, D) in x's type:

        p = softmax(x router_w^T) over ALL published experts, in float32
        S = the top_k largest;  w_e = scaling * p_e / sum_{e' in S} p_e'
        y = sum_{e in S, e held here} w_e ffn_e(x)

    What the experts held elsewhere would add is left out: it is their
    chips' part of the sum. No assignment to a held expert is ever dropped,
    whatever the routing, and shapes stay static: the kept assignments are
    sorted by expert, and where the row buffer of `held_rows` holds them all
    its tokens are gathered, multiplied by groups (the kernels of
    `ops/pallas/grouped_matmul.py`, the SwiGLU and the combine weight inside
    them) and the weighted rows summed by token in float32 (one more
    grouped product, over the kept rows sorted by token). That path costs
    by `kept`, the assignments that fell here this step, not by the buffer:
    no kernel visits a row tile past the last kept row. Where a step's
    routing sends more here than
    the buffer holds, a `lax.cond` takes the exact dense path instead: every
    held expert over every token, times its combine weight (zero where not
    routed). Where the buffer reaches the bound there is no dense path. Both
    paths are recomputed in the backward pass (`jax.checkpoint`), so the
    `cond` keeps neither's residuals.

    `return_aux`: also {"kept": assignments that fell on held experts,
    "max_load", "mean_load": of a held expert, "exact": whether the dense
    path ran}, device values that nothing has to read back."""
    N, D = x.shape
    held, _, two_f = w_gate_up.shape
    F = two_f // 2
    if router_w.shape[0] != published_experts \
            or first_held + held > published_experts:
        raise ValueError(
            f"a router over {router_w.shape[0]} experts and experts "
            f"{first_held}..{first_held + held - 1} held, of a layer that "
            f"publishes {published_experts}")
    n_rows, most = held_rows(N, top_k, held, published_experts)
    f32 = jnp.float32

    with jax.named_scope("mx.moe.route"):
        logits = jnp.dot(x.astype(f32), router_w.astype(f32).T,
                         precision=lax.Precision.HIGHEST)
        vals, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        weight = scaling * vals / jnp.sum(vals, axis=-1, keepdims=True)
        local = idx.astype(jnp.int32) - first_held           # (N, k)
        here = jnp.logical_and(local >= 0, local < held)
        # kept assignments first, by expert; the others behind them
        key = jnp.where(here, local, held).reshape(-1)
        counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                         dtype=jnp.int32)                     # (held,)
        kept = jnp.sum(counts)
        skey, order = lax.sort_key_val(
            key, jnp.arange(N * top_k, dtype=jnp.int32))

    def sorted_rows(x, w_gate_up, w_down, weight):
        with jax.named_scope("mx.moe.experts"):
            chosen, valid = order[:n_rows], skey[:n_rows] < held
            # the grouped products and the sums by token stop at `kept`:
            # what lies behind in the buffer is never read, and what comes
            # back for it (the combine weights' cotangent) is selected away
            w_row = jnp.where(valid, weight.reshape(-1)[chosen], 0.0)
            return routed_swiglu(x, w_gate_up, w_down, w_row,
                                 chosen // top_k, counts, not _on_tpu(x))

    def every_row(x, w_gate_up, w_down, weight):
        with jax.named_scope("mx.moe.experts"):
            combine = jnp.sum(jnp.where(
                local[:, :, None] == jnp.arange(held)[None, None, :],
                weight[:, :, None], 0.0), axis=1)             # (N, held)

            def one(acc, ws):
                gate_up, down, w_e = ws
                gu = x @ gate_up
                y = (jax.nn.silu(gu[:, :F]) * gu[:, F:]).astype(x.dtype) @ down
                return acc + w_e[:, None] * y.astype(f32), None

            acc, _ = lax.scan(one, jnp.zeros((N, D), f32),
                              (w_gate_up, w_down, combine.T))
            return acc.astype(x.dtype)

    exact = kept > n_rows
    if n_rows < most:
        y = lax.cond(exact, jax.checkpoint(every_row),
                     jax.checkpoint(sorted_rows), x, w_gate_up, w_down, weight)
    else:
        y = jax.checkpoint(sorted_rows)(x, w_gate_up, w_down, weight)
    if not return_aux:
        return y
    return y, {"kept": kept, "max_load": jnp.max(counts),
               "mean_load": kept.astype(f32) / held, "exact": exact}


# ---------------------------------------------------------------------------
# Trace-time plumbing for model cells (models/moe_transformer.py): which
# mesh axis the MoE layers should dispatch over, and where they report
# their per-call aux loss / dropped count. Both are plain trace-time
# context stacks — the recipe trainer opens them around the apply-fn call
# inside its loss function, so the collected values are tracers belonging
# to that trace and flow into the fused step's outputs.
# ---------------------------------------------------------------------------

class _ExpertCtx:
    __slots__ = ("axis_name", "comm_dtype")

    def __init__(self, axis_name, comm_dtype):
        self.axis_name = axis_name
        self.comm_dtype = comm_dtype


_EXPERT_STACK: List[_ExpertCtx] = []
_COLLECT_STACK: List["MoEMetrics"] = []


class MoEMetrics:
    """Per-trace accumulator the MoE cells append to."""

    def __init__(self):
        self.aux_losses = []
        self.dropped = []

    def add(self, aux):
        self.aux_losses.append(aux["aux_loss"])
        self.dropped.append(aux["dropped"])

    def aux_loss(self):
        return sum(self.aux_losses) if self.aux_losses else jnp.float32(0.0)

    def dropped_total(self):
        return sum(self.dropped) if self.dropped else jnp.int32(0)


@contextlib.contextmanager
def expert_axis(axis_name: str, comm_dtype: Optional[str] = None):
    """While active, MoE cells traced under this context dispatch with
    ``expert_parallel_moe`` over `axis_name` (their expert params are the
    local ep shards) instead of the single-shard ``moe_ffn``."""
    _EXPERT_STACK.append(_ExpertCtx(axis_name, comm_dtype))
    try:
        yield
    finally:
        _EXPERT_STACK.pop()


def current_expert_axis() -> Optional[_ExpertCtx]:
    return _EXPERT_STACK[-1] if _EXPERT_STACK else None


@contextlib.contextmanager
def collect_metrics():
    """Collect every MoE cell's (aux_loss, dropped) traced inside the
    ``with`` body. Yields the ``MoEMetrics`` accumulator."""
    mc = MoEMetrics()
    _COLLECT_STACK.append(mc)
    try:
        yield mc
    finally:
        _COLLECT_STACK.pop()


def report_metrics(aux):
    """Called by MoE cells after each gated forward."""
    if _COLLECT_STACK:
        _COLLECT_STACK[-1].add(aux)
