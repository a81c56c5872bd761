"""Compute-partitioned (Megatron-style) tensor parallelism for the manual
pipeline programs (parallel/pipeline.py, ``tp_mode="partitioned"``).

The weight-sharded TP path gathers every sharded weight back to full size
once per step (tensor_parallel.gather_tp) — O(params/tp) wire volume and a
full-size weight copy per rank, which caps layer size at one chip's HBM.
This module keeps weights sharded FOREVER and moves the collectives onto
the (much smaller) activations, Megatron-LM style (arXiv:1909.08053):

  - column-parallel Dense (qkv / ffn-in): shard the OUT dim. No forward
    collective; the backward psums the input cotangent (``copy_to_tp``'s
    VJP is that psum).
  - row-parallel Dense (proj / ffn-out): shard the IN dim. The forward
    psums the partial products (``reduce_from_tp``); backward is local.
  - attention: heads split over 'tp' (head-blocks of the fused qkv
    projection land whole q/k/v triples per rank).
  - vocab-parallel embedding + cross-entropy: the (V, C) tables shard on
    vocab; the loss psums the per-rank max / log-normalizer / gold-logit
    pieces so the full-vocab logits tensor is NEVER materialized.
  - sequence parallelism (``sequence_parallel=True``): the regions TP
    cannot partition (layernorm / dropout / residual) run on (B, T/tp, C)
    sequence shards over the SAME tp axis group; the region boundaries
    become all_gather <-> psum_scatter pairs (``gather_from_sp`` /
    ``scatter_to_sp``) instead of pure psums, cutting the non-matmul
    activation memory by the tp factor.

Collectives and the replicated-gradient convention
--------------------------------------------------
All programs run inside ``zero.shard_map_compat`` (check_vma=False), where
a plain ``lax.psum`` transposes to ANOTHER psum — differentiating through
it would inflate gradients by tp (the exact failure pipeline.py's GPipe
loss masking documents). Every boundary collective here is therefore an
explicit ``jax.custom_vjp`` pair:

  ============== ==================== ====================
  op             forward              backward
  ============== ==================== ====================
  copy_to_tp     identity             psum
  reduce_from_tp psum                 identity
  gather_from_sp all_gather (tiled)   psum_scatter (tiled)
  scatter_to_sp  psum_scatter (tiled) all_gather (tiled)
  partial_grad   identity             cotangent / tp
  ============== ==================== ====================

Gradient convention for REPLICATED leaves (layernorm gamma/beta, position
tables, row-parallel biases, the bert MLM dense): the trainer psums their
per-rank gradients over tp, so every program must hand back PARTIAL sums.
Leaves consumed on per-token (sequence-sharded) or per-rank-slice compute
are naturally partial; leaves consumed by replicated compute produce
rank-identical FULL gradients and are wrapped with ``partial_grad`` (its
VJP divides by tp) so the psum reconstructs — not tp-multiplies — them.

Numerical parity: the programs call the registered op functions
(ops/nn.py ``fully_connected``/``layer_norm``/``dropout``/...) directly,
so with tp=1 the partitioned step is the same op sequence the gluon
oracle traces — the tp in {1, 2, 4} parity tests in
tests/test_partitioned_tp.py pin this. Each collective runs under a
``jax.named_scope`` region name (mx.tp.* / mx.sp.*) so span traces and
the roofline ledger attribute tp comm (tools/check_instrumentation.py
gates these).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..ops import attention as _attn_ops
from ..ops import nn as _ops
from .mesh import axis_size as _axis_size

__all__ = [
    "copy_to_tp", "reduce_from_tp", "gather_from_sp", "scatter_to_sp",
    "partial_grad", "vocab_parallel_embedding",
    "vocab_parallel_cross_entropy", "PartitionConfig", "view_shape",
    "view_shard_dim", "CellPlan", "EmbedPlan", "HeadPlan", "plan_cell",
    "plan_embed", "plan_head", "cell_forward", "embed_forward",
    "head_loss_forward",
]


# ---------------------------------------------------------------------------
# Boundary collectives (explicit custom_vjp — see module docstring table)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tp(x, axis: str):
    """Megatron's f operator: identity forward, psum backward. Marks the
    entry of a column-parallel region — the cotangent flowing back out is
    the sum of every rank's partial contribution."""
    with jax.named_scope("mx.tp.copy_in"):
        return x


def _copy_fwd(x, axis):
    return copy_to_tp(x, axis), None


def _copy_bwd(axis, _res, ct):
    with jax.named_scope("mx.tp.grad_psum"):
        return (lax.psum(ct, axis),)


copy_to_tp.defvjp(_copy_fwd, _copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tp(x, axis: str):
    """Megatron's g operator: psum forward (row-parallel partial products
    -> full activation), identity backward (the downstream cotangent is
    already rank-identical)."""
    with jax.named_scope("mx.tp.act_psum"):
        return lax.psum(x, axis)


def _reduce_fwd(x, axis):
    return reduce_from_tp(x, axis), None


def _reduce_bwd(axis, _res, ct):
    return (ct,)


reduce_from_tp.defvjp(_reduce_fwd, _reduce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_from_sp(x, axis: str, dim: int = 1):
    """Sequence-parallel region exit -> tensor-parallel region entry:
    all-gather the sequence shards (forward), psum_scatter the cotangent
    (backward) — each rank's partial cotangent for every token is summed
    and the owning rank keeps its slice."""
    with jax.named_scope("mx.sp.all_gather"):
        return lax.all_gather(x, axis, axis=dim, tiled=True)


def _gather_sp_fwd(x, axis, dim):
    return gather_from_sp(x, axis, dim), None


def _gather_sp_bwd(axis, dim, _res, ct):
    with jax.named_scope("mx.sp.grad_psum_scatter"):
        return (lax.psum_scatter(ct, axis, scatter_dimension=dim,
                                 tiled=True),)


gather_from_sp.defvjp(_gather_sp_fwd, _gather_sp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def scatter_to_sp(x, axis: str, dim: int = 1):
    """Tensor-parallel region exit -> sequence-parallel region entry:
    psum_scatter the partial products (forward — the psum of
    ``reduce_from_tp`` fused with the sequence split), all-gather the
    cotangent shards back (backward)."""
    with jax.named_scope("mx.sp.act_psum_scatter"):
        return lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)


def _scatter_sp_fwd(x, axis, dim):
    return scatter_to_sp(x, axis, dim), None


def _scatter_sp_bwd(axis, dim, _res, ct):
    with jax.named_scope("mx.sp.grad_all_gather"):
        return (lax.all_gather(ct, axis, axis=dim, tiled=True),)


scatter_to_sp.defvjp(_scatter_sp_fwd, _scatter_sp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def partial_grad(x, axis: str):
    """Identity whose VJP divides by the tp degree. Wraps replicated
    leaves consumed by REPLICATED compute, converting their rank-identical
    full gradients to the partial-sum convention the trainer's tp psum
    expects (see module docstring)."""
    with jax.named_scope("mx.tp.partial_grad"):
        return x


def _partial_fwd(x, axis):
    return partial_grad(x, axis), None


def _partial_bwd(axis, _res, ct):
    n = _axis_size(axis)
    return (ct / n if jnp.issubdtype(ct.dtype, jnp.floating)
            else ct,)


partial_grad.defvjp(_partial_fwd, _partial_bwd)


# ---------------------------------------------------------------------------
# Partition configuration + weight-view layout helpers
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PartitionConfig:
    """How the cell/embed/head programs partition: the tp mesh axis, its
    degree, and whether the non-matmul regions are sequence-sharded over
    the same axis group (Megatron sequence parallelism)."""
    axis: str
    n_tp: int
    sp: bool = False


def view_shape(shape: Tuple[int, ...], layout) -> Tuple[int, ...]:
    """Storage shape of a partitioned leaf. ``layout`` is None (replicated)
    or ``(dim, blocks)``: shard ``dim`` over tp in ``blocks`` interleaved
    blocks. blocks > 1 (the fused qkv's (3C, C): q/k/v row blocks) stores
    the leaf reshaped to (..., blocks, size/blocks, ...) and shards the
    WITHIN-block sub-dim, so rank r's slice is (q_r; k_r; v_r) — and the
    stored global shape is tp-degree independent (elastic resharding
    tp=2 -> tp=4 needs no permutation)."""
    if layout is None:
        return tuple(shape)
    dim, blocks = layout
    if blocks <= 1:
        return tuple(shape)
    return tuple(shape[:dim]) + (blocks, shape[dim] // blocks) \
        + tuple(shape[dim + 1:])


def view_shard_dim(layout) -> Optional[int]:
    """Which dim of the VIEW shape carries the tp sharding."""
    if layout is None:
        return None
    dim, blocks = layout
    return dim + 1 if blocks > 1 else dim


def _merge_view(w, layout):
    """Local view shard -> the flat local compute shape (inverse of the
    per-rank slice of ``view_shape``): (..., blocks, rows/tp, ...) ->
    (..., blocks*rows/tp, ...)."""
    if layout is None:
        return w
    dim, blocks = layout
    if blocks <= 1:
        return w
    shape = w.shape[:dim] + (w.shape[dim] * w.shape[dim + 1],) \
        + w.shape[dim + 2:]
    return w.reshape(shape)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding + cross-entropy
# ---------------------------------------------------------------------------

def vocab_parallel_embedding(ids, table_local, axis: str):
    """PARTIAL embedding lookup on a vocab-sharded (V/tp, C) table: tokens
    outside this rank's vocab range contribute zeros. The caller reduces
    (``reduce_from_tp``) or reduce-scatters (``scatter_to_sp``) the
    partials — the full table is never gathered."""
    with jax.named_scope("mx.tp.vocab_embed"):
        v_local = table_local.shape[0]
        off = lax.axis_index(axis) * v_local
        loc = ids.astype(jnp.int32) - off
        ok = jnp.logical_and(loc >= 0, loc < v_local)
        emb = _ops.embedding(jnp.clip(loc, 0, v_local - 1), table_local)
        return jnp.where(ok[..., None], emb, jnp.zeros((), emb.dtype))


def vocab_parallel_cross_entropy(h, w_local, b_local, labels, axis: str):
    """Fused LM head + mean token cross-entropy over a vocab-sharded
    decoder, full-vocab logits never materialized. Per rank: local logits
    (B, T, V/tp) in f32; the global max (psum-free pmax, stop-gradient —
    a shift constant), the log-normalizer and the gold logit each cross
    ranks as (B, T) psums. Matches ``jnp.mean`` of
    gluon.loss.SoftmaxCrossEntropyLoss / recipes.moe.token_cross_entropy
    on the gathered logits to float tolerance."""
    logits = _ops.fully_connected(h, w_local, b_local,
                                  flatten=False).astype(jnp.float32)
    v_local = w_local.shape[0]
    off = lax.axis_index(axis) * v_local
    with jax.named_scope("mx.tp.vocab_pmax"):
        # stop_gradient INSIDE the pmax: pmax has no JVP rule, so the
        # linearization must see a constant (the shift is mathematically
        # gradient-free anyway)
        zmax = lax.pmax(lax.stop_gradient(jnp.max(logits, axis=-1)), axis)
    sumexp = jnp.sum(jnp.exp(logits - zmax[..., None]), axis=-1)
    norm = reduce_from_tp(sumexp, axis)                    # (B, T) psum
    loc = labels.astype(jnp.int32) - off
    ok = jnp.logical_and(loc >= 0, loc < v_local)
    gold_local = jnp.take_along_axis(
        logits, jnp.clip(loc, 0, v_local - 1)[..., None], axis=-1)[..., 0]
    gold = reduce_from_tp(jnp.where(ok, gold_local, 0.0), axis)
    return jnp.mean(zmax + jnp.log(norm) - gold)


# ---------------------------------------------------------------------------
# Layer plans: which plist slot plays which role, and each leaf's layout
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _Dense:
    w: int
    b: Optional[int]


@dataclass(frozen=True)
class _MoE:
    gate_w: int
    w1: int
    w2: int
    top_k: int
    capacity_factor: float
    hidden: int
    n_experts: int


@dataclass(frozen=True)
class CellPlan:
    units: int
    heads: int
    head_major: bool
    use_blockwise: bool          # bert SelfAttention length-adaptive flash
    causal: bool                 # LC RingSelfAttention (causal LM cell)
    dense_oracle: bool           # LC dense_attention parity path
    attn_dropout: float
    ffn_dropout: float
    eps1: float
    eps2: float
    ln1: Tuple[int, int]
    ln2: Tuple[int, int]
    qkv: _Dense
    proj: _Dense
    ffn1: Optional[_Dense]
    ffn2: Optional[_Dense]
    moe: Optional[_MoE]
    layouts: Tuple[Optional[Tuple[int, int]], ...]


@dataclass(frozen=True)
class EmbedPlan:
    units: int
    word_w: int
    pos_w: int
    eps: float
    ln: Tuple[int, int]
    dropout: float
    layouts: Tuple[Optional[Tuple[int, int]], ...]


@dataclass(frozen=True)
class HeadPlan:
    units: int
    vocab: int
    eps: float
    ln: Tuple[int, int]
    mlm_dense: Optional[_Dense]      # bert MLM transform (dense + LN)
    mlm_ln: Optional[Tuple[int, int]]
    mlm_eps: float
    dec: _Dense
    layouts: Tuple[Optional[Tuple[int, int]], ...]


def _slot_map(plist):
    return {id(p): i for i, p in enumerate(plist)}


def _slot(slots, param, what):
    i = slots.get(id(param))
    if i is None:
        raise MXNetError(
            f"partitioned tp: {what} parameter is not in the stage's "
            "parameter list — pipeline stages must own their blocks")
    return i


def _require_divisible(value, n_tp, what):
    if value % n_tp != 0:
        raise MXNetError(
            f"partitioned tp: {what} ({value}) does not divide by "
            f"tp={n_tp}")


def _ln_plan(slots, ln, what):
    eps = float(getattr(ln, "_epsilon", 1e-5))
    return (_slot(slots, ln.gamma, f"{what}.gamma"),
            _slot(slots, ln.beta, f"{what}.beta")), eps


def _drop_rate(block) -> float:
    return float(block._rate) if block is not None else 0.0


def plan_cell(cell, plist, n_tp: int) -> CellPlan:
    """Build the partition plan for one transformer cell. Recognizes the
    bert ``TransformerEncoderCell`` / long-context ``_LCCell`` (dense FFN)
    and ``MoETransformerCell`` (gated-expert FFN) structures; anything
    else — or a non-fused qkv — raises with guidance."""
    from ..models.bert import SelfAttention
    slots = _slot_map(plist)
    attn = getattr(cell, "attn", None)
    ln1, ln2 = getattr(cell, "ln1", None), getattr(cell, "ln2", None)
    if attn is None or ln1 is None or ln2 is None:
        raise MXNetError(
            f"partitioned tp: cell {type(cell).__name__} is not a "
            "pre-LN transformer block (needs .ln1/.attn/.ln2 and an "
            ".ffn or .moe)")
    if getattr(attn, "qkv", None) is None:
        raise MXNetError(
            "partitioned tp requires the fused qkv projection "
            "(SelfAttention(fused_qkv=True)): separate q/k/v matmuls "
            "would shard into three tp-unfriendly K-splits")
    units = int(attn._units)
    heads = int(attn._heads)
    _require_divisible(heads, n_tp, "attention heads")
    is_bert_attn = isinstance(attn, SelfAttention)
    head_major = bool(getattr(attn, "_head_major", False))
    layouts: List[Optional[Tuple[int, int]]] = [None] * len(plist)

    qkv = _Dense(_slot(slots, attn.qkv.weight, "qkv.weight"),
                 _slot(slots, attn.qkv.bias, "qkv.bias"))
    # head-major fused qkv keeps whole (q,k,v,head) triples contiguous in
    # the out dim — a plain 1-block shard; the default (3, H, d) layout
    # shards inside each of the q/k/v row blocks (blocks=3)
    blocks = 1 if head_major else 3
    layouts[qkv.w] = (0, blocks)
    layouts[qkv.b] = (0, blocks)
    proj = _Dense(_slot(slots, attn.proj.weight, "proj.weight"),
                  _slot(slots, attn.proj.bias, "proj.bias"))
    layouts[proj.w] = (1, 1)

    (ln1_idx, eps1) = _ln_plan(slots, ln1, "ln1")
    (ln2_idx, eps2) = _ln_plan(slots, ln2, "ln2")

    ffn1 = ffn2 = moe = None
    ffn = getattr(cell, "ffn", None)
    moe_blk = getattr(cell, "moe", None)
    if ffn is not None:
        hidden = ffn.ffn1.weight.shape[0]
        _require_divisible(hidden, n_tp, "ffn hidden size")
        ffn1 = _Dense(_slot(slots, ffn.ffn1.weight, "ffn1.weight"),
                      _slot(slots, ffn.ffn1.bias, "ffn1.bias"))
        ffn2 = _Dense(_slot(slots, ffn.ffn2.weight, "ffn2.weight"),
                      _slot(slots, ffn.ffn2.bias, "ffn2.bias"))
        layouts[ffn1.w] = (0, 1)
        layouts[ffn1.b] = (0, 1)
        layouts[ffn2.w] = (1, 1)
        ffn_dropout = _drop_rate(getattr(ffn, "dropout", None))
    elif moe_blk is not None:
        if getattr(moe_blk, "_dense_ffn", False):
            raise MXNetError(
                "partitioned tp: the MoE dense_ffn oracle uses expert 0 "
                "only, which lives on one tp rank after expert sharding; "
                "run the oracle with tp_mode='sharded'")
        n_experts = int(moe_blk._num_experts)
        _require_divisible(n_experts, n_tp, "MoE experts")
        moe = _MoE(_slot(slots, moe_blk.gate_w, "moe.gate_w"),
                   _slot(slots, moe_blk.w1, "moe.w1"),
                   _slot(slots, moe_blk.w2, "moe.w2"),
                   int(moe_blk._top_k), float(moe_blk._capacity_factor),
                   int(moe_blk.w1.shape[2]), n_experts)
        layouts[moe.w1] = (0, 1)
        layouts[moe.w2] = (0, 1)
        ffn_dropout = 0.0
    else:
        raise MXNetError(
            f"partitioned tp: cell {type(cell).__name__} has neither "
            ".ffn (PositionwiseFFN) nor .moe (MoEPositionwiseFFN)")

    return CellPlan(
        units=units, heads=heads, head_major=head_major,
        use_blockwise=bool(getattr(attn, "_use_blockwise", False)),
        causal=not is_bert_attn,
        dense_oracle=bool(getattr(attn, "_dense", False)),
        attn_dropout=_drop_rate(getattr(attn, "dropout", None)),
        ffn_dropout=ffn_dropout, eps1=eps1, eps2=eps2,
        ln1=ln1_idx, ln2=ln2_idx, qkv=qkv, proj=proj,
        ffn1=ffn1, ffn2=ffn2, moe=moe, layouts=tuple(layouts))


def plan_embed(embed, plist, n_tp: int) -> EmbedPlan:
    """Partition plan for the embedding stage (word + position tables +
    LN + optional dropout — the bert/_LC/MoE embed-stage shape). Unused
    extra tables (bert's seg_embed) stay replicated with zero grads, like
    the oracle."""
    slots = _slot_map(plist)
    word = getattr(embed, "word_embed", None)
    pos = getattr(embed, "pos_embed", None)
    ln = getattr(embed, "embed_ln", None)
    if word is None or pos is None or ln is None:
        raise MXNetError(
            f"partitioned tp: embed stage {type(embed).__name__} needs "
            ".word_embed/.pos_embed/.embed_ln")
    vocab, units = word.weight.shape
    _require_divisible(vocab, n_tp, "vocab size")
    layouts: List[Optional[Tuple[int, int]]] = [None] * len(plist)
    word_w = _slot(slots, word.weight, "word_embed.weight")
    layouts[word_w] = (0, 1)
    ln_idx, eps = _ln_plan(slots, ln, "embed_ln")
    return EmbedPlan(
        units=int(units), word_w=word_w,
        pos_w=_slot(slots, pos.weight, "pos_embed.weight"),
        eps=eps, ln=ln_idx,
        dropout=_drop_rate(getattr(embed, "drop", None)),
        layouts=tuple(layouts))


def plan_head(head, plist, n_tp: int) -> HeadPlan:
    """Partition plan for the head stage: final LN (+ bert's MLM dense/LN
    transform) + vocab-sharded decoder fused into the cross-entropy."""
    slots = _slot_map(plist)
    ln = getattr(head, "ln", None)
    dec = getattr(head, "mlm_decoder", None) or getattr(head, "decoder",
                                                        None)
    if ln is None or dec is None:
        raise MXNetError(
            f"partitioned tp: head stage {type(head).__name__} needs "
            ".ln and .decoder/.mlm_decoder")
    vocab, units = dec.weight.shape
    _require_divisible(vocab, n_tp, "decoder vocab size")
    layouts: List[Optional[Tuple[int, int]]] = [None] * len(plist)
    dec_idx = _Dense(_slot(slots, dec.weight, "decoder.weight"),
                     _slot(slots, dec.bias, "decoder.bias"))
    layouts[dec_idx.w] = (0, 1)
    layouts[dec_idx.b] = (0, 1)
    ln_idx, eps = _ln_plan(slots, ln, "head.ln")
    mlm_dense = mlm_ln = None
    mlm_eps = 1e-5
    if getattr(head, "mlm_dense", None) is not None:
        mlm_dense = _Dense(
            _slot(slots, head.mlm_dense.weight, "mlm_dense.weight"),
            _slot(slots, head.mlm_dense.bias, "mlm_dense.bias"))
        mlm_ln, mlm_eps = _ln_plan(slots, head.mlm_ln, "mlm_ln")
    return HeadPlan(units=int(units), vocab=int(vocab), eps=eps, ln=ln_idx,
                    mlm_dense=mlm_dense, mlm_ln=mlm_ln, mlm_eps=mlm_eps,
                    dec=dec_idx, layouts=tuple(layouts))


# ---------------------------------------------------------------------------
# Program bodies (called from PipelineTrainer's schedule tick functions)
# ---------------------------------------------------------------------------

def _rep_fn(cfg: PartitionConfig, token_sharded: bool):
    """Leaf wrapper for replicated leaves: identity when their consuming
    compute is token-sharded (gradients are naturally partial), else
    ``partial_grad`` (rank-identical full grads -> partial convention)."""
    if token_sharded or cfg.n_tp <= 1:
        return lambda w: w
    return lambda w: partial_grad(w, cfg.axis)


def _dropout(x, key, rate, cfg: PartitionConfig, full_shape):
    """Dropout with SEQUENCE-PARITY masks: the bernoulli mask is always
    drawn at the full (unsharded) activation shape from the shared step
    key and sliced to the local tokens under sp, so the sp and non-sp
    programs drop the SAME elements for the same key (the sequence-
    parallel dropout parity test depends on it). Mirrors ops/nn.py
    ``dropout`` exactly when full_shape == x.shape."""
    if rate <= 0.0:
        return x
    keep = 1.0 - rate
    with jax.named_scope("mx.tp.dropout"):
        mask = jax.random.bernoulli(key, keep, tuple(full_shape))
        if mask.shape != x.shape:
            t_local = x.shape[1]
            mask = lax.dynamic_slice_in_dim(
                mask, lax.axis_index(cfg.axis) * t_local, t_local, axis=1)
        return jnp.where(mask, x / keep, jnp.zeros((), x.dtype))


def _enter_tp(x, cfg: PartitionConfig):
    """Non-matmul region -> matmul region boundary."""
    if cfg.n_tp <= 1:
        return x
    return gather_from_sp(x, cfg.axis, 1) if cfg.sp \
        else copy_to_tp(x, cfg.axis)


def _exit_tp(x, cfg: PartitionConfig):
    """Matmul region (partial products) -> non-matmul region boundary."""
    if cfg.n_tp <= 1:
        return x
    return scatter_to_sp(x, cfg.axis, 1) if cfg.sp \
        else reduce_from_tp(x, cfg.axis)


def _attention(plan: CellPlan, cfg: PartitionConfig, x, leaves, key,
               train: bool):
    """Head-partitioned self-attention on the gathered (B, T, C) input;
    returns the row-parallel proj's PARTIAL (B, T, C) product (the caller
    crosses the exit boundary and adds the replicated bias). Mirrors
    models/bert.SelfAttention / recipes/long_context.RingSelfAttention
    math exactly on the local head subset."""
    n_tp = cfg.n_tp
    h_local = plan.heads // n_tp
    d = plan.units // plan.heads
    wq = _merge_view(leaves[plan.qkv.w], plan.layouts[plan.qkv.w])
    bq = _merge_view(leaves[plan.qkv.b], plan.layouts[plan.qkv.b])
    qkv = _ops.fully_connected(x, wq, bq, flatten=False)  # (B, T, 3C/tp)
    B, T = qkv.shape[0], qkv.shape[1]
    if plan.head_major:
        qkv = qkv.reshape(B, T, h_local, 3, d)
        q, k, v = (jnp.transpose(qkv[:, :, :, i, :], (0, 2, 1, 3))
                   for i in range(3))
    else:
        qkv = qkv.reshape(B, T, 3, h_local, d)
        q, k, v = (jnp.transpose(qkv[:, :, i], (0, 2, 1, 3))
                   for i in range(3))
    if plan.causal:
        if plan.dense_oracle:
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32) / (d ** 0.5)
            mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            s = jnp.where(mask[None, None], s, -1e30)
            out = jnp.einsum("bhqk,bhkd->bhqd",
                             jax.nn.softmax(s, axis=-1),
                             v.astype(jnp.float32)).astype(q.dtype)
        else:
            out = _attn_ops.flash_attention_op(q, k, v, causal=True)
    else:
        if plan.use_blockwise and _attn_ops.use_flash(T):
            out = _attn_ops.flash_attention_op(q, k, v, causal=False)
        else:
            q2 = q.reshape(B * h_local, T, d)
            k2 = k.reshape(B * h_local, T, d)
            v2 = v.reshape(B * h_local, T, d)
            scores = jnp.matmul(q2, jnp.swapaxes(k2, -1, -2)) \
                / math.sqrt(d)
            att = _ops.softmax(scores, axis=-1)
            out = jnp.matmul(att, v2).reshape(B, h_local, T, d)
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(B, T, h_local * d)
    wp = leaves[plan.proj.w]                  # (C, C/tp): matching columns
    return _ops.fully_connected(out, wp, None, flatten=False)


def _tp_moe(plan: _MoE, cfg: PartitionConfig, flat, gate_w, w1_local,
            w2_local):
    """Expert-partitioned MoE FFN: gating is computed replicated over the
    FULL token set (identical dispatch/combine on every rank — same
    capacity/overflow semantics as the single-shard ``moe_ffn``), then
    each rank applies its E/tp expert slice of the dispatch/combine
    tensors. Gradients of gate_w / the input flow only through the local
    expert slices, so they are naturally partial. Returns the PARTIAL
    (N, C) combine product for the caller's exit collective."""
    from . import moe as _moe
    N = flat.shape[0]
    e_local = w1_local.shape[0]
    capacity = _moe.moe_capacity(N, plan.top_k, plan.capacity_factor,
                                 plan.n_experts)
    logits = flat @ gate_w
    dispatch, combine = _moe.topk_gating(logits, plan.top_k, capacity)
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)       # normalize_gates
    r = lax.axis_index(cfg.axis)
    disp_l = lax.dynamic_slice_in_dim(dispatch, r * e_local, e_local,
                                      axis=1)
    comb_l = lax.dynamic_slice_in_dim(combine, r * e_local, e_local,
                                      axis=1)
    expert_in = jnp.einsum("nd,nec->ecd", flat, disp_l)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w1_local))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2_local)
    return jnp.einsum("ecd,nec->nd", expert_out, comb_l)


def cell_forward(plan: CellPlan, cfg: PartitionConfig, leaves, h, key,
                 train: bool = True):
    """One partitioned transformer cell. ``h`` is (B, T, C) replicated, or
    (B, T/tp, C) under sequence parallelism; ``leaves`` are this rank's
    local view shards in plist order; ``key`` a typed PRNG key unique per
    (step, stage, layer, microbatch)."""
    rep = _rep_fn(cfg, cfg.sp)
    full_T = h.shape[1] * (cfg.n_tp if (cfg.sp and cfg.n_tp > 1) else 1)
    full_act = (h.shape[0], full_T, h.shape[2])

    a = _ops.layer_norm(h, rep(leaves[plan.ln1[0]]),
                        rep(leaves[plan.ln1[1]]), eps=plan.eps1)
    att = _attention(plan, cfg, _enter_tp(a, cfg), leaves,
                     jax.random.fold_in(key, 0), train)
    att = _exit_tp(att, cfg)
    att = att + rep(leaves[plan.proj.b])
    if train and plan.attn_dropout:
        att = _dropout(att, jax.random.fold_in(key, 1), plan.attn_dropout,
                       cfg, full_act)
    h = h + att

    b = _ops.layer_norm(h, rep(leaves[plan.ln2[0]]),
                        rep(leaves[plan.ln2[1]]), eps=plan.eps2)
    bf = _enter_tp(b, cfg)
    if plan.moe is not None:
        B, T, C = bf.shape
        y = _tp_moe(plan.moe, cfg, bf.reshape(B * T, C),
                    leaves[plan.moe.gate_w], leaves[plan.moe.w1],
                    leaves[plan.moe.w2]).reshape(B, T, C)
        y = _exit_tp(y, cfg)
    else:
        w1 = leaves[plan.ffn1.w]
        hdn = _ops.activation(
            _ops.fully_connected(bf, w1, leaves[plan.ffn1.b],
                                 flatten=False), act_type="gelu")
        y = _ops.fully_connected(hdn, leaves[plan.ffn2.w], None,
                                 flatten=False)
        y = _exit_tp(y, cfg)
        y = y + rep(leaves[plan.ffn2.b])
        if train and plan.ffn_dropout:
            y = _dropout(y, jax.random.fold_in(key, 2), plan.ffn_dropout,
                         cfg, full_act)
    return h + y


def embed_forward(plan: EmbedPlan, cfg: PartitionConfig, leaves, tokens,
                  key, train: bool = True):
    """Vocab-parallel embedding stage: partial word lookup -> reduce (or
    reduce-scatter to sequence shards) -> positions -> LN -> dropout.
    tokens: (B, T) int — the FULL sequence on every rank."""
    rep = _rep_fn(cfg, cfg.sp)
    T = tokens.shape[1]
    emb = vocab_parallel_embedding(tokens, leaves[plan.word_w], cfg.axis) \
        if cfg.n_tp > 1 else _ops.embedding(tokens, leaves[plan.word_w])
    pos_w = leaves[plan.pos_w]
    if cfg.sp and cfg.n_tp > 1:
        x = scatter_to_sp(emb, cfg.axis, 1)              # (B, T/tp, C)
        t_local = T // cfg.n_tp
        pos = lax.axis_index(cfg.axis) * t_local \
            + jnp.arange(t_local, dtype=jnp.int32)
        # per-rank position rows: grads land partial with no wrap
        x = x + _ops.embedding(pos, pos_w)[None]
    else:
        x = reduce_from_tp(emb, cfg.axis) if cfg.n_tp > 1 else emb
        pos = jnp.arange(T, dtype=jnp.int32)
        x = x + _ops.embedding(pos, rep(pos_w))[None]
    x = _ops.layer_norm(x, rep(leaves[plan.ln[0]]), rep(leaves[plan.ln[1]]),
                        eps=plan.eps)
    if train and plan.dropout:
        full = (x.shape[0], T, x.shape[2])
        x = _dropout(x, jax.random.fold_in(key, 0), plan.dropout, cfg,
                     full)
    return x


def head_loss_forward(plan: HeadPlan, cfg: PartitionConfig, leaves, h,
                      labels, key=None, train: bool = True):
    """Head stage fused with the vocab-parallel cross-entropy: LN (+ the
    bert MLM transform) on the (optionally sequence-sharded) activations,
    gather to full tokens, then the never-materialize-the-logits loss.
    labels: (B, T) int. Returns the scalar mean token loss (identical on
    every tp rank)."""
    rep = _rep_fn(cfg, cfg.sp)
    x = _ops.layer_norm(h, rep(leaves[plan.ln[0]]), rep(leaves[plan.ln[1]]),
                        eps=plan.eps)
    if plan.mlm_dense is not None:
        x = _ops.activation(
            _ops.fully_connected(x, rep(leaves[plan.mlm_dense.w]),
                                 rep(leaves[plan.mlm_dense.b]),
                                 flatten=False), act_type="gelu")
        x = _ops.layer_norm(x, rep(leaves[plan.mlm_ln[0]]),
                            rep(leaves[plan.mlm_ln[1]]), eps=plan.mlm_eps)
    if cfg.n_tp > 1:
        # region entry: the CE backprops only this rank's vocab slice into
        # x, so the boundary collective (psum / psum_scatter in the VJP)
        # completes x's cotangent before the replicated/per-token compute
        # above it
        x = gather_from_sp(x, cfg.axis, 1) if cfg.sp \
            else copy_to_tp(x, cfg.axis)
        return vocab_parallel_cross_entropy(
            x, leaves[plan.dec.w], leaves[plan.dec.b], labels, cfg.axis)
    logits = _ops.fully_connected(x, leaves[plan.dec.w],
                                  leaves[plan.dec.b],
                                  flatten=False).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
