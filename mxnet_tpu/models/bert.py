"""BERT / transformer encoder — the flagship shardable model.

Reference counterpart: GluonNLP BERT built on the contrib attention matmuls
(reference src/operator/contrib/transformer.cc:650-819) and fused layernorm/
gelu. TPU-native design:

  - names follow the TP sharding rules in parallel/tensor_parallel.py
    (qkv/ffn1 column-parallel, proj/ffn2 row-parallel);
  - attention uses parallel.blockwise_attention (flash-style lax.scan) so
    long sequences fit; under an 'sp' mesh axis the trainer swaps it for
    ring_attention;
  - everything bf16-friendly: matmuls accumulate f32 via the op layer.
"""
from __future__ import annotations

import math

from ..gluon.block import HybridBlock
from ..gluon import nn
from ..ops import attention as _attn_ops

__all__ = ["TransformerEncoderCell", "BertEncoder", "BertModel", "bert_base",
           "bert_large", "bert_tiny"]


def _position_ids(F, token_ids):
    """Position indices (T,) for BOTH the eager/traced NDArray path and
    symbolic export: a Symbol has no concrete ``.shape``, so the exported
    graph builds positions with ``arange_like`` over the sequence axis
    (static under trace, serializable — what lets ``net.export`` produce a
    servable BERT artifact for mxnet_tpu.serving)."""
    shape = getattr(token_ids, "shape", None)
    if shape is not None:
        from .. import ndarray as nd
        return nd.arange(0, shape[1], dtype="int32", ctx=token_ids.ctx)
    return F.arange_like(token_ids, axis=1)


class SelfAttention(HybridBlock):
    """Q/K/V ride ONE (C -> 3C) projection by default — the shape-widening
    fusion the reference hand-writes for GPUs in its interleaved-QKV kernels
    (reference src/operator/contrib/transformer.cc:650-819); on TPU it turns
    three K=768 MXU-unfriendly matmuls into one N=2304 matmul. fused_qkv=False
    keeps the three separate projections for A/B measurement
    (benchmark/qkv_fusion_probe.py)."""

    def __init__(self, units, num_heads, dropout=0.0, use_blockwise=True,
                 fused_qkv=True, head_major_qkv=False, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._heads = num_heads
        self._use_blockwise = use_blockwise
        self._fused_qkv = fused_qkv
        # head_major_qkv reorders the fused projection's output neurons to
        # (head, qkv, d) so a CONTIGUOUS split of the weight's out dim —
        # exactly what P('tp', None) gives — lands whole heads (their q, k
        # AND v) on one shard: tensor parallelism over attention heads with
        # no resharding inside the block. The (3, head, d) default layout
        # would make XLA reshard at the reshape (3 doesn't divide tp).
        # Same parameter shapes; a checkpoint from one layout is a neuron
        # permutation of the other, so pick the layout at pretrain time.
        self._head_major = head_major_qkv
        if fused_qkv:
            self.qkv = nn.Dense(3 * units, flatten=False, in_units=units)
        else:
            self.q_proj = nn.Dense(units, flatten=False, in_units=units)
            self.k_proj = nn.Dense(units, flatten=False, in_units=units)
            self.v_proj = nn.Dense(units, flatten=False, in_units=units)
        self.proj = nn.Dense(units, flatten=False, in_units=units)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, mask=None):
        # x: (B, T, C). Shapes are expressed through MXNet reshape codes
        # (0 copy, -1 infer, -3 merge, -4 split) so the SAME code runs
        # eagerly, under jit trace, AND symbolically for export — a Symbol
        # has no concrete .shape (serving needs the exported graph).
        H = self._heads
        d = self._units // H
        if self._fused_qkv:
            qkv = self.qkv(x)  # (B, T, 3C)
            if self._head_major:
                qkv = F.reshape(qkv, shape=(0, 0, H, 3, d))
                slice_ax, merge = 3, (0, 0, 0, -3)      # merge the 1*d tail
            else:
                qkv = F.reshape(qkv, shape=(0, 0, 3, H, d))
                slice_ax, merge = 2, (0, 0, -3, 0)      # merge the 1*H pair
            q, k, v = (
                F.transpose(                            # (B, H, T, d)
                    F.reshape(                          # (B, T, H, d)
                        F.slice_axis(qkv, axis=slice_ax, begin=i, end=i + 1),
                        shape=merge),
                    axes=(0, 2, 1, 3))
                for i in range(3))
        else:
            q, k, v = (
                F.transpose(                            # split C -> (H, d)
                    F.reshape(proj(x), shape=(0, 0, -4, H, -1)),
                    axes=(0, 2, 1, 3))
                for proj in (self.q_proj, self.k_proj, self.v_proj))
        # Symbolic export (no concrete shape) always lowers the plain path.
        shape = getattr(x, "shape", None)
        if shape is not None and self._use_blockwise and mask is None \
                and _attn_ops.use_flash(shape[1]):
            # registered-op form: dispatches to the Pallas kernel on TPU and
            # records the VJP on the eager autograd tape (raw-array calls
            # would silently detach attention from loss.backward())
            from .. import ndarray as _nd
            out = _nd._contrib_flash_attention(q, k, v, causal=False)
        else:
            q2 = F.reshape(q, shape=(-3, 0, 0))         # (B*H, T, d)
            k2 = F.reshape(k, shape=(-3, 0, 0))
            v2 = F.reshape(v, shape=(-3, 0, 0))
            scores = F.batch_dot(q2, k2, transpose_b=True) / math.sqrt(d)
            if mask is not None:
                scores = scores + (1.0 - mask) * -1e9
            att = F.softmax(scores, axis=-1)
            out = F.batch_dot(att, v2)
            out = F.reshape(out, shape=(-4, -1, H, 0, 0))  # (B, H, T, d)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(0, 0, -3))               # (B, T, C)
        out = self.proj(out)
        if self.dropout:
            out = self.dropout(out)
        return out


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        self.ffn1 = nn.Dense(hidden_size, flatten=False, in_units=units)
        self.ffn2 = nn.Dense(units, flatten=False, in_units=hidden_size)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        h = F.gelu(self.ffn1(x))
        h = self.ffn2(h)
        if self.dropout:
            h = self.dropout(h)
        return h


class TransformerEncoderCell(HybridBlock):
    """Pre-LN encoder block."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 fused_qkv=True, head_major_qkv=False, **kwargs):
        super().__init__(**kwargs)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.attn = SelfAttention(units, num_heads, dropout,
                                  fused_qkv=fused_qkv,
                                  head_major_qkv=head_major_qkv)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout)

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.ln1(x))
        x = x + self.ffn(self.ln2(x))
        return x


class BertEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout=0.0,
                 fused_qkv=True, head_major_qkv=False, **kwargs):
        super().__init__(**kwargs)
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(TransformerEncoderCell(
                units, hidden_size, num_heads, dropout,
                fused_qkv=fused_qkv, head_major_qkv=head_major_qkv))
        self.ln = nn.LayerNorm(in_channels=units)

    def hybrid_forward(self, F, x):
        return self.ln(self.layers(x))


class BertModel(HybridBlock):
    """Token + position + segment embeddings -> encoder -> MLM head."""

    def __init__(self, vocab_size=30522, num_layers=12, units=768,
                 hidden_size=3072, num_heads=12, max_length=512,
                 dropout=0.0, fused_qkv=True, head_major_qkv=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self.word_embed = nn.Embedding(vocab_size, units)
        self.pos_embed = nn.Embedding(max_length, units)
        self.seg_embed = nn.Embedding(2, units)
        self.embed_ln = nn.LayerNorm(in_channels=units)
        self.embed_drop = nn.Dropout(dropout) if dropout else None
        self.encoder = BertEncoder(num_layers, units, hidden_size, num_heads,
                                   dropout, fused_qkv=fused_qkv,
                                   head_major_qkv=head_major_qkv)
        self.mlm_dense = nn.Dense(units, flatten=False, activation="gelu",
                                  in_units=units)
        self.mlm_ln = nn.LayerNorm(in_channels=units)
        self.mlm_decoder = nn.Dense(vocab_size, flatten=False, in_units=units)

    def pipeline_split(self):
        """(embed, cells, head) for parallel.PipelineTrainer. The wrappers
        re-register this model's own child blocks, so parameters are shared
        and sync() writes straight back into this model."""
        cells = [self.encoder.layers[i] for i in range(len(self.encoder.layers))]
        return _BertEmbedStage(self), cells, _BertHeadStage(self)

    def hybrid_forward(self, F, token_ids, segment_ids=None):
        pos = _position_ids(F, token_ids)
        x = self.word_embed(token_ids) + self.pos_embed(pos).expand_dims(axis=0)
        if segment_ids is not None:
            x = x + self.seg_embed(segment_ids)
        x = self.embed_ln(x)
        if self.embed_drop:
            x = self.embed_drop(x)
        x = self.encoder(x)
        h = self.mlm_ln(self.mlm_dense(x))
        return self.mlm_decoder(h)


class _BertEmbedStage(HybridBlock):
    """Pipeline stage 0 body: the embedding section of BertModel's forward.
    Shares the parent model's child blocks (no new parameters)."""

    def __init__(self, bert, **kwargs):
        super().__init__(**kwargs)
        self.word_embed = bert.word_embed
        self.pos_embed = bert.pos_embed
        self.seg_embed = bert.seg_embed
        self.embed_ln = bert.embed_ln
        self.drop = bert.embed_drop

    def hybrid_forward(self, F, token_ids):
        pos = _position_ids(F, token_ids)
        x = self.word_embed(token_ids) + self.pos_embed(pos).expand_dims(axis=0)
        x = self.embed_ln(x)
        if self.drop:
            x = self.drop(x)
        return x


class _BertHeadStage(HybridBlock):
    """Pipeline last-stage tail: final LN + MLM head of BertModel."""

    def __init__(self, bert, **kwargs):
        super().__init__(**kwargs)
        self.ln = bert.encoder.ln
        self.mlm_dense = bert.mlm_dense
        self.mlm_ln = bert.mlm_ln
        self.mlm_decoder = bert.mlm_decoder

    def hybrid_forward(self, F, x):
        h = self.mlm_ln(self.mlm_dense(self.ln(x)))
        return self.mlm_decoder(h)


def bert_tiny(vocab_size=8192, **kw):
    return BertModel(vocab_size, num_layers=2, units=128, hidden_size=512,
                     num_heads=2, **kw)


def bert_base(vocab_size=30522, **kw):
    return BertModel(vocab_size, num_layers=12, units=768, hidden_size=3072,
                     num_heads=12, **kw)


def bert_large(vocab_size=30522, **kw):
    return BertModel(vocab_size, num_layers=24, units=1024, hidden_size=4096,
                     num_heads=16, **kw)
