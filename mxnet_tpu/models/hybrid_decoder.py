"""A decoder built from a configuration's `layer_types`: Mamba-2 state-space
layers and grouped-KV attention layers side by side (IBM Granite 4.0-H,
HF `GraniteMoeHybrid`, with no experts).

No reference counterpart (MXNet 1.x has neither state-space layers nor
grouped KV heads). With `e`, `r`, `s`, `l` the embedding, residual, attention
and logits multipliers:

    h0 = e * E[ids]
    h  = h + r * mixer(rms(h));  h = h + r * ffn(rms(h))      per layer
    logits = rms(h) @ E^T / l                                 tied table E

    ffn(v)    = W_out (silu(g) * u),  [g, u] = W_in v          (SwiGLU, no bias)
    attention = W_o softmax(s q k^T + causal mask) v           no positions;
                32 query heads over 8 KV heads, each KV head serving 4
    mamba     = W_out rms_gated(ssd(x, dt, A, B, C, D), z),
                [z, xBC, dt] = W_in v;  [x, B, C] = silu(conv(xBC));
                dt = softplus(dt + dt_bias);  A = -exp(A_log)
                (ops/ssm.py has the scan's and the convolution's equations)

Every decoder layer may be a recomputed block (`HybridBlock.recompute`): under
a fused trainer's step only the layers' inputs live from the forward pass to
the backward one, which is what lets a model of this width train on one chip.
`jax.named_scope`s name the groups a device trace is read by: `mx.embed`,
`mx.mamba` (with `mx.conv1d` and `mx.ssd` inside it), `mx.attn`, `mx.ffn`,
`mx.head`.
"""
from __future__ import annotations

import jax

from ..gluon.block import HybridBlock
from ..gluon import nn
from ..ops import attention as _attn_ops

__all__ = ["Mamba2Mixer", "GroupedQueryAttention", "SwiGLU",
           "HybridDecoderLayer", "HybridDecoder", "hybrid_decoder_tiny"]


def _dense(units, in_units):
    return nn.Dense(units, flatten=False, use_bias=False, in_units=in_units)


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer: one input projection to (z, xBC, dt), a causal
    depthwise convolution and SiLU over xBC, the state-space scan over heads
    of `head_dim` channels with a state of `state_size`, a gated RMSNorm and
    the output projection."""

    def __init__(self, units, num_heads, head_dim, state_size, conv_kernel=4,
                 num_groups=1, chunk_size=256, conv_bias=True, epsilon=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        self._heads, self._head_dim = num_heads, head_dim
        self._groups, self._state = num_groups, state_size
        self._chunk = chunk_size
        self._inner = inner = num_heads * head_dim
        self._conv_dim = conv_dim = inner + 2 * num_groups * state_size
        self.ssm_in = _dense(inner + conv_dim + num_heads, units)
        self.conv_weight = self.params.get("conv_weight",
                                           shape=(conv_dim, conv_kernel))
        self.conv_bias = self.params.get(
            "conv_bias", shape=(conv_dim,), init="zeros") if conv_bias else None
        self.dt_bias = self.params.get("dt_bias", shape=(num_heads,),
                                       init="zeros")
        self.A_log = self.params.get("A_log", shape=(num_heads,), init="zeros")
        self.D = self.params.get("D", shape=(num_heads,), init="ones")
        self.norm = nn.RMSNorm(epsilon=epsilon, in_channels=inner)
        self.ssm_out = _dense(units, inner)

    def hybrid_forward(self, F, v, conv_weight, dt_bias, A_log, D,
                       conv_bias=None):
        inner, conv_dim = self._inner, self._conv_dim
        gn = self._groups * self._state
        zxbcdt = self.ssm_in(v)
        z = F.slice_axis(zxbcdt, axis=-1, begin=0, end=inner)
        xbc = F.slice_axis(zxbcdt, axis=-1, begin=inner, end=inner + conv_dim)
        dt = F.slice_axis(zxbcdt, axis=-1, begin=inner + conv_dim,
                          end=inner + conv_dim + self._heads)
        xbc = F.silu(F._contrib_causal_conv1d(xbc, conv_weight, conv_bias))
        x = F.reshape(F.slice_axis(xbc, axis=-1, begin=0, end=inner),
                      shape=(0, 0, self._heads, self._head_dim))
        B, C = (F.reshape(F.slice_axis(xbc, axis=-1, begin=inner + i * gn,
                                       end=inner + (i + 1) * gn),
                          shape=(0, 0, self._groups, self._state))
                for i in range(2))
        # the step size and the decay rate in float32, whatever v's type
        dt = F.softrelu(F.broadcast_add(F.cast(dt, dtype="float32"),
                                        F.cast(dt_bias, dtype="float32")))
        A = F.negative(F.exp(F.cast(A_log, dtype="float32")))
        y = F._contrib_ssd_scan(x, dt, A, B, C, D, chunk_size=self._chunk)
        y = self.norm(F.reshape(y, shape=(0, 0, -3)), z)
        return self.ssm_out(y)


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention without positions: `num_heads` query heads over
    `num_kv_heads` key/value heads (each repeated for its queries, so the
    attention kernels see equal head counts), softmax scale `scale` as
    stated (None: 1/sqrt(d)), no bias. Through the flash kernels where
    `ops.attention.use_flash(T)` says so, as models/bert.py."""

    def __init__(self, units, num_heads, num_kv_heads, scale=None, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0 and num_heads % num_kv_heads == 0
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._d = d = units // num_heads
        self._scale = float(scale) if scale is not None else d ** -0.5
        self.query = _dense(units, units)
        self.key = _dense(num_kv_heads * d, units)
        self.value = _dense(num_kv_heads * d, units)
        self.proj = _dense(units, units)

    def hybrid_forward(self, F, x):
        H, d, rep = self._heads, self._d, self._heads // self._kv_heads
        q, k, v = (F.transpose(F.reshape(p(x), shape=(0, 0, -4, -1, d)),
                               axes=(0, 2, 1, 3))           # (B, heads, T, d)
                   for p in (self.query, self.key, self.value))
        if rep > 1:
            k, v = F.repeat(k, repeats=rep, axis=1), \
                F.repeat(v, repeats=rep, axis=1)
        if _attn_ops.use_flash(x.shape[1]):
            out = F._contrib_flash_attention(q, k, v, causal=True,
                                             scale=self._scale)
        else:
            q2, k2, v2 = (F.reshape(a, shape=(-3, 0, 0)) for a in (q, k, v))
            scores = F.batch_dot(q2, k2, transpose_b=True) * self._scale
            # positions in float32: bfloat16 counts exactly to 256 only
            pos = F.arange_like(F.cast(scores, dtype="float32"), axis=1)
            ahead = F.broadcast_lesser(F.expand_dims(pos, axis=1),
                                       F.expand_dims(pos, axis=0))
            scores = F.broadcast_add(
                scores, F.expand_dims(F.cast(ahead * -1e30,
                                             dtype=scores.dtype), axis=0))
            out = F.batch_dot(F.softmax(scores, axis=-1), v2)
            out = F.reshape(out, shape=(-4, -1, H, 0, 0))   # (B, H, T, d)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)), shape=(0, 0, -3))
        return self.proj(out)


class SwiGLU(HybridBlock):
    """W_out (silu(g) * u) with [g, u] = W_in v, no bias."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden_size
        self.ffn1 = _dense(2 * hidden_size, units)
        self.ffn2 = _dense(units, hidden_size)

    def hybrid_forward(self, F, x):
        gu = self.ffn1(x)
        g = F.slice_axis(gu, axis=-1, begin=0, end=self._hidden)
        u = F.slice_axis(gu, axis=-1, begin=self._hidden,
                         end=2 * self._hidden)
        return self.ffn2(F.silu(g) * u)


class HybridDecoderLayer(HybridBlock):
    """h + r * mixer(rms(h)), then h + r * ffn(rms(h)); `mixer` is a
    Mamba-2 mixer or grouped-KV attention."""

    def __init__(self, kind, mixer, units, hidden_size, residual_multiplier,
                 epsilon, **kwargs):
        super().__init__(**kwargs)
        self._mixer_scope = {"mamba": "mx.mamba", "attention": "mx.attn"}[kind]
        self._r = residual_multiplier
        self.mixer_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.mixer = mixer
        self.ffn_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.ffn = SwiGLU(units, hidden_size)

    def hybrid_forward(self, F, x):
        with jax.named_scope(self._mixer_scope):
            x = x + self._r * self.mixer(self.mixer_norm(x))
        with jax.named_scope("mx.ffn"):
            return x + self._r * self.ffn(self.ffn_norm(x))


class HybridDecoder(HybridBlock):
    """Token ids (B, T) -> logits (B, T, vocab_size) over the tied table.

    `layer_types` lists "mamba" or "attention" per layer; `recompute` makes
    every layer a recomputed block. A `vocab_size` below the published one
    is this chip's rows of a table divided by rows: ids, logits and loss are
    over the slice."""

    def __init__(self, vocab_size, units, hidden_size, layer_types, num_heads,
                 num_kv_heads, mamba_heads, mamba_head_dim, mamba_state,
                 mamba_conv=4, mamba_groups=1, mamba_chunk=256,
                 mamba_conv_bias=True, embedding_multiplier=1.0,
                 residual_multiplier=1.0, attention_multiplier=None,
                 logits_scaling=1.0, epsilon=1e-5, recompute=True, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._units = vocab_size, units
        self._e, self._l = embedding_multiplier, logits_scaling
        self.embed_weight = self.params.get("embed_weight",
                                            shape=(vocab_size, units))
        self.layers = nn.HybridSequential()
        for kind in layer_types:
            if kind == "mamba":
                mixer = Mamba2Mixer(units, mamba_heads, mamba_head_dim,
                                    mamba_state, mamba_conv, mamba_groups,
                                    mamba_chunk, mamba_conv_bias, epsilon)
            elif kind == "attention":
                mixer = GroupedQueryAttention(units, num_heads, num_kv_heads,
                                              attention_multiplier)
            else:
                raise ValueError(f"layer type {kind!r}: 'mamba' or 'attention'")
            layer = HybridDecoderLayer(kind, mixer, units, hidden_size,
                                       residual_multiplier, epsilon)
            self.layers.add(layer.recompute() if recompute else layer)
        self.norm = nn.RMSNorm(epsilon=epsilon, in_channels=units)

    def hybrid_forward(self, F, ids, embed_weight):
        with jax.named_scope("mx.embed"):
            h = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                            output_dim=self._units) * self._e
        h = self.layers(h)
        with jax.named_scope("mx.head"):
            return F.FullyConnected(self.norm(h), embed_weight, no_bias=True,
                                    num_hidden=self._vocab,
                                    flatten=False) / self._l


def hybrid_decoder_tiny(vocab_size=256, **kw):
    """Two Mamba-2 layers round one attention layer at toy widths."""
    args = dict(units=64, hidden_size=128,
                layer_types=("mamba", "attention", "mamba"), num_heads=4,
                num_kv_heads=2, mamba_heads=8, mamba_head_dim=16,
                mamba_state=16, mamba_chunk=8, embedding_multiplier=12.0,
                residual_multiplier=0.22, attention_multiplier=1 / 16,
                logits_scaling=8.0)
    args.update(kw)
    return HybridDecoder(vocab_size, **args)
