"""A decoder built from a configuration's `layer_types`: Mamba-2 state-space
layers and grouped-KV attention layers side by side (IBM Granite 4.0-H,
HF `GraniteMoeHybrid`, with no experts); and, since PR 32, full and sliding
window attention layers with per-layer head counts, rotary positions and a
per-head output gate, over a dense or a sparse (routed experts beside a
shared one) feed-forward, with a head of its own (poolside Laguna); and,
since PR 34, attention over the keys a learned indexer selects for each
query, with a QK norm and sectioned rotary positions, over routed experts
without a shared one (Kwai Keye-VL-2.0's language model).

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

The attention kinds (`"full_attention"`, `"sliding_attention"`; H_l query
heads of `head_dim` d over the KV heads, window W on the sliding kind):

    q, k = rope(a Wq), rope(a Wk)         rotary positions, a set per kind
    o_j  = softmax(q_j k^T / sqrt(d) + mask) v,   mask: t' <= t, and on the
           sliding kind also t - t' < W
    mixer = W_o concat_j(sigmoid(a W_g)_j * o_j)  the gate: one number a head

The indexed kind (`"indexed_attention"`; `ops/sparse_select.py` has the
indexer's equations and how the set is found and carried):

    q, k = rope(rms_head(a Wq)), rope(rms_head(a Wk))   QK norm: each head's
           d numbers normed, one weight for all query heads, one for all key
           heads; positions in sections (`ops/rotary.py`)
    S_t  = the top_k keys t' <= t of largest indexer score I[t, t'], from
           qI, kI = layer_norm(..), wI projected from stop_gradient(a);
           the indexer's leaves are frozen (`grad_req` null): a top-k hands
           no gradient back
    o_j  = softmax over t' in S_t of (q_j k^T / sqrt(d)) v

and the sparse feed-forward (`mlp_layer_types[l] == "sparse"`), of which
this chip holds `held` of the published experts (`parallel/moe.py:
held_moe_ffn` has the routing):

    ffn(b) = scaling * sum_{e in top-k, e held} w_e ffn_e(b) + ffn_shared(b)

(no `ffn_shared` where the model has none: `shared_hidden=None`).

Every decoder layer may be a recomputed block (`HybridBlock.recompute`): under
a fused trainer's step only the layers' inputs live from the forward pass to
the backward one, which is what lets a model of this width train on one chip.
`jax.named_scope`s name the groups a device trace is read by: `mx.embed`,
`mx.mamba` (with `mx.conv1d` and `mx.ssd` inside it), `mx.attn`, `mx.ffn`,
`mx.head`; the new kinds' mixers are `mx.attn.full` and `mx.attn.window`
(with `mx.rope` and the kernel call alone, `mx.flash.full` or
`mx.flash.window`, inside), the sparse feed-forward `mx.moe` (with
`mx.moe.route`, `mx.moe.experts` and `mx.moe.shared` inside it); the indexed
kind's mixer is `mx.attn.sparse`, with `mx.qknorm`, `mx.rope`, `mx.index`
(the indexer's projections, `mx.index.select`: the `mx_index_select`
kernels and the layouts round them, `mx.index.unpack`) and the kernel call
alone, `mx.flash.select`, inside.
"""
from __future__ import annotations

import contextlib

import jax

from .. import autograd
from ..gluon.block import HybridBlock, defer_aux_update
from ..gluon import nn
from ..ops import attention as _attn_ops
from ..ops.moe import HELD_REPORT
from ..ops.sparse_select import KEEP_NAME, LAYER_REPORT

__all__ = ["Mamba2Mixer", "GroupedQueryAttention", "SwiGLU",
           "HeldExpertsFFN", "HybridDecoderLayer", "HybridDecoder",
           "hybrid_decoder_tiny", "windowed_moe_decoder_tiny",
           "indexed_moe_decoder_tiny"]


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
    """Causal self-attention: `num_heads` query heads over `num_kv_heads`
    key/value heads (each repeated for its queries, so the attention kernels
    see equal head counts) of `head_dim` numbers (None: units / num_heads),
    softmax scale `scale` as stated (None: 1/sqrt(d)), no bias.

    `window`: a query sees only the `window` latest keys, itself among them
    (None: every earlier key). `rope`: keyword arguments of
    `_contrib_rotary_embedding`, applied to q and k (None: no positions).
    `gate`: each head's output is multiplied by the sigmoid of one number
    projected from the layer's input (head-wise gated attention,
    arXiv:2505.06708). Through the flash kernels where
    `ops.attention.use_flash(T)` says so, as models/bert.py (route `flash`,
    or `flash_window` with a window); the plain scores-softmax route takes
    the same mask. `kernel_scope` names the kernel call alone in a trace.

    `qk_norm`: the epsilon of an RMS norm over each head of q and of k, one
    weight for all query heads and one for all key heads, before the
    positions (None: none). `sections`: the rotary positions come in that
    many rows, each turning its section of the frequency pairs
    (`_contrib_rotary_embedding`'s `sections`; the rows are the text's).
    `indexer`: {"heads", "head_dim", "top_k", "chunk", "rope", "epsilon"}: a
    query attends to the `top_k` keys that an indexer of `heads` small heads
    over one key head selects (`_contrib_indexer_select`; route
    `flash_select`), from the layer's input with its gradient stopped. The
    indexer's leaves are frozen, since a top-k hands no gradient back, and
    ride a fused step as weights without optimizer state. `selection` is
    state like `HeldExpertsFFN.routing`: the last training step's report in
    the order of `ops.sparse_select.LAYER_REPORT` (keys kept a query, tiles
    of the selection with nothing kept)."""

    def __init__(self, units, num_heads, num_kv_heads, scale=None,
                 head_dim=None, window=None, rope=None, gate=False,
                 kernel_scope=None, qk_norm=None, sections=None,
                 indexer=None, **kwargs):
        super().__init__(**kwargs)
        assert num_heads % num_kv_heads == 0
        if head_dim is None:
            assert units % num_heads == 0
            head_dim = units // num_heads
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._d = d = head_dim
        self._scale = float(scale) if scale is not None else d ** -0.5
        self._window = None if window is None else int(window)
        self._rope = None if rope is None else dict(rope)
        if sections is not None:
            self._rope["sections"] = tuple(sections)
        self._kernel_scope = kernel_scope
        self.query = _dense(num_heads * d, units)
        self.key = _dense(num_kv_heads * d, units)
        self.value = _dense(num_kv_heads * d, units)
        self.gate = _dense(num_heads, units) if gate else None
        self.proj = _dense(units, num_heads * d)
        self.query_norm = self.key_norm = None
        if qk_norm is not None:
            self.query_norm = nn.RMSNorm(epsilon=qk_norm, in_channels=d)
            self.key_norm = nn.RMSNorm(epsilon=qk_norm, in_channels=d)
        self._indexer = None if indexer is None else dict(indexer)
        if indexer is not None:
            hi, di = indexer["heads"], indexer["head_dim"]
            self._indexer.setdefault("chunk", 512)
            self.index_query = _dense(hi * di, units)
            self.index_key = _dense(di, units)
            self.index_key_norm = nn.LayerNorm(
                epsilon=indexer.get("epsilon", 1e-6), in_channels=di)
            self.index_weight = _dense(hi, units)
            for block in (self.index_query, self.index_key,
                          self.index_key_norm, self.index_weight):
                for p in block.collect_params().values():
                    p.grad_req = "null"
            self.selection = self.params.get(
                "selection", shape=(len(LAYER_REPORT),), init="zeros",
                grad_req="null", differentiable=False)

    def cast(self, dtype):
        super().cast(dtype)
        if self._indexer is not None:
            self.selection.cast("float32")      # a count and a mean of counts

    def _select(self, F, x):
        """int8 (B, T, T): the keys the indexer keeps for each query."""
        ix = self._indexer
        with jax.named_scope("mx.index"):
            a = F.stop_gradient(x)
            qi = F.transpose(F.reshape(self.index_query(a),
                                       shape=(0, 0, -4, -1, ix["head_dim"])),
                             axes=(0, 2, 1, 3))              # (B, Hi, T, di)
            ki = F.expand_dims(self.index_key_norm(self.index_key(a)), axis=1)
            if ix.get("rope") is not None:
                qi, ki = (F._contrib_rotary_embedding(t, **ix["rope"])
                          for t in (qi, ki))
            packed, report = F._contrib_indexer_select(
                qi, F.reshape(ki, shape=(0, -3, 0)), self.index_weight(a),
                top_k=ix["top_k"], chunk=ix["chunk"])
            if autograd.is_training() or autograd.is_recording():
                defer_aux_update(self.selection,
                                 report._data[:len(LAYER_REPORT)])
            with jax.named_scope("mx.index.unpack"):
                return F._contrib_selection_unpack(packed, keys=x.shape[1])

    def hybrid_forward(self, F, x, selection=None):
        H, d, rep = self._heads, self._d, self._heads // self._kv_heads
        q, k, v = (F.transpose(F.reshape(p(x), shape=(0, 0, -4, -1, d)),
                               axes=(0, 2, 1, 3))           # (B, heads, T, d)
                   for p in (self.query, self.key, self.value))
        if self.query_norm is not None:
            with jax.named_scope("mx.qknorm"):
                q, k = self.query_norm(q), self.key_norm(k)
        if self._rope is not None:
            q, k = (F._contrib_rotary_embedding(a, **self._rope)
                    for a in (q, k))
        if rep > 1:
            k, v = F.repeat(k, repeats=rep, axis=1), \
                F.repeat(v, repeats=rep, axis=1)
        select = () if self._indexer is None else (self._select(F, x),)
        if _attn_ops.use_flash(x.shape[1]):
            with jax.named_scope(self._kernel_scope) if self._kernel_scope \
                    else contextlib.nullcontext():
                out = F._contrib_flash_attention(
                    q, k, v, *select, causal=True, scale=self._scale,
                    window=self._window)
        else:
            q2, k2, v2 = (F.reshape(a, shape=(-3, 0, 0)) for a in (q, k, v))
            scores = F.batch_dot(q2, k2, transpose_b=True) * self._scale
            # positions in float32: bfloat16 counts exactly to 256 only
            pos = F.arange_like(F.cast(scores, dtype="float32"), axis=1)
            ahead = F.broadcast_lesser(F.expand_dims(pos, axis=1),
                                       F.expand_dims(pos, axis=0))
            if self._window is not None:
                behind = F.broadcast_greater_equal(
                    F.expand_dims(pos, axis=1) - self._window,
                    F.expand_dims(pos, axis=0))
                ahead = ahead + behind
            bias = F.expand_dims(F.cast(ahead * -1e30, dtype=scores.dtype),
                                 axis=0)
            if select:      # (B, T, T) -> a row of it for each of its heads
                bias = F.broadcast_add(bias, F.repeat(
                    F.cast(select[0] == 0, dtype=scores.dtype) * -1e30,
                    repeats=H, axis=0))
            scores = F.broadcast_add(scores, bias)
            out = F.batch_dot(F.softmax(scores, axis=-1), v2)
            out = F.reshape(out, shape=(-4, -1, H, 0, 0))   # (B, H, T, d)
        out = F.transpose(out, axes=(0, 2, 1, 3))           # (B, T, H, d)
        if self.gate is not None:
            out = F.broadcast_mul(
                out, F.expand_dims(F.sigmoid(self.gate(x)), axis=-1))
        return self.proj(F.reshape(out, shape=(0, 0, -3)))


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


class HeldExpertsFFN(HybridBlock):
    """The sparse feed-forward of a chip that holds `held` of a layer's
    `published_experts` routed experts (`first_held` onwards) and the shared
    expert whole: a router over all the published experts, `top_k` a token,
    their weights renormalised over the `top_k` and scaled by `scaling`; the
    held experts' part of the sum (`_contrib_held_moe_ffn`: dropless) beside
    the shared expert's output, ungated (`shared_hidden=None`: the model has
    no shared expert). Every expert is a SwiGLU.

    `routing` is state, not a weight: the last training step's report of the
    layer in the order of `ops.moe.HELD_REPORT` (assignments kept here, the
    largest and the mean load of a held expert, 1 where the exact dense path
    ran). A fused step hands it on with the other aux outputs, as BatchNorm's
    statistics, and reads nothing back; `routing.data()` does."""

    def __init__(self, units, expert_hidden, shared_hidden, held,
                 published_experts, top_k, scaling=1.0, first_held=0,
                 **kwargs):
        super().__init__(**kwargs)
        self._op = dict(top_k=top_k, published_experts=published_experts,
                        first_held=first_held, scaling=scaling)
        self.router_weight = self.params.get(
            "router_weight", shape=(published_experts, units))
        self.experts_gate_up = self.params.get(
            "experts_gate_up", shape=(held, units, 2 * expert_hidden))
        self.experts_down = self.params.get(
            "experts_down", shape=(held, expert_hidden, units))
        self.routing = self.params.get(
            "routing", shape=(len(HELD_REPORT),), init="zeros",
            grad_req="null", differentiable=False)
        self.shared = None if shared_hidden is None \
            else SwiGLU(units, shared_hidden)

    def cast(self, dtype):
        super().cast(dtype)
        self.routing.cast("float32")    # counts: bfloat16 holds 256 exactly

    def hybrid_forward(self, F, x, router_weight, experts_gate_up,
                       experts_down, routing):
        y, report = F._contrib_held_moe_ffn(x, router_weight, experts_gate_up,
                                            experts_down, **self._op)
        if autograd.is_training() or autograd.is_recording():
            defer_aux_update(self.routing, report._data)
        if self.shared is None:
            return y
        with jax.named_scope("mx.moe.shared"):
            return y + self.shared(x)


_MIXER_SCOPES = {"mamba": "mx.mamba", "attention": "mx.attn",
                 "full_attention": "mx.attn.full",
                 "sliding_attention": "mx.attn.window",
                 "indexed_attention": "mx.attn.sparse"}


class HybridDecoderLayer(HybridBlock):
    """h + r * mixer(rms(h)), then h + r * ffn(rms(h)); `mixer` is a
    Mamba-2 mixer or grouped-KV attention, `ffn` a SwiGLU of `hidden_size`
    (None) or the block given (the sparse feed-forward)."""

    def __init__(self, kind, mixer, units, hidden_size, residual_multiplier,
                 epsilon, ffn=None, **kwargs):
        super().__init__(**kwargs)
        self._mixer_scope = _MIXER_SCOPES[kind]
        self._ffn_scope = "mx.ffn" if ffn is None else "mx.moe"
        self._r = residual_multiplier
        self.mixer_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.mixer = mixer
        self.ffn_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.ffn = SwiGLU(units, hidden_size) if ffn is None else ffn

    def hybrid_forward(self, F, x):
        with jax.named_scope(self._mixer_scope):
            x = x + self._r * self.mixer(self.mixer_norm(x))
        with jax.named_scope(self._ffn_scope):
            return x + self._r * self.ffn(self.ffn_norm(x))


class HybridDecoder(HybridBlock):
    """Token ids (B, T) -> logits (B, T, vocab_size) over the tied table, or
    over a head of its own (`tie_head=False`).

    `layer_types` lists "mamba", "attention" (causal, no positions: both
    Granite's), "full_attention", "sliding_attention" or
    "indexed_attention" (over the keys an indexer selects: `indexer`, with
    `qk_norm` and `sections` `GroupedQueryAttention`'s arguments) per layer;
    `num_heads` is one number or a number per layer; `head_dim` the heads'
    size where it is not units / num_heads; `window` the sliding kind's;
    `rope` {"full_attention": .., "sliding_attention": ..} the rotary
    parameters of each kind; `gate` the per-head output gate.
    `mlp_layer_types` lists "dense" or "sparse" per layer (None: all dense,
    of `hidden_size`); `moe` holds `HeldExpertsFFN`'s arguments. `recompute`
    makes every layer a recomputed block. A `vocab_size` below the published
    one is this chip's rows of a table divided by rows: ids, logits and loss
    are over the slice."""

    def __init__(self, vocab_size, units, hidden_size, layer_types, num_heads,
                 num_kv_heads, mamba_heads=None, mamba_head_dim=None,
                 mamba_state=None, mamba_conv=4, mamba_groups=1,
                 mamba_chunk=256, mamba_conv_bias=True,
                 embedding_multiplier=1.0, residual_multiplier=1.0,
                 attention_multiplier=None, logits_scaling=1.0, epsilon=1e-5,
                 recompute=True, head_dim=None, window=None, rope=None,
                 gate=False, mlp_layer_types=None, moe=None, tie_head=True,
                 qk_norm=False, sections=None, indexer=None, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._units = vocab_size, units
        self._e, self._l = embedding_multiplier, logits_scaling
        self.embed_weight = self.params.get("embed_weight",
                                            shape=(vocab_size, units))
        self.head_weight = None if tie_head else self.params.get(
            "head_weight", shape=(vocab_size, units))
        self.layers = nn.HybridSequential()
        for i, kind in enumerate(layer_types):
            heads = num_heads if isinstance(num_heads, int) else num_heads[i]
            if kind == "mamba":
                mixer = Mamba2Mixer(units, mamba_heads, mamba_head_dim,
                                    mamba_state, mamba_conv, mamba_groups,
                                    mamba_chunk, mamba_conv_bias, epsilon)
            elif kind == "attention":
                mixer = GroupedQueryAttention(units, heads, num_kv_heads,
                                              attention_multiplier)
            elif kind in ("full_attention", "sliding_attention"):
                sliding = kind == "sliding_attention"
                mixer = GroupedQueryAttention(
                    units, heads, num_kv_heads, attention_multiplier,
                    head_dim=head_dim, window=window if sliding else None,
                    rope=(rope or {}).get(kind), gate=gate,
                    kernel_scope="mx.flash.window" if sliding
                    else "mx.flash.full")
            elif kind == "indexed_attention":
                mixer = GroupedQueryAttention(
                    units, heads, num_kv_heads, attention_multiplier,
                    head_dim=head_dim, rope=(rope or {}).get(kind), gate=gate,
                    kernel_scope="mx.flash.select",
                    qk_norm=epsilon if qk_norm else None, sections=sections,
                    indexer=indexer)
            else:
                raise ValueError(
                    f"layer type {kind!r}: 'mamba', 'attention', "
                    "'full_attention', 'sliding_attention' or "
                    "'indexed_attention'")
            sparse = mlp_layer_types is not None \
                and mlp_layer_types[i] == "sparse"
            layer = HybridDecoderLayer(
                kind, mixer, units, hidden_size, residual_multiplier, epsilon,
                ffn=HeldExpertsFFN(units, **moe) if sparse else None)
            # an indexed layer carries its selection to the backward pass
            keep = (KEEP_NAME,) if kind == "indexed_attention" else ()
            self.layers.add(layer.recompute(keep=keep) if recompute
                            else layer)
        self.norm = nn.RMSNorm(epsilon=epsilon, in_channels=units)

    def hybrid_forward(self, F, ids, embed_weight, head_weight=None):
        with jax.named_scope("mx.embed"):
            h = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                            output_dim=self._units) * self._e
        h = self.layers(h)
        with jax.named_scope("mx.head"):
            return F.FullyConnected(
                self.norm(h),
                embed_weight if head_weight is None else head_weight,
                no_bias=True, num_hidden=self._vocab,
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


def windowed_moe_decoder_tiny(vocab_size=256, **kw):
    """A dense full-attention layer and then a sliding and a full layer over
    sparse feed-forwards, at toy widths: 6 and 4 query heads of 16 over 2 KV
    heads, window 8, partial YaRN and plain rotary positions, 4 of 16
    experts held, 3 a token, beside a shared one; untied head."""
    args = dict(
        units=32, hidden_size=64,
        layer_types=("full_attention", "sliding_attention", "full_attention"),
        mlp_layer_types=("dense", "sparse", "sparse"), num_heads=(4, 6, 4),
        num_kv_heads=2, head_dim=16, window=8, gate=True, tie_head=False,
        rope={"full_attention": dict(base=500000.0, rotary_dim=8,
                                     yarn_factor=8.0, yarn_original_length=32,
                                     attention_factor=1.2),
              "sliding_attention": dict(base=10000.0)},
        moe=dict(expert_hidden=16, shared_hidden=16, held=4,
                 published_experts=16, top_k=3, scaling=2.5),
        epsilon=1e-6)
    args.update(kw)
    return HybridDecoder(vocab_size, **args)


def indexed_moe_decoder_tiny(vocab_size=256, **kw):
    """Two indexed-attention layers over sparse feed-forwards without a
    shared expert, at toy widths: 4 query heads over 2 KV heads of 16 with a
    QK norm and rotary positions in sections 2/3/3, an indexer of 2 heads of
    8 that keeps 8 keys a query (of the 32 of a toy sequence), 4 of 16
    experts held, 3 a token; untied head."""
    args = dict(
        units=32, hidden_size=64,
        layer_types=("indexed_attention",) * 2,
        mlp_layer_types=("sparse",) * 2, num_heads=4, num_kv_heads=2,
        head_dim=16, tie_head=False, qk_norm=True, sections=(2, 3, 3),
        rope={"indexed_attention": dict(base=1e7)},
        indexer=dict(heads=2, head_dim=8, top_k=8, chunk=8,
                     rope=dict(base=1e7)),
        moe=dict(expert_hidden=16, shared_hidden=None, held=4,
                 published_experts=16, top_k=3),
        epsilon=1e-6)
    args.update(kw)
    return HybridDecoder(vocab_size, **args)
