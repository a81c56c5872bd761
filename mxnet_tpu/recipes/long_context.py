"""Long-context training recipe (docs/large_models.md).

A causal LM whose attention path scales past 32k tokens:

  - single device / pure dp: every attention call goes through the
    registered flash kernel (``ops/pallas/flash_attention.py`` on TPU, the
    ``blockwise_attention`` lax.scan fallback elsewhere) — O(T) activation
    memory, so MXNET_TPU_LONG_CONTEXT_SEQ=32768 runs on a CPU host;
  - under ``LongContextTrainer`` the mesh gains an 'sp' axis: the token
    dimension is sharded ``P('dp','sp')`` and the SAME model cells switch
    to ``ring_attention`` (kv shards rotate over ppermute, comm overlaps
    compute) via the ``sequence_axis`` trace context — the long-context
    analog of ``parallel.moe.expert_axis``;
  - the parity oracle is the identical architecture with the dense O(T^2)
    softmax path (``dense_attention=True``); ring and flash/blockwise
    outputs must match it (tests/test_recipes.py).

Sequence chunking: ``TokenWindows`` slices a corpus into shifted
(next-token) windows and rides ``DeviceFeed.for_trainer`` so batches land
pre-sharded on the dp x sp mesh.
"""
from __future__ import annotations

import contextlib
from typing import List

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from ..base import MXNetError, env
from ..ndarray import NDArray
from ..engine import async_feed as _feed
from .. import telemetry as _telem
from ..gluon.block import HybridBlock
from ..gluon import nn
from ..ops.attention import ring_attention
from ..parallel import zero as _zero
from ..parallel.data_parallel import DataParallelTrainer, _make_apply_fn
from ..parallel.mesh import require_axis, P
from ..parallel.step_program import StepProgram
from .moe import token_cross_entropy

__all__ = ["LongContextLM", "LongContextTrainer", "TokenWindows",
           "sequence_axis", "current_sequence_axis", "default_seq_len",
           "make_model", "make_oracle", "make_trainer", "make_feed"]

env.declare("MXNET_TPU_LONG_CONTEXT_SEQ", 32768, int,
            "Default sequence length of the long-context recipe "
            "(recipes/long_context.py); the model builder and bench lane "
            "read it, so one env var scales the whole workload.")


def default_seq_len() -> int:
    return int(env.get("MXNET_TPU_LONG_CONTEXT_SEQ"))


# -- trace context: which mesh axis shards the sequence ---------------------

class _SeqCtx:
    __slots__ = ("axis_name",)

    def __init__(self, axis_name):
        self.axis_name = axis_name


_SEQ_STACK: List[_SeqCtx] = []


@contextlib.contextmanager
def sequence_axis(axis_name: str):
    """Trace context: inside it, LongContextLM's attention runs
    ``ring_attention`` over `axis_name` (the caller must be under a
    shard_map mapping that axis, with (B, T/sp, ...) local activations)."""
    _SEQ_STACK.append(_SeqCtx(axis_name))
    try:
        yield
    finally:
        _SEQ_STACK.pop()


def current_sequence_axis():
    return _SEQ_STACK[-1] if _SEQ_STACK else None


# -- model ------------------------------------------------------------------

class RingSelfAttention(HybridBlock):
    """Causal self-attention with three runtime paths over one parameter
    set: ring (under ``sequence_axis``), flash/blockwise (default), dense
    O(T^2) softmax (``dense_attention=True`` — the parity oracle)."""

    def __init__(self, units, num_heads, dense_attention=False, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._heads = num_heads
        self._dense = dense_attention
        self.qkv = nn.Dense(3 * units, flatten=False, in_units=units)
        self.proj = nn.Dense(units, flatten=False, in_units=units)

    def hybrid_forward(self, F, x):
        if not isinstance(x, NDArray):
            raise MXNetError("RingSelfAttention has no symbolic form; "
                             "export the dense-oracle model instead")
        H = self._heads
        d = self._units // H
        qkv = self.qkv(x)._data                  # (B, T, 3C)
        B, T, _ = qkv.shape
        q, k, v = (jnp.transpose(a.reshape(B, T, H, d), (0, 2, 1, 3))
                   for a in jnp.split(qkv, 3, axis=-1))
        ctx = current_sequence_axis()
        if ctx is not None:
            out = ring_attention(q, k, v, ctx.axis_name, causal=True)
        elif self._dense:
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32) / (d ** 0.5)
            mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            s = jnp.where(mask[None, None], s, -1e30)
            out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                             v.astype(jnp.float32)).astype(q.dtype)
        else:
            # Pallas flash on TPU, blockwise lax.scan fallback elsewhere —
            # O(T) activation memory either way (the >=32k lane's enabler)
            from ..ops.attention import flash_attention_op
            out = flash_attention_op(q, k, v, causal=True)
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(B, T, H * d)
        return self.proj(NDArray(out))


class _LCCell(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dense_attention=False,
                 **kwargs):
        super().__init__(**kwargs)
        from ..models.bert import PositionwiseFFN
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.attn = RingSelfAttention(units, num_heads,
                                      dense_attention=dense_attention)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size)

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.ln1(x))
        x = x + self.ffn(self.ln2(x))
        return x


class LongContextLM(HybridBlock):
    """Pre-LN causal LM over ring/flash attention. Under ``sequence_axis``
    each device holds a T/sp token slice; position embeddings offset by
    ``axis_index(sp) * T_local`` so every shard sees its GLOBAL positions."""

    def __init__(self, vocab_size, num_layers=2, units=64, hidden_size=128,
                 num_heads=2, max_length=None, dense_attention=False,
                 **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._max_length = int(max_length if max_length is not None
                               else default_seq_len())
        self.word_embed = nn.Embedding(vocab_size, units)
        self.pos_embed = nn.Embedding(self._max_length, units)
        self.embed_ln = nn.LayerNorm(in_channels=units)
        self.cells = nn.HybridSequential()
        for _ in range(num_layers):
            self.cells.add(_LCCell(units, hidden_size, num_heads,
                                   dense_attention=dense_attention))
        self.ln = nn.LayerNorm(in_channels=units)
        self.decoder = nn.Dense(vocab_size, flatten=False, in_units=units)

    def hybrid_forward(self, F, token_ids):
        if not isinstance(token_ids, NDArray):
            raise MXNetError("LongContextLM has no symbolic form")
        Tl = token_ids.shape[1]
        pos = jnp.arange(Tl, dtype=jnp.int32)
        ctx = current_sequence_axis()
        if ctx is not None:
            pos = pos + lax.axis_index(ctx.axis_name) * Tl
        x = self.word_embed(token_ids) \
            + self.pos_embed(NDArray(pos)).expand_dims(axis=0)
        x = self.embed_ln(x)
        x = self.cells(x)
        return self.decoder(self.ln(x))

    def pipeline_split(self):
        """(embed, cells, head) for parallel.PipelineTrainer. The wrappers
        re-register this model's own child blocks, so parameters are
        shared and sync() writes straight back into this model."""
        cells = [self.cells[i] for i in range(len(self.cells))]
        return _LCEmbedStage(self), cells, _LCHeadStage(self)


class _LCEmbedStage(HybridBlock):
    """Pipeline stage 0 body: LongContextLM's embedding section (keeps
    the sequence-axis position offset so ring runs still see GLOBAL
    positions)."""

    def __init__(self, lm, **kwargs):
        super().__init__(**kwargs)
        self.word_embed = lm.word_embed
        self.pos_embed = lm.pos_embed
        self.embed_ln = lm.embed_ln

    def hybrid_forward(self, F, token_ids):
        if not isinstance(token_ids, NDArray):
            raise MXNetError("LongContextLM has no symbolic form")
        Tl = token_ids.shape[1]
        pos = jnp.arange(Tl, dtype=jnp.int32)
        ctx = current_sequence_axis()
        if ctx is not None:
            pos = pos + lax.axis_index(ctx.axis_name) * Tl
        x = self.word_embed(token_ids) \
            + self.pos_embed(NDArray(pos)).expand_dims(axis=0)
        return self.embed_ln(x)


class _LCHeadStage(HybridBlock):
    """Pipeline last-stage tail: final LN + LM decoder."""

    def __init__(self, lm, **kwargs):
        super().__init__(**kwargs)
        self.ln = lm.ln
        self.decoder = lm.decoder

    def hybrid_forward(self, F, x):
        return self.decoder(self.ln(x))


# -- sequence chunking through DeviceFeed -----------------------------------

class TokenWindows:
    """Re-iterable (x, y) next-token windows over a flat token stream —
    the ``DeviceFeed`` source for the recipe. Each epoch yields
    ``len(tokens) // (batch_size * seq_len + 1)``-ish batches of shape
    (batch_size, seq_len); y is x shifted by one."""

    def __init__(self, tokens, batch_size, seq_len):
        self._tokens = _np.asarray(tokens, dtype=_np.int32)
        self.batch_size = int(batch_size)
        self.seq_len = int(seq_len)
        span = self.batch_size * self.seq_len
        self.n_batches = max((len(self._tokens) - 1) // span, 0)
        if not self.n_batches:
            raise MXNetError(
                f"token stream too short: {len(self._tokens)} tokens < one "
                f"({batch_size} x {seq_len}) window")

    def __len__(self):
        return self.n_batches

    def __iter__(self):
        span = self.batch_size * self.seq_len
        for b in range(self.n_batches):
            lo = b * span
            x = self._tokens[lo:lo + span]
            y = self._tokens[lo + 1:lo + span + 1]
            yield (x.reshape(self.batch_size, self.seq_len),
                   y.reshape(self.batch_size, self.seq_len))


def make_feed(source, trainer, depth=None):
    """Batches land pre-placed with the trainer's P(dp, sp) input spec."""
    return _feed.DeviceFeed.for_trainer(source, trainer, depth=depth,
                                        name="long_context")


# -- the dp x sp fused trainer ----------------------------------------------

class LongContextTrainer(DataParallelTrainer):
    """Fused step over a {'dp': d, 'sp': s} mesh: batch over dp, SEQUENCE
    over sp (``data_spec=P('dp','sp')``), ring attention inside the cells,
    all parameters replicated with ZeRO-over-dp optimizer state. The
    gradient normalizer folds the sp sum into the dp reduce-scatter —
    psum over sp, reduce-scatter over dp, /(d*s) — so the update equals
    the single-device full-sequence gradient."""

    def __init__(self, net, loss, optimizer="adam", optimizer_params=None,
                 mesh=None, dp_axis="dp", sp_axis="sp", comm_dtype=None,
                 bucket_bytes=None):
        from ..parallel.mesh import current_mesh
        mesh = mesh if mesh is not None else current_mesh()
        require_axis(mesh, dp_axis, "LongContextTrainer data parallelism")
        self._sp_axis = sp_axis
        self._sp_degree = require_axis(mesh, sp_axis,
                                       "LongContextTrainer sequence "
                                       "parallelism")
        super().__init__(net, loss, optimizer=optimizer,
                         optimizer_params=optimizer_params, mesh=mesh,
                         batch_axis_name=dp_axis, dtype="float32",
                         data_spec=P(dp_axis, sp_axis), zero_update=True,
                         bucket_bytes=bucket_bytes, comm_dtype=comm_dtype)
        self._step_key_base = self._step_key_base + (
            ("long_context", sp_axis, self._sp_degree),)
        self._program = StepProgram(
            f"lc.step[{type(net).__name__}]", self._step_key_base)

    def _validate_zero(self, compression):
        """Relax the parent's data-spec check to P(dp, sp); everything else
        (replicated params, dense grads, elementwise optimizer) holds."""
        if compression:
            raise MXNetError("LongContextTrainer does not support 2-bit "
                             "gradient compression")
        bad = [p.name for p, s in zip(self._plist, self._param_shardings)
               if any(ax is not None for ax in s.spec)]
        if bad:
            raise MXNetError("LongContextTrainer requires replicated "
                             f"parameters; offending {bad[:3]}")
        sparse = [p.name for p, lz in zip(self._plist, self._lazy) if lz]
        if sparse:
            raise MXNetError("LongContextTrainer is incompatible with "
                             f"row_sparse parameters ({sparse[:3]})")
        from ..optimizer.optimizer import LAMB, LARS
        if isinstance(self.optimizer, (LAMB, LARS)):
            raise MXNetError(
                f"{type(self.optimizer).__name__} trust ratios do not "
                "decompose over flat bucket shards")

    def _build_step_zero(self):
        aux_order = []
        apply_fn = _make_apply_fn(self.net, self._plist, train=True,
                                  aux_order_out=aux_order)
        plist = self._plist
        update_fn = self._update_fn
        loss_raw = self._loss_raw
        wds = self._wds
        trainable = self._trainable
        mesh = self.mesh
        dp_ax = self.batch_axis
        sp_ax = self._sp_axis
        ndp = self._dp_degree
        nsp = self._sp_degree
        buckets = self._zero_plan
        in_bucket = frozenset(i for b in buckets for i in b.indices)
        comm = self._comm_dtype

        def body(params, opt_state, key, x, y, lr, t, loss_scale):
            bucket_carry, extra_state = opt_state
            dpos = lax.axis_index(dp_ax)
            spos = lax.axis_index(sp_ax)
            kk = jax.random.wrap_key_data(key.astype(jnp.uint32),
                                          impl="threefry2x32")
            key_local = jax.random.key_data(
                jax.random.fold_in(kk, dpos * nsp + spos))

            def lossf(ps):
                with sequence_axis(sp_ax):
                    out, aux = apply_fn(key_local, ps, x)
                pred = out if not isinstance(out, tuple) else out[0]
                # mean over the LOCAL (B/dp, T/sp) token shard; shards are
                # equal-sized, so the cross-axis pmean is the global mean
                return loss_raw(pred, y), aux

            (lossv, aux), grads = jax.value_and_grad(
                lossf, has_aux=True)(params)

            new_params = list(params)
            new_extra = list(extra_state)
            for i, (g, w, s) in enumerate(zip(grads, params, extra_state)):
                if not trainable[i] or i in in_bucket:
                    continue
                gg = lax.pmean(g, (dp_ax, sp_ax))
                w2, s2 = update_fn(gg, w, s, t, lr, jnp.float32(wds[i]))
                new_params[i] = w2.astype(w.dtype)
                new_extra[i] = s2
            new_carry = []
            for b, (wd_vec, st) in zip(buckets, bucket_carry):
                flat_g = lax.psum(_zero.flatten_bucket(b, grads), sp_ax)
                g_shard = _zero.reduce_scatter_bucket(
                    flat_g, dp_ax, ndp, comm) / (ndp * nsp)
                w_shard = _zero.shard_slice(
                    b, _zero.flatten_bucket(b, params), dpos)
                w2, s2 = update_fn(g_shard.astype(w_shard.dtype), w_shard,
                                   st, t, lr, wd_vec)
                full = _zero.all_gather_bucket(w2.astype(w_shard.dtype),
                                               dp_ax)
                for i, arr in _zero.unflatten_bucket(b, full):
                    new_params[i] = arr.astype(params[i].dtype)
                new_carry.append((wd_vec, s2))
            glob_loss = lax.pmean(lossv, (dp_ax, sp_ax))
            aux = jax.tree_util.tree_map(
                lambda v: lax.pmean(v, (dp_ax, sp_ax))
                if jnp.issubdtype(v.dtype, jnp.floating) else v, aux)
            idx_of = {id(p): i for i, p in enumerate(plist)}
            for p, v in zip(aux_order, aux):
                j = idx_of.get(id(p))
                if j is not None and not trainable[j]:
                    new_params[j] = v.astype(new_params[j].dtype)
            return (new_params, (tuple(new_carry), tuple(new_extra)),
                    glob_loss, jnp.isfinite(glob_loss), aux)

        rep = P()
        dp = P(dp_ax)
        param_specs = [s.spec for s in self._param_shardings]
        extra_specs = tuple(rep for _ in self._plist)
        return _zero.shard_map_compat(
            body, mesh=mesh,
            in_specs=(param_specs, (dp, extra_specs), rep, self.data_spec,
                      self.data_spec, rep, rep, rep),
            out_specs=(param_specs, (dp, extra_specs), rep, rep, rep))

    def _record_telemetry(self, sig, examples, steps, flops_key=None):
        if self._sp_degree > 1:
            nbytes, calls = self._ring_step_bytes(sig[0])
            _telem.record_comm("ppermute", nbytes * steps, store="mesh",
                               calls=calls * steps, axis="sp")
        super()._record_telemetry(sig, examples, steps, flops_key=flops_key)

    def _ring_step_bytes(self, x_shape):
        """Per-step ppermute wire bytes: each ring step rotates the local
        k AND v shards (sp-1 hops per attention call), once forward and
        twice in the VJP (rotation replay + cotangent rotation)."""
        B, T = x_shape[0], x_shape[1]  # static python ints (the step sig)
        n_attn = sum(1 for _ in self._ring_cells())
        nsp = self._sp_degree
        per_dev_tokens = (B // self._dp_degree) * (T // nsp)
        units = getattr(self.net, "_units", 0)
        shard = 2 * per_dev_tokens * units * 4           # k + v, f32
        nbytes = 3 * n_attn * shard * (nsp - 1)
        calls = 3 * n_attn * (nsp - 1)
        return nbytes, calls

    def _ring_cells(self):
        def walk(b):
            if isinstance(b, RingSelfAttention):
                yield b
            for c in b._children.values():
                yield from walk(c)
        return walk(self.net)


# -- the recipe triple ------------------------------------------------------

def make_model(vocab_size=512, seq_len=None, dense_attention=False, ctx=None,
               **kw):
    from .. import context as _ctx
    net = LongContextLM(vocab_size, max_length=seq_len,
                        dense_attention=dense_attention, **kw)
    net.initialize(ctx=ctx or _ctx.current_context())
    return net


def make_oracle(vocab_size=512, seq_len=None, ctx=None, **kw):
    """Dense O(T^2) attention — the parity reference at moderate T."""
    return make_model(vocab_size, seq_len=seq_len, dense_attention=True,
                      ctx=ctx, **kw)


def make_trainer(net, mesh, dp_axis="dp", sp_axis="sp", learning_rate=1e-3,
                 **kw):
    return LongContextTrainer(net, token_cross_entropy, optimizer="adam",
                              optimizer_params={"learning_rate":
                                                learning_rate},
                              mesh=mesh, dp_axis=dp_axis, sp_axis=sp_axis,
                              **kw)


from . import Recipe, register  # noqa: E402

register(Recipe("long_context", make_model, make_trainer, make_oracle))
