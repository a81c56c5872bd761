"""Expert-parallel MoE training recipe (docs/large_models.md).

``MoETrainer`` composes three shardings in ONE fused jitted step over a
{'dp': d, 'ep': e} mesh:

  - the batch is sharded over BOTH axes (dp x ep devices each hold a
    token shard — every device does forward/backward work);
  - expert parameters (tagged ``_is_moe_expert`` by the model cell) are
    sharded over 'ep' and updated LOCALLY from the all_to_all-routed
    gradients — true expert parallelism, no replication;
  - the remaining dense parameters ride the ZeRO bucket planner over 'dp'
    exactly as DataParallelTrainer's zero mode (expert leaves are
    excluded from the dp buckets; their optimizer state lives in the
    per-parameter "extras" slots, born ep-sharded).

Gradient math (the parity tests pin it): dense grads are psum'd over ep,
reduce-scattered over dp, and normalized by dp*ep — the mean over all
devices; expert grads already accumulate their cross-ep contributions
through the all_to_all VJP, so they take pmean over dp / ep only.

Everything else — StepProgram artifact cache + roofline rows, bounded
in-flight dispatch, elastic capture/restore (incl. ep-degree resharding:
expert leaves are global-shape arrays, ``_place_like`` re-lays them out) —
is inherited from DataParallelTrainer.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax
from jax.sharding import NamedSharding

from ..base import MXNetError
from ..ndarray import NDArray
from ..engine import async_feed as _feed
from .. import sanitize as _sanitize
from .. import telemetry as _telem
from .. import optimizer as opt_mod
from ..parallel import zero as _zero
from ..parallel import moe as _moe
from ..parallel.data_parallel import (DataParallelTrainer, _make_apply_fn,
                                      next_step_key)
from ..parallel.mesh import require_axis, P
from ..parallel.step_program import StepProgram

__all__ = ["MoETrainer", "token_cross_entropy", "make_model", "make_oracle",
           "make_trainer"]


def token_cross_entropy(logits, labels):
    """Mean token-level cross entropy in f32 — the recipe's loss."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _moe_cells(block, out=None):
    """Every MoEPositionwiseFFN in the tree (for wire-byte accounting)."""
    from ..models.moe_transformer import MoEPositionwiseFFN
    if out is None:
        out = []
    if isinstance(block, MoEPositionwiseFFN):
        out.append(block)
    for child in block._children.values():
        _moe_cells(child, out)
    return out


class MoETrainer(DataParallelTrainer):
    """Fused dp x ep trainer for MoE transformers (see module docstring).

    ``net`` must already be initialized; its expert parameters carry the
    ``_is_moe_expert`` tag (models/moe_transformer.py). The trainer stamps
    ``P(ep, None, ...)`` shardings onto them before the base constructor
    places parameters, so the experts are born distributed.
    """

    def __init__(self, net, loss, optimizer="adam", optimizer_params=None,
                 mesh=None, dp_axis="dp", ep_axis="ep",
                 aux_loss_weight=1e-2, comm_dtype=None, bucket_bytes=None):
        from ..parallel.mesh import current_mesh
        mesh = mesh if mesh is not None else current_mesh()
        require_axis(mesh, dp_axis, "MoETrainer data parallelism")
        self._ep_axis = ep_axis
        self._ep_degree = require_axis(mesh, ep_axis,
                                       "MoETrainer expert parallelism")
        self._aux_weight = float(aux_loss_weight)
        self._expert_flags: List[bool] = []
        self._dropped_handles: list = []
        self._a2a_cache: dict = {}
        n_expert = 0
        for p in net.collect_params().values():
            if getattr(p, "_is_moe_expert", False):
                if p.shape is None:
                    raise MXNetError(f"expert parameter {p.name} has no "
                                     "shape; initialize the net first")
                if p.shape[0] % self._ep_degree:
                    raise MXNetError(
                        f"expert parameter {p.name}: E={p.shape[0]} not "
                        f"divisible by ep={self._ep_degree}")
                p.sharding = P(ep_axis, *([None] * (len(p.shape) - 1)))
                n_expert += 1
        if not n_expert:
            raise MXNetError("net has no _is_moe_expert parameters; "
                             "MoETrainer expects a MoE model "
                             "(models/moe_transformer.py)")
        super().__init__(net, loss, optimizer=optimizer,
                         optimizer_params=optimizer_params, mesh=mesh,
                         batch_axis_name=dp_axis, dtype="float32",
                         data_spec=P((dp_axis, ep_axis)), zero_update=True,
                         bucket_bytes=bucket_bytes, comm_dtype=comm_dtype)
        # MoE-specific compile-key terms: ep layout, aux weight, wire dtype
        # (the a2a exchanges ride the same canonicalized _comm_dtype the
        # base constructor resolved for the zero collectives)
        self._step_key_base = self._step_key_base + (
            ("moe", ep_axis, self._ep_degree, self._aux_weight,
             self._comm_dtype),)
        self._program = StepProgram(
            f"moe.step[{type(net).__name__}]", self._step_key_base)

    # -- zero-mode hooks (called inside the base constructor) ----------------
    def _validate_zero(self, compression):
        """MoE relaxation of the base preconditions: expert parameters ARE
        sharded (over ep) and the batch IS sharded over both axes; any
        other parameter sharding or feature combination stays rejected."""
        self._expert_flags = [bool(getattr(p, "_is_moe_expert", False))
                              for p in self._plist]
        if compression:
            raise MXNetError("MoETrainer does not support 2-bit gradient "
                             "compression; use comm_dtype instead")
        bad = [p.name for p, s, e in zip(self._plist, self._param_shardings,
                                         self._expert_flags)
               if not e and any(ax is not None for ax in s.spec)]
        if bad:
            raise MXNetError(
                "MoETrainer shards only expert parameters (over "
                f"{self._ep_axis!r}); found other sharded params {bad[:3]}")
        sparse = [p.name for p, lz in zip(self._plist, self._lazy) if lz]
        if sparse:
            raise MXNetError("MoETrainer is incompatible with row_sparse "
                             f"lazy-update parameters ({sparse[:3]})")
        from ..optimizer.optimizer import LAMB, LARS
        if isinstance(self.optimizer, (LAMB, LARS)):
            raise MXNetError(
                f"{type(self.optimizer).__name__} per-tensor trust ratios "
                "do not decompose over flat bucket shards")

    def _init_zero_state(self):
        """Base zero-state planning minus the expert leaves: experts join
        the per-parameter extras — their (m, v, ...) state is created from
        the PLACED ep-sharded weights, so it is born distributed and the
        elastic capture sees it as ordinary ``opt.x{i}.{k}`` leaves."""
        dp_sh = NamedSharding(self.mesh, P(self.batch_axis))
        entries = [(i, w.shape, w.dtype)
                   for i, (w, t) in enumerate(zip(self._params_raw,
                                                  self._trainable))
                   if t and jnp.issubdtype(w.dtype, jnp.floating)
                   and not self._expert_flags[i]]
        self._zero_plan = _zero.plan_buckets(entries, self._dp_degree,
                                             self._bucket_bytes)
        in_bucket = frozenset(i for b in self._zero_plan for i in b.indices)
        carry = []
        for b in self._zero_plan:
            flat_w = _zero.flatten_bucket(b, self._params_raw)
            state = opt_mod.init_functional_state(self._init_fn, flat_w,
                                                  sharding=dp_sh)
            wd_dev = self._put_replicated(_zero.wd_vector(b, self._wds),
                                          dp_sh)
            carry.append((wd_dev, state))
        extra = tuple(self._init_fn(w) if (t and i not in in_bucket) else ()
                      for i, (w, t) in enumerate(zip(self._params_raw,
                                                     self._trainable)))
        self._opt_state = (tuple(carry), extra)

    # -- the fused dp x ep step body -----------------------------------------
    def _build_step_zero(self):
        aux_order = []
        apply_fn = _make_apply_fn(self.net, self._plist, train=True,
                                  aux_order_out=aux_order)
        plist = self._plist
        update_fn = self._update_fn
        loss_raw = self._loss_raw
        wds = self._wds
        trainable = self._trainable
        expert = self._expert_flags
        mesh = self.mesh
        dp_ax = self.batch_axis
        ep_ax = self._ep_axis
        ndp = self._dp_degree
        nep = self._ep_degree
        buckets = self._zero_plan
        in_bucket = frozenset(i for b in buckets for i in b.indices)
        comm = self._comm_dtype
        aux_w = self._aux_weight

        def body(params, opt_state, key, x, y, lr, t, loss_scale):
            bucket_carry, extra_state = opt_state
            dpos = lax.axis_index(dp_ax)
            epos = lax.axis_index(ep_ax)
            kk = jax.random.wrap_key_data(key.astype(jnp.uint32),
                                          impl="threefry2x32")
            # fold in the FLAT device position: the stream a device sees
            # depends only on its position in the device list, not on the
            # dp/ep factorization — the ep4-vs-ep1 parity tests rely on it
            key_local = jax.random.key_data(
                jax.random.fold_in(kk, dpos * nep + epos))

            def lossf(ps):
                with _moe.expert_axis(ep_ax, comm), \
                        _moe.collect_metrics() as mc:
                    out, aux = apply_fn(key_local, ps, x)
                pred = out if not isinstance(out, tuple) else out[0]
                task = loss_raw(pred, y)  # mean over the LOCAL token shard
                lossv = task + aux_w * mc.aux_loss()
                return lossv, (mc.dropped_total(), aux)

            (lossv, (dropped, aux)), grads = jax.value_and_grad(
                lossf, has_aux=True)(params)

            new_params = list(params)
            new_extra = list(extra_state)
            for i, (g, w, s) in enumerate(zip(grads, params, extra_state)):
                if not trainable[i] or i in in_bucket:
                    continue
                if expert[i]:
                    # this shard's grad already sums every source device's
                    # routed contribution (all_to_all VJP); dp replicas
                    # average, and /nep matches the dense grads' global
                    # mean normalization
                    gg = lax.pmean(g, dp_ax) / nep
                else:
                    gg = lax.pmean(g, (dp_ax, ep_ax))
                w2, s2 = update_fn(gg, w, s, t, lr, jnp.float32(wds[i]))
                new_params[i] = w2.astype(w.dtype)
                new_extra[i] = s2
            # dense buckets: psum over ep, reduce-scatter over dp, 1/N
            # sharded update, gather back (DataParallelTrainer zero math
            # with the extra ep reduction folded into the normalizer)
            new_carry = []
            for b, (wd_vec, st) in zip(buckets, bucket_carry):
                flat_g = lax.psum(_zero.flatten_bucket(b, grads), ep_ax)
                g_shard = _zero.reduce_scatter_bucket(
                    flat_g, dp_ax, ndp, comm) / (ndp * nep)
                w_shard = _zero.shard_slice(
                    b, _zero.flatten_bucket(b, params), dpos)
                w2, s2 = update_fn(g_shard.astype(w_shard.dtype), w_shard,
                                   st, t, lr, wd_vec)
                full = _zero.all_gather_bucket(w2.astype(w_shard.dtype),
                                               dp_ax)
                for i, arr in _zero.unflatten_bucket(b, full):
                    new_params[i] = arr.astype(params[i].dtype)
                new_carry.append((wd_vec, s2))
            glob_loss = lax.pmean(lossv, (dp_ax, ep_ax))
            glob_drop = lax.psum(dropped, (dp_ax, ep_ax))
            aux = jax.tree_util.tree_map(
                lambda v: lax.pmean(v, (dp_ax, ep_ax))
                if jnp.issubdtype(v.dtype, jnp.floating) else v, aux)
            idx_of = {id(p): i for i, p in enumerate(plist)}
            for p, v in zip(aux_order, aux):
                j = idx_of.get(id(p))
                if j is not None and not trainable[j]:
                    new_params[j] = v.astype(new_params[j].dtype)
            return (new_params, (tuple(new_carry), tuple(new_extra)),
                    glob_loss, glob_drop, aux)

        dspec = self.data_spec
        rep = P()
        dp = P(dp_ax)
        param_specs = [s.spec for s in self._param_shardings]
        extra_specs = tuple(param_specs[i] if expert[i] else rep
                            for i in range(len(self._plist)))
        return _zero.shard_map_compat(
            body, mesh=mesh,
            in_specs=(param_specs, (dp, extra_specs), rep, dspec, dspec,
                      rep, rep, rep),
            out_specs=(param_specs, (dp, extra_specs), rep, rep, rep))

    # -- dispatch ------------------------------------------------------------
    def step(self, x, y, batch_size=None):
        """One fused dp x ep step; returns the global mean loss as a
        PendingScalar. The global dropped-token count rides along as a
        device handle and is booked at ``drain()``/``sync()`` — never a
        per-step host sync."""
        xr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yr = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        bs = batch_size or xr.shape[0]
        self.optimizer.rescale_grad = 1.0
        sig = (xr.shape, str(xr.dtype), yr.shape, str(yr.dtype))
        fn = self._get_step(sig)
        self._t += 1
        self.optimizer.num_update = self._t
        lr = _np.float32(self.optimizer.learning_rate)
        key = next_step_key(self._is_multiprocess())
        xr = self._put_batch(xr, NamedSharding(self.mesh, self.data_spec))
        y_spec = self.data_spec if yr.ndim >= len(self.data_spec) \
            else P(*self.data_spec[:yr.ndim])
        yr = self._put_batch(yr, NamedSharding(self.mesh, y_spec))
        scale = _np.float32(1.0)
        t_in = _np.float32(self._t)
        if not self._is_multiprocess():
            key, lr, t_in, scale = jax.device_put(
                (key, lr, t_in, scale), NamedSharding(self.mesh, P()))
        call_args = (self._params_raw, self._opt_state, key, xr, yr, lr,
                     t_in, scale)
        self._program.capture_cost(sig, fn, *call_args, kind="moe_step")
        with _telem.annotate("mx.moe.step"), _sanitize.guard():
            (self._params_raw, self._opt_state, lossv, dropped,
             aux) = fn(*call_args)
        self._window.admit(lossv)
        self._dropped_handles.append(dropped)
        if _telem._ENABLED:
            self._record_telemetry(sig, bs, 1)
        return _feed.PendingScalar(lossv)

    def drain(self):
        super().drain()
        self._flush_dropped()

    def _flush_dropped(self):
        """Book the accumulated dropped-token handles (drain/sync boundary:
        every dispatched step has completed, reading them costs nothing)."""
        handles, self._dropped_handles = self._dropped_handles, []
        if handles and _telem._ENABLED:
            _telem.record_moe_dropped(sum(int(d) for d in handles),
                                      source="moe")

    # -- telemetry -----------------------------------------------------------
    def _a2a_step_bytes(self, x_shape):
        """(bytes, calls) of one step's all_to_all traffic: per MoE cell,
        2 forward exchanges (dispatch + combine) and their 2 VJP mirrors,
        each ``all_to_all_wire_bytes`` exactly."""
        key = tuple(x_shape)
        hit = self._a2a_cache.get(key)
        if hit is None:
            n_tok = int(_np.prod(x_shape))
            n_local = n_tok // (self._dp_degree * self._ep_degree)
            total = calls = 0
            for cell in _moe_cells(self.net):
                per = _moe.all_to_all_wire_bytes(
                    n_local, cell._units, n_experts=cell._num_experts,
                    top_k=cell._top_k,
                    capacity_factor=cell._capacity_factor,
                    ep=self._ep_degree, comm_dtype=self._comm_dtype)
                total += 4 * per
                calls += 4
            hit = self._a2a_cache[key] = (total, calls)
        return hit

    def _record_telemetry(self, sig, examples, steps, flops_key=None):
        if self._ep_degree > 1:
            nbytes, calls = self._a2a_step_bytes(sig[0])
            _telem.record_comm("all_to_all", nbytes * steps, store="mesh",
                               calls=calls * steps, axis="ep")
        super()._record_telemetry(sig, examples, steps, flops_key=flops_key)


# ---------------------------------------------------------------------------
# The recipe triple
# ---------------------------------------------------------------------------

def make_model(vocab_size=512, num_experts=4, top_k=1, capacity_factor=2.0,
               dense_ffn=False, ctx=None, **kw):
    """Initialized recipe model (tiny config — scale via kwargs)."""
    from .. import context as _ctx
    from ..models import moe_transformer_tiny
    net = moe_transformer_tiny(vocab_size=vocab_size,
                               num_experts=num_experts, top_k=top_k,
                               capacity_factor=capacity_factor,
                               dense_ffn=dense_ffn, **kw)
    net.initialize(ctx=ctx or _ctx.current_context())
    return net


make_oracle = functools.partial(make_model, dense_ffn=True)


def make_trainer(net, mesh, dp_axis="dp", ep_axis="ep", learning_rate=1e-3,
                 **kw):
    return MoETrainer(net, token_cross_entropy, optimizer="adam",
                      optimizer_params={"learning_rate": learning_rate},
                      mesh=mesh, dp_axis=dp_axis, ep_axis=ep_axis, **kw)


from . import Recipe, register  # noqa: E402  (registry lives in the package)

register(Recipe("moe", make_model, make_trainer, make_oracle))
