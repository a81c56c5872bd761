"""gluon.Trainer (reference python/mxnet/gluon/trainer.py:28).

Eager training driver: applies an Optimizer to a ParameterDict, optionally
through a KVStore (push/pull facade). On TPU the heavy path is
`mxnet_tpu.parallel.DataParallelTrainer` which fuses forward+backward+
allreduce+update into one jitted step; this class keeps the reference's
imperative semantics for flexibility and parity.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax

from ..base import MXNetError
from .. import engine as _engine
from ..engine import async_feed as _feed
from .. import optimizer as opt_mod
from .. import kvstore as kvs_mod
from .. import telemetry as _telem
from .parameter import Parameter, ParameterDict


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a ParameterDict/dict/list of Parameter")
        self._params: List[Parameter] = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError(f"invalid parameter {p!r}")
            self._param2idx[p.name] = i
            self._params.append(p)
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._compression_params = compression_params
        self._kvstore_str = kvstore
        self._kvstore: Optional[kvs_mod.KVStore] = None
        self._update_on_kvstore = update_on_kvstore
        self._kv_initialized = False
        self._params_to_init: List[Parameter] = []
        self._contains_sparse_weight = False
        # bounded in-flight dispatch: the eager loop's updates are async
        # jax dispatches; the window back-pressures on the (i-K)th step's
        # updated weights so dispatch can run up to MXNET_TPU_INFLIGHT_STEPS
        # ahead without queueing unboundedly (engine/async_feed)
        self._window = _feed.DispatchWindow(name="trainer")

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            if optimizer_params and set(optimizer_params) - {"rescale_grad"}:
                raise MXNetError("optimizer_params must be None when optimizer "
                                 "is an Optimizer instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt_mod.create(optimizer, param_dict=param_dict,
                                             **optimizer_params)
        self._updaters = [opt_mod.get_updater(self._optimizer)]

    def _init_kvstore(self):
        if self._kvstore_str:
            kv = kvs_mod.create(self._kvstore_str) if isinstance(self._kvstore_str, str) \
                else self._kvstore_str
            self._kvstore = kv
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            update_on_kv = self._update_on_kvstore
            if update_on_kv is None:
                update_on_kv = kv.type.startswith("dist")
            self._update_on_kvstore_flag = update_on_kv
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    kv.init(i, p.data())
            if update_on_kv:
                kv.set_optimizer(self._optimizer)
        else:
            self._kvstore = None
            self._update_on_kvstore_flag = False
        self._kv_initialized = True

    # -- properties ----------------------------------------------------------
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # -- step ------------------------------------------------------------------
    @property
    def donation_active(self) -> bool:
        """True when the update kernels alias weight/optimizer-state buffers
        in place (engine.donation_enabled(); TPU/GPU backends)."""
        return _engine.donation_enabled()

    def step(self, batch_size, ignore_stale_grad=False):
        """rescale grads by 1/batch_size, allreduce, update (reference
        trainer.py:320). The per-param updates run through the donated
        optimizer kernels, so on backends with input-output aliasing each
        weight/state buffer is updated in place; step timing lands in the
        profiler's aggregate table while a profile is running."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        from .. import profiler as _profiler
        t0 = time.perf_counter() if _profiler._state["running"] else None
        self._allreduce_grads()
        self._update(ignore_stale_grad)
        # admit this step's last updated weight into the in-flight window:
        # per-device dispatch order means its readiness implies every
        # earlier-dispatched update of this step completed too
        h = None
        for p in reversed(self._params):
            if p.grad_req != "null":
                h = p.data()._data
                break
        if h is not None:
            if self.donation_active:
                # the NEXT step's update kernel donates the weight buffer,
                # and the window would then block on a deleted array: admit
                # a one-element slice, ready exactly when the weight is
                h = jax.lax.slice(h, (0,) * h.ndim, (1,) * h.ndim)
            self._window.admit(h)
        if t0 is not None:
            _profiler._record("trainer.step", "trainer", t0,
                              time.perf_counter())
        if _telem._ENABLED:
            # roofline ledger: the eager allreduce+update slice gets its own
            # region, so interval pacing attributes the optimizer's wall
            # time here instead of blaming the NEXT forward region for it
            _engine.record_execution(
                "step", 0.0,
                region=f"trainer.update[{type(self._optimizer).__name__}]")
            # step() is the once-per-iteration sync point: the inter-step
            # interval telemetry derives here covers the WHOLE eager loop
            # (forward + backward + update), and the engine's executed-FLOPs
            # delta over the same window yields the MFU estimate
            _telem.record_step(batch_size, source="trainer",
                               lr=float(self._optimizer.learning_rate))

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        if not self._update_on_kvstore_flag:
            live = [(i, p) for i, p in enumerate(self._params)
                    if p.grad_req != "null"]
            if len(live) > 1 and (self._kvstore.type.startswith("dist")
                                  or self._kvstore.type in ("tpu", "nccl")):
                # grads ride the kvstore's bucketed reduce path
                # (parallel/zero.py fusion buckets — one collective per
                # bucket instead of one per key), but one pushpull over ALL
                # keys can only be issued after the whole backward. Plan the
                # same buckets here and issue one pushpull per bucket in
                # reverse declaration order — the order backward finalizes
                # gradients — so each bucket's collective dispatches while
                # earlier-declared grads are still being produced. The
                # reduced values land in the same grad buffers either way.
                from ..parallel import zero as _zero
                from ..base import env as _env
                grad_of = {i: p.grad() for i, p in live}
                entries = [(i, grad_of[i].shape, grad_of[i].dtype)
                           for i, _ in live]
                buckets = _zero.plan_buckets(
                    entries, 1, int(_env.get("MXNET_TPU_BUCKET_BYTES")))
                for b in sorted(buckets, key=lambda b: -max(b.indices)):
                    grads = [grad_of[i] for i in b.indices]
                    self._kvstore.pushpull(list(b.indices), grads,
                                           out=grads)
                return
            for i, p in live:
                self._kvstore.push(i, p.grad())
            return
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            # weights live on the store: fused pushpull applies update there
            self._kvstore.pushpull(i, p.grad(), out=p.data())

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._kvstore is not None and self._update_on_kvstore_flag:
            return  # already applied in pushpull
        updater = self._updaters[0]
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            updater(i, p.grad(), p.data())

    def zero_grad(self):
        for p in self._params:
            p.zero_grad()

    def drain(self):
        """Block until every dispatched step's updates completed (epoch /
        checkpoint boundary drain point)."""
        self._window.drain()

    # -- states ----------------------------------------------------------------
    def state_dict(self):
        """Schedule counters the legacy save_states path drops: optimizer
        num_update / per-index update counts / mutable lr-scheduler fields
        and the grad rescale. Elastic snapshots carry this so a resumed
        eager loop sees the same lr at step K+1 (elastic/state.py)."""
        from ..elastic import state as _estate
        return {"sched": _estate.sched_state(self._optimizer),
                "scale": self._scale}

    def load_state_dict(self, d):
        from ..elastic import state as _estate
        if d.get("sched"):
            _estate.install_sched(self._optimizer, d["sched"])
        if "scale" in d:
            self._scale = float(d["scale"])

    def save_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is not None and self._update_on_kvstore_flag:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as f:
                f.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is not None and self._update_on_kvstore_flag:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updaters[0].set_states(f.read())
            self._optimizer = self._updaters[0].optimizer
