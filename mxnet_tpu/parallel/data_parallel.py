"""Fused data-parallel training step (the TPU path that replaces reference
SURVEY.md §3.5: Trainer.step → kvstore pushpull → Comm/NCCL/ps-lite).

One `jax.jit` computes forward + backward + allreduce + optimizer update:
batch enters sharded over the 'dp' mesh axis, parameters stay replicated (or
sharded per their Parameter.sharding spec for TP), and XLA inserts the grad
all-reduce over ICI. Weight update runs replicated, or sharded — ZeRO-style
(arXiv:2004.13336) — with ``zero_update=True``/``MXNET_TPU_ZERO=1``:
gradients flatten into fusion buckets (parallel/zero.py), reduce-scatter
over dp (optionally bf16/int8-compressed, ``MXNET_TPU_COMM_DTYPE``), each
replica updates its 1/N shard against 1/N of the optimizer state, and the
updated shards all-gather back into the replicated weights inside the same
jit so XLA can overlap the gather with the next forward.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Tuple

import jax
from jax import lax
import jax.numpy as jnp
import numpy as _np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError, env
from ..ndarray import NDArray
from .. import autograd
from .. import engine as _engine
from ..engine import async_feed as _feed
from .. import random as _rng
from .. import sanitize as _sanitize
from .. import telemetry as _telem
from ..telemetry import tracing as _tracing
from ..gluon.block import HybridBlock, _AUX_STACK
from ..gluon.parameter import Parameter
from .. import optimizer as opt_mod
from ..ops import registry as _op_registry
from . import zero as _zero
from .mesh import current_mesh, P
from .step_program import StepProgram


# ---------------------------------------------------------------------------
# Functional adapters over the eager Optimizer kernels
# ---------------------------------------------------------------------------

def functional_optimizer(opt: "opt_mod.Optimizer"):
    """Return (init_state(w_tree)->s_tree, update(g,w,s,t)->(w,s)) for an
    Optimizer instance, reusing its update formulas."""
    from ..optimizer.optimizer import (SGD, NAG, Adam, AdamW, LAMB, LARS,
                                       RMSProp, AdaGrad, _k_sgd, _k_sgd_mom,
                                       _k_nag, _k_adam, _k_adamw, _k_lamb,
                                       _k_lars, _k_rmsprop, _k_adagrad)

    # UNWRAP the @jax.jit kernels: inside the fused train step each jitted
    # kernel traces as a closed pjit call, so ~160 per-param updates become
    # ~160 separate XLA computations per step that cannot fuse with each
    # other or the backward. Measured on ResNet-50 bs32 (chip): the true
    # SGD-momentum cost is 0.38 ms/step inlined vs ~5 ms through the
    # nested-jit calls (benchmark/opt_overhead_probe.py). The eager
    # Updater path still uses the jitted aliases directly.
    (_k_sgd, _k_sgd_mom, _k_nag, _k_adam, _k_adamw, _k_lamb, _k_lars,
     _k_rmsprop, _k_adagrad) = (
        getattr(k, "__wrapped__", k)
        for k in (_k_sgd, _k_sgd_mom, _k_nag, _k_adam, _k_adamw, _k_lamb,
                  _k_lars, _k_rmsprop, _k_adagrad))

    def _f(x):
        return jnp.float32(x)

    clip = opt.clip_gradient if opt.clip_gradient is not None else -1.0

    if isinstance(opt, AdamW):
        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def update(g, w, s, t, lr, wd):
            m, v = s
            c1 = 1 - opt.beta1 ** t
            c2 = 1 - opt.beta2 ** t
            w2, m2, v2 = _k_adamw(w, g, m, v, lr, _f(opt.eta), wd,
                                  _f(opt.rescale_grad), _f(clip), _f(opt.beta1),
                                  _f(opt.beta2), _f(opt.epsilon), c1, c2)
            return w2, (m2, v2)
        return init, update

    if isinstance(opt, LAMB):
        def init(w):
            return (jnp.zeros_like(w, dtype=jnp.float32),
                    jnp.zeros_like(w, dtype=jnp.float32))

        def update(g, w, s, t, lr, wd):
            m, v = s
            c1 = 1 - opt.beta1 ** t
            c2 = 1 - opt.beta2 ** t
            w2, m2, v2 = _k_lamb(w, g, m, v, lr, wd, _f(opt.rescale_grad),
                                 _f(clip), _f(opt.beta1), _f(opt.beta2),
                                 _f(opt.epsilon), c1, c2,
                                 _f(opt.lower_bound or 0.0),
                                 _f(opt.upper_bound or jnp.inf),
                                 jnp.bool_(opt.bias_correction))
            return w2, (m2, v2)
        return init, update

    if isinstance(opt, Adam):
        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def update(g, w, s, t, lr, wd):
            m, v = s
            c1 = 1 - opt.beta1 ** t
            c2 = 1 - opt.beta2 ** t
            w2, m2, v2 = _k_adam(w, g, m, v, lr, wd, _f(opt.rescale_grad),
                                 _f(clip), _f(opt.beta1), _f(opt.beta2),
                                 _f(opt.epsilon), c1, c2)
            return w2, (m2, v2)
        return init, update

    if isinstance(opt, LARS):
        def init(w):
            return jnp.zeros_like(w)

        def update(g, w, s, t, lr, wd):
            w2, s2 = _k_lars(w, g, s, lr, wd, _f(opt.rescale_grad), _f(clip),
                             _f(opt.momentum), _f(opt.eta), _f(opt.epsilon))
            return w2, s2
        return init, update

    if isinstance(opt, NAG):
        def init(w):
            return jnp.zeros_like(w)

        def update(g, w, s, t, lr, wd):
            w2, s2 = _k_nag(w, g, s, lr, wd, _f(opt.rescale_grad), _f(clip),
                            _f(opt.momentum))
            return w2, s2
        return init, update

    if isinstance(opt, RMSProp) and not opt.centered:
        def init(w):
            return jnp.zeros_like(w)

        def update(g, w, s, t, lr, wd):
            w2, s2 = _k_rmsprop(w, g, s, lr, wd, _f(opt.rescale_grad), _f(clip),
                                _f(opt.gamma1), _f(opt.epsilon))
            return w2, s2
        return init, update

    if isinstance(opt, AdaGrad):
        def init(w):
            return jnp.zeros_like(w)

        def update(g, w, s, t, lr, wd):
            w2, s2 = _k_adagrad(w, g, s, lr, wd, _f(opt.rescale_grad), _f(clip),
                                _f(opt.float_stable_eps))
            return w2, s2
        return init, update

    if isinstance(opt, SGD):
        mom = getattr(opt, "momentum", 0.0)
        if mom == 0.0:
            def init(w):
                return ()

            def update(g, w, s, t, lr, wd):
                return _k_sgd(w, g, lr, wd, _f(opt.rescale_grad), _f(clip)), ()
            return init, update

        def init(w):
            return jnp.zeros_like(w)

        def update(g, w, s, t, lr, wd):
            w2, s2 = _k_sgd_mom(w, g, s, lr, wd, _f(opt.rescale_grad), _f(clip),
                                _f(mom))
            return w2, s2
        return init, update

    raise MXNetError(f"no functional adapter for optimizer "
                     f"{type(opt).__name__}; use gluon.Trainer or add one")


def functional_lazy_update(opt: "opt_mod.Optimizer"):
    """Lazy (row-sparse) variant of the functional update — applied per
    parameter whose grad_stype is row_sparse (reference lazy_update
    semantics: untouched rows skip wd/momentum decay entirely). Returns
    None when the optimizer has no lazy form."""
    from ..optimizer.optimizer import (SGD, NAG, Adam, AdamW, LAMB,
                                       _k_sgd_lazy, _k_sgd_mom_lazy,
                                       _k_adam_lazy)

    # unwrap nested jits for the same fusion reason as functional_optimizer
    _k_sgd_lazy, _k_sgd_mom_lazy, _k_adam_lazy = (
        getattr(k, "__wrapped__", k)
        for k in (_k_sgd_lazy, _k_sgd_mom_lazy, _k_adam_lazy))

    if not getattr(opt, "lazy_update", False):
        return None

    def _f(x):
        return jnp.float32(x)

    clip = opt.clip_gradient if opt.clip_gradient is not None else -1.0

    if isinstance(opt, (AdamW, LAMB, NAG)):
        return None  # no lazy form in the reference either
    if isinstance(opt, Adam):
        def update(g, w, s, t, lr, wd):
            m, v = s
            c1 = 1 - opt.beta1 ** t
            c2 = 1 - opt.beta2 ** t
            w2, m2, v2 = _k_adam_lazy(w, g, m, v, lr, wd,
                                      _f(opt.rescale_grad), _f(clip),
                                      _f(opt.beta1), _f(opt.beta2),
                                      _f(opt.epsilon), c1, c2)
            return w2, (m2, v2)
        return update
    if isinstance(opt, SGD):  # includes LBSGD, which inherits SGD.update
        mom = getattr(opt, "momentum", 0.0)
        if mom == 0.0:
            def update(g, w, s, t, lr, wd):
                return _k_sgd_lazy(w, g, lr, wd, _f(opt.rescale_grad),
                                   _f(clip)), ()
            return update

        def update(g, w, s, t, lr, wd):
            w2, s2 = _k_sgd_mom_lazy(w, g, s, lr, wd, _f(opt.rescale_grad),
                                     _f(clip), _f(mom))
            return w2, s2
        return update
    return None


def next_step_key(host: bool):
    """The raw key of one fused training step: one split of the global
    stream (mxnet_tpu/random.py), the same values on both routes.

    ``host=False`` (a single process): the key stays the device array the
    split made. Its two tiny programs queue behind the running step and the
    host goes on to dispatch the next one; the trainer's ``device_put`` of
    the step's scalars then places it device to device. ``host=True``
    (multi-process SPMD): a host value, which blocks until the key is
    computed, that is until the step before it has finished."""
    if host:
        # device_put cannot target the non-addressable devices of a mesh
        # spanning processes, so the jitted step takes the key as a host
        # value there: the one designed read-back of the key
        return _np.asarray(_rng.next_key_raw())  # mxlint: disable=host-sync
    return _rng.next_key_raw()


def _make_apply_fn(block: HybridBlock, plist: List[Parameter], train: bool,
                   aux_order_out: Optional[List[Parameter]] = None):
    """Pure fn(key_raw, params_raw_list, *inputs_raw) -> (outputs, aux_list).
    Same parameter-swap trick as HybridBlock's cached graph. When
    aux_order_out is given, the Parameters whose aux values the forward
    emits (BN running stats) are recorded there on the first call, in the
    same order as the returned aux_list."""
    def apply_fn(key_raw, params_raw, *raw_inputs):
        in_nds = [NDArray(r) for r in raw_inputs]
        saved = [p._data._data for p in plist]
        aux: List[Tuple[Parameter, Any]] = []
        _AUX_STACK.append(aux)
        from ..gluon.block import _TRACE_DEPTH
        _TRACE_DEPTH[0] += 1
        prev_rec = autograd.set_recording(False)
        prev_train = autograd.set_training(train)
        _rng.push_trace_key(key_raw)
        try:
            for p, r in zip(plist, params_raw):
                p._data._data = r
            out = block._forward_unhybridized(*in_nds)
        finally:
            _rng.pop_trace_key()
            for p, s in zip(plist, saved):
                p._data._data = s
            _AUX_STACK.pop()
            _TRACE_DEPTH[0] -= 1
            autograd.set_recording(prev_rec)
            autograd.set_training(prev_train)
        leaves = jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: isinstance(x, NDArray))
        raw_out = [l._data if isinstance(l, NDArray) else l for l in leaves]
        if aux_order_out is not None and not aux_order_out:
            aux_order_out.extend(p for p, _ in aux)
        return raw_out[0] if len(raw_out) == 1 else tuple(raw_out), \
            [v for _, v in aux]
    return apply_fn


class DataParallelTrainer:
    """One-jit data-parallel trainer.

    net must be a HybridBlock already initialized; loss_fn(F-less) maps
    (pred_raw, label_raw) -> scalar raw loss, built from jax ops, OR pass a
    gluon Loss block.

    step(x, y) -> float loss. Parameters/optimizer state live on device as
    raw arrays between steps (donated — no host round-trip), synced back into
    the gluon Parameters on `sync()` / checkpoint.
    """

    def __init__(self, net: HybridBlock, loss, optimizer="sgd",
                 optimizer_params=None, mesh: Optional[Mesh] = None,
                 batch_axis_name: str = "dp", dtype=None, data_spec=None,
                 compression=None, zero_update=None, bucket_bytes=None,
                 comm_dtype=None):
        # set-up on record (docs/observability.md): the call is the span
        # mx.dp.init, each phase a child span and a field of its record
        with _tracing.phased("setup", "mx.dp.init",
                             source="data_parallel") as rec:
            with rec.phase("collect"):
                self.net = net
                # Mixed precision: dtype="bfloat16" (or "float16") runs forward/backward
                # in low precision with fp32 master weights + fp32 optimizer math —
                # the TPU-native analog of reference AMP (python/mxnet/contrib/amp/).
                self.compute_dtype = None
                if dtype is None:
                    # amp.init() makes low-precision the session default
                    try:
                        from ..contrib.amp import amp as _amp
                        dtype = _amp.target_dtype()
                    except ImportError:
                        pass
                if dtype is not None and jnp.dtype(dtype) != jnp.dtype(jnp.float32):
                    self.compute_dtype = jnp.dtype(dtype)
                    if self.compute_dtype not in (jnp.dtype(jnp.bfloat16),
                                                  jnp.dtype(jnp.float16)):
                        raise MXNetError(
                            "dtype must be float32/bfloat16/float16, got %r" % dtype)
                # fp16 needs dynamic loss scaling (grads under 2^-24 flush to zero);
                # bf16/f32 don't — scaler stays None and the step skips that logic
                self._scaler = None
                if self.compute_dtype == jnp.dtype(jnp.float16):
                    from ..contrib.amp.loss_scaler import LossScaler
                    self._scaler = LossScaler()
                self.mesh = mesh if mesh is not None else current_mesh()
                # computed once: the mesh never changes after construction, and the
                # per-step placement helpers sit on the hot path
                self._multiprocess = any(d.process_index != jax.process_index()
                                         for d in self.mesh.devices.flat)
                self.batch_axis = batch_axis_name
                # input PartitionSpec; default = batch over the dp axis only. Pass
                # e.g. P('dp', 'sp') to also shard the sequence dim (context parallel).
                self.data_spec = data_spec if data_spec is not None else P(batch_axis_name)
                self.optimizer = optimizer if isinstance(optimizer, opt_mod.Optimizer) \
                    else opt_mod.create(optimizer, **(optimizer_params or {}))
                self._init_fn, self._update_fn = functional_optimizer(self.optimizer)
                self._lazy_update_fn = functional_lazy_update(self.optimizer)
                self.loss = loss
                deferred = [p.name for p in net.collect_params().values()
                            if p._data is None and p._deferred_init is not None]
                if deferred:
                    raise MXNetError(
                        "net has deferred-init parameters (%s…); run one eager "
                        "forward pass before constructing DataParallelTrainer"
                        % deferred[0])
                self._plist = [p for p in net.collect_params().values()
                               if p._data is not None]
                self._trainable = [p.grad_req != "null" for p in self._plist]
                self._lazy = [self._lazy_update_fn is not None and
                              getattr(p, "grad_stype", "default") == "row_sparse"
                              for p in self._plist]
                self._params_raw = [p._data._data for p in self._plist]
                self._t = 0
                # bounded in-flight dispatch (MXNET_TPU_INFLIGHT_STEPS): step()
                # returns without blocking and the window back-pressures on the
                # (i-K)th step's outputs — the reference dependency engine's
                # pending-op bound, realized over jax async dispatch
                self._window = _feed.DispatchWindow(name="dp")
                self._dp_degree = int(dict(self.mesh.shape).get(batch_axis_name, 1))
                self._ar_bytes: Optional[int] = None
                self._rs_bytes: Optional[int] = None   # zero: reduce-scatter wire
                self._ag_bytes: Optional[int] = None   # zero: all-gather wire
                self._opt_bytes: Optional[int] = None  # per-replica state footprint
                self._wds = [self.optimizer._get_wd(i)
                             for i in range(len(self._plist))]

                # ZeRO-style sharded weight update (arXiv:2004.13336; parallel/zero)
                if zero_update is None:
                    zero_update = bool(env.get("MXNET_TPU_ZERO"))
                self._zero = bool(zero_update)
                self._bucket_bytes = int(bucket_bytes if bucket_bytes is not None
                                         else env.get("MXNET_TPU_BUCKET_BYTES"))
                if comm_dtype is None:
                    comm_dtype = env.get("MXNET_TPU_COMM_DTYPE") or None
                self._comm_dtype = _zero.canonical_comm_dtype(comm_dtype) \
                    if self._zero else None

                # shardings: params per their spec (default replicated)
                self._param_shardings = [
                    NamedSharding(self.mesh, p.sharding if p.sharding is not None else P())
                    for p in self._plist]
            with rec.phase("place_params"):
                self._params_raw = [self._place_param(w, s)
                                    for w, s in zip(self._params_raw,
                                                    self._param_shardings)]
                rec.set_attr("leaves", len(self._plist))
                rec.set_attr("bytes", int(sum(
                    w.nbytes for w in self._params_raw)))
            with rec.phase("init_opt_state"):
                # Optimizer state is created from the PLACED master weights, so each
                # leaf is born with its final placement (zeros_like inherits the
                # NamedSharding) — single-process included: the step jit requires
                # params and opt_state co-located, and net init under mx.cpu() on a
                # TPU-visible process otherwise leaves the state on the host. In
                # multi-controller SPMD this doubles as the global-array lift
                # (identical-per-process seeded state, the reference's rank-0
                # broadcast contract). Zero mode instead shards the state 1/dp over
                # flat fusion buckets.
                if self._zero:
                    self._validate_zero(compression)
                    self._init_zero_state()
                else:
                    self._zero_plan = ()
                    self._opt_state = [self._init_fn(w) if t else ()
                                       for w, t in zip(self._params_raw,
                                                       self._trainable)]

            with rec.phase("compression"):
                # 2-bit gradient compression with per-device error feedback
                # (reference src/kvstore/gradient_compression.cc:60). Each device
                # quantizes its LOCAL gradient (+ residual) to {-thr, 0, +thr}
                # before the cross-dp reduce — the collective then carries the
                # quantized tensor, like the reference's ps-lite push path. Needs
                # explicit per-device semantics, so the compressed step runs the
                # grad computation under shard_map over the dp axis; that is only
                # well-defined for pure data parallelism (replicated params,
                # batch-only data sharding), matching the reference's dist-DP scope.
                self._compression = dict(compression) if compression else None
                if self._compression:
                    ctype = self._compression.get("type", "2bit")
                    if ctype != "2bit":
                        raise MXNetError(f"unsupported gradient compression {ctype!r}")
                    bad = [p.name for p, s in zip(self._plist, self._param_shardings)
                           if any(ax is not None for ax in s.spec)]
                    if bad or tuple(self.data_spec) != (self.batch_axis,):
                        raise MXNetError(
                            "gradient compression requires pure data parallelism "
                            "(replicated parameters, data sharded over the batch "
                            f"axis only); offending params={bad[:3]} "
                            f"data_spec={self.data_spec}")
                    sparse = [p.name for p, lz in zip(self._plist, self._lazy) if lz]
                    if sparse:
                        # a {-t,0,+t}-quantized gradient has no meaningful 'absent
                        # rows' — lazy semantics would silently change under
                        # compression (the reference also restricts compression to
                        # dense gradients, src/kvstore/kvstore_dist.h)
                        raise MXNetError(
                            "gradient compression is incompatible with row_sparse "
                            f"lazy-update parameters ({sparse[:3]}); use dense "
                            "gradients or disable compression")
                    ndp = self.mesh.shape[self.batch_axis]
                    thr_sh = NamedSharding(self.mesh, P(self.batch_axis))

                    def _zeros_on(shape, sharding):
                        # zeros are servable from every process: placement works on
                        # multi-host meshes where device_put cannot reach
                        # non-addressable devices
                        if not self._multiprocess:
                            return jax.device_put(jnp.zeros(shape, jnp.float32),
                                                  sharding)
                        def _shard_zeros(idx, _s=shape):
                            dims = [len(range(*sl.indices(dim)))
                                    for sl, dim in zip(idx, _s)]
                            return _np.zeros(tuple(dims), _np.float32)
                        return jax.make_array_from_callback(shape, sharding,
                                                            _shard_zeros)

                    self._comp_resid = [
                        _zeros_on((ndp,) + w.shape, thr_sh)
                        if t and jnp.issubdtype(w.dtype, jnp.floating) else
                        _zeros_on((ndp, 1), thr_sh)
                        for w, t in zip(self._params_raw, self._trainable)]
                else:
                    self._comp_resid = []

            with rec.phase("program"):
                # process-wide engine-cache key base: N trainers over one model
                # structure and configuration share compiled step artifacts, while
                # any change to the zero/bucket/comm-dtype (or precision, mesh,
                # optimizer, compression) configuration compiles apart
                # (docs/compilation.md "fused-step fingerprints")
                self._step_key_base = (
                    "dp_step",
                    _engine.structural_fingerprint(net),
                    _engine.config_fingerprint(
                        optimizer=type(self.optimizer).__name__,
                        opt_conf=tuple(sorted(
                            (k, repr(v)) for k, v in vars(self.optimizer).items()
                            if isinstance(v, (int, float, bool, str, type(None))))),
                        wds=tuple(float(w) for w in self._wds),
                        loss=self.loss,
                        mesh=tuple(sorted(dict(self.mesh.shape).items())),
                        axis_order=tuple(self.mesh.axis_names),
                        devices=tuple(int(d.id) for d in self.mesh.devices.flat),
                        batch_axis=self.batch_axis,
                        data_spec=tuple(str(a) for a in self.data_spec),
                        param_specs=tuple(str(s.spec) for s in self._param_shardings),
                        trainable=tuple(self._trainable),
                        lazy=tuple(self._lazy),
                        compute_dtype=str(self.compute_dtype),
                        scaled=self._scaler is not None,
                        compression=tuple(sorted(self._compression.items()))
                        if self._compression else None,
                        zero=self._zero,
                        bucket_bytes=self._bucket_bytes if self._zero else None,
                        comm_dtype=self._comm_dtype))
                # executables, cost captures and roofline regions live in the
                # PROCESS-WIDE engine cache behind this program (parallel/
                # step_program.py) — same-config trainers share compiles
                self._program = StepProgram(
                    f"dp.step[{type(self.net).__name__}]", self._step_key_base)

    # -- ZeRO-style sharded update setup ------------------------------------
    def _validate_zero(self, compression):
        """zero_update preconditions: the flat-shard update is only defined
        for pure data parallelism with dense gradients and an elementwise
        optimizer."""
        if compression:
            raise MXNetError(
                "zero_update is incompatible with 2-bit gradient "
                "compression; use comm_dtype='bfloat16'/'int8' for "
                "compressed collectives instead")
        bad = [p.name for p, s in zip(self._plist, self._param_shardings)
               if any(ax is not None for ax in s.spec)]
        if bad or tuple(self.data_spec) != (self.batch_axis,):
            raise MXNetError(
                "zero_update requires pure data parallelism (replicated "
                "parameters, data sharded over the batch axis only); "
                f"offending params={bad[:3]} data_spec={self.data_spec}")
        sparse = [p.name for p, lz in zip(self._plist, self._lazy) if lz]
        if sparse:
            raise MXNetError(
                "zero_update is incompatible with row_sparse lazy-update "
                f"parameters ({sparse[:3]}): absent rows have no meaning "
                "inside a flattened bucket shard")
        from ..optimizer.optimizer import LAMB, LARS
        if isinstance(self.optimizer, (LAMB, LARS)):
            raise MXNetError(
                f"zero_update does not support "
                f"{type(self.optimizer).__name__}: its per-tensor "
                "trust-ratio norms do not decompose over flat bucket "
                "shards; use sgd/adam/adamw/...")

    def _init_zero_state(self):
        """Plan fusion buckets over the trainable master weights and create
        the optimizer state SHARDED: every bucket-state leaf lives under a
        per-shard NamedSharding over the dp axis, so each replica holds
        ~1/dp of the optimizer footprint (the
        mx_optimizer_state_per_replica_bytes gauge reports it). The
        per-bucket carry is (wd_vector, state_tree); the per-element wd
        vector rides the carry — sharded and donated through the step —
        instead of being baked into the trace as a full-size constant."""
        dp_sh = NamedSharding(self.mesh, P(self.batch_axis))
        entries = [(i, w.shape, w.dtype)
                   for i, (w, t) in enumerate(zip(self._params_raw,
                                                  self._trainable))
                   if t and jnp.issubdtype(w.dtype, jnp.floating)]
        self._zero_plan = _zero.plan_buckets(
            entries, self._dp_degree, self._bucket_bytes)
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

    # -- multi-process placement --------------------------------------------
    def _is_multiprocess(self):
        return self._multiprocess

    def _put_replicated(self, arr, sharding):
        """Place a host value onto a (possibly multi-host) sharding. With a
        mesh spanning processes, jax.device_put cannot target non-addressable
        devices — build the global array from per-shard callbacks instead
        (every process holds the full value, so any index is servable)."""
        if not self._is_multiprocess():
            return jax.device_put(arr, sharding)
        host = _np.asarray(arr)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    def _place_param(self, w, sharding):
        """Donation-safe master-weight placement. The step jit donates these
        buffers, so the gluon Parameter's own array must never alias them.
        A host (numpy) value — or, multi-process, any value: the feed goes
        through a host round-trip — lands in fresh device buffers, as does
        a jax.Array resident on devices DISJOINT from the target mesh; no
        defensive copy needed for those (the old unconditional
        ``jnp.array(copy=True)`` round-tripped every parameter through an
        extra full copy at construction). An array already living on ANY
        target device does need the copy first: device_put passes a
        same-sharding array through as-is, and even a resharding
        device_put shares the overlapping device's shard buffer with its
        output — donating the placed array would then delete the
        Parameter's own buffer (tests/test_zero_dp.py regression)."""
        if not self._is_multiprocess() and isinstance(w, jax.Array):
            cur = getattr(w, "sharding", None)
            if cur is not None and \
                    set(cur.device_set) & set(sharding.device_set):
                w = jnp.array(w, copy=True)
        return self._put_replicated(w, sharding)

    def _put_batch(self, arr, sharding):
        """Batch input: in multi-process SPMD each process passes its LOCAL
        shard of the global batch (reference dist-DP feeds per-worker
        partitions); single-process passes the global batch.

        An array that is already placed compatibly is passed through as
        the SAME array: a batch the DeviceFeed (or the caller) placed is not
        dispatched a second time, and the guarded step sees no transfer.
        A 1-device NamedSharding is satisfied by any single-device array
        on that device; otherwise require an exactly-equivalent sharding."""
        if not self._is_multiprocess():
            if isinstance(arr, jax.Array):
                cur = arr.sharding
                dev = set(cur.device_set)
                want = set(sharding.device_set)
                if dev == want and (
                        len(want) == 1
                        or cur.is_equivalent_to(sharding, arr.ndim)):
                    return arr
            return jax.device_put(arr, sharding)
        # multi-host feed: make_array_from_process_local_data requires the
        # per-process batch shard as host numpy — a protocol boundary, not
        # a stray sync
        host = _np.asarray(arr)  # mxlint: disable=host-sync
        return jax.make_array_from_process_local_data(sharding, host)

    # -- telemetry -----------------------------------------------------------
    def _grad_allreduce_bytes(self) -> int:
        """Wire bytes of the per-step gradient all-reduce over the dp axis
        (ring estimate: 2*(n-1)/n of the trainable-param footprint)."""
        if self._ar_bytes is None:
            n = self._dp_degree
            total = sum(int(w.nbytes) for w, t in
                        zip(self._params_raw, self._trainable) if t)
            self._ar_bytes = (total * 2 * (n - 1)) // n if n > 1 else 0
        return self._ar_bytes

    def _record_zero_telemetry(self, steps):
        """Zero-mode collective accounting: distinct per-kind counters
        (reduce_scatter of the gradient buckets, all_gather of the updated
        shards — ring estimates over the fusion-bucket plan)."""
        if self._rs_bytes is None:
            self._rs_bytes = _zero.reduce_scatter_wire_bytes(
                self._zero_plan, self._dp_degree, self._comm_dtype)
            self._ag_bytes = _zero.all_gather_wire_bytes(
                self._zero_plan, self._dp_degree)
        nb = len(self._zero_plan)
        _telem.record_comm("reduce_scatter", self._rs_bytes * steps,
                           store="mesh", calls=steps * nb, axis="dp")
        _telem.record_comm("all_gather", self._ag_bytes * steps,
                           store="mesh", calls=steps * nb, axis="dp")

    def _opt_state_replica_bytes(self) -> int:
        if self._opt_bytes is None:
            tree = self._opt_state
            if self._zero:
                # the wd vector riding each bucket carry is a hyperparameter
                # constant, not optimizer state — the gauge compares the
                # state footprint against the replicated trainer's
                carry, extra = self._opt_state
                tree = ([st for _, st in carry], extra)
            self._opt_bytes = _zero.per_replica_state_bytes(tree)
        return self._opt_bytes

    def _region_name(self, cost_key) -> str:
        """Roofline-ledger row key for this trainer's fused step artifact:
        a readable net-class prefix plus a digest of the full compile key
        (structural fingerprint + config_fingerprint + signature) — two
        configs that compile apart ledger apart, N same-config trainers
        aggregate into one row (StepProgram.region)."""
        return self._program.region(cost_key)

    def _record_telemetry(self, sig, examples, steps, flops_key=None):
        cost_key = flops_key if flops_key is not None else sig
        cost = self._program.cost(cost_key)
        flops = cost.get("flops")
        if self._dp_degree > 1:
            if self._zero:
                self._record_zero_telemetry(steps)
            else:
                _telem.record_comm("allreduce",
                                   self._grad_allreduce_bytes() * steps,
                                   store="mesh", calls=steps, axis="dp")
        _telem.record_optimizer_state(self._opt_state_replica_bytes(),
                                      source="data_parallel")
        # roofline ledger + aggregate flops/bytes through the ONE engine
        # funnel (called after window admission: completion-paced, no sync)
        _engine.record_execution(
            "step", flops or 0.0,
            bytes_accessed=cost.get("bytes_accessed", 0.0),
            region=self._region_name(cost_key), steps=steps, cost=cost)
        _telem.record_step(examples, source="data_parallel", steps=steps,
                           flops_per_step=(flops / steps if flops else None),
                           lr=float(self.optimizer.learning_rate),
                           dispatch_wait_seconds=self._window.wait_seconds)

    # -- loss plumbing -------------------------------------------------------
    def _loss_raw(self, pred_raw, label_raw):
        from ..gluon.loss import Loss as GluonLoss
        if isinstance(self.loss, GluonLoss):
            out = self.loss._forward_unhybridized(NDArray(pred_raw), NDArray(label_raw))
            return jnp.mean(out._data)
        return jnp.mean(self.loss(pred_raw, label_raw))

    def _build_step(self, x_shape_dtype, y_shape_dtype):
        aux_order: List[Parameter] = []
        apply_fn = _make_apply_fn(self.net, self._plist, train=True,
                                  aux_order_out=aux_order)
        plist = self._plist
        update_fn = self._update_fn
        lazy_fn, lazy = self._lazy_update_fn, self._lazy
        loss_raw = self._loss_raw
        wds = [self.optimizer._get_wd(i) for i in range(len(self._plist))]
        trainable = self._trainable
        mesh = self.mesh
        batch_axis = self.batch_axis

        x_sh = NamedSharding(mesh, P(batch_axis))
        rep = NamedSharding(mesh, P())
        p_sh = self._param_shardings
        cdt = self.compute_dtype

        def _low(a):
            if cdt is not None and jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(cdt)
            return a

        # params/opt_state/x/y arrive pre-placed (device_put with NamedSharding);
        # XLA propagates shardings and inserts the dp all-reduce on grads.
        scaled = self._scaler is not None

        def step(params, opt_state, key, x, y, lr, t, loss_scale):
            def lossf(ps):
                # casting inside the differentiated fn keeps fp32 master
                # weights: astype's vjp casts the low-precision grads back
                out, aux = apply_fn(key, [_low(p) for p in ps], _low(x))
                pred = out if not isinstance(out, tuple) else out[0]
                lossv = loss_raw(pred, y)
                return lossv * loss_scale, (lossv, aux)
            # this body alone is a plain GSPMD program: an op XLA cannot
            # partition (a Mosaic kernel) learns here over which axis the
            # batch lies (the zero and compressed bodies trace inside a
            # shard_map of their own and say nothing)
            tok = _op_registry.batch_partition.set((mesh, batch_axis))
            try:
                (_, (lossv, aux)), grads = jax.value_and_grad(
                    lossf, has_aux=True)(params)
            finally:
                _op_registry.batch_partition.reset(tok)
            if scaled:
                inv = 1.0 / loss_scale
                grads = [g * inv if jnp.issubdtype(g.dtype, jnp.floating) else g
                         for g in grads]
                finite = jnp.bool_(True)
                for i, g in enumerate(grads):
                    if trainable[i] and jnp.issubdtype(g.dtype, jnp.floating):
                        finite = jnp.logical_and(
                            finite, jnp.all(jnp.isfinite(g.astype(jnp.float32))))
            else:
                finite = jnp.bool_(True)
            new_params, new_state = [], []
            for i, (g, w, s) in enumerate(zip(grads, params, opt_state)):
                if trainable[i]:
                    fn = lazy_fn if lazy[i] else update_fn
                    w2, s2 = fn(g, w, s, t, lr, jnp.float32(wds[i]))
                    w2 = w2.astype(w.dtype)
                    if scaled:  # skip the whole update on overflow
                        w2 = jnp.where(finite, w2, w)
                        s2 = jax.tree_util.tree_map(
                            lambda new, old: jnp.where(finite, new, old), s2, s)
                    new_params.append(w2)
                    new_state.append(s2)
                else:
                    new_params.append(w)
                    new_state.append(s)
            # BN running stats (aux) flow through the param carry so they
            # accumulate across steps and sync() sees them — non-trainable
            # params otherwise pass through untouched
            idx_of = {id(p): i for i, p in enumerate(plist)}
            for p, v in zip(aux_order, aux):
                j = idx_of.get(id(p))
                if j is not None and not trainable[j]:
                    new_params[j] = v.astype(new_params[j].dtype)
            return new_params, new_state, lossv, finite, aux
        return step

    def _build_step_compressed(self):
        """Fused step with 2-bit compression + error feedback before the
        cross-dp reduce (reference gradient_compression.cc semantics on the
        XLA collective path). Per-device gradients exist only under explicit
        SPMD, so the whole step body runs in shard_map over the dp axis."""
        aux_order: List[Parameter] = []
        apply_fn = _make_apply_fn(self.net, self._plist, train=True,
                                  aux_order_out=aux_order)
        plist = self._plist
        update_fn = self._update_fn
        loss_raw = self._loss_raw
        wds = [self.optimizer._get_wd(i) for i in range(len(self._plist))]
        trainable = self._trainable
        mesh = self.mesh
        ax = self.batch_axis
        thr = jnp.float32(self._compression.get("threshold", 0.5))
        cdt = self.compute_dtype
        scaled = self._scaler is not None

        def _low(a):
            if cdt is not None and jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(cdt)
            return a

        def body(params, opt_state, resid, key, x, y, lr, t, loss_scale):
            # x/y/resid are the device-local tiles; params are replicated
            idx = lax.axis_index(ax)
            kk = jax.random.wrap_key_data(key.astype(jnp.uint32),
                                          impl="threefry2x32")
            key_local = jax.random.key_data(jax.random.fold_in(kk, idx))

            def lossf(ps):
                out, aux = apply_fn(key_local, [_low(p) for p in ps], _low(x))
                pred = out if not isinstance(out, tuple) else out[0]
                lossv = loss_raw(pred, y)  # mean over the LOCAL batch
                return lossv * loss_scale, (lossv, aux)

            (_, (lossv, aux)), grads = jax.value_and_grad(
                lossf, has_aux=True)(params)
            if scaled:
                inv = 1.0 / loss_scale
                grads = [g * inv if jnp.issubdtype(g.dtype, jnp.floating)
                         else g for g in grads]
                fin = jnp.bool_(True)
                for i, g in enumerate(grads):
                    if trainable[i] and jnp.issubdtype(g.dtype, jnp.floating):
                        fin = jnp.logical_and(
                            fin, jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                finite = lax.pmin(fin.astype(jnp.int32), ax).astype(jnp.bool_)
            else:
                finite = jnp.bool_(True)

            new_params, new_state, new_resid = [], [], []
            for i, (g, w, s, r) in enumerate(
                    zip(grads, params, opt_state, resid)):
                if not trainable[i]:
                    new_params.append(w)
                    new_state.append(s)
                    new_resid.append(r)
                    continue
                if jnp.issubdtype(w.dtype, jnp.floating):
                    # quantize LOCAL grad + residual to {-thr, 0, +thr};
                    # only the 2-bit tensor rides the collective
                    acc = g.astype(jnp.float32) + r[0]
                    q = jnp.where(acc >= thr, thr,
                                  jnp.where(acc <= -thr, -thr,
                                            jnp.zeros_like(acc)))
                    if scaled:
                        # an overflow step must not poison the error-feedback
                        # carry: NaN acc would make q == 0 forever after
                        new_resid.append(jnp.where(finite, acc - q, r[0])[None])
                    else:
                        new_resid.append((acc - q)[None])
                    gg = lax.pmean(q, ax)
                else:
                    new_resid.append(r)
                    gg = lax.pmean(g, ax)
                w2, s2 = update_fn(gg, w, s, t, lr, jnp.float32(wds[i]))
                w2 = w2.astype(w.dtype)
                if scaled:
                    w2 = jnp.where(finite, w2, w)
                    s2 = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(finite, new, old), s2, s)
                new_params.append(w2)
                new_state.append(s2)
            glob_loss = lax.pmean(lossv, ax)
            aux = jax.tree_util.tree_map(
                lambda v: lax.pmean(v, ax)
                if jnp.issubdtype(v.dtype, jnp.floating) else v, aux)
            # cross-device-averaged BN running stats flow through the carry
            idx_of = {id(p): i for i, p in enumerate(plist)}
            for p, v in zip(aux_order, aux):
                j = idx_of.get(id(p))
                if j is not None and not trainable[j]:
                    new_params[j] = v.astype(new_params[j].dtype)
            return new_params, new_state, new_resid, glob_loss, finite, aux

        dp = P(ax)
        rep = P()
        return _zero.shard_map_compat(
            body, mesh=mesh,
            in_specs=(rep, rep, dp, rep, dp, dp, rep, rep, rep),
            out_specs=(rep, rep, dp, rep, rep, rep))

    def _build_step_zero(self):
        """Fused step with the ZeRO-style sharded weight update
        (arXiv:2004.13336): local gradients flatten into dtype-homogeneous
        fusion buckets, each bucket is reduce-scattered over the dp axis
        (optionally bf16/int8-compressed on the wire, EQuARX-style), every
        replica runs the functional optimizer on its contiguous 1/N shard
        against 1/N of the optimizer state, and the updated shards are
        all-gathered back into the replicated weights — one shard_map body
        inside the single jitted step, so XLA can overlap the all-gather
        with the next forward. Same call/return contract as _build_step."""
        aux_order: List[Parameter] = []
        apply_fn = _make_apply_fn(self.net, self._plist, train=True,
                                  aux_order_out=aux_order)
        plist = self._plist
        update_fn = self._update_fn
        loss_raw = self._loss_raw
        wds = self._wds
        trainable = self._trainable
        mesh = self.mesh
        ax = self.batch_axis
        ndp = self._dp_degree
        buckets = self._zero_plan
        in_bucket = frozenset(i for b in buckets for i in b.indices)
        comm = self._comm_dtype
        cdt = self.compute_dtype
        scaled = self._scaler is not None

        def _low(a):
            if cdt is not None and jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(cdt)
            return a

        def body(params, opt_state, key, x, y, lr, t, loss_scale):
            # x/y are the device-local batch tiles; params replicated
            bucket_carry, extra_state = opt_state
            pos = lax.axis_index(ax)
            kk = jax.random.wrap_key_data(key.astype(jnp.uint32),
                                          impl="threefry2x32")
            key_local = jax.random.key_data(jax.random.fold_in(kk, pos))

            def lossf(ps):
                out, aux = apply_fn(key_local, [_low(p) for p in ps], _low(x))
                pred = out if not isinstance(out, tuple) else out[0]
                lossv = loss_raw(pred, y)  # mean over the LOCAL batch
                return lossv * loss_scale, (lossv, aux)

            (_, (lossv, aux)), grads = jax.value_and_grad(
                lossf, has_aux=True)(params)
            if scaled:
                inv = 1.0 / loss_scale
                grads = [g * inv if jnp.issubdtype(g.dtype, jnp.floating)
                         else g for g in grads]
                fin = jnp.bool_(True)
                for i, g in enumerate(grads):
                    if trainable[i] and jnp.issubdtype(g.dtype, jnp.floating):
                        fin = jnp.logical_and(
                            fin, jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                finite = lax.pmin(fin.astype(jnp.int32), ax).astype(jnp.bool_)
            else:
                finite = jnp.bool_(True)

            def _gate(new, old):
                # fp16 overflow step: keep the old buffer contents
                return jnp.where(finite, new, old) if scaled else new

            new_params = list(params)
            new_extra = list(extra_state)
            # trainables outside every bucket (non-float dtypes): replicated
            # update on the pmean'd gradient — the plain step's math
            for i, (g, w, s) in enumerate(zip(grads, params, extra_state)):
                if not trainable[i] or i in in_bucket:
                    continue
                gg = lax.pmean(g, ax)
                w2, s2 = update_fn(gg, w, s, t, lr, jnp.float32(wds[i]))
                new_params[i] = _gate(w2.astype(w.dtype), w)
                new_extra[i] = jax.tree_util.tree_map(_gate, s2, s) \
                    if scaled else s2
            # buckets: reduce-scatter -> 1/N sharded update -> all-gather
            new_carry = []
            for b, (wd_vec, st) in zip(buckets, bucket_carry):
                flat_g = _zero.flatten_bucket(b, grads)
                g_shard = _zero.reduce_scatter_bucket(flat_g, ax, ndp,
                                                      comm) / ndp
                w_shard = _zero.shard_slice(
                    b, _zero.flatten_bucket(b, params), pos)
                w2, s2 = update_fn(g_shard.astype(w_shard.dtype), w_shard,
                                   st, t, lr, wd_vec)
                w2 = _gate(w2.astype(w_shard.dtype), w_shard)
                s2 = jax.tree_util.tree_map(_gate, s2, st) if scaled else s2
                full = _zero.all_gather_bucket(w2, ax)
                for i, arr in _zero.unflatten_bucket(b, full):
                    new_params[i] = arr.astype(params[i].dtype)
                new_carry.append((wd_vec, s2))
            glob_loss = lax.pmean(lossv, ax)
            aux = jax.tree_util.tree_map(
                lambda v: lax.pmean(v, ax)
                if jnp.issubdtype(v.dtype, jnp.floating) else v, aux)
            # cross-device-averaged BN running stats flow through the carry
            idx_of = {id(p): i for i, p in enumerate(plist)}
            for p, v in zip(aux_order, aux):
                j = idx_of.get(id(p))
                if j is not None and not trainable[j]:
                    new_params[j] = v.astype(new_params[j].dtype)
            return (new_params, (tuple(new_carry), tuple(new_extra)),
                    glob_loss, finite, aux)

        dp = P(ax)
        rep = P()
        return _zero.shard_map_compat(
            body, mesh=mesh,
            in_specs=(rep, (P(ax), rep), rep, dp, dp, rep, rep, rep),
            out_specs=(rep, (P(ax), rep), rep, rep, rep))

    def _build_any_step(self):
        """Pick the step body for this trainer's configuration."""
        if self._compression:
            return self._build_step_compressed()
        if self._zero:
            return self._build_step_zero()
        return self._build_step(None, None)

    def _get_step(self, sig):
        donate = (0, 1, 2) if self._compression else (0, 1)
        return self._program.get(
            (sig,),
            lambda: jax.jit(self._build_any_step(), donate_argnums=donate))

    def _get_multi(self, sig, n, stacked):
        def build():
            compressed = self._compression is not None
            body = self._build_any_step()

            @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
            def multi(params, opt_state, resid, key_raw, x, y, lr, t0,
                      loss_scale):
                kk = jax.random.wrap_key_data(key_raw.astype(jnp.uint32),
                                              impl="threefry2x32")

                def sbody(carry, i):
                    params, opt_state, resid, t = carry
                    ki = jax.random.key_data(jax.random.fold_in(kk, i))
                    # per-step batch when x is stacked (n, B, ...), else reuse
                    xi = x[i] if stacked else x
                    yi = y[i] if stacked else y
                    if compressed:
                        p2, s2, r2, lossv, finite, aux = body(
                            params, opt_state, resid, ki, xi, yi, lr[i], t,
                            loss_scale)
                    else:
                        p2, s2, lossv, finite, aux = body(
                            params, opt_state, ki, xi, yi, lr[i], t,
                            loss_scale)
                        r2 = resid
                    return (p2, s2, r2, t + 1.0), (lossv, finite)

                (p, s, r, t_out), (losses, finites) = lax.scan(
                    sbody, (params, opt_state, resid, t0), jnp.arange(n))
                # advance the carried RNG stream and step counter ON DEVICE:
                # returning them lets run_steps keep every per-call scalar
                # device-resident, so a repeat call uploads nothing
                key_next = jax.random.key_data(
                    jax.random.fold_in(kk, jnp.int32(n)))
                return p, s, r, losses, jnp.all(finites), key_next, t_out
            return multi
        return self._program.get((sig, "multi", n), build)

    def run_steps(self, x, y, n, stacked=False):
        """Run `n` fused steps in ONE compiled computation (lax.scan over
        the step body) — the on-device training loop. Removes per-step host
        dispatch entirely; use with device-resident batches.

        stacked=False (default): x/y are one batch reused every step
        (benchmark mode). stacked=True: x/y carry a leading per-step axis
        (n, B, ...). The learning-rate schedule is honored per step (the
        scheduler is evaluated host-side for each of the n steps and the
        resulting lr array is scanned); the fp16 loss scale, however, is
        constant within one call — split into shorter calls if dynamic
        scaling needs to react faster. Returns the per-step loss array."""
        with _tracing.phased("step", "mx.dp.run_steps", steps=n,
                             step=self._t,
                             source="data_parallel") as rec:
            # the call's phases (docs/observability.md has the table): each
            # is a child span of mx.dp.run_steps and a field of its record
            with rec.phase("get_step"):
                xr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
                yr = y._data if isinstance(y, NDArray) else jnp.asarray(y)
                self.optimizer.rescale_grad = 1.0
                if stacked and (xr.shape[0] != n or yr.shape[0] != n):
                    raise MXNetError(
                        f"run_steps(stacked=True): leading dim must be n={n}, "
                        f"got {xr.shape[0]}/{yr.shape[0]}")
                sig = (xr.shape, str(xr.dtype), yr.shape, str(yr.dtype),
                       stacked)
                fn = self._get_multi(sig, n, stacked)
            # All per-call scalars are kept device-resident, so a repeat call
            # makes no host->device transfer at all (explicit or implicit —
            # the latter is what sanitize mode's transfer guard rejects):
            # lr/scale are cached by host value, and the RNG key + step counter
            # ride the donated carry (multi returns their advanced values).
            with rec.phase("put_scalars"):
                lrs = []
                for i in range(n):
                    self.optimizer.num_update = self._t + 1 + i
                    lrs.append(float(self.optimizer.learning_rate))
                scale_val = float(self._scaler.loss_scale if self._scaler
                                  else 1.0)
                multiprocess = self._is_multiprocess()
                ep = _rng._host_state["epoch"]
                new_key = multiprocess \
                    or getattr(self, "_key_dev", None) is None \
                    or self._key_epoch != ep
                if multiprocess:
                    # multi-process SPMD: plain host values (device_put cannot
                    # target non-addressable devices)
                    lr_in = _np.asarray(lrs, _np.float32)
                    scale_in = _np.float32(scale_val)
                else:
                    # replicated ON THE MESH, like the values multi hands back:
                    # a plain device_put has no mesh in its type, so the second
                    # call (fed the first call's outputs) would retrace and
                    # recompile
                    rep = NamedSharding(self.mesh, P())
                    lr_sig = (tuple(lrs),)
                    if getattr(self, "_lr_cache_sig", None) != lr_sig:
                        self._lr_dev = jax.device_put(
                            _np.asarray(lrs, _np.float32), rep)
                        self._lr_cache_sig = lr_sig
                    if getattr(self, "_scale_cache_val", None) != scale_val:
                        self._scale_dev = jax.device_put(
                            _np.float32(scale_val), rep)
                        self._scale_cache_val = scale_val
                    lr_in, scale_in = self._lr_dev, self._scale_dev
            if new_key:
                with rec.phase("rng_key"):
                    # as in step(): dispatched and not read in one process
                    key_in = next_step_key(multiprocess)
            with rec.phase("put_scalars"):
                if multiprocess:
                    t_in = _np.float32(self._t + 1)
                else:
                    if new_key:
                        self._key_dev = jax.device_put(key_in, rep)
                        self._key_epoch = ep
                    if getattr(self, "_t_dev_val", None) != self._t:
                        self._t_dev = jax.device_put(
                            _np.float32(self._t + 1), rep)
                        self._t_dev_val = self._t
                    key_in, t_in = self._key_dev, self._t_dev
            with rec.phase("put_batch"):
                spec = self.data_spec
                if stacked:
                    spec = P(None, *self.data_spec)
                xr = self._put_batch(
                    xr, NamedSharding(self.mesh, P(*spec[:xr.ndim])))
                yr = self._put_batch(
                    yr, NamedSharding(self.mesh, P(*spec[:yr.ndim])))
            with rec.phase("capture_cost"):
                cost_key = (sig, "multi", n)
                self._program.capture_cost(
                    cost_key, fn, self._params_raw, self._opt_state,
                    self._comp_resid, key_in, xr, yr, lr_in, t_in, scale_in,
                    kind="dp_multi")
            with rec.phase("launch"), _sanitize.guard():
                (self._params_raw, self._opt_state, self._comp_resid, losses,
                 finite, key_out, t_out) = fn(
                    self._params_raw, self._opt_state, self._comp_resid,
                    key_in, xr, yr, lr_in, t_in, scale_in)
            with rec.phase("admit"):
                # one run_steps call = one in-flight entry (n fused steps
                # inside a single executable); telemetry after admission, as
                # in step()
                wait0 = self._window.wait_seconds
                self._window.admit(losses)
                if _telem._ENABLED:
                    per_step_batch = xr.shape[1] if stacked else xr.shape[0]
                    self._record_telemetry(sig, per_step_batch * n, n,
                                           flops_key=cost_key)
                self._t += n
                if not multiprocess:
                    self._key_dev, self._t_dev = key_out, t_out
                    self._t_dev_val = self._t
                self.optimizer.num_update = self._t
            rec.split("admit", "admit_wait", self._window.wait_seconds - wait0)
            if self._scaler is not None:
                with rec.phase("scaler_sync"):
                    self._scaler.update_from_step(finite)
            return losses

    def step(self, x, y, batch_size=None):
        """Run one fused training step; x/y are NDArrays (global batch)."""
        with _tracing.phased("step", "mx.dp.step", step=self._t + 1,
                             source="data_parallel") as rec:
            # the call's phases (docs/observability.md has the table): each
            # is a child span of mx.dp.step and a field of its record
            with rec.phase("get_step"):
                xr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
                yr = y._data if isinstance(y, NDArray) else jnp.asarray(y)
                bs = batch_size or xr.shape[0]
                self.optimizer.rescale_grad = 1.0
                sig = (xr.shape, str(xr.dtype), yr.shape, str(yr.dtype))
                fn = self._get_step(sig)
                self._t += 1
                self.optimizer.num_update = self._t
                lr = _np.float32(self.optimizer.learning_rate)
            multiprocess = self._is_multiprocess()
            with rec.phase("rng_key"):
                # in one process the key stays on the device: this is the
                # dispatch of its split, nothing here waits for the step
                # before and the window fills. Multi-process SPMD reads it
                # back, which does wait (next_step_key). Booked as a sync
                # on both routes: whatever waits here waits on the device
                key = next_step_key(multiprocess)
            with rec.phase("put_batch"):
                xr = self._put_batch(
                    xr, NamedSharding(self.mesh, self.data_spec))
                y_spec = self.data_spec if yr.ndim >= len(self.data_spec) \
                    else P(*self.data_spec[:yr.ndim])
                yr = self._put_batch(yr, NamedSharding(self.mesh, y_spec))
            with rec.phase("put_scalars"):
                scale = _np.float32(self._scaler.loss_scale if self._scaler
                                    else 1.0)
                t_in = _np.float32(self._t)
                if not multiprocess:
                    # EXPLICIT placement of the per-step host scalars: the
                    # uploads happen either way, but implicit numpy->device
                    # transfers are exactly what sanitize mode's transfer guard
                    # rejects. The key is a device array already: device to
                    # device, on a one-chip mesh no copy at all
                    key, lr, t_in, scale = jax.device_put(
                        (key, lr, t_in, scale), NamedSharding(self.mesh, P()))
                call_args = ((self._params_raw, self._opt_state,
                              self._comp_resid, key, xr, yr, lr, t_in, scale)
                             if self._compression
                             else (self._params_raw, self._opt_state, key, xr,
                                   yr, lr, t_in, scale))
            with rec.phase("capture_cost"):
                # cost_analysis FLOPs of the fused step, captured once per
                # signature at artifact-build time (AOT lower shares XLA
                # caches)
                self._program.capture_cost(sig, fn, *call_args,
                                           kind="dp_step")
            with rec.phase("launch"), _sanitize.guard():
                if self._compression:
                    (self._params_raw, self._opt_state, self._comp_resid,
                     lossv, finite, aux) = fn(*call_args)
                else:
                    self._params_raw, self._opt_state, lossv, finite, aux = fn(
                        *call_args)
                # the donated inputs are dead and this tuple holds the last
                # references to them: let the several hundred handles go
                # inside the call's record, not when the frame dies after it.
                # The same for the unused aux outputs and the step's inputs
                del call_args, aux, xr, yr, key, lr, t_in, scale
            if self._scaler is not None:
                with rec.phase("scaler_sync"):
                    # fp16 dynamic loss scaling reads the finite flag per
                    # step — the one sync the overlap window cannot remove
                    # (docs/input_pipeline.md "when overlap cannot help")
                    self._scaler.update_from_step(finite)
            with rec.phase("admit"):
                # non-blocking dispatch: admit the step into the bounded window
                # (blocks on the (i-K)th step, never this one), THEN record
                # telemetry — the interval-based step timing thereby runs at
                # completion pace under backpressure instead of dispatch pace,
                # and never adds a sync of its own
                wait0 = self._window.wait_seconds
                self._window.admit(lossv)
                if _telem._ENABLED:
                    self._record_telemetry(sig, bs, 1)
            rec.split("admit", "admit_wait", self._window.wait_seconds - wait0)
            return _feed.PendingScalar(lossv)

    def drain(self):
        """Block until every dispatched step completed — the designed
        epoch/eval-boundary sync point for an overlapped loop that
        collected PendingScalar losses."""
        self._window.drain()

    def sync(self):
        """Write device params back into the gluon Parameters."""
        self.drain()
        for p, w in zip(self._plist, self._params_raw):
            p._data._set_data(w)

    def save_checkpoint(self, prefix: str):
        self.sync()
        self.net.save_parameters(prefix + ".params")

    # -- elastic fault tolerance ---------------------------------------------
    def state_dict(self):
        """Full training state in the elastic snapshot schema
        ``{"leaves": {name: device array}, "meta": {...}}`` — params,
        optimizer state (incl. per-replica ZeRO shards), RNG, step/schedule
        counters, loss-scaler state. Feed it to
        ``elastic.SnapshotManager.save`` (async, no gather) or to another
        trainer's ``load_state_dict``."""
        from ..elastic import state as _estate
        return _estate.capture(self)

    def load_state_dict(self, snapshot):
        """Install a ``state_dict()``/manifest snapshot into this trainer,
        resharding onto this trainer's mesh if it differs from the saving
        run's (see docs/checkpointing.md for the resharding rules)."""
        from ..elastic import state as _estate
        self.drain()
        leaves, meta = snapshot["leaves"], snapshot["meta"]
        _estate.install(self, meta, leaves.__getitem__, set(leaves))
        return self

    @property
    def num_update(self):
        return self._t
