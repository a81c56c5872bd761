"""Block / HybridBlock (reference python/mxnet/gluon/block.py:228,838).

`hybridize()` is the reference's CachedOp boundary (src/imperative/cached_op.h)
re-designed for XLA (SURVEY.md §3.3): the block's forward is traced ONCE per
(input-signature, train-mode) into a single `jax.vjp`-based artifact — the
training forward returns outputs PLUS the VJP residuals, autograd's tape
keeps the residual handle, and `backward()` invokes the compiled pullback
directly, so one training step runs the forward computation exactly once
(the reference's one-CachedOp-artifact contract, not the recompute-forward
mirror mode earlier revisions used). Compiled artifacts live in the
process-wide `mxnet_tpu.engine` cache keyed on (structure fingerprint,
signature, train flag), so N instances of the same model compile once.

Mutable aux state (BatchNorm running stats) is threaded functionally through
`defer_aux_update`: under a trace the new value becomes an extra output and is
written back after the compiled call returns.
"""
from __future__ import annotations

import contextlib
import re
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

import os

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray import NDArray
from .. import ndarray as nd
from .. import autograd
from .. import engine as _engine
from .. import random as _rng
from .. import telemetry as _telem
from .parameter import Parameter, ParameterDict, DeferredInitializationError

_tracing = _telem.tracing


# ---------------------------------------------------------------------------
# Aux-state side-channel (BatchNorm moving stats etc.)
# ---------------------------------------------------------------------------

_AUX_STACK: List[List[Tuple[Parameter, Any]]] = []
_TRACE_DEPTH = [0]  # >0 while tracing/probing: children fold into the trace
# during a symbolic trace: stack of {id(Parameter): structured name} for the
# root block being exported, so nested blocks name their param Variables by
# the same keys save_parameters uses
_SYM_PARAM_NAMES: list = []


def in_trace() -> bool:
    return _TRACE_DEPTH[0] > 0


# ---------------------------------------------------------------------------
# Set-up on record: net init and deferred init (docs/observability.md)
# ---------------------------------------------------------------------------

_OPEN_SETUP = threading.local()  # names of the setup records open on a thread


@contextlib.contextmanager
def _outermost(name: str, **attrs):
    """The ``setup`` record ``name`` (`tracing.phased`) round the outermost
    such call of this thread. A call nested in an open one yields None and
    leaves no record: its time is the outer call's."""
    if getattr(_OPEN_SETUP, name, False):
        yield None
        return
    setattr(_OPEN_SETUP, name, True)
    try:
        with _tracing.phased("setup", name, **attrs) as rec:
            yield rec
    finally:
        setattr(_OPEN_SETUP, name, False)


def _phase(rec, name: str):
    return contextlib.nullcontext() if rec is None else rec.phase(name)


@contextlib.contextmanager
def _cold_start(block):
    """`mx.block.deferred_init`: the record of a forward that found deferred
    parameters, opened in the cold branch only (a forward whose parameters
    are ready never comes here). Phases `probe` (shape inference), `finish`
    (the initializers of the pending leaves) and `forward` (the call itself,
    eager, an op a program); `params`: the leaves it finished. One record
    for the outermost cold forward of a call: a hybridized net's first call
    leaves one, an unhybridized container one for each child that was cold
    (the container itself never takes the branch)."""
    with _outermost("mx.block.deferred_init") as rec:
        if rec is None:
            yield None
            return
        pending = [p for p in block.collect_params().values()
                   if p._deferred_init is not None]
        try:
            yield rec
        finally:
            rec.set_attr("params", sum(p._deferred_init is None
                                       for p in pending))


def defer_aux_update(param: Parameter, new_raw):
    """Write `new_raw` into param — immediately in eager mode, functionally
    (as an extra traced output) inside a hybridized trace."""
    if _AUX_STACK:
        _AUX_STACK[-1].append((param, jax.lax.stop_gradient(new_raw)))
    elif not in_trace():
        param._data._set_data(new_raw)
    # inside a shape probe (in_trace, no aux stack): drop the abstract update


class _NameManager:
    _lock = threading.Lock()
    _counters: Dict[str, int] = {}

    @classmethod
    def fresh(cls, hint: str) -> str:
        with cls._lock:
            i = cls._counters.get(hint, 0)
            cls._counters[hint] = i + 1
        return f"{hint}{i}_"


class _BlockScope:
    """Hierarchical naming (reference gluon/block.py _BlockScope): a block
    created inside a parent's `with self.name_scope():` gets the parent's
    prefix prepended and draws its counter from the PARENT's per-hint
    counters, so `Net(prefix='mynet_')` yields `mynet_dense0_weight` —
    exactly the reference naming contract save/load and symbol export
    rely on."""

    _tls = threading.local()

    def __init__(self, block: "Block"):
        self._block = block
        self._counters: Dict[str, int] = {}

    @classmethod
    def _stack(cls) -> List["_BlockScope"]:
        st = getattr(cls._tls, "stack", None)
        if st is None:
            st = cls._tls.stack = []
        return st

    @classmethod
    def create_prefix(cls, prefix: Optional[str], hint: str) -> str:
        st = cls._stack()
        if not st:
            return prefix if prefix is not None \
                else _NameManager.fresh(hint)
        scope = st[-1]
        if prefix is None:
            i = scope._counters.get(hint, 0)
            scope._counters[hint] = i + 1
            prefix = f"{hint}{i}_"
        return scope._block.prefix + prefix

    def __enter__(self):
        self._stack().append(self)
        return self

    def __exit__(self, *a):
        self._stack().pop()
        return False


class HookHandle:
    """Detachable hook registration (reference gluon/utils.py HookHandle)."""

    def __init__(self, hooks_list: List, hook):
        self._hooks_list = hooks_list
        self._hook = hook

    def detach(self):
        if self._hook in self._hooks_list:
            self._hooks_list.remove(self._hook)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.detach()
        return False


class Block:
    """Base container (reference gluon/block.py:228)."""

    def __init__(self, prefix: Optional[str] = None, params: Optional[ParameterDict] = None):
        self._empty_init_guard = True
        self._prefix = _BlockScope.create_prefix(
            prefix, type(self).__name__.lower())
        self._params = ParameterDict(self._prefix, shared=params)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._forward_hooks: List = []
        self._forward_pre_hooks: List = []
        self._scope = _BlockScope(self)

    # -- naming / params -----------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return self._scope

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = getattr(self, "_children", None)
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = getattr(self, "_reg_params", None)
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return HookHandle(self._forward_pre_hooks, hook)

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pattern = re.compile(select)
            ret.update({k: v for k, v in self._params.items() if pattern.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    # -- lifecycle -----------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from .. import initializer as init_mod
        params = self.collect_params()
        # set-up on record: `mx.block.initialize`, entry to return
        with _outermost("mx.block.initialize", params=len(params)):
            params.initialize(init or init_mod.Uniform(), ctx, verbose,
                              force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- checkpointing ---------------------------------------------------------
    def _collect_params_with_prefix(self, prefix="") -> "OrderedDict[str, Parameter]":
        """Structural names ('features.0.weight') — stable across instances
        regardless of global name counters (reference block.py same method)."""
        if prefix:
            prefix += "."
        ret = OrderedDict()
        for name, p in self._reg_params.items():
            ret[prefix + name] = p
        for cname, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + cname))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        from ..serialization import save_ndarrays
        arg = {"arg:" + k: p.data() for k, p in params.items()}
        save_ndarrays(filename, arg)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from ..serialization import load_ndarrays
        loaded = load_ndarrays(filename)
        loaded = {k.split(":", 1)[1] if ":" in k else k: v for k, v in loaded.items()}
        params = self._collect_params_with_prefix()
        for key, p in params.items():
            if key in loaded:
                p.set_data(loaded[key])
            elif not allow_missing:
                raise MXNetError(f"parameter {p.name} ({key}) missing in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(f"extra parameters in file: {sorted(extra)[:5]}")

    save_params = save_parameters
    load_params = load_parameters

    # -- execution -------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        lines = [f"{'Layer':<40}{'Output':<24}{'Params':>12}"]
        total = 0
        for name, p in self.collect_params().items():
            n = 1
            for s in (p.shape or ()):
                n *= s
            total += n
            lines.append(f"{name:<40}{str(p.shape):<24}{n:>12}")
        lines.append(f"Total params: {total}")
        print("\n".join(lines))

    def __repr__(self):
        mods = "\n".join(f"  ({k}): {v!r}".replace("\n", "\n  ")
                         for k, v in self._children.items())
        return f"{type(self).__name__}(\n{mods}\n)" if mods else f"{type(self).__name__}()"


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------

def _flatten_nd(args):
    """Flatten a nested structure of NDArrays -> (raw leaves, treedef)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        args, is_leaf=lambda x: isinstance(x, NDArray))
    raw = [l._data if isinstance(l, NDArray) else l for l in leaves]
    return raw, treedef, [isinstance(l, NDArray) for l in leaves]


class _CachedGraph:
    """One shared compiled artifact per (fingerprint, signature, train) key.

    - ``fwd``:     jitted inference forward ``(key, *flat) -> (outs, aux)``
    - ``fwd_res``: jitted training forward ``(key, *flat) -> (outs, aux,
                   residuals)`` — the forward of ``jax.vjp``, residuals out
    - ``bwd``:     jitted pullback ``(residuals, cots) -> input cotangents``
                   (never re-runs the forward)

    Aux params (BN running stats) are stored as structural PATHS so a
    different instance of the same model can map them onto its own
    Parameters when it reuses the artifact from the engine cache.
    """

    __slots__ = ("fwd", "fwd_res", "bwd", "bwd_recompute", "out_treedef",
                 "res_treedef", "aux_paths", "aux_params_builder",
                 "builder_id", "cost", "bwd_cost")

    def __init__(self):
        self.fwd = None
        self.fwd_res = None
        self.bwd = None
        self.bwd_recompute = None
        self.out_treedef = None
        self.res_treedef = None
        self.aux_paths = None          # set on first trace
        self.aux_params_builder = None
        self.builder_id = None
        self.cost = None               # cost_analysis capture (telemetry on)
        self.bwd_cost = None           # pullback cost: real cost_analysis of
                                       # the compiled vjp where available,
                                       # else the 2x-fwd heuristic (flagged
                                       # "estimated" in the roofline ledger)


class HybridBlock(Block):
    """reference gluon/block.py:838; hybridize() == trace-to-XLA cache."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_graphs: Dict[Any, list] = {}
        self._fingerprint_memo: Optional[str] = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape)
        self._cached_graphs.clear()
        self._fingerprint_memo = None
        super().hybridize(active, **kwargs)

    # whether `recompute()` was asked of this block (an instance attribute
    # once set, so it enters the structural fingerprint of those blocks only)
    _recompute = False
    _recompute_keep = ()

    def recompute(self, active=True, keep=()):
        """Recompute this block's forward in the backward pass instead of
        keeping its activations, wherever the block is traced into an
        enclosing program (a fused trainer's step, a hybridized parent):
        only the block's inputs live from the forward pass to the backward
        one. A property of the model, set where the model is built; the
        eager tape is not affected (its `MXNET_TPU_REMAT_BWD` is its own).
        `keep`: names (`jax.ad_checkpoint.checkpoint_name`) of values inside
        the block that are carried from the forward pass all the same and
        not computed again: a small discrete result (the keys a learned
        sparse attention selected) that a second computation must not be
        free to round otherwise. Returns the block."""
        self._recompute = bool(active)
        self._recompute_keep = tuple(keep)
        self.clear_cache()
        return self

    def _forward_recomputed(self, *args):
        """`jax.checkpoint` of the forward over the block's array inputs.
        The parameters are what the enclosing trace put in their place
        (closed-over tracers, which `jax.checkpoint` takes as further
        inputs); deferred aux updates (BatchNorm's statistics) leave the
        recomputed region as outputs and are handed on outside it."""
        raw, treedef, is_nd = _flatten_nd(list(args))
        traced = [i for i, r in enumerate(raw) if isinstance(r, jax.core.Tracer)]
        aux_params: List[Parameter] = []
        out_treedef = []

        def run(*dyn):
            leaves = list(raw)
            for i, r in zip(traced, dyn):
                leaves[i] = r
            nds = jax.tree_util.tree_unflatten(
                treedef, [NDArray(l) if n else l
                          for l, n in zip(leaves, is_nd)])
            aux: List[Tuple[Parameter, Any]] = []
            _AUX_STACK.append(aux)
            try:
                out = self._forward_unhybridized(*nds)
            finally:
                _AUX_STACK.pop()
            flat, tdef, _ = _flatten_nd(out)
            out_treedef[:] = [tdef]
            aux_params[:] = [p for p, _ in aux]
            return tuple(flat), tuple(v for _, v in aux)

        policy = {"policy": jax.checkpoint_policies.save_only_these_names(
            *self._recompute_keep)} if self._recompute_keep else {}
        flat, aux_vals = jax.checkpoint(run, **policy)(
            *[raw[i] for i in traced])
        for p, v in zip(aux_params, aux_vals):
            defer_aux_update(p, v)
        return jax.tree_util.tree_unflatten(
            out_treedef[0], [NDArray(r) for r in flat])

    def clear_cache(self):
        # drop this block's entries from the process-wide cache too, so a
        # structurally-stale artifact can't be handed back on the next call
        if self._fingerprint_memo is not None:
            _engine.clear_compilation_cache(self._fingerprint_memo)
        self._fingerprint_memo = None
        self._cached_graphs.clear()
        for c in self._children.values():
            if isinstance(c, HybridBlock):
                c.clear_cache()

    def cast(self, dtype):
        if self._fingerprint_memo is not None:
            _engine.clear_compilation_cache(self._fingerprint_memo)
        self._fingerprint_memo = None
        self._cached_graphs.clear()
        super().cast(dtype)

    # -- deferred shape inference ---------------------------------------------
    def infer_shape(self, *args):
        """Layers override to resolve deferred param shapes from inputs."""

    def _ensure_params_ready(self, args, rec=None):
        params = self.collect_params()
        pending = [p for p in params.values() if p._deferred_init is not None]
        if not pending:
            return
        # run shape inference down the tree by a dry eager call per block
        with _phase(rec, "probe"):
            self._shape_probe(*args)
        with _phase(rec, "finish"):
            for p in pending:
                if p._deferred_init is not None:
                    p._finish_deferred_init()

    def _shape_probe(self, *args):
        """Default probe: call infer_shape hooks recursively by executing the
        forward with ShapeDtypeStruct abstract eval."""
        def run(*raw):
            nds = [NDArray(r) for r in raw]
            with autograd.pause():
                out = self._forward_unhybridized(*nds)
            flat, _, _ = _flatten_nd(out)
            return tuple(flat)
        raw, _, _ = _flatten_nd(list(args))
        _TRACE_DEPTH[0] += 1
        try:
            try:
                jax.eval_shape(run, *raw)
            except DeferredInitializationError:
                raise
            except Exception:
                # some layers need concrete values; fall back to real execution
                nds = [NDArray(r) for r in raw]
                with autograd.pause():
                    self._forward_unhybridized(*nds)
        finally:
            _TRACE_DEPTH[0] -= 1

    # -- forward ---------------------------------------------------------------
    def forward(self, *args):
        x = args[0] if args else None
        if not isinstance(x, NDArray):
            from ..symbol.symbol import Symbol
            if isinstance(x, Symbol):
                # symbolic trace: gluon -> Symbol graph (reference
                # HybridBlock._build_cache's symbol pass; used by export)
                return self._forward_symbolic(*args)
            raise MXNetError(f"{type(self).__name__}.forward expects NDArray input")
        # inside an enclosing trace, fold into the same XLA program instead of
        # nesting another cached graph (keeps one fused computation)
        use_cached = self._active and not in_trace()
        if use_cached:
            run = self._call_cached
        elif self._recompute and in_trace():
            run = self._forward_recomputed
        else:
            run = self._forward_unhybridized
        try:
            return run(*args)
        except DeferredInitializationError:
            with _cold_start(self) as rec:
                self._ensure_params_ready(list(args), rec)
                with _phase(rec, "forward"):
                    return run(*args)

    def _forward_unhybridized(self, *args):
        kwargs = {}
        for name, p in self._reg_params.items():
            try:
                kwargs[name] = p.data()
            except DeferredInitializationError:
                return self._forward_cold(*args)
        return self.hybrid_forward(nd, *args, **kwargs)

    def _forward_cold(self, *args):
        """`_forward_unhybridized` of a block whose own parameters are
        deferred: infer their shapes from the inputs, run their
        initializers, then the forward."""
        with _cold_start(self) as rec:
            with _phase(rec, "probe"):
                self.infer_shape(*args)
            with _phase(rec, "finish"):
                for p in self._reg_params.values():
                    if p._data is None and p._deferred_init is not None:
                        p._finish_deferred_init()
            with _phase(rec, "forward"):
                kwargs = {name: p.data()
                          for name, p in self._reg_params.items()}
                return self.hybrid_forward(nd, *args, **kwargs)

    def _forward_symbolic(self, *args):
        """Trace this block into a Symbol graph. Parameter Variables are
        named by their structured path (the save_parameters key), so the
        exported symbol binds directly against the exported params file."""
        from .. import symbol as sym_mod
        own_map = not _SYM_PARAM_NAMES
        if own_map:
            _SYM_PARAM_NAMES.append(
                {id(p): k for k, p in
                 self._collect_params_with_prefix().items()})
        name_of = _SYM_PARAM_NAMES[-1]
        try:
            kwargs = {}
            for name, p in self._reg_params.items():
                kwargs[name] = sym_mod.Variable(name_of.get(id(p), p.name))
            return self.hybrid_forward(sym_mod, *args, **kwargs)
        finally:
            if own_map:
                _SYM_PARAM_NAMES.pop()

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- CachedOp path ---------------------------------------------------------
    def _signature(self, raw_inputs):
        return (tuple((tuple(r.shape), str(r.dtype)) for r in raw_inputs),
                autograd.is_training())

    def _fingerprint(self) -> str:
        if self._fingerprint_memo is None:
            self._fingerprint_memo = _engine.structural_fingerprint(self)
        return self._fingerprint_memo

    def _resolve_aux_params(self, graph: _CachedGraph) -> Optional[List[Parameter]]:
        """Map the artifact's aux-param paths onto THIS instance's Parameters.
        Returns None when the artifact can't be adopted (an aux param of the
        builder has no structural path and we are not the builder)."""
        if not graph.aux_paths:
            return []
        if None not in graph.aux_paths:
            by_path = self._collect_params_with_prefix()
            try:
                return [by_path[p] for p in graph.aux_paths]
            except KeyError:
                return None
        return graph.aux_params_builder if graph.builder_id == id(self) \
            else None

    def _call_cached(self, *args):
        params_dict = self.collect_params()
        plist = [p for p in params_dict.values() if p._data is not None or p._deferred_init is not None]
        for p in plist:
            if p._deferred_init is not None:
                raise DeferredInitializationError(p.name)
        raw_inputs, in_treedef, _ = _flatten_nd(list(args))
        raw_params = [p._data._data for p in plist]
        sig = self._signature(raw_inputs)
        entry = self._cached_graphs.get(sig)
        if entry is None:
            cache_key = ("gluon", self._fingerprint(), sig)
            graph = _engine.lookup(cache_key)
            if graph is None:
                with _engine.compile_timer(f"gluon:{type(self).__name__}"):
                    graph = self._build_graph(args, in_treedef, plist, sig)
                _engine.insert(cache_key, graph)
            entry = [graph, None]  # aux mapping resolved after first trace
            self._cached_graphs[sig] = entry
        graph = entry[0]
        key = _rng.next_key_raw()
        recording = autograd.is_recording()
        # MXNET_TPU_REMAT_BWD=1: rematerialized backward (the reference's
        # MXNET_BACKWARD_DO_MIRROR) — forward saves NO residuals and the
        # pullback re-runs the forward, trading ~2x forward FLOPs for
        # activation memory. Default is the residual-caching vjp artifact.
        remat = os.environ.get("MXNET_TPU_REMAT_BWD", "") not in ("", "0")
        all_raw = tuple(raw_inputs) + tuple(raw_params)
        if _telem._ENABLED and graph.cost is None:
            # artifact-build-time FLOPs capture for the MFU/roofline gauges
            # (one AOT lower+compile per artifact, shared with jax's caches)
            graph.cost = _engine.estimate_cost(graph.fwd, key, *all_raw,
                                               kind="gluon_fwd")
        res = None
        if recording and not remat:
            outs_flat, aux_vals, res = graph.fwd_res(key, *all_raw)
        else:
            outs_flat, aux_vals = graph.fwd(key, *all_raw)
        fwd_flops = (graph.cost or {}).get("flops", 0.0)
        # roofline region: one row per shared artifact (structural
        # fingerprint), so N instances of one block aggregate together
        region = (f"gluon:{type(self).__name__}#{self._fingerprint()[:6]}"
                  if _telem._ENABLED else None)
        _engine.record_execution(
            "fwd", fwd_flops,
            bytes_accessed=(graph.cost or {}).get("bytes_accessed", 0.0),
            region=region, cost=graph.cost)
        if entry[1] is None:
            aux_params = self._resolve_aux_params(graph)
            if aux_params is None:
                # artifact not adoptable by this instance: build a private
                # one (keyed by instance identity) and redo the call
                cache_key = ("gluon", self._fingerprint(), sig, id(self))
                graph = _engine.lookup(cache_key)
                if graph is None:
                    with _engine.compile_timer(
                            f"gluon:{type(self).__name__}"):
                        graph = self._build_graph(args, in_treedef, plist,
                                                  sig)
                    _engine.insert(cache_key, graph)
                entry[0] = graph
                if _telem._ENABLED and graph.cost is None:
                    graph.cost = _engine.estimate_cost(
                        graph.fwd, key, *all_raw, kind="gluon_fwd")
                    fwd_flops = (graph.cost or {}).get("flops", 0.0)
                if recording and not remat:
                    outs_flat, aux_vals, res = graph.fwd_res(key, *all_raw)
                else:
                    outs_flat, aux_vals = graph.fwd(key, *all_raw)
                aux_params = graph.aux_params_builder
            entry[1] = aux_params
        # apply aux updates (BN running stats) outside the trace
        for p, v in zip(entry[1], aux_vals):
            p._data._set_data(v)
        ctx = args[0].ctx if isinstance(args[0], NDArray) else current_context()
        out_nds = [NDArray(o, ctx) for o in outs_flat]
        if recording:
            input_nds = [a for a in jax.tree_util.tree_leaves(
                list(args), is_leaf=lambda x: isinstance(x, NDArray))]
            param_nds = [p._data for p in plist]
            out_dtypes = [o.dtype for o in outs_flat]

            def _bwd_cost_of(_graph, capture, _ffl=fwd_flops):
                """Pullback cost: real cost_analysis of the compiled vjp
                artifact, captured once at first backward (the AOT lower
                shares XLA's caches); falls back to the 2x-forward
                roofline convention, flagged 'estimated' so ledger rows
                built on it render distinguishably."""
                if _graph.bwd_cost is None and _telem._ENABLED:
                    c = capture()
                    if not c.get("flops"):
                        c = {"flops": 2.0 * _ffl, "estimated": 1.0}
                    _graph.bwd_cost = c
                return _graph.bwd_cost or {"flops": 2.0 * _ffl,
                                           "estimated": 1.0}

            def _record_bwd(c, _region=region):
                _engine.record_execution(
                    "bwd", c.get("flops", 0.0),
                    bytes_accessed=c.get("bytes_accessed", 0.0),
                    region=f"{_region}/bwd" if _region else None,
                    estimated=bool(c.get("estimated")), cost=c)

            if res is not None:
                def vjp_fn(cots, _graph=graph, _res=res, _dts=out_dtypes):
                    cots_t = cots if isinstance(cots, tuple) else (cots,)
                    # the compiled pullback's cotangent avals are fixed;
                    # cast mismatched head grads instead of tripping a
                    # vjp error
                    cots_t = tuple(
                        c if getattr(c, "dtype", None) == dt else
                        jnp.asarray(c, dt)
                        for c, dt in zip(cots_t, _dts))
                    _record_bwd(_bwd_cost_of(
                        _graph, lambda: _engine.estimate_cost(
                            _graph.bwd, _res, cots_t, kind="gluon_bwd")))
                    return _graph.bwd(_res, cots_t)
            else:
                def vjp_fn(cots, _graph=graph, _key=key, _all_raw=all_raw,
                           _dts=out_dtypes):
                    cots_t = cots if isinstance(cots, tuple) else (cots,)
                    cots_t = tuple(
                        c if getattr(c, "dtype", None) == dt else
                        jnp.asarray(c, dt)
                        for c, dt in zip(cots_t, _dts))
                    _record_bwd(_bwd_cost_of(
                        _graph, lambda: _engine.estimate_cost(
                            _graph.bwd_recompute, _key, _all_raw, cots_t,
                            kind="gluon_bwd_recompute")))
                    return _graph.bwd_recompute(_key, _all_raw, cots_t)

            autograd.record_op(vjp_fn, input_nds + param_nds, out_nds,
                               out_is_tuple=len(out_nds) > 1, residuals=res)
        out_tree = jax.tree_util.tree_unflatten(graph.out_treedef, out_nds)
        return out_tree

    def _build_graph(self, args, in_treedef, plist, sig) -> _CachedGraph:
        graph = _CachedGraph()
        graph.builder_id = id(self)
        n_in = len(_flatten_nd(list(args))[0])
        train_flag = sig[1]
        block = self
        first_trace = {"done": False}

        def pure_fn(key_raw, *flat):
            _engine.record_trace()
            raw_inputs = flat[:n_in]
            raw_params = flat[n_in:]
            in_nds = [NDArray(r) for r in raw_inputs]
            args_nd = jax.tree_util.tree_unflatten(in_treedef, in_nds)
            saved = [p._data._data for p in plist]
            aux_collector: List[Tuple[Parameter, Any]] = []
            _AUX_STACK.append(aux_collector)
            _TRACE_DEPTH[0] += 1
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(train_flag)
            _rng.push_trace_key(key_raw)
            try:
                for p, r in zip(plist, raw_params):
                    p._data._data = r
                out = block._forward_unhybridized(*args_nd)
            finally:
                _rng.pop_trace_key()
                for p, s in zip(plist, saved):
                    p._data._data = s
                _AUX_STACK.pop()
                _TRACE_DEPTH[0] -= 1
                autograd.set_recording(prev_rec)
                autograd.set_training(prev_train)
            out_flat, out_treedef, _ = _flatten_nd(out)
            if not first_trace["done"]:
                graph.out_treedef = out_treedef
                aux_order = [p for p, _ in aux_collector]
                path_of = {id(p): k for k, p in
                           block._collect_params_with_prefix().items()}
                graph.aux_paths = [path_of.get(id(p)) for p in aux_order]
                graph.aux_params_builder = aux_order
                first_trace["done"] = True
            return tuple(out_flat), tuple(v for _, v in aux_collector)

        graph.fwd = jax.jit(pure_fn)

        def fwd_res_impl(key_raw, *flat):
            # ONE vjp artifact: forward emits outputs + aux + residuals; the
            # pullback below consumes the residuals without recomputing the
            # forward (jax's vjp closure is a Partial pytree, so its leaves
            # cross the jit boundary as ordinary arrays)
            def f(*ins):
                return pure_fn(key_raw, *ins)

            outs, vjp_fn, aux = jax.vjp(f, *flat, has_aux=True)
            res_leaves, res_treedef = jax.tree_util.tree_flatten(vjp_fn)
            graph.res_treedef = res_treedef
            return outs, aux, tuple(res_leaves)

        graph.fwd_res = jax.jit(fwd_res_impl)

        def bwd_impl(res_leaves, cots):
            vjp_fn = jax.tree_util.tree_unflatten(graph.res_treedef,
                                                  list(res_leaves))
            return vjp_fn(tuple(cots))

        graph.bwd = jax.jit(bwd_impl)

        def bwd_recompute_impl(key_raw, all_raw, cots):
            # MXNET_TPU_REMAT_BWD mode: re-derive the forward inside the
            # pullback (never compiled unless that mode is active)
            def fwd_only(*flat):
                outs, _aux = pure_fn(key_raw, *flat)
                return outs

            _, vjp = jax.vjp(fwd_only, *all_raw)
            return vjp(tuple(cots))

        graph.bwd_recompute = jax.jit(bwd_recompute_impl)
        return graph

    # -- deployment -----------------------------------------------------------
    def export(self, path, epoch=0, remove_amp_cast=True, n_inputs=1):
        """Serialize to symbol-JSON + params (reference HybridBlock.export,
        python/mxnet/gluon/block.py:1150): the block is traced symbolically
        into a Symbol graph whose parameter Variables carry the structured
        save_parameters names, and the params file uses the reference
        arg:/aux: checkpoint format — so `SymbolBlock.imports`,
        `model.load_checkpoint`, Module, and the ONNX exporter can all
        consume the artifact without the python model code."""
        from .. import symbol as sym_mod
        from ..model import save_params_file

        inputs = [sym_mod.Variable("data" if i == 0 else f"data{i}")
                  for i in range(n_inputs)]
        out = self(*inputs)
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        out.save(f"{path}-symbol.json")
        arg, aux = {}, {}
        aux_names = set(out.list_auxiliary_states())
        for k, p in self._collect_params_with_prefix().items():
            (aux if k in aux_names else arg)[k] = p.data()
        save_params_file(f"{path}-{epoch:04d}.params", arg, aux)
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"

    def optimize_for(self, x, backend=None, **kwargs):
        self.hybridize()
        return self(x)


class SymbolBlock(HybridBlock):
    """Serve an exported symbol graph without its python model code
    (reference gluon/block.py:1193; together with HybridBlock.export this
    replaces the c_predict_api load-and-run deployment path)."""

    def __init__(self, outputs, inputs, params=None, prefix=None, **kwargs):
        super().__init__(prefix=prefix or "", **kwargs)
        from .. import symbol as sym_mod
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(list(outputs))
        self._out_sym = outputs
        self._input_names = [i.name if hasattr(i, "name") else str(i)
                             for i in (inputs if isinstance(inputs, (list, tuple))
                                       else [inputs])]
        self._arg_params = dict(params or {})
        self._exec_cache = {}
        self._param_objs = None
        self._feed_cache = {}

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        from ..model import load_params
        out = sym_mod.load(symbol_file)
        params = {}
        if param_file:
            arg, aux = load_params(param_file)
            params = {**arg, **aux}
        if isinstance(input_names, str):
            input_names = [input_names]
        blk = SymbolBlock(out, [sym_mod.Variable(n) for n in input_names],
                          params=params)
        blk._ctx = ctx
        return blk

    def _live_params(self):
        # persistent Parameter objects so collect_params()/set_data/load
        # feed every subsequent forward (not a first-call snapshot)
        if self._param_objs is None:
            from .parameter import Parameter, ParameterDict
            pd = ParameterDict()
            for k, v in self._arg_params.items():
                p = Parameter(k, shape=tuple(v.shape), dtype=str(v.dtype),
                              grad_req="null")
                p.set_data(v if isinstance(v, NDArray) else NDArray(v._data))
                pd._params[k] = p
            self._param_objs = pd
        return self._param_objs

    def forward(self, *args):
        from ..context import current_context
        ctx = getattr(self, "_ctx", None) or \
            (args[0].ctx if isinstance(args[0], NDArray) else current_context())
        # ctx is part of the key: each device gets its own bound executor,
        # so a ctx-B call never reuses the ctx-A binding with ctx-B feeds
        key = (str(ctx),) + tuple((tuple(a.shape), str(a.dtype)) for a in args)
        feed = dict(zip(self._input_names, args))
        # params follow the bind ctx; the device copy is cached per ctx and
        # per (array identity, version) so serving pays it once per device,
        # not per call — even when calls alternate between devices
        conv = self._feed_cache.setdefault(ctx, {})
        for k, p in self._live_params()._params.items():
            d = p.data()
            ent = conv.get(k)
            if ent is None or ent[0] is not d or ent[1] != d.version:
                conv[k] = ent = (d, d.version, d.as_in_context(ctx))
            feed[k] = ent[2]
        ex = self._exec_cache.get(key)
        if ex is None:
            ex = self._out_sym.bind(ctx, dict(feed))
            self._exec_cache[key] = ex
        # always re-feed current param values so post-construction
        # set_data/load on collect_params() results affect inference
        outs = ex.forward(**feed)
        return outs[0] if len(outs) == 1 else outs

    def collect_params(self, select=None):
        import re as _re
        from .parameter import ParameterDict
        live = self._live_params()
        if not select:
            return live
        pat = _re.compile(select)
        pd = ParameterDict()
        for k, p in live._params.items():
            if pat.match(k):
                pd._params[k] = p
        return pd
