"""Operator registry and eager dispatch.

TPU-native replacement for the reference's NNVM op registry
(reference: include/mxnet/op_attr_types.h:218-332, src/operator/* NNVM_REGISTER_OP,
python/mxnet/ndarray/register.py codegen).

Design (SURVEY.md section 7): every operator is ONE pure jax function
``fn(*arrays, **params) -> array | tuple``. From that single definition we derive:

  - eager execution: `jax.jit`-compiled per (param-signature); jax caches by
    input shape/dtype, so the per-op dispatch cost is a dict lookup — this is
    the analog of the reference's CachedOp-free imperative path, but compiled.
  - shape/dtype inference: `jax.eval_shape` (replaces FInferShape/FInferType
    fixpoint passes — XLA's tracing gives both at once).
  - gradients: `jax.vjp` at record time (replaces FGradient + MXGradient pass).
  - symbolic/hybridized execution: the same fn is traced into an enclosing jit.

Params are declarative and typed (keeps dmlc::Parameter ergonomics): each op
may declare a `params` spec used for doc + coercion of list->tuple etc.
"""
from __future__ import annotations

import contextvars
import functools
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as _np

from ..base import MXNetError, env

_OP_REGISTRY: Dict[str, "Op"] = {}

# Platform the CURRENT computation is being built for. Backend-dependent op
# lowerings (e.g. Pallas flash attention vs the lax.scan fallback) cannot
# trust jax.default_backend() under a trace — on a machine with a TPU plugin
# it says "tpu" even while jit is compiling for CPU arrays. The eager invoke
# path and the graph compilers set this from the CONCRETE inputs/devices.
exec_platform: contextvars.ContextVar = contextvars.ContextVar(
    "mxnet_tpu_exec_platform", default=None)


# (mesh, batch axis name) when the CURRENT trace is a GSPMD program whose batch
# is sharded over that axis: set by DataParallelTrainer._build_step inside its
# traced body, and by nothing that already traces inside a shard_map. An op
# whose lowering XLA cannot partition by itself (a Mosaic kernel) reads it and
# wraps its call in a shard_map over that axis (ops/pallas/flash_attention.py).
batch_partition: contextvars.ContextVar = contextvars.ContextVar(
    "mxnet_tpu_batch_partition", default=None)


def _platform_of(arrays) -> Optional[str]:
    for a in arrays:
        try:
            devs = a.devices()
        except Exception:
            continue
        for d in devs:
            return d.platform
    return None


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    if isinstance(v, _np.ndarray):
        return (v.shape, str(v.dtype), v.tobytes())
    return v


class Op:
    """A registered operator: one pure jax function + metadata."""

    __slots__ = ("name", "fn", "differentiable", "aliases", "doc", "_jit_cache",
                 "nondiff_argnums", "multi_output", "state_inputs")

    def __init__(self, name: str, fn: Callable, differentiable: bool = True,
                 aliases: Tuple[str, ...] = (), doc: str = "", multi_output: bool = False,
                 state_inputs=None):
        self.name = name
        self.fn = fn
        self.differentiable = differentiable
        self.aliases = aliases
        self.doc = doc or (fn.__doc__ or "")
        self.multi_output = multi_output
        # optimizer-style in-place state semantics: ((input_idx, output_idx),
        # ...) or callable (raw_inputs, params) -> same. The nd invoke path
        # writes output[out_idx] back into input[in_idx] and strips it from
        # the returned outputs (reference ops mutate state NDArrays in place).
        self.state_inputs = state_inputs
        self._jit_cache: Dict[Any, Callable] = {}

    def bound(self, params: Dict[str, Any]) -> Callable:
        """Return the jitted array-only closure for a given param setting."""
        key = _hashable(params)
        cached = self._jit_cache.get(key)
        if cached is None:
            fn = self.fn
            if params:
                fn = functools.partial(fn, **params)
            cached = jax.jit(fn)
            self._jit_cache[key] = cached
        return cached

    def unbound(self, params: Dict[str, Any]) -> Callable:
        """The raw (unjitted) closure. Used (a) under an enclosing trace —
        nesting jit would slow compiles and this jax version cannot linearize
        through an inner pjit for some primitives (reduce_window_max), and
        (b) for eager jax.vjp at record time, same reason."""
        fn = self.fn
        if params:
            fn = functools.partial(fn, **params)
        return fn

    def __call__(self, *arrays, **params):
        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            return self.unbound(params)(*arrays)
        plat = _platform_of(arrays)
        if plat is None:
            return self.bound(params)(*arrays)
        token = exec_platform.set(plat)
        try:
            return self.bound(params)(*arrays)
        finally:
            exec_platform.reset(token)

    def __repr__(self):
        return f"<Op {self.name}>"


def register(name: str, aliases: Tuple[str, ...] = (), differentiable: bool = True,
             multi_output: bool = False, state_inputs=None):
    """Decorator: register a pure jax function as an operator."""
    def deco(fn: Callable) -> Callable:
        op = Op(name, fn, differentiable=differentiable, aliases=tuple(aliases),
                multi_output=multi_output, state_inputs=state_inputs)
        _OP_REGISTRY[name] = op
        for a in aliases:
            _OP_REGISTRY[a] = op
        return fn
    return deco


def get_op(name: str) -> Op:
    try:
        return _OP_REGISTRY[name]
    except KeyError:
        raise MXNetError(f"operator '{name}' is not registered") from None


def list_ops():
    return sorted({op.name for op in _OP_REGISTRY.values()})


def all_ops() -> Dict[str, Op]:
    return dict(_OP_REGISTRY)


# ---------------------------------------------------------------------------
# Eager invoke (the imperative path)
# ---------------------------------------------------------------------------
# The autograd module installs these hooks at import to avoid circular deps.
_is_recording_hook: Callable[[], bool] = lambda: False
_record_hook: Optional[Callable] = None


def set_autograd_hooks(is_recording, record):
    global _is_recording_hook, _record_hook
    _is_recording_hook = is_recording
    _record_hook = record


# profiler.set_state('run') swaps this for a timing wrapper consumed by
# ndarray.invoke (the eager dispatch path); a None check per eager call is
# the entire cost when profiling is off (reference profiler.h IsProfiling()
# check in imperative invoke)
_profile_hook: Optional[Callable] = None


def set_profile_hook(hook: Optional[Callable]):
    global _profile_hook
    _profile_hook = hook


def invoke_raw(op: Op, raw_inputs, params):
    """Execute op on raw jax arrays. Returns (outputs_tuple, vjp_fn|None).

    When autograd is recording and the op is differentiable, we run through
    `jax.vjp` so the forward is computed ONCE and a compiled transpose is kept
    for the backward tape (replaces the reference's AGInfo/RecordOp,
    src/imperative/imperative.cc:193).
    """
    fn = op.bound(params)
    recording = _is_recording_hook() and op.differentiable
    if recording:
        outs, vjp_fn = jax.vjp(fn, *raw_inputs)
    else:
        outs, vjp_fn = fn(*raw_inputs), None
    if not isinstance(outs, tuple):
        outs = (outs,)
    if env.get("MXNET_ENGINE_TYPE") == "Naive":
        jax.block_until_ready(outs)
    return outs, vjp_fn
