"""Minimal standalone predict runtime (reference
include/mxnet/c_predict_api.h:1-348 + amalgamation/: the load-and-serve
path that ships without training machinery).

`mxnet_tpu.predict` imports ONLY the symbolic core (symbol graph, ops,
ndarray) — no gluon, no optimizer, no parallel, no io. Together with the
lazy package __init__ this keeps a serving process slim:

    from mxnet_tpu.predict import Predictor
    p = Predictor("model-symbol.json", "model-0000.params",
                  input_shapes={"data": (1, 3, 224, 224)})
    out = p.predict(x)          # numpy in, numpy out

Construction binds the graph and runs the single XLA compile for the
declared input shapes (the c_predict_api contract: shapes fixed at
MXPredCreate, `reshape` rebinds); `predict` afterwards never compiles.

Compiled forwards are **shared, pinned engine artifacts**: the executable
for one (graph fingerprint, full input signature) lives in the process-wide
``mxnet_tpu.engine`` cache under a ``config_fingerprint``-style key, so N
predictors (or N serving buckets — ``mxnet_tpu.serving``) over the same
exported model compile ONCE and every reuse is a visible cache hit in
``compilation_stats()``. Each holder pins its entry (``engine.pin``) so a
fingerprint-scoped invalidation can't evict a live serving executable;
``Predictor.reshape`` releases the old shape's pin when it rebinds, and
jax's persistent cache (``JAX_COMPILATION_CACHE_DIR``) keeps the XLA
executables so a restarted serving process warms from disk instead of
recompiling.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as _np

from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray
from . import engine as _engine

__all__ = ["Predictor", "ForwardArtifact", "acquire_forward", "load_params"]


def load_params(param_file: str) -> Tuple[Dict, Dict]:
    """Read a `-0000.params` checkpoint (arg:/aux: key format) without
    importing model/module machinery."""
    from .serialization import load_ndarrays
    arg_params, aux_params = {}, {}
    for k, v in load_ndarrays(param_file).items():
        tp, name = k.split(":", 1) if ":" in k else ("arg", k)
        (arg_params if tp == "arg" else aux_params)[name] = v
    return arg_params, aux_params


# ---------------------------------------------------------------------------
# Shared compiled inference artifacts
# ---------------------------------------------------------------------------

class ForwardArtifact:
    """One compiled inference forward for a (graph, full input signature)
    pair, shared process-wide through the engine cache.

    ``arg_names``/``aux_names`` fix the positional order callers must
    assemble values in; ``__call__`` dispatches the compiled executable and
    returns the raw output arrays WITHOUT a host sync (serving slices and
    syncs at completion time, off the dispatch path).
    """

    __slots__ = ("key", "fn", "arg_names", "aux_names", "num_outputs",
                 "flops", "cost", "region", "_rng_key")

    def __init__(self, key, fn, arg_names, aux_names, num_outputs, rng_key,
                 flops: float = 0.0, cost=None):
        self.key = key
        self.fn = fn
        self.arg_names = arg_names
        self.aux_names = aux_names
        self.num_outputs = num_outputs
        self.flops = flops
        self.cost = cost or {}
        # roofline-ledger row key: the graph fingerprint inside the engine
        # cache key, so every Predictor/serving bucket over one exported
        # model aggregates into one row per compiled signature
        self.region = f"predict#{key[1][:6]}" if len(key) > 1 else "predict"
        self._rng_key = rng_key

    def __call__(self, arg_vals: Sequence, aux_vals: Sequence = ()):
        outs, _ = self.fn(tuple(arg_vals), tuple(aux_vals), self._rng_key)
        from . import telemetry as _telem
        _engine.record_execution(
            "fwd", self.flops,
            bytes_accessed=self.cost.get("bytes_accessed", 0.0),
            region=self.region if _telem._ENABLED else None, cost=self.cost)
        return outs

    def release(self):
        """Drop this holder's pin (the entry stays cached until evicted)."""
        _engine.unpin(self.key)


def _aval_items(avals: Dict[str, Tuple[Tuple[int, ...], str]]):
    return tuple((n,) + (tuple(int(d) for d in s), str(t))
                 for n, (s, t) in sorted(avals.items()))


def acquire_forward(symbol, arg_avals: Dict[str, Tuple[Tuple[int, ...], str]],
                    aux_avals: Optional[Dict[str, Tuple[Tuple[int, ...],
                                                        str]]] = None,
                    sharding_tag: str = "",
                    place: Optional[Callable[[str, Any], Any]] = None
                    ) -> ForwardArtifact:
    """Get-or-build the compiled inference forward for ``symbol`` at the
    given full argument signature, through the process-wide engine cache.

    The key is ``("predict", graph_fingerprint, config_fingerprint(...))``
    over every argument/aux (name, shape, dtype) plus a caller-chosen
    ``sharding_tag`` (serving uses it to compile dp-sharded buckets apart
    from replicated ones). On a miss the artifact is built AND warmed — one
    traced+compiled execution on zeros, placed by ``place(name, zeros)``
    when given (how serving warms each bucket with its real input sharding)
    — so a registry's eager warmup at startup is exactly one call here per
    bucket. The entry comes back pinned; callers own one ``release()``.
    """
    import jax
    import jax.numpy as jnp

    aux_avals = aux_avals or {}
    fp = _engine.graph_fingerprint(symbol.tojson())
    cfg = _engine.config_fingerprint(
        args=_aval_items(arg_avals), aux=_aval_items(aux_avals),
        sharding=sharding_tag)
    key = ("predict", fp, cfg)
    art = _engine.lookup(key)
    if art is None:
        from .symbol.executor import _graph_runner
        with _engine.compile_timer("predict:bind"):
            run, arg_nodes, aux_nodes, _rng = _graph_runner(symbol, False)
            arg_names = tuple(n.name for n in arg_nodes)
            aux_names = tuple(n.name for n in aux_nodes)
            missing = [n for n in arg_names if n not in arg_avals]
            if missing:
                raise MXNetError(
                    f"acquire_forward: no shape/dtype for arguments "
                    f"{missing}")
            jitted = jax.jit(run)
            rng_key = jax.random.PRNGKey(0)

            def zero(name, avals):
                s, t = avals[name]
                z = jnp.zeros(tuple(s), jnp.dtype(t))
                return place(name, z) if place is not None else z

            warm_args = tuple(zero(n, arg_avals) for n in arg_names)
            warm_aux = tuple(zero(n, aux_avals) for n in aux_names)
            cost = {}
            from . import telemetry as _telem
            if _telem._ENABLED:
                # ledger/audit region mirrors the artifact cache key, so
                # two distinct exported graphs fingerprint apart while
                # re-binds of the same graph+signature share one row
                cost = _engine.estimate_cost(
                    jitted, warm_args, warm_aux, rng_key, kind="predict",
                    region=f"predict#{_engine.region_digest(key, 'fwd')}")
            outs, _ = jitted(warm_args, warm_aux, rng_key)
            jax.block_until_ready(outs)  # the single compile, at bind time
            art = ForwardArtifact(key, jitted, arg_names, aux_names,
                                  len(outs), rng_key,
                                  cost.get("flops", 0.0), cost=cost)
            _engine.insert(key, art)
    _engine.pin(key)
    return art


class Predictor:
    """Fixed-shape inference executor over an exported symbol graph
    (reference c_predict_api.h MXPredCreate/MXPredForward/MXPredGetOutput).
    """

    def __init__(self, symbol_file: str, param_file: Optional[str] = None,
                 input_shapes: Optional[Dict[str, Sequence[int]]] = None,
                 ctx: Optional[Context] = None, dtype: str = "float32",
                 dtypes: Optional[Dict[str, str]] = None):
        from . import symbol as sym_mod
        self._sym = sym_mod.load(symbol_file)
        self._ctx = ctx or current_context()
        self._dtype = dtype
        self._dtypes = dict(dtypes or {})
        arg_params, aux_params = ({}, {}) if param_file is None \
            else load_params(param_file)
        self._arg_params = {k: self._to_device(v) for k, v in
                            arg_params.items()}
        self._aux_params = {k: self._to_device(v) for k, v in
                            aux_params.items()}
        known = set(self._arg_params)
        self._input_names = [n for n in self._sym.list_arguments()
                             if n not in known]
        self._art: Optional[ForwardArtifact] = None
        self._shapes: Optional[Dict[str, Tuple[int, ...]]] = None
        if input_shapes:
            self.reshape(input_shapes)

    def _to_device(self, v):
        v = v if isinstance(v, NDArray) else NDArray(v._data)
        return v.as_in_context(self._ctx).handle

    def _input_dtype(self, name: str) -> str:
        return self._dtypes.get(name, self._dtype)

    # -- binding -------------------------------------------------------------
    def reshape(self, input_shapes: Dict[str, Sequence[int]]) -> None:
        """(Re)bind for new input shapes (c_predict_api.h MXPredReshape).
        Acquires the shared pinned artifact for the new signature — the one
        XLA compile, at load time, shared with every other holder of the
        same (graph, signature) — and releases the OLD signature's pin so
        rebinding never leaks a pinned cache entry."""
        missing = [n for n in self._input_names if n not in input_shapes]
        if missing:
            raise MXNetError(
                f"input_shapes missing {missing}; the graph's data inputs "
                f"are {self._input_names}")
        arg_avals = {
            name: (tuple(int(s) for s in shape), self._input_dtype(name))
            for name, shape in input_shapes.items()}
        for name, v in self._arg_params.items():
            arg_avals[name] = (tuple(v.shape), str(v.dtype))
        aux_avals = {name: (tuple(v.shape), str(v.dtype))
                     for name, v in self._aux_params.items()}
        old = self._art
        # warm with the bound parameters themselves: a jit call keys on
        # whether each argument is committed to a device, which fresh zeros
        # are not, so a zeros warmup built an executable predict() never hit
        params = {**self._arg_params, **self._aux_params}
        self._art = acquire_forward(
            self._sym, arg_avals, aux_avals,
            place=lambda name, z: params.get(name, z))
        if old is not None:
            old.release()
        self._shapes = {k: tuple(int(s) for s in v)
                        for k, v in input_shapes.items()}

    # -- serving -------------------------------------------------------------
    def predict(self, *args, **kwargs) -> Union[_np.ndarray,
                                                List[_np.ndarray]]:
        """Positional args follow the graph's input order; kwargs override
        by name. Accepts numpy or NDArray; returns numpy."""
        if len(args) > len(self._input_names):
            raise MXNetError(
                f"predict: {len(args)} positional inputs but the graph has "
                f"only {self._input_names}")
        named = dict(zip(self._input_names, args))
        named.update(kwargs)
        unknown = [n for n in named if n not in self._input_names]
        if unknown:
            raise MXNetError(
                f"predict: unknown inputs {unknown}; the graph's data "
                f"inputs are {self._input_names}")
        missing = [n for n in self._input_names if n not in named]
        if missing:
            raise MXNetError(
                f"predict: missing inputs {missing}; the graph's data "
                f"inputs are {self._input_names}")
        if self._art is None:
            self.reshape({n: tuple(_np.shape(a)) for n, a in named.items()})
        import jax.numpy as jnp
        feed = {}
        for name, a in named.items():
            if self._shapes and tuple(_np.shape(a)) != self._shapes[name]:
                raise MXNetError(
                    f"input {name!r} has shape {tuple(_np.shape(a))}, bound "
                    f"for {self._shapes[name]}; call reshape() for new "
                    "shapes (c_predict_api fixed-shape contract)")
            if isinstance(a, NDArray):
                a = a.handle
            else:
                a = jnp.asarray(_np.asarray(a, self._input_dtype(name)))
            feed[name] = a
        arg_vals = tuple(feed[n] if n in feed else self._arg_params[n]
                         for n in self._art.arg_names)
        aux_vals = tuple(self._aux_params[n] for n in self._art.aux_names)
        outs = self._art(arg_vals, aux_vals)
        res = [_np.asarray(o) for o in outs]
        return res[0] if len(res) == 1 else res

    __call__ = predict

    def close(self) -> None:
        """Release this predictor's pin on its compiled artifact."""
        art, self._art = self._art, None
        if art is not None:
            art.release()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @property
    def output_names(self) -> List[str]:
        return self._sym.list_outputs()

    @property
    def input_names(self) -> List[str]:
        return list(self._input_names)


def _selftest() -> int:
    """`python -m mxnet_tpu.predict model-prefix N C H W` smoke entry."""
    import sys
    import time
    prefix = sys.argv[1]
    shape = tuple(int(s) for s in sys.argv[2:]) or (1, 3, 224, 224)
    t0 = time.perf_counter()
    p = Predictor(prefix + "-symbol.json", prefix + "-0000.params",
                  input_shapes={"data": shape})
    t1 = time.perf_counter()
    out = p.predict(_np.zeros(shape, _np.float32))
    t2 = time.perf_counter()
    print(f"bind+compile {t1 - t0:.2f}s, predict {t2 - t1 :.4f}s, "
          f"out shape {getattr(out, 'shape', [o.shape for o in out])}")
    return 0


if __name__ == "__main__":
    raise SystemExit(_selftest())
