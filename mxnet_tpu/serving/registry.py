"""Model registry: exported symbol+params -> per-bucket pinned artifacts.

A ``RegisteredModel`` loads one exported model (symbol-JSON + params, the
same files ``Predictor`` consumes) ONCE, places the parameters on device
(replicated over the mesh when one is given), and eagerly acquires one
compiled inference artifact per batch bucket through
``predict.acquire_forward`` — i.e. through the process-wide engine
compilation cache under ``("predict", graph_fp, config_fingerprint)`` keys.
Registration therefore IS the warmup: every bucket compiles (or loads from
``JAX_COMPILATION_CACHE_DIR`` — restart != recompile) before the
first request arrives, and the steady-state serve path never compiles.
Entries are pinned for the model's lifetime; ``close()`` releases them.

Memory budgeting: parameters are held exactly once per model regardless of
bucket count (artifacts are parameter-free pure functions — params enter
as call inputs), so a registry's device footprint is
``sum(model.param_bytes)`` plus XLA's per-bucket executables.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError
from ..predict import ForwardArtifact, acquire_forward, load_params

__all__ = ["RegisteredModel", "ModelRegistry"]


class RegisteredModel:
    """One served model: shared params + one pinned artifact per bucket.

    ``input_shapes`` maps each graph data input to its PER-ROW shape (no
    batch dimension) — bucket ``B`` binds input ``(B, *row_shape)``. With
    ``mesh`` + ``data_spec`` the request batch is dp-sharded over the mesh
    (params replicated), the same explicit-``device_put`` placement rule as
    ``engine.DeviceFeed``; every bucket must then divide evenly over the
    sharded axis.
    """

    def __init__(self, name: str, symbol_file: str,
                 param_file: Optional[str] = None,
                 input_shapes: Optional[Dict[str, Sequence[int]]] = None,
                 buckets: Sequence[int] = (1, 8, 64),
                 dtype: str = "float32",
                 dtypes: Optional[Dict[str, str]] = None,
                 mesh=None, data_spec=None):
        from .. import faults as _faults
        from .. import symbol as sym_mod
        self.name = name
        # artifact loads ride the same transient-IO retry as elastic
        # snapshots (a registry boot on a flaky model store should not
        # need an operator retry loop)
        self._sym = _faults.io_retry("serving.load", sym_mod.load,
                                     symbol_file)
        self._dtype = dtype
        self._dtypes = dict(dtypes or {})
        self._mesh = mesh
        self._data_spec = data_spec
        self.buckets: Tuple[int, ...] = tuple(sorted(
            {int(b) for b in buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise MXNetError(f"buckets must be positive ints, got {buckets}")
        arg_params, aux_params = ({}, {}) if param_file is None \
            else _faults.io_retry("serving.load", load_params, param_file)
        self._arg_params = {k: self._place_param(self._raw(v))
                            for k, v in arg_params.items()}
        self._aux_params = {k: self._place_param(self._raw(v))
                            for k, v in aux_params.items()}
        self.input_names: List[str] = [
            n for n in self._sym.list_arguments() if n not in self._arg_params]
        self.output_names: List[str] = self._sym.list_outputs()
        if input_shapes is None:
            raise MXNetError(
                "RegisteredModel needs input_shapes: per-row shapes (no "
                f"batch dim) for the graph inputs {self.input_names}")
        missing = [n for n in self.input_names if n not in input_shapes]
        if missing:
            raise MXNetError(
                f"input_shapes missing {missing}; the graph's data inputs "
                f"are {self.input_names}")
        self._row_shapes = {k: tuple(int(s) for s in v)
                            for k, v in input_shapes.items()}
        if self._mesh is not None:
            axis = self._batch_axis_size()
            bad = [b for b in self.buckets if b % axis]
            if bad:
                raise MXNetError(
                    f"buckets {bad} do not divide over the sharded batch "
                    f"axis (size {axis}) of mesh {dict(self._mesh.shape)}")
        self._arts: Dict[int, ForwardArtifact] = {}
        self._closed = False
        self._warm_all()

    # -- placement (the DeviceFeed explicit-device_put rule) -----------------
    @staticmethod
    def _raw(v):
        return getattr(v, "handle", getattr(v, "_data", v))

    def _batch_axis_size(self) -> int:
        from jax.sharding import PartitionSpec
        spec = self._data_spec if self._data_spec is not None \
            else PartitionSpec(*self._mesh.axis_names[:1])
        first = tuple(spec)[0] if tuple(spec) else None
        if first is None:
            return 1
        names = first if isinstance(first, tuple) else (first,)
        n = 1
        for a in names:
            n *= self._mesh.shape[a]
        return n

    def _place_param(self, raw):
        import jax
        if self._mesh is None:
            return jax.device_put(raw)
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(raw, NamedSharding(self._mesh,
                                                 PartitionSpec()))

    def place_input(self, name: str, raw):
        """Explicit ``device_put`` of one request tensor with the model's
        input placement (dp-sharded batch dim under a mesh) — the transfer
        the dispatch loop pays up front so the compiled call itself is
        transfer-free."""
        import jax
        if self._mesh is None:
            return jax.device_put(raw)
        from jax.sharding import NamedSharding, PartitionSpec
        spec = self._data_spec if self._data_spec is not None \
            else PartitionSpec(*self._mesh.axis_names[:1])
        ndim = getattr(raw, "ndim", len(self._row_shapes[name]) + 1)
        clipped = PartitionSpec(*tuple(spec)[:ndim])
        return jax.device_put(raw, NamedSharding(self._mesh, clipped))

    # -- signature helpers ---------------------------------------------------
    def input_dtype(self, name: str) -> str:
        return self._dtypes.get(name, self._dtype)

    def row_shape(self, name: str) -> Tuple[int, ...]:
        return self._row_shapes[name]

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def smallest_bucket(self, rows: int) -> int:
        """The smallest configured bucket covering ``rows`` (the padded
        batch the dispatch loop will run)."""
        for b in self.buckets:
            if b >= rows:
                return b
        raise MXNetError(
            f"{rows} rows exceed the largest bucket {self.max_bucket} of "
            f"model {self.name!r}")

    @property
    def param_bytes(self) -> int:
        """Device bytes held by this model's parameters (once per model —
        the multi-model memory-budgeting number in docs/serving.md)."""
        total = 0
        for v in list(self._arg_params.values()) \
                + list(self._aux_params.values()):
            total += int(getattr(v, "nbytes", 0) or 0)
        return total

    # -- artifacts -----------------------------------------------------------
    def _sharding_tag(self) -> str:
        if self._mesh is None:
            return ""
        spec = tuple(self._data_spec) if self._data_spec is not None \
            else tuple(self._mesh.axis_names[:1])
        return f"mesh={tuple(sorted(self._mesh.shape.items()))},spec={spec}"

    def _avals(self, bucket: int):
        arg_avals = {
            n: ((bucket,) + self._row_shapes[n], self.input_dtype(n))
            for n in self.input_names}
        for n, v in self._arg_params.items():
            arg_avals[n] = (tuple(v.shape), str(v.dtype))
        aux_avals = {n: (tuple(v.shape), str(v.dtype))
                     for n, v in self._aux_params.items()}
        return arg_avals, aux_avals

    def _warm_all(self):
        """Eager startup warmup: one acquire (compile or persistent-cache
        load) per bucket, so the first real request hits a ready
        executable."""
        params = {**self._arg_params, **self._aux_params}

        def place(name, z):
            # warm with the parameters the requests will pass, not zeros
            # placed like them: a jit call keys on whether each argument is
            # committed to its device, and a fresh zeros array is not, so a
            # zeros warmup compiled an executable no request ever used
            return params[name] if name in params \
                else self.place_input(name, z)

        for b in self.buckets:
            arg_avals, aux_avals = self._avals(b)
            self._arts[b] = acquire_forward(
                self._sym, arg_avals, aux_avals,
                sharding_tag=self._sharding_tag(), place=place)

    def forward(self, bucket: int, feed: Dict[str, Any]):
        """Dispatch one padded bucket batch on the compiled artifact.
        ``feed`` values must already be device-placed (``place_input``);
        returns the RAW output arrays — no host sync on this path."""
        art = self._arts[bucket]
        arg_vals = tuple(feed[n] if n in feed else self._arg_params[n]
                         for n in art.arg_names)
        aux_vals = tuple(self._aux_params[n] for n in art.aux_names)
        return art(arg_vals, aux_vals)

    def close(self):
        """Release every bucket artifact's pin."""
        if self._closed:
            return
        self._closed = True
        for art in self._arts.values():
            art.release()
        self._arts.clear()


class ModelRegistry:
    """Name -> RegisteredModel, with aggregate memory accounting."""

    def __init__(self):
        self._lock = threading.RLock()
        self._models: "OrderedDict[str, RegisteredModel]" = OrderedDict()

    def register(self, name: str, symbol_file: str,
                 param_file: Optional[str] = None, **kwargs
                 ) -> RegisteredModel:
        with self._lock:
            if name in self._models:
                raise MXNetError(f"model {name!r} already registered")
        model = RegisteredModel(name, symbol_file, param_file, **kwargs)
        with self._lock:
            self._models[name] = model
        return model

    def get(self, name: str) -> RegisteredModel:
        with self._lock:
            try:
                return self._models[name]
            except KeyError:
                raise MXNetError(
                    f"unknown model {name!r}; registered: "
                    f"{list(self._models)}") from None

    def names(self) -> List[str]:
        with self._lock:
            return list(self._models)

    def unregister(self, name: str):
        with self._lock:
            model = self._models.pop(name, None)
        if model is not None:
            model.close()

    def total_param_bytes(self) -> int:
        with self._lock:
            models = list(self._models.values())
        return sum(m.param_bytes for m in models)

    def close(self):
        with self._lock:
            models = list(self._models.values())
            self._models.clear()
        for m in models:
            m.close()
