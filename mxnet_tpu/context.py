"""Device contexts.

Replaces the reference's `Context` (include/mxnet/base.h:104-108, python/mxnet/context.py)
with a TPU-first design: a Context names a logical device (`tpu(i)`, `cpu(0)`) and maps
onto a concrete jax.Device. `gpu(i)` is accepted as an alias of `tpu(i)` so reference
scripts that say `ctx=mx.gpu(0)` keep working.

Unlike the reference there is no per-context stream/worker machinery here — XLA/PJRT
owns async dispatch (SURVEY.md section 7 mapping table).
"""
from __future__ import annotations

import threading
from typing import List, Optional

import jax

from .base import MXNetError


class Context:
    """A logical device. devtype in {'cpu', 'tpu'}; 'gpu' aliases 'tpu'."""

    _default_ctx = threading.local()

    devtype2id = {"cpu": 1, "gpu": 2, "tpu": 2, "cpu_pinned": 3, "cpu_shared": 5}
    devid2type = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type == "gpu":  # alias: accelerator == TPU in this framework
            device_type = "tpu"
        if device_type in ("cpu_pinned", "cpu_shared"):
            device_type = "cpu"
        if device_type not in ("cpu", "tpu"):
            raise ValueError(f"unknown device type {device_type}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devtype2id[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- jax mapping -------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        devs = _devices_of(self.device_type)
        if not devs and self.device_type == "tpu" and _held_to_cpu():
            # the process was explicitly held to the CPU (JAX_PLATFORMS=cpu,
            # the unit suite and logic checks): reference scripts that say
            # ctx=mx.gpu(0) still run there
            devs = _devices_of("cpu")
        if not devs:
            raise MXNetError(
                f"{self!r} requested but this process has no "
                f"{self.device_type} device (jax.local_devices() = "
                f"{jax.local_devices()}); set JAX_PLATFORMS=cpu to run "
                "accelerator contexts on the host")
        return devs[self.device_id % len(devs)]

    def empty_cache(self):
        """Parity with mx.Context.empty_cache; XLA manages pools itself."""
        return None

    # -- default-context stack (with ctx: ...) -----------------------------
    def __enter__(self):
        stack = _ctx_stack()
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _ctx_stack().pop()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = _ctx_stack()
        if stack:
            return stack[-1]
        return _initial_default_ctx()


def _ctx_stack() -> List[Context]:
    st = getattr(Context._default_ctx, "stack", None)
    if st is None:
        st = []
        Context._default_ctx.stack = st
    return st


_dev_cache = {}


def _devices_of(kind: str):
    if kind not in _dev_cache:
        # local_devices, not devices: in a multi-process (jax.distributed)
        # job the global list contains other workers' non-addressable
        # devices — Context must only ever resolve to a local one
        if kind == "cpu":
            try:
                _dev_cache[kind] = jax.local_devices(backend="cpu")
            except RuntimeError:
                # JAX_PLATFORMS without cpu: there is no host backend
                _dev_cache[kind] = []
        else:
            # the default backend's devices when it is an accelerator; a
            # runtime that fails to come up raises from here
            _dev_cache[kind] = [d for d in jax.local_devices()
                                if d.platform != "cpu"]
    return _dev_cache[kind]


def _held_to_cpu() -> bool:
    """True when JAX_PLATFORMS (or jax_platforms) names cpu explicitly."""
    plats = jax.config.jax_platforms or ""
    return "cpu" in [p.strip() for p in plats.split(",")]


_INITIAL_DEFAULT = None


def _initial_default_ctx() -> Context:
    global _INITIAL_DEFAULT
    if _INITIAL_DEFAULT is None:
        _INITIAL_DEFAULT = tpu(0) if _devices_of("tpu") else cpu(0)
    return _INITIAL_DEFAULT


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of tpu() — keeps reference scripts (`ctx=mx.gpu()`) running."""
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_devices_of("tpu"))


def num_tpus() -> int:
    return len(_devices_of("tpu"))


def current_context() -> Context:
    return Context.default_ctx()
