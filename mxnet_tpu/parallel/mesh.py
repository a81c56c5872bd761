"""Device-mesh helpers (the TPU replacement for ctx lists / kvstore topology).

reference analog: src/kvstore/gpu_topology.h built reduction trees from PCIe
adjacency; on TPU the torus is expressed as a jax.sharding.Mesh and XLA lays
collectives on ICI rings itself.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Union

import jax
import numpy as _np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

_DEFAULT_MESH: Optional[Mesh] = None


def axis_size(name: str) -> int:
    """Static size of a mapped mesh axis, inside shard_map/pmap bodies
    (a Python int)."""
    return jax.lax.axis_size(name)


def require_axis(mesh: Mesh, name: str, role: str = "this trainer") -> int:
    """Validate that `name` is an axis of `mesh`; returns its size."""
    if name not in mesh.shape:
        from ..base import MXNetError
        raise MXNetError(
            f"mesh has no {name!r} axis for {role}: {dict(mesh.shape)}")
    return mesh.shape[name]


def make_mesh(axes: Union[Dict[str, int], Sequence[int]], names: Optional[Sequence[str]] = None,
              devices=None) -> Mesh:
    """make_mesh({'dp': 4, 'tp': 2}) or make_mesh((4, 2), ('dp', 'tp'))."""
    if isinstance(axes, dict):
        names = tuple(axes.keys())
        shape = tuple(axes.values())
    else:
        shape = tuple(axes)
        names = tuple(names or [f"axis{i}" for i in range(len(shape))])
    devices = devices if devices is not None else jax.devices()
    n = int(_np.prod(shape))
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    dev_array = _np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, names)


def local_mesh(dp: Optional[int] = None, name: str = "dp") -> Mesh:
    """1-D data-parallel mesh over all local devices."""
    devs = jax.devices()
    dp = dp or len(devs)
    return make_mesh({name: dp}, devices=devs)


def set_default_mesh(mesh: Optional[Mesh]):
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def current_mesh() -> Mesh:
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = local_mesh()
    return _DEFAULT_MESH


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, axis: str = "dp", ndim: int = 2) -> NamedSharding:
    """Batch dim sharded over `axis`, rest replicated."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def param_sharding(mesh: Mesh, spec: Optional[PartitionSpec]) -> NamedSharding:
    return NamedSharding(mesh, spec if spec is not None else P())
