"""ZeRO-style cross-replica sharded weight update: fusion buckets +
reduce-scatter/all-gather collectives (arXiv:2004.13336).

The replicated data-parallel step all-reduces every gradient and runs the
full optimizer update on every replica — N identical updates over N copies
of the optimizer state. Xu et al. (arXiv:2004.13336) observed that the
update decomposes: reduce-scatter the gradients so each replica owns 1/N of
them, update only that shard (with only that shard's optimizer state), and
all-gather the updated weights back. Wire bytes stay ~the all-reduce's
(reduce-scatter + all-gather IS how XLA lowers a ring all-reduce), but the
update compute and the optimizer-state memory both shrink by ~1/N.

This module holds the pieces `DataParallelTrainer(zero_update=True)` and the
kvstore's bucketed ``pushpull`` share:

  - a **bucket planner**: parameters are greedily packed, in declaration
    order, into dtype-homogeneous flat fusion buckets capped at
    ``MXNET_TPU_BUCKET_BYTES`` so small tensors amortize collective latency
    (the reference's kvstore big-array batching, inverted);
  - **flatten / unflatten / shard** helpers used inside the traced step;
  - the **reduce-scatter** itself, optionally compressed on the wire
    (``MXNET_TPU_COMM_DTYPE``): bf16, or EQuARX-style (arXiv:2506.17615)
    chunk-scaled int8 with fp32 accumulation of the scatter result;
  - wire-byte estimators feeding telemetry's per-kind collective counters.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
from jax import lax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError, env
from .. import engine as _engine

__all__ = ["BucketSpec", "plan_buckets", "flatten_bucket", "unflatten_bucket",
           "shard_slice", "wd_vector", "reduce_scatter_bucket",
           "all_gather_bucket", "reduce_scatter_wire_bytes",
           "all_gather_wire_bytes", "per_replica_state_bytes",
           "canonical_comm_dtype", "shard_map_compat"]

env.declare("MXNET_TPU_ZERO", False, bool,
            "Default DataParallelTrainer(zero_update=...) to the ZeRO-style "
            "sharded weight update (reduce-scatter + 1/N update + all-gather)")
env.declare("MXNET_TPU_BUCKET_BYTES", 32 * 1024 * 1024, int,
            "Size cap per gradient fusion bucket in the sharded update / "
            "bucketed kvstore pushpull (bytes of the bucket dtype)")
env.declare("MXNET_TPU_COMM_DTYPE", "", str,
            "Wire dtype for the sharded-update reduce-scatter: '' (native), "
            "'bfloat16', or 'int8' (chunk-scaled, fp32 accumulation)")


def canonical_comm_dtype(dtype) -> Optional[str]:
    """Normalize a comm-dtype spec to None | 'bfloat16' | 'int8'."""
    if dtype is None:
        return None
    name = str(jnp.dtype(dtype).name) if not isinstance(dtype, str) else dtype
    name = name.strip().lower()
    if name in ("", "none", "float32", "fp32"):
        return None
    if name in ("bfloat16", "bf16"):
        return "bfloat16"
    if name == "int8":
        return "int8"
    raise MXNetError(
        f"unsupported comm dtype {dtype!r}; use 'bfloat16' or 'int8' "
        "(MXNET_TPU_COMM_DTYPE)")


@dataclass(frozen=True)
class BucketSpec:
    """One flat fusion bucket: which parameter slots it packs and where.

    ``padded_size`` is a multiple of ``ndp`` so the bucket reduce-scatters
    into ``ndp`` equal contiguous shards; the tail pad stays zero through
    the update (zero grad, zero wd — see ``wd_vector``)."""
    dtype: str
    indices: Tuple[int, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    padded_size: int
    ndp: int

    @property
    def used_size(self) -> int:
        return self.offsets[-1] + self.sizes[-1]

    @property
    def pad(self) -> int:
        return self.padded_size - self.used_size

    @property
    def shard_size(self) -> int:
        return self.padded_size // self.ndp

    @property
    def nbytes(self) -> int:
        return self.padded_size * jnp.dtype(self.dtype).itemsize


def plan_buckets(entries: Sequence[Tuple[int, Sequence[int], Any]],
                 ndp: int, bucket_bytes: int) -> Tuple[BucketSpec, ...]:
    """Pack ``(slot_index, shape, dtype)`` entries into dtype-homogeneous
    buckets, greedily in order, size-capped at ``bucket_bytes`` (a tensor
    larger than the cap gets a bucket of its own). Every bucket is padded to
    a multiple of ``ndp`` elements."""
    ndp = max(int(ndp), 1)
    groups: List[Tuple[str, List[Tuple[int, Tuple[int, ...], int]]]] = []
    by_dtype = {}
    for idx, shape, dtype in entries:
        key = str(jnp.dtype(dtype))
        if key not in by_dtype:
            by_dtype[key] = []
            groups.append((key, by_dtype[key]))
        shape = tuple(int(d) for d in shape)
        size = 1
        for d in shape:
            size *= d
        by_dtype[key].append((idx, shape, size))

    buckets: List[BucketSpec] = []

    def close(dtype, members):
        if not members:
            return
        offsets, off = [], 0
        for _, _, size in members:
            offsets.append(off)
            off += size
        padded = -(-off // ndp) * ndp
        buckets.append(BucketSpec(
            dtype=dtype,
            indices=tuple(i for i, _, _ in members),
            offsets=tuple(offsets),
            sizes=tuple(s for _, _, s in members),
            shapes=tuple(shp for _, shp, _ in members),
            padded_size=padded, ndp=ndp))

    for dtype, members in groups:
        cap = max(int(bucket_bytes) // jnp.dtype(dtype).itemsize, 1)
        cur, total = [], 0
        for idx, shape, size in members:
            if cur and total + size > cap:
                close(dtype, cur)
                cur, total = [], 0
            cur.append((idx, shape, size))
            total += size
        close(dtype, cur)
    return tuple(buckets)


def flatten_bucket(bucket: BucketSpec, arrays) -> jnp.ndarray:
    """Concatenate the bucket's slots of ``arrays`` (indexed by
    ``bucket.indices``) into one flat padded vector."""
    parts = [jnp.reshape(arrays[i], (-1,)) for i in bucket.indices]
    if bucket.pad:
        parts.append(jnp.zeros((bucket.pad,), parts[0].dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def unflatten_bucket(bucket: BucketSpec, flat):
    """Inverse of ``flatten_bucket``: yields ``(slot_index, array)`` views
    reshaped back to each parameter's shape (the pad is dropped)."""
    return [(i, jnp.reshape(flat[o:o + s], shp))
            for i, o, s, shp in zip(bucket.indices, bucket.offsets,
                                    bucket.sizes, bucket.shapes)]


def shard_slice(bucket: BucketSpec, flat, position):
    """This replica's contiguous 1/ndp shard of a flat bucket; ``position``
    is the (traced) index along the dp axis."""
    return lax.dynamic_slice_in_dim(
        flat, position * bucket.shard_size, bucket.shard_size)


def wd_vector(bucket: BucketSpec, wds) -> _np.ndarray:
    """Per-element weight-decay vector for a bucket (the flat shard spans
    parameters with different wd; the update kernels broadcast it
    elementwise). The pad region gets wd=0 so padded weights stay zero."""
    out = _np.zeros((bucket.padded_size,), _np.float32)
    for i, o, s in zip(bucket.indices, bucket.offsets, bucket.sizes):
        out[o:o + s] = float(wds[i])
    return out


# ---------------------------------------------------------------------------
# Collectives (called inside the traced step, under shard_map over dp)
# ---------------------------------------------------------------------------

def shard_map_compat(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the step
    bodies mix replicated and per-shard values by construction)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def reduce_scatter_bucket(flat, axis_name: str, ndp: int,
                          comm_dtype: Optional[str] = None):
    """Cross-replica reduce-scatter of one flat bucket: returns this
    replica's 1/ndp shard of the SUM, as float32.

    comm_dtype None: native ``lax.psum_scatter`` (XLA schedules the ring).
    'bfloat16': the wire carries bf16 chunks (half the bytes); the scatter
    is realized as all_to_all + local sum so ACCUMULATION stays fp32.
    'int8': EQuARX-style chunk-scaled quantization — each (replica, shard)
    tile ships as int8 plus one fp32 scale (max/127), and the dequantized
    tiles are summed in fp32."""
    if ndp <= 1:
        return flat.astype(jnp.float32)
    if comm_dtype is None:
        return lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                tiled=True).astype(jnp.float32)
    chunks = jnp.reshape(flat, (ndp, -1))
    if comm_dtype == "bfloat16":
        recv = lax.all_to_all(chunks.astype(jnp.bfloat16), axis_name,
                              split_axis=0, concat_axis=0, tiled=True)
        return jnp.sum(recv.astype(jnp.float32), axis=0)
    if comm_dtype == "int8":
        chunks = chunks.astype(jnp.float32)
        amax = jnp.max(jnp.abs(chunks), axis=1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, jnp.float32(1.0))
        q = jnp.clip(jnp.round(chunks / scale), -127, 127).astype(jnp.int8)
        recv = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)
        rscale = lax.all_to_all(scale, axis_name, split_axis=0,
                                concat_axis=0, tiled=True)
        return jnp.sum(recv.astype(jnp.float32) * rscale, axis=0)
    raise MXNetError(f"unsupported comm dtype {comm_dtype!r}")


def all_gather_bucket(shard, axis_name: str):
    """Gather every replica's updated shard back into the full flat bucket
    (XLA overlaps this with the next forward when it can)."""
    return lax.all_gather(shard, axis_name, axis=0, tiled=True)


# ---------------------------------------------------------------------------
# Accounting (telemetry estimates; ring schedule, like _grad_allreduce_bytes)
# ---------------------------------------------------------------------------

def reduce_scatter_wire_bytes(buckets, ndp: int,
                              comm_dtype: Optional[str] = None) -> int:
    """Per-step wire bytes of the bucket reduce-scatters: each replica
    sends (n-1)/n of every bucket once (plus the int8 path's scales)."""
    if ndp <= 1:
        return 0
    total = 0
    for b in buckets:
        itemsize = jnp.dtype(comm_dtype or b.dtype).itemsize
        nbytes = b.padded_size * itemsize
        if comm_dtype == "int8":
            nbytes += b.ndp * 4  # one fp32 scale per (replica, shard) tile
        total += nbytes * (ndp - 1) // ndp
    return total


def all_gather_wire_bytes(buckets, ndp: int) -> int:
    """Per-step wire bytes of gathering the updated shards (always the
    weight dtype — quantizing the weights themselves would bias training)."""
    if ndp <= 1:
        return 0
    return sum(b.padded_size * jnp.dtype(b.dtype).itemsize * (ndp - 1) // ndp
               for b in buckets)


def per_replica_state_bytes(tree) -> int:
    """Bytes of optimizer state ONE replica actually holds: dp-sharded
    leaves count their local shard only, replicated leaves their full size
    (feeds the mx_optimizer_state_per_replica_bytes gauge)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            try:
                shape = sharding.shard_shape(tuple(shape))
            except Exception:
                pass
        elems = 1
        for d in shape:
            elems *= int(d)
        total += elems * jnp.dtype(dtype).itemsize
    return total


# ---------------------------------------------------------------------------
# Eager sharded-update kernels (kvstore / host-driven paths)
# ---------------------------------------------------------------------------

def _sharded_update_kernel(*donate):
    """``optimizer._update_kernel``'s analog for flat fusion buckets: jit
    the kernel donating the given argnums, so a reduce-scattered bucket
    (and any optimizer-state shard riding with it) aliases its output in
    place. mxlint's donation-safety pass knows this decorator — reading a
    donated bucket, or any view sliced out of it, after the call is
    flagged."""
    def wrap(fn):
        cache = {"jit": None}

        @functools.wraps(fn)
        def call(*args):
            if cache["jit"] is None:
                donating = bool(donate) and _engine.donation_enabled()
                cache["jit"] = jax.jit(
                    fn, donate_argnums=donate if donating else ())
            return cache["jit"](*args)
        call.__wrapped__ = fn
        return call
    return wrap


@_sharded_update_kernel(0)
def _k_bucket_reduce(stacked):
    """Sum a (contributors, bucket_size) stack of bucket gradients in fp32 —
    one fused XLA reduction for a whole bucket; the stack is dead afterwards
    and is donated."""
    return jnp.sum(stacked.astype(jnp.float32), axis=0)
