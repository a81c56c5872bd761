"""TPU compiler flag helper for comm/compute overlap (async collectives +
the latency-hiding scheduler).

The overlapped step (parallel/overlap.py) arranges the HLO so each fusion
bucket's collective is issuable while later backward segments still
compute; whether the DMA actually hides under the dots is the compiler
scheduler's call. On TPU that scheduler sits behind libtpu flags which are
read ONCE, when the backend initializes — setting them after the first jax
call is a silent no-op. ``ensure_overlap_flags()`` appends the missing
flags to ``LIBTPU_INIT_ARGS`` when called early enough and warns (once per
process) when it is already too late; `DataParallelTrainer(
overlap_grads=True)` calls it at construction.

The flags go to ``LIBTPU_INIT_ARGS`` and never to ``XLA_FLAGS``: jaxlib's
own ``XLA_FLAGS`` parser does not know the ``--xla_tpu_*`` spellings and
aborts the process on them (``parse_flags_from_env.cc: Unknown flag``),
while libtpu parses ``LIBTPU_INIT_ARGS`` itself and nothing reads that
variable on a CPU-only process.

Env knobs:
  - ``LIBTPU_INIT_ARGS``: flags already present (by flag name) are never
    overridden — operator settings win;
  - ``MXNET_TPU_OVERLAP_XLA_FLAGS``: 'off' disables the helper entirely;
    otherwise a space-separated flag list REPLACING the built-in set (you
    own the spelling).
"""
from __future__ import annotations

import os
import warnings
from typing import Tuple

from ..base import env

__all__ = ["OVERLAP_XLA_FLAGS", "overlap_flags", "backend_initialized",
           "ensure_overlap_flags"]

# Async collectives give each DMA its own start/done pair instead of one
# blocking instruction; the latency-hiding scheduler then moves unrelated
# compute between start and done. The spellings cover all-reduce /
# reduce-scatter fusion plus the gather-back.
OVERLAP_XLA_FLAGS: Tuple[str, ...] = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
)

env.declare("MXNET_TPU_OVERLAP_XLA_FLAGS", "", str,
            "Override for ensure_overlap_flags: 'off' disables the helper, "
            "any other non-empty value is a space-separated libtpu flag "
            "list used instead of the built-in async-collective set")

_WARNED = [False]


def overlap_flags() -> Tuple[str, ...]:
    """The flag set ensure_overlap_flags applies, after the env override."""
    override = str(env.get("MXNET_TPU_OVERLAP_XLA_FLAGS")).strip()
    if override.lower() == "off":
        return ()
    if override:
        return tuple(override.split())
    return OVERLAP_XLA_FLAGS


def backend_initialized() -> bool:
    """Whether jax already initialized a backend (libtpu flags frozen)."""
    from jax._src import xla_bridge as _xb
    return bool(getattr(_xb, "_backends", None))


def ensure_overlap_flags(warn: bool = True) -> bool:
    """Append the missing overlap flags to ``LIBTPU_INIT_ARGS`` if the
    backend has not initialized yet. Returns True when every flag is (now)
    in effect; False when the helper was disabled or came too late — in the
    late case a UserWarning fires once per process (suppress with
    warn=False)."""
    flags = overlap_flags()
    if not flags:
        return False
    have = os.environ.get("LIBTPU_INIT_ARGS", "")
    present = {f.split("=", 1)[0] for f in have.split()}
    missing = [f for f in flags if f.split("=", 1)[0] not in present]
    if not missing:
        return True
    if backend_initialized():
        if warn and not _WARNED[0]:
            _WARNED[0] = True
            warnings.warn(
                "ensure_overlap_flags: the XLA backend is already "
                "initialized, so the async-collective / latency-hiding "
                "scheduler flags cannot take effect this process. Set "
                "LIBTPU_INIT_ARGS before launch or call "
                "ensure_overlap_flags() before the first jax operation "
                "(docs/data_parallel.md, 'Overlapping gradient "
                "communication').",
                UserWarning, stacklevel=2)
        return False
    os.environ["LIBTPU_INIT_ARGS"] = (have + " " + " ".join(missing)).strip()
    return True
