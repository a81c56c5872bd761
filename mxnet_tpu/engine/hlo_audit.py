"""Compiled-HLO hazard audit: what did XLA *actually* build? (mxcheck)

The AST passes (tools/mxlint/passes/collective_order.py, partition_spec.py)
prove properties of the python we wrote; this module audits the optimized
HLO the compiler produced — hazards no source-level analysis can see:

  host_transfer   infeed/outfeed/host callbacks in a step artifact: every
                  execution stalls the TPU on the host roundtrip (the
                  host-sync lint rule's compiled-program twin)
  f64             f64 ops in a framework whose numerics are f32/bf16 —
                  almost always an accidental promotion (python float,
                  np.float64 constant) silently doubling bytes + flops
  no_alias        donation that produced zero input/output aliases — the
                  donated buffers were copied, not reused

Hooked into ``engine.estimate_cost`` (the once-per-artifact AOT
lower+compile already captured for the roofline ledger), so every fused DP
step, 1F1B pipeline tick, and serving artifact gets a **hazard
fingerprint**: counts per hazard + the collective mix, persisted as JSON
next to the persistent compilation cache (``MXNET_TPU_HLO_AUDIT_DIR``,
default ``hlo_audit/`` under ``jax.config.jax_compilation_cache_dir``) and
diffed by the ``tools/hlo_audit_gate.py`` CI gate — a refactor that silently
regresses
fusion/overlap/donation fails tier-1 instead of a bench round three PRs
later. Telemetry: ``mx_hlo_hazards_total{kind,region}`` (kind = hazard
vocabulary above) on /statusz and Prometheus.
"""
from __future__ import annotations

import json
import os
import re
import threading
from typing import Any, Dict, List, Optional

__all__ = ["audit_text", "audit_compiled", "fingerprints", "audit_dir",
           "reset", "HAZARD_KINDS"]

HAZARD_KINDS = ("host_transfer", "f64", "no_alias")

# -- HLO text patterns -------------------------------------------------------
# host boundary crossings: infeed/outfeed ops, is_host_transfer sends/recvs,
# and the cpu-callback custom-calls jax lowers io_callback/pure_callback/
# debug.print to (the planted-regression lane in tests/test_mxcheck.py uses
# exactly that lowering)
_HOST_RE = re.compile(
    r"\b(?:infeed|outfeed)\b"
    r"|is_host_transfer=true"
    r"|custom_call_target=\"(?:xla_python_cpu_callback"
    r"|xla_ffi_python_cpu_callback|xla_python_gpu_callback"
    r"|MoveToHost|MoveFromHost)\"")
_F64_RE = re.compile(r"\bf64\[")
# collective ops: plain form = synchronous (compute waits); ``-start`` =
# async (latency-hiding pair). ``-done`` is the join of a start and is not
# counted separately.
_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")
_ALIAS_RE = re.compile(r"\b(?:may|must)-alias\b")
# Pallas kernels compiled by Mosaic: the one observable trace, in the
# optimized module, that a kernel did NOT take its jnp/lax.scan fallback
_MOSAIC_RE = re.compile(r"custom_call_target=\"tpu_custom_call\"")
_DONATED_RE = re.compile(r"\bdonated\b")

_LOCK = threading.Lock()
_FINGERPRINTS: Dict[str, Dict[str, Any]] = {}


def audit_dir() -> Optional[str]:
    """Where fingerprints persist: MXNET_TPU_HLO_AUDIT_DIR, else an
    ``hlo_audit/`` subdir of the persistent compilation cache, else None
    (in-memory only)."""
    d = os.environ.get("MXNET_TPU_HLO_AUDIT_DIR")
    if d:
        return d
    import jax
    cache = jax.config.jax_compilation_cache_dir
    if cache:
        return os.path.join(cache, "hlo_audit")
    return None


def audit_text(hlo_text: str, *, kind: str = "artifact",
               region: str = "",
               donation_expected: bool = False) -> Dict[str, Any]:
    """Scan one optimized-HLO module; return its hazard fingerprint.
    Pure text analysis — no jax import, no device."""
    host = len(_HOST_RE.findall(hlo_text))
    f64 = len(_F64_RE.findall(hlo_text))
    sync = 0
    async_ = 0
    mix: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        op, suffix = m.group(1), m.group(2)
        if suffix == "-done":
            continue
        key = op + (suffix or "")
        mix[key] = mix.get(key, 0) + 1
        if suffix == "-start":
            async_ += 1
        else:
            sync += 1
    alias = len(_ALIAS_RE.findall(hlo_text))
    donated = len(_DONATED_RE.findall(hlo_text))

    hazards: List[Dict[str, Any]] = []
    if host:
        hazards.append({"kind": "host_transfer", "count": host})
    if f64:
        hazards.append({"kind": "f64", "count": f64})
    if donation_expected and donated and not alias:
        hazards.append({"kind": "no_alias", "count": donated})

    label = region.split("#", 1)[0] if region else kind
    return {
        "version": 1,
        "region": region or kind,
        "label": label,
        "kind": kind,
        "counts": {
            "host_transfers": host,
            "f64_ops": f64,
            "collectives_sync": sync,
            "collectives_async": async_,
            "alias_pairs": alias,
            "donated_params": donated,
            "mosaic_kernels": len(_MOSAIC_RE.findall(hlo_text)),
        },
        "collectives": mix,
        "hazards": hazards,
    }


def audit_compiled(compiled, *, kind: str = "artifact", region: str = "",
                   donation_expected: bool = False) -> Optional[Dict[str, Any]]:
    """Audit a jax ``Compiled`` object (post-optimization HLO), record the
    fingerprint (memory + telemetry + on-disk). Best-effort: backends that
    cannot render HLO text return None instead of raising into the
    artifact build."""
    try:
        text = compiled.as_text()
    except Exception:
        return None
    if not text:
        return None
    fp = audit_text(text, kind=kind, region=region,
                    donation_expected=donation_expected)
    _record(fp)
    return fp


def _record(fp: Dict[str, Any]):
    with _LOCK:
        _FINGERPRINTS[fp["region"]] = fp
    from .. import telemetry as _telem
    if _telem._ENABLED:
        c = _telem.counter(
            "mx_hlo_hazards_total",
            "Hazards the compiled-HLO audit found in built artifacts "
            "(host transfers, f64 ops, non-aliasing donation)", ("kind", "region"))
        for h in fp["hazards"]:
            c.labels(h["kind"], fp["label"]).inc(h["count"])
    d = audit_dir()
    if d:
        try:
            os.makedirs(d, exist_ok=True)
            slug = re.sub(r"[^\w.\-]+", "_", fp["region"])[:100]
            path = os.path.join(d, f"{slug}.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(fp, f, indent=2, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass  # audit persistence must never fail an artifact build


def fingerprints() -> Dict[str, Dict[str, Any]]:
    """Snapshot of every fingerprint captured in this process (tests and
    /statusz read this; the CI gate reads the on-disk copies)."""
    with _LOCK:
        return {k: dict(v) for k, v in sorted(_FINGERPRINTS.items())}


def reset():
    with _LOCK:
        _FINGERPRINTS.clear()
