"""Shared step-program plumbing for the fused trainers.

`DataParallelTrainer` and `PipelineTrainer` both follow the same executable
lifecycle: a config-fingerprinted key base names the trainer's compiled
step family, per-signature variants resolve through the PROCESS-WIDE engine
cache (so N same-config trainers share one executable instead of each
holding a private jit), the XLA cost model is captured once per variant at
build time, and every execution is booked against a roofline-ledger region
derived from the same fingerprint. `StepProgram` owns that lifecycle;
the trainers keep only their step bodies.

Key layout (docs/compilation.md "fused-step fingerprints"):

    key_base = ("dp_step" | "pp_step",
                engine.structural_fingerprint(net),
                engine.config_fingerprint(**trainer_config))
    cache key = key_base + variant        # variant = (sig,) or (sig, ...)
    region    = f"{label}#{sha1(repr((key_base, cost_key)))[:6]}"

The region digest covers the FULL compile key, so two configurations that
compile apart ledger apart, while any number of same-config trainers
aggregate into one row — the contract tests/test_roofline.py pins for dp
and tests/test_pipeline_1f1b.py pins for pp.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

from .. import engine as _engine
from .. import telemetry as _telem

__all__ = ["StepProgram"]


class StepProgram:
    """Engine-cache-backed executable family for one trainer configuration.

    label:    readable region prefix, e.g. ``dp.step[BertModel]``.
    key_base: the fingerprint tuple above; equal key_base => shared
              executables, shared cost captures, shared ledger rows.
    """

    __slots__ = ("label", "key_base", "_local", "_costs", "_regions")

    def __init__(self, label: str, key_base: Tuple):
        self.label = label
        self.key_base = key_base
        self._local: Dict[Any, Callable] = {}
        self._costs: Dict[Any, Dict[str, float]] = {}
        self._regions: Dict[Any, str] = {}

    @property
    def fingerprint(self) -> str:
        """Stable digest of the trainer configuration (network structure +
        trainer config, NOT the mesh placement of a particular run).
        Elastic snapshots record it; ``resume_or_init`` compares it to
        classify a boot as same-program "resumed" vs "resharded"."""
        return _engine.region_digest(self.key_base, "program")

    # -- executables --------------------------------------------------------
    def get(self, variant: Tuple, build: Callable[[], Callable]):
        """The compiled step for ``key_base + variant``: local memo ->
        engine.lookup -> build() + engine.insert. ``build`` returns the
        final jitted callable (donation decided by the caller); the engine
        cache owns it, so a second same-config trainer scores a cache hit
        instead of a second compile."""
        fn = self._local.get(variant)
        if fn is None:
            ck = self.key_base + variant
            fn = _engine.lookup(ck)
            if fn is None:
                fn = _engine.insert(ck, build())
            self._local[variant] = fn
        return fn

    # -- roofline regions ---------------------------------------------------
    def region(self, cost_key) -> str:
        """Ledger row key: readable label + digest of (key_base, cost_key)."""
        name = self._regions.get(cost_key)
        if name is None:
            digest = _engine.region_digest(self.key_base, cost_key)
            name = f"{self.label}#{digest}"
            self._regions[cost_key] = name
        return name

    # -- cost capture -------------------------------------------------------
    def capture_cost(self, cost_key, fn, *args, kind: str = "artifact"):
        """XLA cost_analysis/memory_analysis of ``fn`` at ``args``, captured
        ONCE per cost_key and only while telemetry is enabled (the AOT
        lower+compile shares XLA's compilation caches with the real call).
        The same compile feeds the HLO hazard audit, fingerprinted under
        this program's ledger region (engine/hlo_audit.py)."""
        if _telem._ENABLED and cost_key not in self._costs:
            self._costs[cost_key] = _engine.estimate_cost(
                fn, *args, kind=kind, region=self.region(cost_key))
        return self._costs.get(cost_key, {})

    def cost(self, cost_key) -> Dict[str, float]:
        return self._costs.get(cost_key, {})
