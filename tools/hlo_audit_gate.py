"""CI gate over compiled-HLO hazard fingerprints (mxcheck, ISSUE 18).

``mxnet_tpu/engine/hlo_audit.py`` persists one JSON fingerprint per
compiled artifact region (host-transfer/f64/collective/alias counts) next
to the compilation cache. This gate diffs those fingerprints against a
checked-in baseline so a refactor that silently regresses what XLA builds
— a host callback sneaking into a step body, f64 promotion, collectives
losing their async overlap, donation that stopped aliasing — fails tier-1
instead of a bench round later.

Matching is by LABEL (the readable region prefix before ``#``): the digest
half of a region covers the full compile fingerprint and legitimately
changes with configuration, while the label names the artifact family the
baseline constrains.

Regression predicates per label present in both sides:
  host_transfers    increased
  f64_ops           increased
  collectives_sync  increased while collectives_async did not
  alias_pairs       decreased
New labels FAIL only if they carry hazards (the shipped default baseline
is empty = "no artifact ships with hazards"); labels missing from the
current run are reported but pass (CI shards build artifact subsets).

Usage:
  python -m tools.hlo_audit_gate [--audit-dir DIR] [--baseline FILE]
                                 [--write-baseline] [--format text|json]
Exit codes: 0 clean, 1 regression, 2 usage/IO error.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional

REPO_ROOT = Path(__file__).resolve().parent.parent
DEFAULT_BASELINE = Path(__file__).resolve().parent / "hlo_audit_baseline.json"

_COUNT_KEYS = ("host_transfers", "f64_ops", "collectives_sync",
               "collectives_async", "alias_pairs", "donated_params")


def load_fingerprints(audit_dir: Path) -> Dict[str, dict]:
    """label -> fingerprint (latest wins per label; regions of one label
    differ only in config digest)."""
    out: Dict[str, dict] = {}
    if not audit_dir.is_dir():
        return out
    for p in sorted(audit_dir.glob("*.json")):
        try:
            fp = json.loads(p.read_text())
        except (OSError, ValueError):
            continue
        label = fp.get("label") or str(fp.get("region", "")).split("#", 1)[0]
        if label:
            out[label] = fp
    return out


def load_baseline(path: Path) -> Dict[str, dict]:
    if not path.exists():
        return {}
    data = json.loads(path.read_text())
    return dict(data.get("regions", {}))


def write_baseline(path: Path, fps: Dict[str, dict]):
    payload = {
        "version": 1,
        "comment": "Per-label HLO hazard counts tier-1 holds the line on. "
                   "Regenerate: python -m tools.hlo_audit_gate "
                   "--write-baseline",
        "regions": {
            label: {"counts": {k: int(fp.get("counts", {}).get(k, 0))
                               for k in _COUNT_KEYS}}
            for label, fp in sorted(fps.items())},
    }
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")


def diff(fps: Dict[str, dict], baseline: Dict[str, dict]):
    """-> (regressions, notes): regressions are gate failures, notes are
    informational (new hazard-free labels, labels not rebuilt this run)."""
    regressions: List[str] = []
    notes: List[str] = []
    for label, fp in sorted(fps.items()):
        cur = {k: int(fp.get("counts", {}).get(k, 0)) for k in _COUNT_KEYS}
        base_ent = baseline.get(label)
        if base_ent is None:
            hazards = fp.get("hazards", [])
            if hazards:
                kinds = ", ".join(f"{h['kind']}x{h['count']}"
                                  for h in hazards)
                regressions.append(
                    f"{label}: new artifact carries hazards ({kinds}) and "
                    f"is not in the baseline")
            else:
                notes.append(f"{label}: new hazard-free artifact "
                             f"(--write-baseline to track)")
            continue
        base = {k: int(base_ent.get("counts", {}).get(k, 0))
                for k in _COUNT_KEYS}
        if cur["host_transfers"] > base["host_transfers"]:
            regressions.append(
                f"{label}: host transfers {base['host_transfers']} -> "
                f"{cur['host_transfers']} (a step artifact now stalls on "
                f"the host every execution)")
        if cur["f64_ops"] > base["f64_ops"]:
            regressions.append(
                f"{label}: f64 ops {base['f64_ops']} -> {cur['f64_ops']} "
                f"(accidental double-precision promotion)")
        if cur["collectives_sync"] > base["collectives_sync"] \
                and cur["collectives_async"] <= base["collectives_async"]:
            regressions.append(
                f"{label}: sync collectives {base['collectives_sync']} -> "
                f"{cur['collectives_sync']} with no new async pairs "
                f"(overlap regressed; compute now waits on the wire)")
        if cur["alias_pairs"] < base["alias_pairs"]:
            regressions.append(
                f"{label}: input/output aliases {base['alias_pairs']} -> "
                f"{cur['alias_pairs']} (donation stopped aliasing; donated "
                f"buffers are being copied)")
    for label in sorted(set(baseline) - set(fps)):
        notes.append(f"{label}: in baseline but not built this run")
    return regressions, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.hlo_audit_gate",
        description="diff compiled-HLO hazard fingerprints vs baseline")
    ap.add_argument("--audit-dir", default=None,
                    help="fingerprint dir (default: engine.hlo_audit."
                         "audit_dir() from the environment)")
    ap.add_argument("--baseline", default=str(DEFAULT_BASELINE))
    ap.add_argument("--write-baseline", action="store_true")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    d: Optional[str] = args.audit_dir
    if d is None:
        sys.path.insert(0, str(REPO_ROOT))
        from mxnet_tpu.engine import hlo_audit
        d = hlo_audit.audit_dir()
    if not d:
        print("hlo_audit_gate: no audit dir (set MXNET_TPU_HLO_AUDIT_DIR "
              "or JAX_COMPILATION_CACHE_DIR)", file=sys.stderr)
        return 2
    fps = load_fingerprints(Path(d))

    baseline_path = Path(args.baseline)
    if args.write_baseline:
        write_baseline(baseline_path, fps)
        print(f"hlo_audit_gate: wrote {len(fps)} label(s) to "
              f"{baseline_path}")
        return 0

    try:
        baseline = load_baseline(baseline_path)
    except ValueError as e:
        print(f"hlo_audit_gate: bad baseline {baseline_path}: {e}",
              file=sys.stderr)
        return 2
    regressions, notes = diff(fps, baseline)

    if args.format == "json":
        print(json.dumps({"regressions": regressions, "notes": notes,
                          "labels": sorted(fps)}, indent=2))
    else:
        for r in regressions:
            print(f"REGRESSION {r}")
        for n in notes:
            print(f"note: {n}")
        print(f"hlo_audit_gate: {len(fps)} label(s), "
              f"{len(regressions)} regression(s)")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
