"""tools/bench_regress.py: the bench-round regression gate (ISSUE 17).

Synthetic BENCH_r*.json rounds in a tmpdir drive the gate end to end:
direction inference (throughput drops vs overhead rises), the noise
threshold, unusable-round filtering (nonzero rc / empty parsed), and the
exit-code contract (1 on regression, 0 clean or under-populated).
"""
import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location(
    "bench_regress", REPO / "tools" / "bench_regress.py")
br = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(br)


def _round(d, n, parsed, rc=0):
    (Path(d) / f"BENCH_r{n:02d}.json").write_text(json.dumps(
        {"n": n, "cmd": "python bench.py", "rc": rc, "tail": "",
         "parsed": parsed}))


def _parsed(img_s, overhead_pct=1.0, neutral=7.0):
    return {"metric": "resnet50_train_throughput_bs32", "value": img_s,
            "unit": "img/s", "vs_baseline": 1.0,
            "extra": {"tracing": {"train_overhead_pct": overhead_pct,
                                  "pass_2pct": True},
                      "misc": {"some_setting": neutral}}}


def test_direction_inference():
    assert br._direction("img_s") == 1
    assert br._direction("tokens_s") == 1
    assert br._direction("value", unit="img/s") == 1
    assert br._direction("train_overhead_pct") == -1
    assert br._direction("step_seconds") == -1
    assert br._direction("p99_ms") == -1
    assert br._direction("feed_stall") == -1
    assert br._direction("some_setting") == 0


def test_throughput_drop_flags_regression(tmp_path):
    _round(tmp_path, 1, _parsed(2000.0))
    _round(tmp_path, 2, _parsed(1500.0))  # -25% img/s
    rc = br.main(["--dir", str(tmp_path)])
    assert rc == 1
    (_, old), (_, new) = br.load_rounds(tmp_path)[-2:]
    regs, _, _ = br.compare(old, new, 10.0)
    assert any(r["key"] == "value" for r in regs)


def test_overhead_rise_flags_regression(tmp_path):
    _round(tmp_path, 1, _parsed(2000.0, overhead_pct=1.0))
    _round(tmp_path, 2, _parsed(2000.0, overhead_pct=1.5))  # +50%
    assert br.main(["--dir", str(tmp_path)]) == 1


def test_improvement_and_noise_pass(tmp_path):
    _round(tmp_path, 1, _parsed(2000.0, overhead_pct=1.0))
    # +20% throughput (improvement), -10% overhead (improvement),
    # neutral key moved (informational only)
    _round(tmp_path, 2, _parsed(2400.0, overhead_pct=0.9, neutral=70.0))
    assert br.main(["--dir", str(tmp_path)]) == 0
    # movement inside the threshold never flags
    _round(tmp_path, 3, _parsed(2300.0, overhead_pct=0.95))
    assert br.main(["--dir", str(tmp_path)]) == 0


def test_unusable_rounds_are_skipped(tmp_path):
    _round(tmp_path, 1, _parsed(2000.0))
    _round(tmp_path, 2, _parsed(100.0), rc=1)       # failed run: ignored
    _round(tmp_path, 3, {})                          # empty parsed: ignored
    (tmp_path / "BENCH_r04.json").write_text("{not json")
    assert len(br.load_rounds(tmp_path)) == 1
    assert br.main(["--dir", str(tmp_path)]) == 0   # <2 usable: no gate


def test_compares_newest_two_not_oldest(tmp_path):
    _round(tmp_path, 1, _parsed(4000.0))  # old regression, already gated
    _round(tmp_path, 2, _parsed(2000.0))
    _round(tmp_path, 3, _parsed(2050.0))  # newest pair is clean
    assert br.main(["--dir", str(tmp_path)]) == 0


def test_json_output_schema(tmp_path, capsys):
    _round(tmp_path, 1, _parsed(2000.0))
    _round(tmp_path, 2, _parsed(1000.0))
    assert br.main(["--dir", str(tmp_path), "--json"]) == 1
    d = json.loads(capsys.readouterr().out)
    assert d["old_round"] == 1 and d["new_round"] == 2
    assert d["regressions"] and d["regressions"][0]["delta_pct"] == -50.0


def test_no_rounds_is_nothing_to_compare(tmp_path, capsys):
    """The repo root holds no BENCH_r*.json (the old records were withdrawn
    in PR 21): a plain message and exit 0, not a traceback."""
    assert br.main(["--dir", str(tmp_path)]) == 0
    assert "nothing to compare" in capsys.readouterr().out


def test_missing_dir_is_usage_error(tmp_path):
    assert br.main(["--dir", str(tmp_path / "nope")]) == 2


def test_cli_runs_against_repo_root():
    """The default invocation must work on the real repo (whatever rounds
    the driver has written) without crashing; exit 0 or 1 are both legal
    outcomes, 2 is not."""
    import subprocess
    p = subprocess.run(
        [sys.executable, str(REPO / "tools" / "bench_regress.py")],
        capture_output=True, text=True)
    assert p.returncode in (0, 1), p.stderr
