"""Every cell's files exist and keep to the contract's names; every metric a
cell reports per layer moves an end-to-end metric that the cell reports."""
import json
import os
import re

import pytest

import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(cells.REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


def _metric(name):
    return next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                if m["name"] == name)


def _cells_of(metric):
    return metric.get("workloads", WORKLOADS)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files(workload):
    cell = cells.Cell(workload)
    entry = cell.entry
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key]), entry[key]
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert cell.traffic["chips"] == entry["chips"] in (1, 4)
    assert cell.traffic["items"] in ("tokens", "images")
    assert cell.items_per_step() > 0
    for kind in ("programs", "reference", "flops"):
        assert cell.module(kind) is not None
    assert cell.module("reference").ITEMS == cell.traffic["items"]
    # the limits `correct` is held to: an entry for every number compared,
    # null where the cell has no upper reading for it (PERF.md section 4)
    want = {"loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
            "change_norm_gap", "grad_total_gap", "change_total_gap",
            "feed_mismatch"}
    assert set(cell.limits) == want
    assert cell.limits["feed_mismatch"] == 0
    held = [v for v in cell.limits.values() if v is not None]
    assert len(held) >= 4 and all(v >= 0 for v in held)
    assert any(cell.limits[k] is not None for k in ("loss1_gap", "loss2_gap",
                                                     "loss3_gap"))
    assert any(cell.limits[k] is not None for k in want if "grad" in k)
    assert any(cell.limits[k] is not None for k in want if "change" in k)
    config = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert os.path.exists(os.path.join(cells.REPO, config["file"]))
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   for k in config["reduced"])


@pytest.mark.parametrize("name", METRICS)
def test_names_and_units(name):
    m = _metric(name)
    assert NAME.match(name) and UNIT.match(m["unit"]), (name, m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for w in m.get("workloads", []):
        assert w in WORKLOADS


@pytest.mark.parametrize("name", PER_LAYER)
def test_layer_metric_moves_what_its_cells_report(name):
    m = _metric(name)
    moved = _metric(m["moves"])
    assert moved in BENCH["end_to_end"]
    for w in _cells_of(m):
        assert w in _cells_of(moved), (name, w, m["moves"])


@pytest.mark.parametrize("name", PER_LAYER)
def test_layer_metric_file(name):
    """layer_metrics/<name>.json says what BENCHMARK.json says, and names a
    reader that exists."""
    m = _metric(name)
    spec = cells.load_json(os.path.join(cells.HERE, "layer_metrics",
                                        name + ".json"))
    for key, value in m.items():
        assert spec[key] == value, (name, key)
    assert 1 <= len(m["layer"]) <= 200
    reader = cells.load_module("readers", spec["reader"])
    assert callable(reader.read)


def test_every_cell_reports_enough():
    for w in WORKLOADS:
        e2e = [m["name"] for m in BENCH["end_to_end"] if w in _cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w in _cells_of(m) for m in BENCH["per_layer"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(WORKLOADS) // 4)


def test_command_and_paths():
    assert BENCH["command"] == ["python3", "benchmark/chip/run.py"]
    assert set(BENCH["paths"]) == {"benchmark/chip", "tests/chip_benchmark"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] == "host_clock"


def test_peaks_table():
    v5e = cells.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("TPU v99")
