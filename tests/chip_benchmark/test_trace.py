"""The reduction from a profiler trace to numbers, on small traces kept as
fixtures: one written by hand in XSpace text form, whose answers are known,
and one recorded on the v5e (a toy BERT, cut to its first steps)."""
import os
import types

import pytest

import trace as T

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
DEV = "/device:TPU:0"


def _load(name):
    from jax.profiler import ProfileData
    with open(os.path.join(FIXTURES, name)) as f:
        return T.load(ProfileData.from_text_proto(f.read()))


@pytest.fixture(scope="module")
def by_hand():
    return _load("trace_by_hand.txt")


def test_names_and_categories_come_from_the_hlo_text(by_hand):
    ops = by_hand["devices"][DEV]["ops"]
    assert [(n, c) for n, c, _, _ in ops[:5]] == [
        ("fusion.1", "fusion:kOutput"), ("fusion.2", "fusion:kLoop"),
        ("custom-call.3", "custom-call:tpu_custom_call"),
        ("all-reduce.4", "all-reduce"), ("copy.5", "copy")]
    assert [m[0] for m in by_hand["devices"][DEV]["modules"]] \
        == ["jit_step(123)"] * 2
    # only the benchmark's own host spans are kept
    assert [h[0] for h in by_hand["host"]] == [
        "bench.feed_next", "bench.trainer_step", "bench.stamp"]


def test_busy_union_and_idle_share(by_hand):
    d = T.reduce(by_hand)[DEV]
    # two programs of 100 us, 20 us apart; all-reduce and copy overlap 10 us
    assert d["window_ns"] == pytest.approx(220_000)
    assert d["busy_ns"] == pytest.approx(200_000)
    assert d["op_ns"] == pytest.approx(220_000)
    assert d["programs"] == 2
    assert [(round(s), round(e)) for s, e in d["gaps"]] == [(101_000, 121_000)]
    assert 100 * (1 - d["busy_ns"] / d["window_ns"]) == pytest.approx(9.0909,
                                                                      abs=1e-3)


def test_category_grouping(by_hand):
    d = T.reduce(by_hand)[DEV]
    assert d["by_category"] == pytest.approx({
        "fusion:kOutput": 80_000, "fusion:kLoop": 40_000,
        "custom-call:tpu_custom_call": 20_000, "all-reduce": 40_000,
        "copy": 40_000})
    assert T.group_ns(by_hand, DEV, ["fusion:kOutput", "convolution"]) \
        == pytest.approx(80_000)
    assert T.group_ns(by_hand, DEV, (), ["custom-call"]) \
        == pytest.approx(20_000)
    assert T.fullest(T.reduce(by_hand)) == DEV


def test_exposed_time_of_a_collective(by_hand):
    # each all-reduce runs 20 us, 10 of them under the copy
    assert T.exposed_ns(by_hand, DEV, ["all-reduce"]) == pytest.approx(20_000)


def test_breakdown_names_ops_and_host_spans(by_hand):
    b = T.breakdown(by_hand, T.reduce(by_hand))
    assert b["device_ops"][0] == ["fusion.1 [fusion:kOutput]",
                                  pytest.approx(80e-6)]
    assert len(b["device_ops"]) == 5
    # the one idle gap lies under the host's trainer_step span
    assert b["idle_gaps"] == [["bench.trainer_step", pytest.approx(20e-6)]]


def test_readers_on_the_trace(by_hand):
    """The per-layer readers of the device trace, driven by the parameters
    their layer_metrics files give, on the hand-written trace."""
    import cells
    reduced = T.reduce(by_hand)
    cell = cells.Cell("bert_base_train_t512")
    say = []
    view = types.SimpleNamespace(
        cell=cell, peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        window=None, traced={"steps": 2, "seconds": 220e-6}, loaded=by_hand,
        devices=reduced, chips=1, flops=cell.module("flops"),
        memory_peak_bytes=9e9, say=say.append)

    def read(metric):
        spec = cell.layer_metric(metric)
        return cells.load_module("readers", spec["reader"]).read(
            view, spec["params"])

    assert read("device_idle_share") == pytest.approx(9.0909, abs=1e-3)
    # everything but the kOutput fusions and the kernel: 120 of 220 us
    assert read("non_mxu_time_share") == pytest.approx(100 * 120 / 220)
    assert read("peak_hbm_gb") == 9.0
    flops = cell.module("flops")
    per_step = flops.mxu_flops_per_item(cell.config, cell.traffic,
                                        exclude_attention=True) * 16384
    assert read("mxu_roofline") == pytest.approx(
        100 * (per_step / 197e12) / 40e-6)
    spec = cells.load_json(os.path.join(cells.HERE, "layer_metrics",
                                        "flash_time_share.json"))
    share = cells.load_module("readers", spec["reader"]).read(
        view, spec["params"])
    assert share == pytest.approx(100 * 20 / 220)
    spec = cells.load_json(os.path.join(cells.HERE, "layer_metrics",
                                        "flash_roofline.json"))
    ops, nbytes = flops.attention_kernel_work(cell.config, cell.traffic)
    roof = cells.load_module("readers", spec["reader"]).read(
        view, spec["params"])
    assert roof == pytest.approx(
        100 * max(ops / 197e12, nbytes / 819e9) / 10e-6)
    assert "bound by operations" in say[0]
    spec = cells.load_json(os.path.join(cells.HERE, "layer_metrics",
                                        "collective_exposed_share.json"))
    assert cells.load_module("readers", spec["reader"]).read(
        view, spec["params"]) == pytest.approx(100 * 20 / 220)


def test_reader_with_nothing_to_read_returns_nothing(by_hand):
    import cells
    view = types.SimpleNamespace(devices=None, traced=None, peaks=None,
                                 loaded=None)
    for reader in ("device_idle", "time_share", "mxu_roofline",
                   "kernel_roofline", "exposed_share", "step_mfu"):
        mod = cells.load_module("readers", reader)
        assert mod.read(view, {"name_has": ["x"], "work": "w"}) is None
    # a trace without the kernel: no share of a roofline is ever 0
    reduced = T.reduce(by_hand)
    view = types.SimpleNamespace(devices=reduced, loaded=by_hand, traced={"steps": 2},
                    peaks={"flops_per_s": 1.0, "bytes_per_s": 1.0}, cell=None)
    assert cells.load_module("readers", "time_share").read(
        view, {"categories": ["custom-call:absent"]}) is None
    assert cells.load_module("readers", "kernel_roofline").read(
        view, {"categories": ["custom-call:absent"], "work": "w"}) is None


# -- a trace recorded on the v5e (PR 24): the toy BERT of toy.py, one run of
# run.py --trace 1, cut to its first three steps by keeping the events of the
# device's `XLA Modules` and `XLA Ops` lines and the benchmark's host spans --

@pytest.fixture(scope="module")
def recorded():
    return _load("trace_v5e_toy_bert.txt")


def test_recorded_trace_loads_as_the_reduction_expects(recorded):
    dev = recorded["devices"][DEV]
    programs = [m[0].split("(")[0] for m in dev["modules"]]
    assert programs.count("jit_step") == 3
    cats = {c for _, c, _, _ in dev["ops"]}
    assert {"fusion:kOutput", "fusion:kLoop", "copy"} <= cats
    assert "" not in cats                       # every event has a category
    assert all(" = " not in n and not n.startswith("%")
               for n, _, _, _ in dev["ops"])
    assert {h[0] for h in recorded["host"]} == {
        "bench.feed_next", "bench.trainer_step", "bench.stamp"}


def test_recorded_trace_busy_and_idle(recorded):
    d = T.reduce(recorded)[DEV]
    ops = recorded["devices"][DEV]["ops"]
    # events of `XLA Ops` neither nest nor overlap: the union is the sum
    assert d["busy_ns"] == pytest.approx(sum(o[3] for o in ops), rel=1e-6)
    assert 0 < d["busy_ns"] < d["window_ns"]
    assert sum(d["by_category"].values()) == pytest.approx(d["op_ns"])
    assert sum(e - s for s, e in d["gaps"]) == pytest.approx(
        d["window_ns"] - d["busy_ns"], rel=1e-6)
    # a toy step is 60 us of device work under 4.7 ms of host dispatch
    idle = 100 * (1 - d["busy_ns"] / d["window_ns"])
    assert 98 < idle < 99.5
    b = T.breakdown(recorded, T.reduce(recorded))
    assert b["idle_gaps"][0][0] == "bench.trainer_step"
    assert b["idle_gaps"][0][1] == pytest.approx(
        (d["window_ns"] - d["busy_ns"]) / 1e9, rel=0.02)
