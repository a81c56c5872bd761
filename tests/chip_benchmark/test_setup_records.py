"""The `setup_records` reader and the six per-layer metrics that are its data
(`setup_net_init_s`, `setup_place_s`, `setup_step_trace_s`,
`setup_step_load_s`, `setup_small_builds_s`, `setup_small_builds`): on
hand-made records, against a program from before the kinds and one that lost
its records, and in a traced toy run."""
import threading
import types

import numpy as np
import pytest

import cells
import run
from mxnet_tpu.telemetry import tracing

METRICS = ("setup_net_init_s", "setup_place_s", "setup_step_trace_s",
           "setup_step_load_s", "setup_small_builds_s", "setup_small_builds")
BUILD_METRICS = METRICS[2:]
READER = cells.load_module("readers", "setup_records")
TID = threading.get_ident()


def _params(metric):
    spec = cells.load_json(f"{cells.HERE}/layer_metrics/{metric}.json")
    assert spec["reader"] == "setup_records" and spec["moves"] == "setup_s"
    return spec["params"]


def _view(t0):
    return types.SimpleNamespace(window={"t0": t0, "seconds": 30.0,
                                         "steps": 100})


def _call(kind, name, ts, thread=TID, **phases):
    return {"kind": kind, "name": name, "ts": ts, "dur": sum(phases.values()),
            "phases": phases, "thread": thread}


def _build(fun, ts, thread=TID, cache="hit", **stages):
    phases = dict({"trace": 0.0, "lower": 0.0, "compile": 0.0,
                   "cache_load": 0.0}, **stages)
    return {"kind": "build", "name": "mx.build", "fun": fun, "ts": ts,
            "dur": sum(phases.values()), "phases": phases, "cache": cache,
            "thread": thread}


@pytest.fixture
def ring():
    """The program's ring, emptied, for hand-made records."""
    tracing.reset()
    yield tracing._RING
    tracing.reset()


def _hand_made(ring):
    """A set-up from t = 10 to the window's t0 = 100: net init with two host
    programs, a cold forward with three, the trainer's placement with two and
    one more on the feed's thread, the first step's build, a second step that
    lowered the step again; then the window and the reference's builds."""
    ring.append(_build("zeros", 10.1, compile=0.5, cache="miss"))
    ring.append(_build("fill", 10.7, cache_load=0.25))
    ring.append(_call("setup", "mx.block.initialize", 10.0, all=1.5))
    for i in range(3):
        ring.append(_build(f"op{i}", 12.1 + i, trace=0.125, lower=0.125,
                           cache_load=0.25))
    ring.append(_call("setup", "mx.block.deferred_init", 12.0, probe=0.5,
                      finish=1.0, forward=2.5))
    ring.append(_build("copy", 20.5, cache_load=0.125))
    ring.append(_build("zeros_like", 22.5, cache_load=0.125))
    ring.append(_build("put", 22.6, thread=TID + 1, cache_load=1.0))
    ring.append(_call("setup", "mx.dp.init", 20.0, collect=0.25,
                      place_params=2.0, init_opt_state=1.0, compression=0.0,
                      program=0.125))
    ring.append(_build("split", 30.05, cache_load=0.0625))
    ring.append(_build("step", 30.2, trace=4.0, lower=2.0, cache_load=8.0))
    ring.append(_call("step", "mx.dp.step", 30.0, get_step=0.01,
                      rng_key=0.1, launch=14.5, admit=0.0))
    ring.append(_build("step", 50.1, lower=1.0, compile=3.0, cache="miss"))
    ring.append(_call("step", "mx.dp.step", 50.0, launch=4.5, admit=0.0))
    # the window: steps, and nothing built
    for i in range(3):
        ring.append(_call("step", "mx.dp.step", 100.0 + i, launch=0.5))
    # after it: the reference's programs, and a trainer a test built later
    ring.append(_build("ref_step", 140.0, trace=5.0, lower=5.0, compile=50.0))
    ring.append(_build("step", 200.1, trace=1.0))
    ring.append(_call("step", "mx.dp.step", 200.0, launch=2.0))
    ring.append(_call("setup", "mx.dp.init", 190.0, place_params=9.0))
    return _view(100.0)


def test_each_metric_on_hand_made_records(ring):
    view = _hand_made(ring)
    got = {m: READER.read(view, _params(m)) for m in METRICS}
    # both net records, whole
    assert got["setup_net_init_s"] == pytest.approx(1.5 + 4.0)
    # two phases of the trainer's record, the later trainer left out
    assert got["setup_place_s"] == pytest.approx(3.0)
    # the builds under a step record: the first step's and the second's
    assert got["setup_step_trace_s"] == pytest.approx(4.0 + 2.0 + 1.0)
    assert got["setup_step_load_s"] == pytest.approx(8.0 + 3.0)
    # every other build that began before the window, whatever it ran under:
    # the key's split too, which began in the step's `rng_key`
    assert got["setup_small_builds"] == 2 + 3 + 3 + 1
    assert got["setup_small_builds_s"] == pytest.approx(
        0.75 + 3 * 0.5 + 0.125 + 0.125 + 1.0 + 0.0625)


def test_a_build_is_the_steps_by_the_phase_it_began_in(ring):
    """What is attributed is the call and the phase a build ran under, not
    its name: the same build in `launch` is the step's, in `rng_key` not."""
    ring.append(_build("split", 30.05, cache_load=0.0625))
    ring.append(_call("step", "mx.dp.step", 30.0, get_step=0.01, rng_key=0.1,
                      launch=1.0))
    ring.append(_build("split", 40.5, cache_load=0.25))
    ring.append(_call("step", "mx.dp.step", 40.0, get_step=0.01, rng_key=0.1,
                      launch=1.0))
    view = _view(100.0)
    assert READER.read(view, _params("setup_step_load_s")) \
        == pytest.approx(0.25)
    assert READER.read(view, _params("setup_small_builds_s")) \
        == pytest.approx(0.0625)
    assert READER.read(view, _params("setup_small_builds")) == 1


def test_builds_after_t0_are_left_out(ring):
    view = _hand_made(ring)
    base = {m: READER.read(view, _params(m)) for m in METRICS}
    ring.append(_build("late", 100.5, compile=99.0))
    ring.append(_build("step", 101.1, trace=99.0))
    ring.append(_call("step", "mx.dp.step", 101.0, launch=100.0))
    assert {m: READER.read(view, _params(m)) for m in METRICS} == base
    # ..and with an earlier t0 the second step's build is the window's
    early = _view(40.0)
    assert READER.read(early, _params("setup_step_trace_s")) \
        == pytest.approx(6.0)
    assert READER.read(early, _params("setup_step_load_s")) \
        == pytest.approx(8.0)


def test_a_call_that_raised_is_left_out(ring):
    view = _hand_made(ring)
    ring.append(dict(_call("setup", "mx.dp.init", 25.0, place_params=7.0),
                     error="MXNetError"))
    assert READER.read(view, _params("setup_place_s")) == pytest.approx(3.0)


def test_a_stage_jax_did_not_report_reads_zero(ring):
    ring.append(_build("step", 30.1, cache_load=8.0))
    ring.append(_call("step", "mx.dp.step", 30.0, launch=8.5))
    assert READER.read(_view(100.0), _params("setup_step_trace_s")) == 0.0
    assert READER.read(_view(100.0), _params("setup_step_load_s")) == 8.0


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_kinds_gives_nothing(monkeypatch, ring, metric):
    """The parent commit's program has no `RECORD_KINDS`: the reader returns
    nothing and does not raise, and the line leaves the metric out."""
    _hand_made(ring)
    monkeypatch.delattr(tracing, "RECORD_KINDS")
    assert READER.read(_view(100.0), _params(metric)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_kinds_of_another_program_give_nothing(monkeypatch, ring, metric):
    monkeypatch.setattr(tracing, "RECORD_KINDS", ("step", "batch"))
    assert READER.read(_view(100.0), _params(metric)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_that_kept_none_is_an_error(ring, metric):
    """The kinds are declared and the ring holds the steps alone."""
    ring.append(_call("step", "mx.dp.step", 30.0, launch=14.5))
    ring.append(_build("late", 100.5, compile=1.0))
    ring.append(_call("setup", "mx.dp.init", 190.0, place_params=9.0))
    ring.append(_call("setup", "mx.block.initialize", 190.0, all=1.0))
    with pytest.raises(RuntimeError, match="kept no .*record"):
        READER.read(_view(100.0), _params(metric))


def test_builds_and_no_step_build_is_a_reading_of_zero(ring):
    """A step whose program another trainer of the process had built: its
    record holds no build, and that is 0 and no error."""
    ring.append(_build("zeros", 10.1, cache_load=0.5))
    ring.append(_call("step", "mx.dp.step", 30.0, launch=0.01))
    view = _view(100.0)
    assert READER.read(view, _params("setup_step_trace_s")) == 0.0
    assert READER.read(view, _params("setup_step_load_s")) == 0.0
    assert READER.read(view, _params("setup_small_builds")) == 1


def test_traced_toy_run_reports_the_six(toy_root):
    """A whole `--trace 1` run on the CPU with the ring emptied first: the
    six are on the line, the step's two stages sum to no more than its
    build, and the window built nothing."""
    from mxnet_tpu import engine
    tracing.reset()
    engine.reset_stats()
    result = run.run_cell("resnet_toy_train", (1 << 31) + 7, 0.5, 1,
                          root=toy_root,
                          bench_json=toy_root + "/BENCHMARK.json",
                          require_tpu=False)
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(METRICS) <= set(m)
    assert all(np.isfinite(m[k]) and m[k] >= 0 for k in METRICS)
    assert result["metrics"]["setup_small_builds"]["unit"] == "count"
    assert m["compiles_in_window"] == 0
    # the toy ResNet has deferred shapes: a cold forward, an op a program
    assert m["setup_net_init_s"] > 0 and m["setup_place_s"] > 0
    assert m["setup_step_trace_s"] > 0 and m["setup_step_load_s"] > 0
    assert m["setup_step_trace_s"] + m["setup_step_load_s"] \
        <= m["setup_step_build_s"]
    # the step's program is most of its build: what jax does between the
    # stages, and the dispatch, are the rest
    assert m["setup_step_trace_s"] + m["setup_step_load_s"] \
        > 0.5 * m["setup_step_build_s"]
    # the engine's sum holds every build of the process, the reference's too
    builds = tracing.step_records("mx.build")
    assert engine.cache_stats()["compile_seconds"] == pytest.approx(
        sum(b["dur"] for b in builds), rel=1e-6)
    assert engine.cache_stats()["compile_seconds"] \
        > m["setup_step_trace_s"] + m["setup_step_load_s"]
    tracing.reset()


def test_the_probe_partitions_a_toy_cells_set_up(toy_root, tmp_path, capsys):
    """`tools/setup_probe.py` on the CPU: the timeline sums to its set-up,
    the program's records and what lies between them fill `Program(...)` and
    `first_steps()`, and a stretch after set-up builds nothing."""
    import importlib.util
    import json
    spec = importlib.util.spec_from_file_location(
        "setup_probe", f"{cells.REPO}/tools/setup_probe.py")
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    tracing.reset()
    out = tmp_path / "probe.json"
    probe.main(["--workload", "resnet_toy_train", "--cpu", "--root", toy_root,
                "--window", "0.2", "--out", str(out)])
    got = json.loads(out.read_text())
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == got
    line = got["timeline"]
    assert sum(v for k, v in line.items()
               if v is not None and not k.startswith("interpreter")) \
        == pytest.approx(got["setup_s"], rel=1e-6)
    names = [r["name"] for r in got["setup_records"]]
    assert names[0] == "mx.block.initialize" and names[-1] == "mx.dp.init"
    assert any(n.startswith("mx.block.deferred_init x") for n in names)
    assert [r["attrs"]["step"] for r in got["step_records"]] == [1, 2, 3]
    first = got["step_records"][0]
    assert "step" in first["builds"]["by_fun"]
    assert first["builds"]["seconds"] <= first["dur"]
    in_program = sum(r["dur"] for r in got["setup_records"]) \
        + got["not_covered"]["in_program"]
    assert in_program == pytest.approx(line["Program(...)"], rel=1e-6)
    assert got["not_covered"]["in_program"] >= 0
    assert got["not_covered"]["in_first_steps"] >= 0
    assert 0 < got["covered_share"] < 1
    assert got["compile_seconds"] >= got["builds"]["seconds"] > 0
    assert got["window"]["steps"] > 0 and got["window"]["builds"] == []
    tracing.reset()
