"""The training runner at toy sizes on the CPU: one device and a dp=4 mesh of
four virtual devices, feed and stamps included; a toy cell added from data
files alone; the timed path broken underneath, and the control."""
import json

import jax
import numpy as np
import pytest

import run
import toy


def _run(toy_root, workload, trace=0, seed=(1 << 31) + 77, **kw):
    return run.run_cell(workload, seed, 0.5, trace, root=toy_root,
                        bench_json=toy_root + "/BENCHMARK.json",
                        require_tpu=False, **kw)


def _need(workload):
    if len(jax.devices()) < toy.TRAFFIC[toy.CELLS[workload][1]]["chips"]:
        pytest.skip("needs four (virtual) devices")


@pytest.mark.parametrize("workload", ["bert_toy_train", "resnet_toy_train",
                                      "bert_toy_dp4"])
def test_toy_cell_runs_from_data_files_alone(toy_root, workload):
    """The cell exists only as files in a temporary directory; the harness
    finds them by the names in that directory's BENCHMARK.json."""
    _need(workload)
    result = _run(toy_root, workload)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    m = result["metrics"]
    assert set(m) == {"train_items_per_s", "step_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert m["train_items_per_s"]["unit"] == "items/s"
    chips = toy.TRAFFIC[toy.CELLS[workload][1]]["chips"]
    assert result["device"]["count"] == chips
    assert set(result["compared"]) | set(result["not_compared"]) >= {
        "loss1_gap", "grad_norm_gap", "change_norm_gap", "feed_mismatch"}
    for value, limit in result["compared"].values():
        assert limit is not None and value <= limit
    json.dumps(result)


def test_traced_run_reports_layer_metrics_it_can_read(toy_root):
    """--trace 1 on the CPU: the host counters are read; the readers of the
    device trace find no device plane, return nothing and are left out (no
    share of a peak is ever 0 for want of a reading)."""
    result = _run(toy_root, "bert_toy_train", trace=1)
    m = result["metrics"]
    assert {"feed_stall_share", "dispatch_ms", "compiles_in_window",
            "setup_cache_misses"} <= set(m)
    assert m["compiles_in_window"]["value"] == 0
    assert m["dispatch_ms"]["value"] > 0
    for name in ("step_mfu", "mxu_roofline", "device_idle_share",
                 "flash_roofline", "collective_exposed_share"):
        assert name not in m
    assert "busy_s" not in result["device"]


def test_window_counts_all_steps_and_all_time(toy_root):
    import cells
    import runner
    import traffic
    from reference import steps
    cell = cells.Cell("bert_toy_train", toy_root + "/BENCHMARK.json",
                      toy_root)
    spec = cell.module("reference").param_spec(cell.config)
    pool = traffic.make_pool(cell.traffic, cell.config, 5)
    prog = runner.Program(cell, steps.make_weights(spec, 5), pool, 5,
                          jax.devices()[:1])
    prog.first_steps()
    before = prog.feed.batches_delivered
    w = prog.stretch(0.3)
    assert w["steps"] == len(w["stamps"]) \
        == prog.feed.batches_delivered - before
    assert w["stamps"] == sorted(w["stamps"]) and w["seconds"] >= 0.3
    e2e = runner.end_to_end(w, cell.items_per_step())
    assert e2e["train_items_per_s"] == pytest.approx(
        w["steps"] * 8 * 32 / (w["stamps"][-1] - w["t0"]))
    gaps = np.diff(w["stamps"]) * 1e3
    assert gaps.min() <= e2e["step_p95_ms"] <= gaps.max()
    prog.close()


# -- the timed path broken underneath: `correct` has to come out false -------

def _state_unchanged(prog):
    """A step that returns its state unchanged."""
    tr, real = prog.trainer, prog.trainer.step

    def step(x, y):
        params, state = tr._params_raw, tr._opt_state
        keep = jax.tree_util.tree_map(lambda a: a + 0, (params, state))
        loss = real(x, y)
        tr.drain()
        tr._params_raw, tr._opt_state = keep
        return loss
    tr.step = step


def _half_batch_loss(logits, labels):
    """Half of the batch left out, the mean taken over the rest."""
    import runner
    half = logits.shape[0] // 2
    return runner.token_loss(logits[:half], labels[:half])


def _quarter_batch_loss(logits, labels):
    """The exchange between the four chips left out: what chip 0 computes
    from its own rows alone."""
    import runner
    part = logits.shape[0] // 4
    return runner.token_loss(logits[:part], labels[:part])


@pytest.mark.parametrize("workload,fault", [
    ("bert_toy_train", "state_unchanged"), ("bert_toy_train", "half_batch"),
    ("resnet_toy_train", "state_unchanged"),
    ("resnet_toy_train", "half_batch"),
    ("bert_toy_dp4", "exchange_left_out")])
def test_broken_timed_path_is_not_correct(toy_root, workload, fault):
    _need(workload)
    kw = {"state_unchanged": {"program_hook": _state_unchanged},
          "half_batch": {"loss": _half_batch_loss},
          "exchange_left_out": {"loss": _quarter_batch_loss}}[fault]
    result = _run(toy_root, workload, **kw)
    assert result["correct"] is False
    over = [n for n, (v, lim) in result["compared"].items() if v > lim]
    assert over and "feed_mismatch" not in over, result["compared"]


def test_feed_that_delivers_another_batch_is_not_correct(toy_root):
    # the feed wraps the pool it was built with; the check reads this one
    result = _run(toy_root, "bert_toy_train", program_hook=lambda prog:
                  setattr(prog, "pool", [prog.pool[1]] + prog.pool[1:]))
    assert result["correct"] is False
    assert result["compared"]["feed_mismatch"][0] > 0


def test_control_one_precision_down_is_not_correct(toy_root):
    """The control: the reference put in the program's place with its matrix
    products in FP8, the step below the configuration's bfloat16. It has to
    fail the limits that the program passes (both at this toy size)."""
    import cells
    import check
    import control
    cell = cells.Cell("bert_toy_train", toy_root + "/BENCHMARK.json",
                      toy_root)
    rows = list(control.readings(
        "bert_toy_train", [100, 101, 102], 3, root=toy_root,
        bench_json=toy_root + "/BENCHMARK.json", require_tpu=False))
    for row in rows:
        assert check.decide(row["program"], cell.limits)[0] is True
        assert check.decide(row["control"], cell.limits)[0] is False
        assert check.decide(row["half_batch"], cell.limits)[0] is False
        assert check.decide(row["unchanged"], cell.limits)[0] is False
        assert row["unchanged"]["change_norm_gap"] == pytest.approx(1.0)
    # a cell that holds no number at all is never correct
    assert check.decide(rows[0]["program"], {})[0] is False
