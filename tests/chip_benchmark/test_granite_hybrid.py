"""The hybrid decoder's family in the chip benchmark (PR 28), at toy sizes on
the CPU: its toy cells run from data files alone, the float32 program is the
reference's arithmetic, the FP8 control and the model's own fault (the state
emptied at every chunk boundary) come out as not correct, the operation
counts are the real model's matrices, and the four per-layer metrics read a
trace of the toy cell recorded on the chip."""
import importlib.util
import os
import types

import numpy as np
import pytest

import cells
import granite_toy

CELL = "granite4_h_micro_train_t2048"
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_v5e_granite_toy.txt")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return granite_toy.lay_out(str(tmp_path_factory.mktemp("granite_toy")))


def _trial():
    spec = importlib.util.spec_from_file_location(
        "granite_trial", os.path.join(cells.HERE, "tools",
                                      "granite_trial.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _over(numbers, limits):
    return [k for k, v in numbers.items()
            if limits.get(k) is not None and v > limits[k]]


# -- the toy cells --------------------------------------------------------------

def test_toy_cell_runs_from_data_files_alone(root):
    import run
    result = run.run_cell("granite_toy_train", 100, 0.3, 0, root=root,
                          bench_json=root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    m = result["metrics"]
    assert m["train_items_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert set(result["not_compared"]) == {"loss2_gap"}


def test_float32_program_is_the_references_arithmetic(root):
    import run
    import toy
    result = run.run_cell("granite_toy_f32", 12345, 0.2, 0, root=root,
                          bench_json=root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert {k: lim for k, (_, lim) in result["compared"].items()} == toy.EXACT
    c = result["compared"]
    assert max(c[f"loss{i}_gap"][0] for i in (1, 2, 3)) < 1e-5
    assert c["grad_norm_gap"][0] < 2e-3 and c["change_norm_gap"][0] < 2e-3


def test_control_and_faults_are_not_correct(root):
    import control
    row, = control.readings("granite_toy_train", [101], 1, root=root,
                            bench_json=root + "/BENCHMARK.json",
                            require_tpu=False)
    limits = granite_toy.LIMITS
    assert _over(row["program"], limits) == []
    for kind in ("control", "half_batch", "unchanged"):
        assert _over(row[kind], limits), kind
    assert "grad_norm_gap" in _over(row["control"], limits)


def test_state_emptied_at_chunk_boundaries_is_not_correct(root):
    """The model's own fault: with most of the toy's heads remembering past a
    chunk of 8, dropping the carry shows in the losses and the gradients."""
    rows = _trial().main(["chunk_reset", "--workload", "granite_toy_train",
                          "--seeds", "100,102"], require_tpu=False, root=root,
                         bench_json=root + "/BENCHMARK.json")
    for row in rows:
        over = _over(row["chunk_reset"], granite_toy.LIMITS)
        assert "grad_norm_gap" in over and len(over) >= 3, row


def test_param_spec_is_the_programs_leaves_and_a_log_spreads():
    import mxnet_tpu as mx
    cell = cells.Cell(CELL)
    cfg = cell.config
    spec = cell.module("reference").param_spec(cfg)
    net, _ = cell.module("programs").build(cfg, cell.traffic)
    with mx.cpu():
        shapes = [tuple(p.shape) for p in net.collect_params().values()]
    assert shapes == [tuple(s[1]) for s in spec]
    total = sum(int(np.prod(s[1])) for s in spec)
    assert total == 772_160_448                  # ISSUE 28's 772.2M
    a_log = [s for s in spec if s[0].endswith("A_log")]
    assert len(a_log) == 9 and all(s[2] == ("normal", 3.0) for s in a_log)
    from reference import steps
    toy_cfg = granite_toy.CONFIG
    w = steps.make_weights(cell.module("reference").param_spec(toy_cfg), 100)
    a = np.exp(np.concatenate([v for k, v in w.items()
                               if k.endswith("A_log")]))
    assert a.min() < 0.1 and a.max() > 10        # long and short memory


# -- the configuration ------------------------------------------------------------

# ibm-granite/granite-4.0-h-micro config.json as the catalog beside the
# `model-configs` guide holds it (every key but `layer_types`, which is
# attention at layers 5, 15, 25, 35 of 40 and Mamba elsewhere)
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
          "config.json")
PUBLISHED = {
    "attention_bias": False,
    "attention_multiplier": 0.015625,
    "embedding_multiplier": 12,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "logits_scaling": 8,
    "mamba_chunk_size": 256,
    "mamba_conv_bias": True,
    "mamba_d_conv": 4,
    "mamba_d_head": 64,
    "mamba_d_state": 128,
    "mamba_expand": 2,
    "mamba_n_groups": 1,
    "mamba_n_heads": 64,
    "mamba_proj_bias": False,
    "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm",
    "num_attention_heads": 32,
    "num_experts_per_tok": 0,
    "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "num_local_experts": 0,
    "position_embedding_type": "nope",
    "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 10000,
    "shared_intermediate_size": 8192,
    "tie_word_embeddings": True,
    "vocab_size": 100352
}
PUBLISHED["layer_types"] = ["attention" if i % 10 == 5 else "mamba"
                            for i in range(40)]


def test_configuration_keeps_every_published_number():
    cell = cells.Cell(CELL)
    cfg = cell.config
    reduced = {"num_hidden_layers", "layer_types", "vocab_size"}
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "granite4_h_micro")
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == SOURCE == cfg["source"]
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:10]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 10
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "8 chips" in cfg["deployment"]
    assert cells.reduced_faults(entry, cfg) == []


# -- the operation counts -----------------------------------------------------------

def test_flops_against_the_real_models_matrices():
    import mxnet_tpu as mx
    cell = cells.Cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    net, _ = cell.module("programs").build(cfg, traffic)
    with mx.cpu():
        params = net.collect_params()
    # every projection is applied once to every token: 2 ops a weight; the
    # tied table once, as the head (the embedding is a lookup)
    dense = sum(2 * int(np.prod(p.shape)) for n, p in params.items()
                if "dense" in n and n.endswith("weight"))
    head = 2 * int(np.prod(net.embed_weight.shape))
    seq, tokens = traffic["seq"], traffic["batch"] * traffic["seq"]
    q, n, h, p = 256, 128, 64, 64
    scan = 9 * (2 * q * n + 2 * q * h * p + 4 * h * p * n)
    scores = 2 * seq * cfg["hidden_size"]        # one layer, causal half
    flops = cell.module("flops")
    assert flops.train_flops_per_item(cfg, traffic) \
        == 3 * (dense + head + scan + scores)
    assert flops.mxu_flops_per_item(cfg, traffic, exclude_attention=True) \
        == 3 * (dense + head + scan)
    assert flops.mxu_flops_per_item(cfg, traffic) \
        == flops.train_flops_per_item(cfg, traffic)
    ops, nbytes = flops.ssd_scan_work(cfg, traffic)
    assert ops == 3 * scan * tokens
    assert nbytes == 2 * 9 * tokens * (2 * (2 * h * p + 2 * n) + 4 * h)
    # ISSUE 28's counts: 4.26 MFLOP a token a layer, 4.77 GFLOP a token
    assert scan / 9 == pytest.approx(4.26e6, rel=2e-3)
    assert flops.train_flops_per_item(cfg, traffic) == pytest.approx(
        4.77e9, rel=1e-3)
    # the scan is bound by its operations on the v5e, not by its bytes
    v5e = cells.peaks("TPU v5 lite")
    assert ops / v5e["flops_per_s"] > nbytes / v5e["bytes_per_s"]


# -- the four per-layer metrics on a trace recorded on the chip -----------------------

@pytest.fixture(scope="module")
def recorded():
    import trace as T
    with open(FIXTURE) as f:
        return T.load(T.xspace_from_text(f.read()))


def _view(recorded, steps=3):
    import trace as T
    cell = cells.Cell(CELL)
    return types.SimpleNamespace(
        cell=cell, loaded=recorded, devices=T.reduce(recorded),
        traced={"steps": steps}, chips=1, flops=cell.module("flops"),
        peaks=cells.peaks("TPU v5 lite"), say=lambda line: None)


def _by_hand(recorded, has):
    """ns of the device's events whose scope holds `has`, and of all."""
    (ops,) = [d["ops"] for d in recorded["devices"].values()]
    return sum(o[3] for o in ops if has in o[4]), sum(o[3] for o in ops)


@pytest.mark.parametrize("metric,has", [
    ("ssd_time_share", "mx.ssd"), ("attention_time_share", "mx.attn"),
    ("recompute_time_share", "rematted_computation")])
def test_time_share_files_read_the_recorded_scopes(recorded, metric, has):
    spec = cells.Cell(CELL).layer_metric(metric)
    assert spec["reader"] == "time_share" and spec["params"] == {
        "scope_has": [has]}
    mine, total = _by_hand(recorded, has)
    assert mine > 0
    got = cells.load_module("readers", "time_share").read(
        _view(recorded), spec["params"])
    assert got == pytest.approx(100 * mine / total, rel=1e-9)
    assert 0 < got < 100


def test_the_recorded_trace_holds_the_models_scopes(recorded):
    (ops,) = [d["ops"] for d in recorded["devices"].values()]
    (modules,) = [d["modules"] for d in recorded["devices"].values()]
    assert sum(m[0].startswith("jit_step") for m in modules) == 3
    scopes = {o[4] for o in ops}
    for name in ("mx.embed", "mx.mamba/mx.ssd", "mx.mamba/mx.conv1d",
                 "mx.attn", "mx.ffn", "mx.head",
                 "checkpoint/rematted_computation/mx.mamba/mx.ssd"):
        assert any(name in s for s in scopes), name
    # the scan's events are in the forward pass, in the recomputed forward
    # and in the backward pass; the scan lies inside its mixer
    ssd = {s for s in scopes if "mx.ssd" in s}
    assert any("jvp(mx.mamba)" in s for s in ssd)
    assert any("rematted_computation" in s for s in ssd)
    assert any("transpose(" in s and "rematted" not in s for s in ssd)
    assert all("mx.mamba" in s for s in ssd)


def test_ssd_roofline_file_reads_the_recorded_scan(recorded):
    cell = cells.Cell(CELL)
    spec = cell.layer_metric("ssd_roofline")
    assert spec["reader"] == "kernel_roofline"
    assert spec["params"] == {"scope_has": ["mx.ssd"],
                              "work": "ssd_scan_work"}
    mine, _ = _by_hand(recorded, "mx.ssd")
    ops, nbytes = cell.module("flops").ssd_scan_work(cell.config,
                                                     cell.traffic)
    least = max(ops / 197e12, nbytes / 819e9)
    got = cells.load_module("readers", "kernel_roofline").read(
        _view(recorded), spec["params"])
    # the real cell's work over the toy's recorded time: only the arithmetic
    # of the reading is checked here, the number means nothing
    assert got == pytest.approx(100 * least / (mine / 1e9 / 3), rel=1e-9)
    # a trace without the scope (the parent's programs): nothing to read
    empty = dict(recorded, devices={
        k: dict(d, ops=[o[:4] + ("",) for o in d["ops"]])
        for k, d in recorded["devices"].items()})
    for name in ("ssd_roofline", "ssd_time_share", "attention_time_share",
                 "recompute_time_share"):
        spec = cell.layer_metric(name)
        reader = cells.load_module("readers", spec["reader"])
        assert reader.read(_view(empty), spec["params"]) is None
