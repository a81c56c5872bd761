"""The `keye_vl2` family in the chip benchmark (PR 34), at toy sizes on the
CPU: its toy cells run from data files alone, the float32 program is the
reference's arithmetic with the frozen leaves unmoved on both sides, the FP8
control and each of the model's own faults come out as not correct, the
shares of the experts add up to the uncut layer, the configuration keeps
every published number, the operation counts are the real model's matrices,
and the five per-layer metrics read a trace of the toy cell recorded on the
chip."""
import importlib.util
import os
import types

import numpy as np
import pytest

import cells
import keye_toy

CELL = "keye_vl2_30b_a3b_train_t16384"
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_v5e_keye_toy.txt")
FAULTS = ("selection_ignored", "selection_first", "qknorm_dropped",
          "positions_dropped", "routed_dropped")
FROZEN = ("index_query", "index_key", "index_key_norm", "index_key_bias",
          "index_weight")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return keye_toy.lay_out(str(tmp_path_factory.mktemp("keye_toy")))


def _trial():
    spec = importlib.util.spec_from_file_location(
        "keye_trial", os.path.join(cells.HERE, "tools", "keye_trial.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _over(numbers, limits):
    return [k for k, v in numbers.items()
            if limits.get(k) is not None and v > limits[k]]


# -- the toy cells --------------------------------------------------------------

def test_toy_cell_runs_from_data_files_alone(root):
    import run
    result = run.run_cell("keye_toy_train", 100, 0.3, 0, root=root,
                          bench_json=root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    m = result["metrics"]
    assert m["train_items_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert set(result["compared"]) == {"grad_norm_gap", "change_norm_gap",
                                       "feed_mismatch"}


def test_float32_program_is_the_references_arithmetic(root):
    """Losses, per-leaf gradients and the change after three steps: the
    program's indexer, bisected top-k, packed and carried set, QK norm,
    sectioned rotary op and sorted expert layer against float32 scores,
    `lax.top_k`, a scattered mask, masked softmax, written-out positions and
    experts applied densely."""
    import run
    import toy
    result = run.run_cell("keye_toy_f32", 12345, 0.2, 0, root=root,
                          bench_json=root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert {k: lim for k, (_, lim) in result["compared"].items()} == toy.EXACT
    c = result["compared"]
    assert max(c[f"loss{i}_gap"][0] for i in (1, 2, 3)) < 1e-5
    assert c["grad_norm_gap"][0] < 2e-3 and c["change_norm_gap"][0] < 2e-3


@pytest.mark.parametrize("interpret", ["0", "1"])
def test_float32_program_through_the_selecting_route(root, monkeypatch,
                                                     interpret):
    """The same with the crossover under the toy's 32 positions: every layer
    goes to `flash_attention(select=)`: off the TPU its blockwise form, and
    the Pallas kernels themselves in interpret mode."""
    import run
    monkeypatch.setenv("MXNET_FLASH_ATTENTION_MIN_SEQ", "16")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", interpret)
    result = run.run_cell("keye_toy_f32", 54321, 0.2, 0, root=root,
                          bench_json=root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]


def test_frozen_leaves_are_unmoved_on_both_sides(root):
    """Three steps of the program and of the reference: the indexer's leaves
    are what the seed made them, bit for bit, in the program's trainer; the
    reference has no gradient and no change to report for them."""
    import runner
    import traffic
    from reference import steps
    cell = cells.Cell("keye_toy_f32", root=root,
                      bench_json=root + "/BENCHMARK.json")
    model = cell.module("reference")
    spec = model.param_spec(cell.config)
    weights = steps.make_weights(spec, 7)
    pool = traffic.make_pool(dict(cell.traffic, pool=3), cell.config, 7)
    import jax
    prog = runner.Program(cell, weights, pool, 7, jax.devices()[:1])
    got, _ = prog.first_steps()
    after = dict(zip(prog.names, prog.trainer._params_raw))
    state = dict(zip(prog.names, prog.trainer._opt_state))
    frozen = [n for n, _, _, t in spec if not t]
    assert {n.split(".")[-1] for n in frozen} \
        == set(FROZEN) | {"selection", "routing"}
    for n in frozen:
        if n.split(".")[-1] in FROZEN:
            assert (np.asarray(after[n]) == weights[n]).all(), n
            assert state[n] == ()
        assert n not in got["grad_norms"] and n not in got["change_norms"]
    prog.close()
    ref = steps.follow(model, cell.config, weights, pool)
    assert set(ref["grad_norms"]) == set(got["grad_norms"]) \
        == {n for n, _, _, t in spec if t}
    assert min(ref["change_norms"].values()) > 0


def test_control_and_general_faults_are_not_correct(root):
    import control
    row, = control.readings("keye_toy_train", [101], 1, root=root,
                            bench_json=root + "/BENCHMARK.json",
                            require_tpu=False)
    limits = keye_toy.LIMITS
    assert _over(row["program"], limits) == []
    for kind in ("control", "half_batch", "unchanged"):
        assert _over(row[kind], limits), kind
    assert "grad_norm_gap" in _over(row["control"], limits)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_is_not_correct(root, fault):
    """The reference in the program's place with one mechanism left out: the
    selection (every causal key, or the first keys), the QK norm, the
    positions, the routed sum."""
    rows = _trial().main(["faults", "--workload", "keye_toy_train",
                          "--seeds", "100,102", "--faults", fault],
                         require_tpu=False, root=root,
                         bench_json=root + "/BENCHMARK.json")
    for row in rows:
        assert "grad_norm_gap" in _over(row[fault], keye_toy.LIMITS), row


def test_the_layers_report_their_selection_and_routing_as_state(root):
    """`keye_trial.py selection`: the toy trained as a run drives it, each
    layer's `selection` and `routing` state read every few steps. No step
    reads them back."""
    rows = _trial().main(["selection", "--workload", "keye_toy_train",
                          "--seeds", "100", "--steps", "10"],
                         require_tpu=False, root=root,
                         bench_json=root + "/BENCHMARK.json")
    assert [r["step"] for r in rows] == [1, 2, 3, 5, 10]
    cell = cells.Cell("keye_toy_train", root=root,
                      bench_json=root + "/BENCHMARK.json")
    seq, top_k = cell.traffic["seq"], cell.config["sa_config"]["topk"]
    kept = np.minimum(np.arange(seq) + 1, top_k).mean()
    tokens = cell.traffic["batch"] * seq
    for row in rows:
        assert len(row["selection"]) == len(row["routing"]) == 3
        for mean, empty in row["selection"]:
            assert mean == pytest.approx(kept) and empty == int(empty) >= 0
        for n, max_load, mean_load, exact in row["routing"]:
            assert 0 < n <= tokens * 3 and mean_load == n / 4 <= max_load
            assert exact in (0.0, 1.0)


def test_param_spec_is_the_programs_leaves():
    import mxnet_tpu as mx
    cell = cells.Cell(CELL)
    cfg = cell.config
    spec = cell.module("reference").param_spec(cfg)
    net, _ = cell.module("programs").build(cfg, cell.traffic)
    with mx.cpu():
        params = list(net.collect_params().values())
    assert [tuple(p.shape) for p in params] == [tuple(s[1]) for s in spec]
    # what is trained: the indexer's five leaves a layer and the two leaves
    # of state are not, in the program (`grad_req` null) and here alike
    assert [p.grad_req != "null" for p in params] == [s[3] for s in spec]
    assert [s[0].split(".")[-1] for s in spec if not s[3]] \
        == (["selection"] + list(FROZEN) + ["routing"]) * 6
    count = lambda keep, trained=True: sum(
        int(np.prod(s[1])) for s in spec if keep(s[0]) and s[3] == trained)
    last = lambda n: n.split(".")[-1]
    frozen = count(lambda n: last(n) in FROZEN, False)
    assert count(lambda n: True) == 645_623_296 == cfg["parameters"]["trained"]
    assert frozen == 13_566_720 == cfg["parameters"]["frozen"]
    assert count(lambda n: True) + frozen == 659_190_016 \
        == cfg["parameters"]["total"]                    # ISSUE 34's table
    mixer = ("query", "key", "value", "proj", "query_norm", "key_norm")
    assert count(lambda n: n.startswith("layer0.")
                 and last(n) in mixer) == 18_874_624
    assert count(lambda n: n.startswith("layer3.") and last(n) in FROZEN,
                 False) == 2_261_120
    assert count(lambda n: n == "layer2.router") == 262_144
    assert count(lambda n: n.startswith("layer5.experts")) == 16 * 4_718_592
    assert count(lambda n: n.startswith("layer1.")) \
        + 2_261_120 == 96_899_456
    assert count(lambda n: n in ("embed", "head")) == 77_791_232


# -- the shares add up ------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_references_layer():
    """One expert layer at toy size: the routed parts that the 4 shares of 4
    experts give, from the reference told which experts it holds and from
    the program's op, equal what the uncut reference (all 16 experts held)
    gives for the whole layer. There is no shared expert to add."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe
    ref = cells.load_module("reference", "keye_vl2")
    cfg = dict(keye_toy.CONFIG)
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    c, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rs = np.random.RandomState(7)
    arr = lambda scale, *s: jnp.asarray(rs.normal(0, scale, s)
                                        .astype(np.float32))
    v = arr(1, 2, 40, c)
    p = {"router": arr(0.3, routed, c),
         "experts_gate_up": arr(0.1, routed, c, 2 * fe),
         "experts_down": arr(0.1, routed, fe, c)}
    ident = lambda a: a
    whole = ref._experts(v, p, dict(cfg, num_experts=routed), ident)
    from_reference = from_program = 0
    for first in range(0, routed, held):
        share = dict(p, experts_gate_up=p["experts_gate_up"][first:first + held],
                     experts_down=p["experts_down"][first:first + held])
        from_reference += ref._experts(
            v, share, dict(cfg, first_held_expert=first), ident)
        from_program += moe.held_moe_ffn(
            v.reshape(-1, c), p["router"], share["experts_gate_up"],
            share["experts_down"], top_k=cfg["num_experts_per_tok"],
            published_experts=routed, first_held=first).reshape(v.shape)
    for parts in (from_reference, from_program):
        np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                                   rtol=2e-5, atol=2e-6)
    # and one share alone is not the layer
    one = ref._experts(v, dict(p, experts_gate_up=p["experts_gate_up"][:held],
                               experts_down=p["experts_down"][:held]),
                       cfg, ident)
    assert float(jnp.abs(one - whole).max()) > 1e-2


# -- the configuration ------------------------------------------------------------

# Kwai-Keye/Keye-VL-2.0-30B-A3B config.json as the catalog beside the
# `model-configs` guide holds it
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def test_configuration_keeps_every_published_number():
    cell = cells.Cell(CELL)
    cfg = cell.config
    reduced = {"num_hidden_layers", "num_experts", "num_local_experts",
               "vocab_size"}
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "keye_vl2_30b_a3b")
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == SOURCE == cfg["source"]
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6
    assert cfg["num_experts"] == cfg["num_local_experts"] == 16
    assert cfg["vocab_size"] * 8 == 151936 and cfg["first_held_expert"] == 0
    assert "8 pipeline stages" in cfg["deployment"] \
        and "16 a chip" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 6 and len(cfg["departures"]) >= 5
    assert any("alignment term is not run" in d for d in cfg["departures"])
    assert cells.reduced_faults(entry, cfg) == []
    assert cell.traffic["batch"] == 1 and cell.traffic["seq"] == 16384
    assert cell.traffic["pool"] == 8 and cell.reference == {"donate": True}
    assert cell.entry["chips"] == 1
    assert cell.entry["traffic"] == "train_b1_t16384"
    # the one leaf whose draw departs (`assumed` says why), and the rate
    spec = cell.module("reference").param_spec(cfg)
    assert spec[0][:3] == ("embed", (18992, 2048), ("normal", 1.0))
    assert {s[2] for s in spec if isinstance(s[2], tuple)
            and s[0] != "embed"} == {("normal", 0.02)}
    assert cfg["optimizer"]["learning_rate"] == 5e-6
    assert any("normal(0, 1)" in t for t in cfg["assumed"])


# -- the operation counts -----------------------------------------------------------

def test_flops_against_the_real_models_matrices():
    import mxnet_tpu as mx
    cell = cells.Cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    net, _ = cell.module("programs").build(cfg, traffic)
    with mx.cpu():
        params = list(net.collect_params().values())
    seq = tokens = traffic["seq"]
    # every trained projection and the routers and the head are applied once
    # to every token: 2 ops a weight, three times with the backward pass;
    # the indexer's projections forward only; an expert to the rows routed to
    # it, of which 8 x 16/128 = 1 a token is expected here
    size = lambda p: int(np.prod(p.shape))
    trained = sum(2 * size(p) for p in params if p.grad_req != "null"
                  and len(p.shape) == 2 and "embed" not in p.name
                  and "head" not in p.name)
    index_proj = sum(2 * size(p) for p in params
                     if p.grad_req == "null" and len(p.shape) == 2)
    head = 2 * size(net.head_weight)
    routed = sum(2 * size(p) // 16 for p in params if "experts_" in p.name) \
        * 8 * 16 / 128
    selected = sum(min(t + 1, 2048) for t in range(seq))
    causal = seq * (seq + 1) // 2
    assert selected == 31_458_304 and causal == 134_225_920   # ISSUE 34's
    attention = 6 * 4 * 32 * 128 * selected / seq
    scores = 6 * 2 * 16 * 64 * causal / seq
    flops = cell.module("flops")
    assert flops.train_flops_per_item(cfg, traffic) == pytest.approx(
        3 * (trained + head + routed + attention) + index_proj + scores,
        rel=1e-12)
    assert flops.mxu_flops_per_item(cfg, traffic, exclude_attention=True) \
        == pytest.approx(3 * (trained + head) + index_proj + scores,
                         rel=1e-12)
    assert flops.mxu_flops_per_item(cfg, traffic) \
        == flops.train_flops_per_item(cfg, traffic)
    per_item = flops.train_flops_per_item(cfg, traffic)
    # a step: dense products 18.3 TFLOP, attention at the selected pairs
    # 9.28, the indexer's scores 1.65
    assert (3 * (trained + head + routed) + index_proj) * tokens / 1e12 \
        == pytest.approx(18.34, abs=0.01)
    assert 3 * attention * tokens / 1e12 == pytest.approx(9.28, abs=0.01)
    assert scores * tokens / 1e12 == pytest.approx(1.65, abs=0.01)
    assert per_item * tokens / 1e12 == pytest.approx(29.27, abs=0.01)
    ops, nbytes = flops.sparse_attention_kernel_work(cfg, traffic)
    assert ops == pytest.approx(3 * attention * tokens, rel=1e-12)
    assert nbytes == 6 * (2 * tokens * 128 * (4 * 32 + 4 * 4)
                          + 2 * causal / 8)
    ops_i, nbytes_i = flops.indexer_work(cfg, traffic)
    assert ops_i == pytest.approx((index_proj + scores) * tokens, rel=1e-12)
    out = 16 * 64 + 64 + 16
    assert nbytes_i == 6 * (2 * (tokens * 2048 + 2048 * out + tokens * out)
                            + seq * seq / 8)
    # both are bound by their operations on the v5e, not by their bytes
    v5e = cells.peaks("TPU v5 lite")
    for o, n in ((ops, nbytes), (ops_i, nbytes_i)):
        assert o / v5e["flops_per_s"] > n / v5e["bytes_per_s"]


# -- the five per-layer metrics on a trace recorded on the chip -----------------------

METRICS = {
    "sparse_attention_time_share": ("time_share",
                                    {"scope_has": ["mx.attn.sparse"]}),
    "indexer_time_share": ("time_share", {"scope_has": ["mx.index"]}),
    "keye_moe_time_share": ("time_share", {"scope_has": ["mx.moe"],
                                           "name_has": ["ragged-dot"]}),
    "select_flash_roofline": ("kernel_roofline",
                              {"scope_has": ["mx.flash.select"],
                               "work": "sparse_attention_kernel_work"}),
    "indexer_roofline": ("kernel_roofline",
                         {"scope_has": ["mx.index"],
                          "work": "indexer_work"}),
}


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


def _by_hand(recorded, scope, name=None):
    """ns of the device's events whose scope holds `scope` or whose name
    holds `name`, and of all."""
    (ops,) = [d["ops"] for d in recorded["devices"].values()]
    return sum(o[3] for o in ops if scope in o[4]
               or (name and name in o[0])), sum(o[3] for o in ops)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_metric_files_read_the_recorded_scopes(recorded, metric):
    cell = cells.Cell(CELL)
    spec = cell.layer_metric(metric)
    reader, params = METRICS[metric]
    assert spec["reader"] == reader and spec["params"] == params
    assert spec["workloads"] == [CELL]
    assert spec["moves"] == "train_items_per_s"
    entry = next(m for m in cell.bench["per_layer"] if m["name"] == metric)
    assert entry == {k: spec[k] for k in entry}
    mine, total = _by_hand(recorded, params["scope_has"][0],
                           (params.get("name_has") or [None])[0])
    assert mine > 0
    got = cells.load_module("readers", reader).read(_view(recorded), params)
    if reader == "time_share":
        assert got == pytest.approx(100 * mine / total, rel=1e-9)
        assert 0 < got < 100
    else:
        # the real cell's work over the toy's recorded time: only the
        # arithmetic of the reading is checked here, the number means nothing
        ops, nbytes = getattr(cell.module("flops"), params["work"])(
            cell.config, cell.traffic)
        least = max(ops / 197e12, nbytes / 819e9)
        assert got == pytest.approx(100 * least / (mine / 1e9 / 3), rel=1e-9)
    # a trace without the scopes and the kernel (the parent's programs):
    # nothing to read
    empty = dict(recorded, devices={
        k: dict(d, ops=[("fusion",) + o[1:4] + ("",) for o in d["ops"]])
        for k, d in recorded["devices"].items()})
    assert cells.load_module("readers", reader).read(
        _view(empty), params) is None


def test_the_recorded_trace_holds_the_models_scopes(recorded):
    (ops,) = [d["ops"] for d in recorded["devices"].values()]
    (modules,) = [d["modules"] for d in recorded["devices"].values()]
    assert sum(m[0].startswith("jit_step") for m in modules) == 3
    scopes = {o[4] for o in ops}
    for name in ("mx.embed", "mx.head", "mx.attn.sparse)/mx.qknorm",
                 "mx.attn.sparse)/mx.rope", "mx.attn.sparse)/mx.index/",
                 "mx.index/mx.index.unpack", "mx.attn.sparse)/mx.flash.select",
                 "mx.moe)/mx.moe.route",
                 "rematted_computation/mx.attn.sparse/mx.flash.select",
                 "rematted_computation/mx.attn.sparse/mx.qknorm"):
        assert any(name in s for s in scopes), name
    # the chunks of queries are a loop's body: its scopes lie behind it
    for name in ("mx.index.score", "mx.index.select"):
        assert any(name in s.split("/while/body/")[-1] for s in scopes
                   if "mx.index/" in s), name
    # the set is carried: the recomputed forward unpacks it again and neither
    # scores nor selects
    again = {s for s in scopes if "rematted_computation" in s}
    assert any("mx.index.unpack" in s for s in again)
    assert not [s for s in again if "mx.index.score" in s
                or "mx.index.select" in s]
    # the Mosaic calls of the selecting kernels carry their scope
    flash = [o for o in ops if "mx_flash" in o[0]]
    assert flash and all("mx.flash.select" in o[4] for o in flash)
    assert {o[1] for o in flash} == {"custom-call:tpu_custom_call"}
