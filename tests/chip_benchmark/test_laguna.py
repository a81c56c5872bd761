"""The `laguna` family in the chip benchmark (PR 32), at toy sizes on the
CPU: its toy cells run from data files alone, the float32 program is the
reference's arithmetic, the FP8 control and each of the model's own faults
come out as not correct, the shares of the experts add up to the uncut
layer, the configuration keeps every published number, the operation counts
are the real model's matrices, and the five per-layer metrics read a trace
of the toy cell recorded on the chip."""
import importlib.util
import os
import types

import numpy as np
import pytest

import cells
import laguna_toy

CELL = "laguna_s_2_1_train_t8192"
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_v5e_laguna_toy.txt")
FAULTS = ("window_ignored", "positions_dropped", "routed_dropped",
          "gate_dropped")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return laguna_toy.lay_out(str(tmp_path_factory.mktemp("laguna_toy")))


def _trial():
    spec = importlib.util.spec_from_file_location(
        "laguna_trial", os.path.join(cells.HERE, "tools", "laguna_trial.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _over(numbers, limits):
    return [k for k, v in numbers.items()
            if limits.get(k) is not None and v > limits[k]]


# -- the toy cells --------------------------------------------------------------

def test_toy_cell_runs_from_data_files_alone(root):
    import run
    result = run.run_cell("laguna_toy_train", 100, 0.3, 0, root=root,
                          bench_json=root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    m = result["metrics"]
    assert m["train_items_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert set(result["not_compared"]) == {"loss2_gap"}


def test_float32_program_is_the_references_arithmetic(root):
    """Losses, per-leaf gradients and the change after three steps: the
    program's kernels' fallback, its rotary op and its sorted, gathered and
    scattered expert layer against masked softmax, written-out positions and
    experts applied densely."""
    import run
    import toy
    result = run.run_cell("laguna_toy_f32", 12345, 0.2, 0, root=root,
                          bench_json=root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert {k: lim for k, (_, lim) in result["compared"].items()} == toy.EXACT
    c = result["compared"]
    assert max(c[f"loss{i}_gap"][0] for i in (1, 2, 3)) < 1e-5
    assert c["grad_norm_gap"][0] < 2e-3 and c["change_norm_gap"][0] < 2e-3


def test_float32_program_through_the_flash_route(root, monkeypatch):
    """The same with the crossover under the toy's 32 positions: every
    layer goes to `flash_attention` (off the TPU its blockwise form), the
    sliding layers with their window."""
    import run
    monkeypatch.setenv("MXNET_FLASH_ATTENTION_MIN_SEQ", "16")
    result = run.run_cell("laguna_toy_f32", 54321, 0.2, 0, root=root,
                          bench_json=root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]


def test_control_and_general_faults_are_not_correct(root):
    import control
    row, = control.readings("laguna_toy_train", [101], 1, root=root,
                            bench_json=root + "/BENCHMARK.json",
                            require_tpu=False)
    limits = laguna_toy.LIMITS
    assert _over(row["program"], limits) == []
    for kind in ("control", "half_batch", "unchanged"):
        assert _over(row[kind], limits), kind
    assert "grad_norm_gap" in _over(row["control"], limits)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_is_not_correct(root, fault):
    """The reference in the program's place with one mechanism left out: the
    window, the positions, the routed sum, the gate."""
    rows = _trial().main(["faults", "--workload", "laguna_toy_train",
                          "--seeds", "100,102", "--faults", fault],
                         require_tpu=False, root=root,
                         bench_json=root + "/BENCHMARK.json")
    for row in rows:
        assert "grad_norm_gap" in _over(row[fault], laguna_toy.LIMITS), row


def test_the_expert_layers_report_their_routing_as_state(root):
    """`laguna_trial.py routing`: the toy trained as a run drives it, each
    expert layer's `routing` state read every few steps: the assignments
    that fell on the experts held, their largest and mean load, and whether
    the exact dense path ran. No step reads it back."""
    rows = _trial().main(["routing", "--workload", "laguna_toy_train",
                          "--seeds", "100", "--steps", "10"],
                         require_tpu=False, root=root,
                         bench_json=root + "/BENCHMARK.json")
    assert [r["step"] for r in rows] == [1, 2, 3, 5, 10]
    cell = cells.Cell("laguna_toy_train", root=root,
                      bench_json=root + "/BENCHMARK.json")
    tokens = cell.traffic["batch"] * cell.traffic["seq"]
    held, k = cell.config["num_experts"], cell.config["num_experts_per_tok"]
    for row in rows:
        assert len(row["routing"]) == 4            # the four sparse layers
        for kept, max_load, mean_load, exact in row["routing"]:
            assert 0 < kept <= tokens * min(k, held) and kept == int(kept)
            assert mean_load == kept / held <= max_load <= tokens
            assert exact in (0.0, 1.0)


def test_param_spec_is_the_programs_leaves():
    import mxnet_tpu as mx
    cell = cells.Cell(CELL)
    cfg = cell.config
    spec = cell.module("reference").param_spec(cfg)
    net, _ = cell.module("programs").build(cfg, cell.traffic)
    with mx.cpu():
        shapes = [tuple(p.shape) for p in net.collect_params().values()]
    assert shapes == [tuple(s[1]) for s in spec]
    # the parameters trained: an expert layer's `routing` (four numbers of
    # state, its last report) is a leaf and not one of them
    assert [s[0].split(".")[-1] for s in spec if not s[3]] == ["routing"] * 4
    count = lambda keep: sum(int(np.prod(s[1])) for s in spec
                             if s[3] and keep(s[0]))
    assert count(lambda n: True) == 811_017_216 \
        == cfg["parameters"]["total"]                    # ISSUE 32's table
    assert count(lambda n: n.startswith("layer0.")) == 157_440_000
    assert count(lambda n: n.startswith("layer1.")) == 148_862_976
    assert count(lambda n: n.startswith("layer4.")) == 129_914_880
    assert count(lambda n: n in ("embed", "head")) == 77_070_336
    mixer = ("query", "key", "value", "gate", "proj")
    assert count(lambda n: n.split(".")[-1] in mixer
                 and n.startswith("layer0.")) == 44_187_648
    assert count(lambda n: n.split(".")[-1] in mixer
                 and n.startswith("layer2.")) == 63_135_744


# -- the shares add up ------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_references_layer():
    """One expert layer at toy size: the routed parts that the 8 shares of
    8 experts give, from the reference told which experts it holds and from
    the program's op, plus the shared expert once, equal what the uncut
    reference (all 64 experts held) gives for the whole layer."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe
    ref = cells.load_module("reference", "laguna")
    cfg = dict(laguna_toy.CONFIG)
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    c, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rs = np.random.RandomState(7)
    arr = lambda scale, *s: jnp.asarray(rs.normal(0, scale, s)
                                        .astype(np.float32))
    v = arr(1, 2, 40, c)
    p = {"router": arr(0.3, routed, c),
         "experts_gate_up": arr(0.1, routed, c, 2 * fe),
         "experts_down": arr(0.1, routed, fe, c),
         "shared1": arr(0.1, 2 * fe, c), "shared2": arr(0.1, c, fe)}
    ident = lambda a: a
    whole = ref._experts(v, p, dict(cfg, num_experts=routed), ident)
    shared = ref._swiglu(v, p["shared1"], p["shared2"], ident)
    from_reference = from_program = 0
    for first in range(0, routed, held):
        share = dict(p, experts_gate_up=p["experts_gate_up"][first:first + held],
                     experts_down=p["experts_down"][first:first + held])
        from_reference += ref._experts(
            v, share, dict(cfg, first_held_expert=first), ident) - shared
        from_program += moe.held_moe_ffn(
            v.reshape(-1, c), p["router"], share["experts_gate_up"],
            share["experts_down"], top_k=cfg["num_experts_per_tok"],
            published_experts=routed, first_held=first,
            scaling=cfg["moe_routed_scaling_factor"]).reshape(v.shape)
    for parts in (from_reference, from_program):
        np.testing.assert_allclose(np.asarray(parts + shared),
                                   np.asarray(whole), rtol=2e-5, atol=2e-6)
    # and one share alone is not the layer
    assert float(jnp.abs(from_program).max()) > 1e-2


# -- the configuration ------------------------------------------------------------

# poolside/Laguna-S-2.1 config.json as the catalog beside the `model-configs`
# guide holds it
SOURCE = "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"] * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0,
}


def test_configuration_keeps_every_published_number():
    cell = cells.Cell(CELL)
    cfg = cell.config
    reduced = {"num_hidden_layers", "layer_types", "mlp_layer_types",
               "num_experts", "vocab_size"}
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == "laguna_s_2_1")
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == SOURCE == cfg["source"]
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 5
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:5]
    assert cfg["mlp_layer_types"] == PUBLISHED["mlp_layer_types"][:5]
    assert cfg["num_experts"] == 8 and cfg["vocab_size"] * 8 == 100352
    assert "32 chips" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 6 and len(cfg["departures"]) >= 4
    assert cells.reduced_faults(entry, cfg) == []
    assert cell.traffic["batch"] == 1 and cell.traffic["seq"] == 8192
    assert cell.traffic["pool"] == 8 and cell.reference == {"donate": True}


# -- the operation counts -----------------------------------------------------------

def test_flops_against_the_real_models_matrices():
    import mxnet_tpu as mx
    cell = cells.Cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    net, _ = cell.module("programs").build(cfg, traffic)
    with mx.cpu():
        params = net.collect_params()
    seq = tokens = traffic["seq"]
    # every projection (the gates, the shared experts, the dense feed-forward
    # among them) is applied once to every token: 2 ops a weight; so are the
    # routers and the head; an expert to the rows routed to it, of which
    # 10 x 8/256 a token are expected here
    dense = sum(2 * int(np.prod(p.shape)) for n, p in params.items()
                if ("dense" in n and n.endswith("weight"))
                or n.endswith("router_weight"))
    head = 2 * int(np.prod(net.head_weight.shape))
    routed = sum(2 * int(np.prod(p.shape)) // 8 for n, p in params.items()
                 if "experts_" in n) * 10 * 8 / 256
    full = 2 * 4 * 48 * 128 * seq / 2
    w = 512
    seen = (sum(range(1, w + 1)) + (seq - w) * w) / seq
    window = 3 * 4 * 72 * 128 * seen
    flops = cell.module("flops")
    assert flops.train_flops_per_item(cfg, traffic) == pytest.approx(
        3 * (dense + head + routed + full + window), rel=1e-12)
    assert flops.mxu_flops_per_item(cfg, traffic, exclude_attention=True) \
        == pytest.approx(3 * (dense + head), rel=1e-12)
    assert flops.mxu_flops_per_item(cfg, traffic) \
        == flops.train_flops_per_item(cfg, traffic)
    # ISSUE 32's counts: 1.22 GFLOP a token forward (dense products 0.96,
    # full attention 0.20, window 0.06), 3.67 with the backward, 30 TFLOP a step
    assert (dense + head + routed) / 1e9 == pytest.approx(0.965, abs=5e-3)
    assert full / 1e9 == pytest.approx(0.201, abs=1e-3)
    assert window / 1e9 == pytest.approx(0.055, abs=1e-3)
    per_item = flops.train_flops_per_item(cfg, traffic)
    assert per_item / 1e9 == pytest.approx(3.66, abs=0.01)
    assert per_item * tokens / 1e12 == pytest.approx(30.0, abs=0.1)
    ops, nbytes = flops.window_attention_kernel_work(cfg, traffic)
    assert ops == pytest.approx(3 * window * tokens, rel=1e-12)
    assert nbytes == 3 * 2 * tokens * 128 * (4 * 72 + 4 * 8)
    ops_f, nbytes_f = flops.full_attention_kernel_work(cfg, traffic)
    assert ops_f == pytest.approx(3 * full * tokens, rel=1e-12)
    assert nbytes_f == 2 * 2 * tokens * 128 * (4 * 48 + 4 * 8)
    ops_e, nbytes_e = flops.moe_experts_work(cfg, traffic)
    assert ops_e == pytest.approx(3 * routed * tokens, rel=1e-12)
    rows = tokens * 10 * 8 / 256
    assert rows == 2560
    assert nbytes_e == 4 * (4 * rows * 3072 * 2 + 3 * 8 * 9_437_184 * 2)
    # all three are bound by their operations on the v5e, not by their bytes
    v5e = cells.peaks("TPU v5 lite")
    for o, n in ((ops, nbytes), (ops_f, nbytes_f), (ops_e, nbytes_e)):
        assert o / v5e["flops_per_s"] > n / v5e["bytes_per_s"]


# -- the seven per-layer metrics on a trace recorded on the chip ---------------------

METRICS = {
    "window_attention_time_share": ("time_share",
                                    {"scope_has": ["mx.attn.window"]}),
    "full_attention_time_share": ("time_share",
                                  {"scope_has": ["mx.attn.full"]}),
    "moe_time_share": ("time_share", {"scope_has": ["mx.moe"],
                                      "name_has": ["ragged-dot"]}),
    "window_flash_roofline": ("kernel_roofline",
                              {"scope_has": ["mx.flash.window"],
                               "work": "window_attention_kernel_work"}),
    "moe_experts_roofline": ("kernel_roofline",
                             {"scope_has": ["mx.moe.experts"],
                              "name_has": ["ragged-dot"],
                              "work": "moe_experts_work"}),
    "full_flash_roofline": ("kernel_roofline",
                            {"scope_has": ["mx.flash.full"],
                             "work": "full_attention_kernel_work"}),
    "laguna_recompute_time_share": ("time_share",
                                    {"scope_has": ["rematted_computation"]}),
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
    for name in ("mx.embed", "mx.attn.full/mx.rope", "mx.attn.window/mx.rope",
                 "mx.attn.full/mx.flash.full", "mx.ffn", "mx.head",
                 "mx.attn.window/mx.flash.window", "mx.moe/mx.moe.route",
                 "mx.moe/mx.moe.shared",
                 "rematted_computation/mx.attn.window/mx.flash.window"):
        assert any(name in s for s in scopes), name
    # the experts' products lie behind the `cond` and its recomputed branch
    experts = {s for s in scopes if "mx.moe.experts" in s}
    assert experts and all("mx.moe" in s.split("/cond/branch_")[0]
                           for s in experts)
    # the Mosaic calls of the flash kernels carry their scope
    flash = [o for o in ops if "mx_flash" in o[0]]
    assert flash and all("mx.flash." in o[4] for o in flash)
    assert {o[1] for o in flash} == {"custom-call:tpu_custom_call"}
    # by the recorded steps (the toy trains at 1e-4 for hundreds of steps
    # before the profiler starts) the toy's routing has collapsed and the
    # dense path runs: plain dots in a loop, inside the scope. The sorted
    # path's grouped products are, at the cell's widths, XLA's own kernel
    # (`ragged-dot-none.N`), whose events carry no scope: the next test
    assert all("branch_1_fun" in s for s in experts)
    assert not [o for o in ops if "ragged-dot" in o[0]]


def test_the_experts_metrics_find_the_grouped_products_by_name():
    """The cell's compiled step (for a described v5e) names the grouped
    products `ragged-dot-none.N` and `ragged-dot-metadata.N` with that same
    text as `op_name`: no scope. The two expert-layer metrics match them by
    name; the attention metrics do not."""
    import trace as T
    ops = [("ragged-dot-none.9", "custom-call:tpu_custom_call", 0, 700,
            "ragged-dot-none"),
           ("ragged-dot-metadata.2", "custom-call:tpu_custom_call", 700, 50,
            "ragged-dot-metadata"),
           ("fusion.7", "fusion:kLoop", 750, 250,
            "jit(step)/jvp(mx.moe)/mx.moe.route/top_k:"),
           ("mx_flash_fwd.3", "custom-call:tpu_custom_call", 1000, 1000,
            "jit(step)/jvp(mx.attn.window)/mx.flash.window/jit(_fwd)/"
            "mx_flash_fwd/pallas_call:")]
    cell = cells.Cell(CELL)
    ns = lambda name: sum(o[3] for o in ops if T.matches(
        o, *T.group_of(cell.layer_metric(name)["params"])))
    assert ns("moe_time_share") == 1000
    assert ns("moe_experts_roofline") == 750
    assert ns("window_flash_roofline") == ns("window_attention_time_share") \
        == 1000
    assert ns("full_attention_time_share") == 0
