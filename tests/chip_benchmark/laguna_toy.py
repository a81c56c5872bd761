"""The toy cells of the `laguna` family (window and full attention with
per-layer head counts, gate and rotary positions; routed experts of which a
share is held, beside a shared one), laid out beside toy.py's as data files
alone: what test_laguna.py runs on the CPU and tools/laguna_trial.py records
a trace of on the chip."""
import json
import os

import toy

_KINDS = ["full_attention", "sliding_attention", "sliding_attention",
          "sliding_attention"] * 2
_MLPS = ["dense"] + ["sparse"] * 7
CONFIG = {
    "family": "laguna", "model_type": "laguna", "hidden_size": 64,
    "head_dim": 16, "num_key_value_heads": 2, "num_attention_heads": 4,
    # the per-layer lists keep their published length (8): 5 layers are read
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6, 6, 6],
    "gating_types": ["per_head"] * 8, "gating": "per-head",
    "num_hidden_layers": 5, "layer_types": _KINDS[:5],
    "mlp_layer_types": _MLPS[:5], "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "first_held_expert": 0,
    "tie_word_embeddings": False, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "published": {"num_hidden_layers": 8, "layer_types": _KINDS,
                  "mlp_layer_types": _MLPS, "num_experts": 64,
                  "vocab_size": 2048},
    "deployment": "64 experts a layer over 8 chips, 8 here; table and head "
                  "over 8 chips",
    "initializer_range": 0.05,
    "precision": toy.PRECISION, "optimizer": toy.ADAMW}
CONFIGS = {"laguna_toy": CONFIG,
           "laguna_toy_f32": dict(CONFIG, precision={"compute": "float32",
                                                     "master": "float32"})}
# Readings on the CPU (PR 32): the program over seeds 100..107 reads at most
# loss1 1.1e-4, loss2 1.7e-4, loss3 1.1e-4, gradient 0.0132, change 0.0097,
# whole gradient 2.2e-3, whole change 1.4e-3; over seeds 100..102 the FP8
# control reads at least loss1 9.2e-4, loss3 6.3e-4, gradient 0.069 (its
# loss2 5.8e-5 lies under the program's: not held); half a batch at least
# gradient 0.49, whole gradient 0.37; the window ignored at least gradient
# 0.18, loss1 1.9e-3; positions dropped gradient 0.17; the routed sum dropped
# gradient 1.0, change 0.999, whole change 0.35; the gate dropped gradient
# 1.0, change 0.36. (At 1/sqrt(64) = 0.125 the bfloat16 program itself reads
# loss gaps of 1e-3, with dense feed-forwards too: the toy has no multiplier
# that damps its logits.)
LIMITS = {"loss1_gap": 3e-4, "loss2_gap": None, "loss3_gap": 3e-4,
          "grad_norm_gap": 0.03, "change_norm_gap": 0.03,
          "grad_total_gap": 6e-3, "change_total_gap": 5e-3,
          "feed_mismatch": 0}
CELLS = {"laguna_toy_train": ("laguna_toy", LIMITS),
         "laguna_toy_f32": ("laguna_toy_f32", toy.EXACT)}


def lay_out(root):
    """toy.py's benchmark under `root` with this family's two cells added;
    returns `root`."""
    toy.lay_out(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for name, cfg in CONFIGS.items():
        toy._write(root, f"configs/{name}.json", dict(cfg, name=name))
    for name, (cfg, limits) in CELLS.items():
        toy._write(root, f"limits/{name}.json",
                   {"limits": limits, "reference": {"donate": True}})
        bench["workloads"].append(
            {"name": name, "config": cfg, "traffic": "toy_tokens",
             "chips": 1, "why": "toy"})
    toy._write(root, "BENCHMARK.json", bench)
    return root
