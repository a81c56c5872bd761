"""The toy cells of the hybrid decoder's family (`granite_hybrid`), laid out
beside toy.py's as data files alone: what test_granite_hybrid.py runs on the
CPU and tools/granite_trial.py records a trace of on the chip."""
import json
import os

import toy

CONFIG = {
    "family": "granite_hybrid", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["mamba", "attention", "mamba"], "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_chunk_size": 8,
    "mamba_conv_bias": True, "mamba_expand": 2, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
    "logits_scaling": 8, "rms_norm_eps": 1e-5,
    # 1/sqrt(64): projections of unit variance, as 0.02 gives at 2048
    "initializer_range": 0.125,
    "precision": toy.PRECISION, "optimizer": toy.ADAMW}
CONFIGS = {"granite_toy": CONFIG,
           "granite_toy_f32": dict(CONFIG, precision={"compute": "float32",
                                                      "master": "float32"})}
# Readings on the CPU (PR 28): the program over seeds 100..107 reads at most
# loss1 1.1e-5, loss3 1.8e-5, gradient 0.0049, change 0.0100, whole gradient
# 4.8e-4, whole change 1.1e-4; over seeds 100..102 the FP8 control reads at
# least loss1 4.3e-5, loss3 8.7e-5, gradient 0.038, whole gradient 2.7e-3,
# whole change 8.2e-4 (its loss2 9.5e-6 lies under the program's 1.5e-5: not
# held); the state emptied at every chunk boundary at least loss1 9.9e-5,
# loss3 6.9e-5, gradient 0.118, change 0.043, whole gradient 5.0e-3; half a
# batch at least gradient 0.52, change 0.046.
LIMITS = {"loss1_gap": 3e-5, "loss2_gap": None, "loss3_gap": 4.5e-5,
          "grad_norm_gap": 0.015, "change_norm_gap": 0.03,
          "grad_total_gap": 1.5e-3, "change_total_gap": 3.5e-4,
          "feed_mismatch": 0}
CELLS = {"granite_toy_train": ("granite_toy", LIMITS),
         "granite_toy_f32": ("granite_toy_f32", toy.EXACT)}


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
