"""The toy cells of the `keye_vl2` family (grouped-KV attention over the
keys a learned indexer selects, a QK norm, sectioned rotary positions;
routed experts of which a share is held, no shared one), laid out beside
toy.py's as data files alone: what test_keye_vl2.py runs on the CPU and
tools/keye_trial.py records a trace of on the chip."""
import json
import os

import toy

CONFIG = {
    "family": "keye_vl2", "model_type": "KeyeVL2", "hidden_size": 64,
    "head_dim": 16, "num_key_value_heads": 2, "num_attention_heads": 4,
    "num_hidden_layers": 3, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 4, "num_local_experts": 4,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "use_sliding_window": False, "sliding_window": None,
    "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    # a query keeps 8 keys of the 32 of a toy sequence, scored in chunks of 8
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                  "q_chunk_size": 8, "topk": 8},
    "first_held_expert": 0, "tie_word_embeddings": False, "vocab_size": 256,
    "rms_norm_eps": 1e-6,
    "published": {"num_hidden_layers": 8, "num_experts": 16,
                  "num_local_experts": 16, "vocab_size": 2048},
    "deployment": "16 experts a layer over 4 chips, 4 here; table and head "
                  "over 8 chips",
    "initializer_range": 0.05,
    "precision": toy.PRECISION, "optimizer": toy.ADAMW}
CONFIGS = {"keye_toy": CONFIG,
           "keye_toy_f32": dict(CONFIG, precision={"compute": "float32",
                                                   "master": "float32"})}
# Readings on the CPU (PR 34): the program over seeds 100..107 reads at most
# gradient 0.054 (the keys nearest the cut of 8 that bfloat16 flips, in a row
# of 32), change 0.020, whole gradient 0.010, losses 1.1e-3; over seeds
# 100..102 the FP8 control reads at least gradient 0.086, half a batch 0.51
# (change 0.14), an unchanged state change 1; the selection ignored at least
# gradient 0.147, the first keys in its place 0.20, the QK norm dropped 0.87,
# positions dropped 0.111, the routed sum dropped 1.0. At this size the
# losses and the whole-tensor numbers of the program reach the control's:
# not held.
LIMITS = {"loss1_gap": None, "loss2_gap": None, "loss3_gap": None,
          "grad_norm_gap": 0.07, "change_norm_gap": 0.06,
          "grad_total_gap": None, "change_total_gap": None,
          "feed_mismatch": 0}
CELLS = {"keye_toy_train": ("keye_toy", LIMITS),
         "keye_toy_f32": ("keye_toy_f32", toy.EXACT)}


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
