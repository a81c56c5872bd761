"""A toy benchmark as data files: what a later PR does to add cells, done in
a temporary directory. The code (readers, flops, reference, programs) is the
benchmark's own, found by the names these files give."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CHIP = os.path.join(REPO, "benchmark", "chip")

PRECISION = {"compute": "bfloat16", "master": "float32"}
ADAMW = {"name": "adamw", "learning_rate": 1e-4, "beta1": 0.9,
         "beta2": 0.999, "epsilon": 1e-8, "wd": 1e-6}
SGD = {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}

CONFIGS = {
    "bert_toy": {
        "family": "bert", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 2, "intermediate_size": 128,
        "vocab_size": 512, "type_vocab_size": 2, "initializer_range": 0.02,
        "max_position_embeddings": 64, "hidden_dropout_prob": 0.0,
        "attention_probs_dropout_prob": 0.0, "layer_norm_eps": 1e-5,
        "precision": PRECISION, "optimizer": ADAMW},
    "resnet_toy": {
        "family": "resnet", "layers": [1, 1], "channels": [8, 16, 32],
        "classes": 10, "bn_eps": 1e-5, "precision": PRECISION,
        "optimizer": SGD},
}
CONFIGS["bert_toy_f32"] = dict(
    CONFIGS["bert_toy"], precision={"compute": "float32",
                                    "master": "float32"})
CONFIGS["resnet_toy_f32"] = dict(
    CONFIGS["resnet_toy"], precision={"compute": "float32",
                                      "master": "float32"})

TRAFFIC = {
    "toy_tokens": {"kind": "train", "items": "tokens", "batch": 8, "seq": 32,
                   "pool": 4, "mesh": {"dp": 1}, "chips": 1},
    "toy_tokens_dp4": {"kind": "train", "items": "tokens", "batch": 8,
                       "seq": 32, "pool": 4, "mesh": {"dp": 4}, "chips": 4},
    "toy_images": {"kind": "train", "items": "images", "batch": 8,
                   "image": 32, "pool": 4, "mesh": {"dp": 1}, "chips": 1},
}

# name -> (config, traffic, limits, reference row blocks). The limits were
# set as the real cells' are (PERF.md): above what the program reads at this
# size over a dozen seeds, below what the control and the faults read.
# Readings on the CPU, seeds 100..111 (PR 24): bert_toy program at most
# loss 2.2e-5, gradient 0.079, change 0.035; FP8 control at least loss2 6.9e-5,
# change 0.135; half batch at least gradient 0.45. resnet_toy program at most
# loss 1.3e-4, gradient 0.40, change 0.22; half batch at least loss 7e-3,
# gradient 0.93, change 0.73 (its control overlaps the program at this size:
# batch statistics over 8 rows; the real cell's readings are in PERF.md).
BERT_LIMITS = {"loss1_gap": 4.5e-5, "loss2_gap": 4.5e-5, "loss3_gap": 4.5e-5,
               "grad_norm_gap": 0.2, "change_norm_gap": 0.07,
               "feed_mismatch": 0}
RESNET_LIMITS = {"loss1_gap": 1e-3, "loss2_gap": 1e-3, "loss3_gap": 1e-3,
                 "grad_norm_gap": 0.6, "change_norm_gap": 0.45,
                 "feed_mismatch": 0}
EXACT = {"loss1_gap": 1e-5, "loss2_gap": 1e-5, "loss3_gap": 1e-5,
         "grad_total_gap": 2e-3, "change_total_gap": 2e-3,
         "grad_norm_gap": 2e-3, "change_norm_gap": 2e-3, "feed_mismatch": 0}
CELLS = {
    "bert_toy_train": ("bert_toy", "toy_tokens", BERT_LIMITS, 1),
    "bert_toy_dp4": ("bert_toy", "toy_tokens_dp4", BERT_LIMITS, 2),
    "resnet_toy_train": ("resnet_toy", "toy_images", RESNET_LIMITS, 1),
    "bert_toy_f32": ("bert_toy_f32", "toy_tokens", EXACT, 1),
    "resnet_toy_f32": ("resnet_toy_f32", "toy_images", EXACT, 1),
}


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def lay_out(root):
    """Write the toy benchmark under `root`; returns `root`."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, cfg in CONFIGS.items():
        _write(root, f"configs/{name}.json", dict(cfg, name=name))
    for name, mix in TRAFFIC.items():
        _write(root, f"traffic/{name}.json", dict(mix, name=name))
    bench["workloads"] = []
    for name, (cfg, mix, limits, blocks) in CELLS.items():
        _write(root, f"limits/{name}.json",
               {"limits": limits, "reference": {"row_blocks": blocks}})
        bench["workloads"].append(
            {"name": name, "config": cfg, "traffic": mix,
             "chips": TRAFFIC[mix]["chips"], "why": "toy"})
    for metric in bench["per_layer"]:
        metric.pop("workloads", None)   # every toy cell reports every metric
        with open(os.path.join(CHIP, "layer_metrics",
                               metric["name"] + ".json")) as f:
            _write(root, f"layer_metrics/{metric['name']}.json", json.load(f))
    with open(os.path.join(CHIP, "peaks.json")) as f:
        _write(root, "peaks.json", json.load(f))
    _write(root, "BENCHMARK.json", bench)
    return root
