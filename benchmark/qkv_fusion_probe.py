"""Fused-vs-unfused QKV A/B on the real chip (VERDICT r3 'try fused QKV
before conceding BERT-base's ceiling').

The model already projects Q,K,V as ONE (768 -> 3*768) matmul
(mxnet_tpu/models/bert.py SelfAttention, the TPU analog of the reference's
interleaved-QKV GPU kernels — reference src/operator/contrib/
transformer.cc:650-819). This probe quantifies what that fusion buys by
training BERT-base MLM both ways through the same fused trainer and
publishing tokens/s for each.

Run on the chip: `python benchmark/qkv_fusion_probe.py`
Prints one JSON line per variant.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

BATCH = int(os.environ.get("QKV_BATCH", 16))
SEQ = int(os.environ.get("QKV_SEQ", 512))
STEPS = int(os.environ.get("QKV_STEPS", 20))
VOCAB = int(os.environ.get("QKV_VOCAB", 8192))


def _loss(logits, labels):
    import jax
    import jax.numpy as jnp
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def bench_variant(fused: bool):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.bert import BertModel
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    from benchmark.bench_util import measure_stabilized

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    net = BertModel(vocab_size=VOCAB, fused_qkv=fused)
    with mx.cpu():
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, SEQ), ctx=mx.cpu(), dtype="int32"))
    trainer = DataParallelTrainer(
        net, _loss, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-4}, mesh=mesh,
        dtype="bfloat16")
    rs = np.random.RandomState(0)
    x = nd.array(rs.randint(0, VOCAB, (BATCH, SEQ)), dtype="int32")
    y = nd.array(rs.randint(0, VOCAB, (BATCH, SEQ)), dtype="int32")

    def once():
        t0 = time.perf_counter()
        losses = trainer.run_steps(x, y, STEPS)
        float(losses[-1])
        return time.perf_counter() - t0

    dt = measure_stabilized(once, max_warm=10)
    return BATCH * SEQ * STEPS / dt


def main():
    from mxnet_tpu import engine
    engine.enable_compile_cache()
    results = {}
    for fused in (True, False):
        tok_s = bench_variant(fused)
        results["fused" if fused else "unfused"] = round(tok_s, 1)
        print(json.dumps({"variant": "fused_qkv" if fused else "unfused_qkv",
                          "tokens_s": round(tok_s, 1)}), flush=True)
    if results.get("unfused"):
        print(json.dumps({"fused_speedup":
                          round(results["fused"] / results["unfused"], 4)}))


if __name__ == "__main__":
    main()
