"""BERT-base bs16xT512 step ablation — attribute the gap between the
whole-model 84 TF/s and the ~172 TF/s its GEMM shapes sustain in
isolation (benchmark/results/bert_gemm_table.md).

Cuts, all jitted, bf16 compute, same lowering as the fused trainer:

  fwd          forward only
  fwd+bwd      value_and_grad, every grad kept live
  full         DataParallelTrainer fused step (fwd+bwd+adamw)
  -attn        fwd+bwd with attention MIXING removed (qkv + out-proj
               GEMMs kept; scores/softmax/attend and the two transposes
               dropped) — the attention-overhead share
  -ln          fwd+bwd with every LayerNorm an identity — the
               normalization-reduction share
  -ce          fwd+bwd with the softmax-CE replaced by mean(logits)
               (vocab-head GEMM kept) — the loss-op share

Usage: python benchmark/bert_step_ablation.py          (real chip)
       BA_QUICK=1 ... (tiny model, logic smoke on CPU)
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

QUICK = os.environ.get("BA_QUICK") == "1"
BATCH = int(os.environ.get("BERT_BATCH", 2 if QUICK else 16))
SEQ = int(os.environ.get("BERT_SEQ", 64 if QUICK else 512))
VOCAB = 512 if QUICK else 8192
REPS = int(os.environ.get("ABL_REPS", 2 if QUICK else 10))


def build_net():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import bert_base, bert_tiny
    with mx.cpu():
        net = (bert_tiny if QUICK else bert_base)(vocab_size=VOCAB)
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, SEQ), ctx=mx.cpu(), dtype="int32"))
    return net


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.models import bert as bert_mod
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    from mxnet_tpu.parallel.data_parallel import _make_apply_fn
    from benchmark.bench_util import measure_stabilized
    from bench import _loss_tokens

    mx.engine.enable_compile_cache()
    rng = np.random.RandomState(0)
    x_np = rng.randint(1, VOCAB, (BATCH, SEQ)).astype(np.int32)
    y_np = rng.randint(1, VOCAB, (BATCH, SEQ)).astype(np.int32)

    from mxnet_tpu import random as _rng_mod

    def timed_fwd_bwd(net, loss_fn, bwd=True):
        plist = [p for p in net.collect_params().values()
                 if p._data is not None]
        apply_fn = _make_apply_fn(net, plist, train=True)
        params = [jnp.asarray(np.asarray(p._data._data)) for p in plist]
        key = np.asarray(_rng_mod.next_key_raw())
        x = jnp.asarray(x_np)
        y = jnp.asarray(y_np)

        def low(p):
            return p.astype(jnp.bfloat16) \
                if jnp.issubdtype(p.dtype, jnp.floating) else p

        def fwd_loss(ps, xi):
            out, _ = apply_fn(key, [low(p) for p in ps], xi)
            pred = out if not isinstance(out, tuple) else out[0]
            return loss_fn(pred, y)

        if bwd:
            @jax.jit
            def run(ps, xi):
                def body(acc, i):
                    l, gs = jax.value_and_grad(fwd_loss)(
                        [p + acc.astype(p.dtype) * 0 for p in ps], xi)
                    for g in gs:
                        l = l + jnp.sum(g.astype(jnp.float32)) * 1e-12
                    return l, None
                acc, _ = lax.scan(body, jnp.float32(0.0), jnp.arange(REPS))
                return acc
        else:
            @jax.jit
            def run(ps, xi):
                def body(acc, i):
                    return fwd_loss(ps, xi) + acc * 1e-12, None
                acc, _ = lax.scan(body, jnp.float32(0.0), jnp.arange(REPS))
                return acc

        def once():
            t0 = time.perf_counter()
            float(run(params, x))
            return time.perf_counter() - t0
        return measure_stabilized(once, max_warm=6) / REPS

    results = {}

    net = build_net()
    results["fwd_ms"] = timed_fwd_bwd(net, _loss_tokens, bwd=False) * 1e3
    results["fwd_bwd_ms"] = timed_fwd_bwd(net, _loss_tokens) * 1e3

    # full fused trainer step (bench.py's exact path)
    tr = DataParallelTrainer(
        net, _loss_tokens, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-4},
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        dtype="bfloat16")
    xb = nd.array(x_np, dtype="int32")
    yb = nd.array(y_np, dtype="int32")

    def once_full():
        t0 = time.perf_counter()
        losses = tr.run_steps(xb, yb, REPS)
        float(losses[-1])
        return time.perf_counter() - t0
    results["full_step_ms"] = measure_stabilized(once_full, max_warm=6) \
        / REPS * 1e3

    # -attn: keep qkv + out-proj GEMMs, drop the mixing
    orig_attn = bert_mod.SelfAttention.hybrid_forward

    def attn_no_mix(self, F, x, mask=None):
        B, T, C = x.shape
        out = self.qkv(x)[:, :, :C] if self._fused_qkv else self.q_proj(x)
        return self.proj(out)

    bert_mod.SelfAttention.hybrid_forward = attn_no_mix
    try:
        results["no_attn_mix_fwd_bwd_ms"] = \
            timed_fwd_bwd(build_net(), _loss_tokens) * 1e3
    finally:
        bert_mod.SelfAttention.hybrid_forward = orig_attn

    # -ln: every LayerNorm an identity
    orig_ln = nn.LayerNorm.hybrid_forward

    def ln_identity(self, F, x, gamma=None, beta=None):
        return x

    nn.LayerNorm.hybrid_forward = ln_identity
    try:
        results["no_ln_fwd_bwd_ms"] = \
            timed_fwd_bwd(build_net(), _loss_tokens) * 1e3
    finally:
        nn.LayerNorm.hybrid_forward = orig_ln

    # -ce: vocab-head GEMM kept, softmax-CE dropped
    def loss_mean(logits, labels):
        import jax.numpy as jnp2
        return jnp2.mean(logits.astype(jnp2.float32))

    results["no_ce_fwd_bwd_ms"] = timed_fwd_bwd(build_net(), loss_mean) * 1e3

    fb = results["fwd_bwd_ms"]
    results["attn_mix_share_ms"] = round(fb - results["no_attn_mix_fwd_bwd_ms"], 3)
    results["ln_share_ms"] = round(fb - results["no_ln_fwd_bwd_ms"], 3)
    results["ce_share_ms"] = round(fb - results["no_ce_fwd_bwd_ms"], 3)
    results["optimizer_share_ms"] = round(
        results["full_step_ms"] - fb, 3)
    results["bwd_share_ms"] = round(fb - results["fwd_ms"], 3)
    results = {k: (round(v, 3) if isinstance(v, float) else v)
               for k, v in results.items()}
    print(json.dumps({"metric": "bert_base_step_ablation",
                      "batch": BATCH, "seq": SEQ, **results}))


if __name__ == "__main__":
    main()
