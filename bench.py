"""Headline benchmark: ResNet-50 training throughput + MFU, batch 32.

Reference baseline: 109 img/s on 1x K80, batch 32
(example/image-classification/README.md:154; BASELINE.md training table).
Runs the fused data-parallel training step (forward+backward+update in one
jit) on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
"tflops" and "mfu" against the chip's nominal bf16 peak (197 TF/s, TPU v5e)
and the device it ran on ("platform", "device_kind", "n_devices", as jax
reports them). Unless BENCH_QUICK=1, four secondary configs run and land in
the same line under "extra": ResNet-50 at batch 256, BERT-base and
BERT-large MLM training (tokens/s + MFU; BASELINE.md north-star) and a
wide-conv control.

These are device metrics: without a TPU the run fails. The one exception is
an explicit JAX_PLATFORMS=cpu (a logic check of this file); the line is then
stamped "platform": "cpu" and carries no tflops/mfu field. A configuration
that raises ends the run with its traceback and a non-zero exit.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 109.0  # reference resnet-50 train, 1 device, batch 32
PEAK_BF16 = 197e12      # TPU v5e nominal bf16 peak FLOP/s

BATCH = int(os.environ.get("BENCH_BATCH", 32))
WARMUP = int(os.environ.get("BENCH_WARMUP", 1))
STEPS = int(os.environ.get("BENCH_STEPS", 60))
IMAGE = int(os.environ.get("BENCH_IMAGE", 224))
QUICK = os.environ.get("BENCH_QUICK") == "1"


def resnet50_train_flops_per_image(image=224):
    """Forward 7.64 GFLOP per 224^2 image at 2 FLOP/MAC; train = 3x
    (backward ~2x forward). Scales with spatial resolution.

    Rounds 1-4 used 4.089e9 here, labeled '2 FLOP/MAC' — that figure is
    actually the MAC count (the fvcore/torchvision \"4.1 GFLOPs\"
    convention counts multiply-accumulates), so reported TF/s and MFU
    were ~2x LOW. The direct per-conv inventory of the real model
    (benchmark/results/resnet_layer_ledger.md: every conv's
    N*C*K*k_h*k_w*H_out*W_out summed) gives 3.82 GMAC = 7.64 GFLOP
    forward, which this constant now reflects. BERT's formula below was
    already 2-FLOP/MAC and is unchanged."""
    return 3 * 7.64e9 * (image / 224.0) ** 2


def bert_train_flops_per_token(layers, hidden, ffn_mult, seq, vocab):
    """Per-token matmul FLOPs: per layer 24*H^2 (qkv/out/ffn at 4H) +
    4*T*H (scores + attention-weighted values), plus the 2*H*V vocab head;
    train = 3x forward."""
    per_layer = 24 * hidden * hidden * (ffn_mult / 4.0) + 4 * seq * hidden
    return 3 * (layers * per_layer + 2 * hidden * vocab)


def _loss_tokens(logits, labels):
    import jax
    import jax.numpy as jnp
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _stamp(devices=None):
    """The device a result was taken on, as jax reports it. Every line this
    file prints carries it."""
    import jax
    devs = list(devices) if devices is not None else jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def _emit(result, devices=None):
    print(json.dumps({**result, **_stamp(devices)}))


def _chip_device():
    """The one device the headline and its extras run on. They are device
    metrics, so anything but a TPU is an error — except under an explicit
    JAX_PLATFORMS=cpu, the logic check of this file."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"bench.py: jax's default platform is {dev.platform!r} "
            f"({dev.device_kind}), not 'tpu'. Throughput and MFU are device "
            "metrics; set JAX_PLATFORMS=cpu only to check this file's logic.")
    return dev


def _rates(flops_per_s, device):
    """tflops/mfu fields for a result taken on `device`: none off the TPU,
    where a FLOP rate against the v5e peak would mean nothing."""
    if device.platform != "tpu":
        return {}
    return {"tflops": round(flops_per_s / 1e12, 2),
            "mfu": round(flops_per_s / PEAK_BF16, 4)}


def _lane_devices(need):
    """The `need` devices a multi-device scenario lane builds its mesh on:
    the default backend's, else virtual CPU devices. main() stamps the
    lane's line with the same call, so the stamp names what actually ran."""
    import jax
    devs = jax.devices()
    if len(devs) < need:
        devs = jax.devices("cpu")
    assert len(devs) >= need, f"need {need} devices, have {len(devs)}"
    return devs[:need]


def _timed_steps(trainer, x, y, steps, warmup):
    """One compiled on-device lax.scan loop per call, closed by a host read
    of the last loss. Warm until two consecutive timings agree within 8%
    (first calls of a fresh executable run slow), then report min-of-3
    measured reps (benchmark/bench_util.py)."""
    from benchmark.bench_util import measure_stabilized

    def once():
        t0 = time.perf_counter()
        losses = trainer.run_steps(x, y, steps)
        float(losses[-1])
        return time.perf_counter() - t0

    return measure_stabilized(once, max_warm=max(warmup, 10))


def bench_resnet(batch, image, steps, warmup):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    dev = _chip_device()
    mesh = make_mesh({"dp": 1}, devices=[dev])
    # BENCH_S2D=1 swaps in the math-equivalent space-to-depth stem
    # (model_zoo resnet.SpaceToDepthStem) for A/B on the chip
    net = resnet50_v1(s2d_stem=os.environ.get("BENCH_S2D") == "1")
    # Initialize + deferred shape inference on CPU (ms-scale compiles);
    # the accelerator sees exactly one compile — the fused train step.
    with mx.cpu():
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, 3, image, image), ctx=mx.cpu()))
    trainer = DataParallelTrainer(
        net, _loss_tokens, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
        mesh=mesh, dtype=os.environ.get("BENCH_DTYPE", "bfloat16"))
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (batch, 3, image, image)).astype(np.float32))
    y = nd.array(rng.randint(0, 1000, (batch,)), dtype="int32")
    dt = _timed_steps(trainer, x, y, steps, warmup)
    img_s = batch * steps / dt
    flops = img_s * resnet50_train_flops_per_image(image)
    return {"img_s": round(img_s, 2), **_rates(flops, dev)}


def bench_bert(batch, seq, steps, warmup, large=False):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import bert_base, bert_large
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    vocab = int(os.environ.get("BERT_VOCAB", 8192))
    dev = _chip_device()
    mesh = make_mesh({"dp": 1}, devices=[dev])
    net = (bert_large if large else bert_base)(vocab_size=vocab)
    with mx.cpu():
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, seq), ctx=mx.cpu(), dtype="int32"))
    trainer = DataParallelTrainer(
        net, _loss_tokens, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-4}, mesh=mesh,
        dtype=os.environ.get("BENCH_DTYPE", "bfloat16"))
    rs = np.random.RandomState(0)
    x = nd.array(rs.randint(0, vocab, (batch, seq)), dtype="int32")
    y = nd.array(rs.randint(0, vocab, (batch, seq)), dtype="int32")
    dt = _timed_steps(trainer, x, y, steps, warmup)
    tok_s = batch * seq * steps / dt
    layers, hidden = (24, 1024) if large else (12, 768)
    flops = tok_s * bert_train_flops_per_token(layers, hidden, 4.0, seq,
                                               vocab)
    return {"tokens_s": round(tok_s, 1), **_rates(flops, dev)}


def bench_wide_conv(batch, steps, warmup, ch=768, hw=28):
    """Control: conv shapes that tile the MXU well (N=768 output
    channels), beside ResNet-50 bs32's small-N shapes."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    dev = _chip_device()
    mesh = make_mesh({"dp": 1}, devices=[dev])
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(ch, 3, padding=1, activation="relu"),
            gluon.nn.Conv2D(ch, 3, padding=1, activation="relu"),
            gluon.nn.Conv2D(ch, 3, padding=1, activation="relu"),
            gluon.nn.Conv2D(ch, 3, padding=1, activation="relu"),
            gluon.nn.GlobalAvgPool2D(), gluon.nn.Flatten(),
            gluon.nn.Dense(1000))
    with mx.cpu():
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, 3, hw, hw), ctx=mx.cpu()))
    trainer = DataParallelTrainer(
        net, _loss_tokens, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05}, mesh=mesh,
        dtype=os.environ.get("BENCH_DTYPE", "bfloat16"))
    rs = np.random.RandomState(0)
    x = nd.array(rs.uniform(-1, 1, (batch, 3, hw, hw)).astype(np.float32))
    y = nd.array(rs.randint(0, 1000, (batch,)), dtype="int32")
    dt = _timed_steps(trainer, x, y, steps, warmup)
    per_img = 2 * 9 * hw * hw * (3 * ch + 3 * ch * ch) + 2 * ch * 1000
    flops = 3 * per_img * batch * steps / dt
    return {"img_s": round(batch * steps / dt, 1), **_rates(flops, dev)}


def _make_train_net(body):
    """Wrap body+softmax-CE loss into one HybridBlock so the whole training
    forward (incl. loss) is a single compiled artifact."""
    from mxnet_tpu import gluon

    class _TrainNet(gluon.HybridBlock):
        def __init__(self, b):
            super().__init__()
            self.body = b
            self.ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, x, y):
            return self.ce(self.body(x), y).mean()

    return _TrainNet(body)


def _eager_train_loop(net, x, y, steps, trainer=None, lr=0.05):
    """One eager-gluon training loop: record -> forward -> backward ->
    trainer.step. This is the hot path the vjp-artifact refactor targets
    (DataParallelTrainer fuses the whole step separately)."""
    from mxnet_tpu import autograd, gluon

    if trainer is None:
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": lr, "momentum": 0.9})
    loss = None
    for _ in range(steps):
        with autograd.record():
            loss = net(x, y)
        loss.backward()
        trainer.step(x.shape[0])
    return loss, trainer


def bench_train_step(steps, warmup):
    """Eager train-step throughput + recompile accounting for a small MLP
    and a conv(ResNet-ish) block, fused residual-caching backward vs the
    MXNET_TPU_REMAT_BWD=1 recompute-forward baseline."""
    import os as _os
    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon
    from mxnet_tpu import engine

    rs = np.random.RandomState(0)

    def mlp():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(64))
        return net

    def resnet_block():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(64, 3, padding=1),
                gluon.nn.BatchNorm(),
                gluon.nn.Activation("relu"),
                gluon.nn.Conv2D(64, 3, padding=1),
                gluon.nn.BatchNorm(),
                gluon.nn.GlobalAvgPool2D(),
                gluon.nn.Flatten(),
                gluon.nn.Dense(10))
        return net

    def run(make_net, x, y, remat):
        prev = _os.environ.pop("MXNET_TPU_REMAT_BWD", None)
        if remat:
            _os.environ["MXNET_TPU_REMAT_BWD"] = "1"
        try:
            net = _make_train_net(make_net())
            net.initialize()
            net(x, y)  # shape inference
            net.hybridize()
            # fresh artifact accounting per run (a later run would otherwise
            # adopt the earlier run's shared executables and report 0)
            engine.clear_compilation_cache()
            engine.reset_stats()
            _, trainer = _eager_train_loop(net, x, y, warmup)
            assert engine.cache_stats()["compiles"] >= 1
            warm_stats = engine.cache_stats()
            t0 = time.perf_counter()
            out, _ = _eager_train_loop(net, x, y, steps, trainer=trainer)
            out.asnumpy()
            dt = time.perf_counter() - t0
            stats = engine.cache_stats()
            return {
                "steps_s": round(steps / dt, 2),
                "compiles": stats["compiles"],
                "retraces_in_measured_loop":
                    stats["traces"] - warm_stats["traces"],
            }
        finally:
            _os.environ.pop("MXNET_TPU_REMAT_BWD", None)
            if prev is not None:
                _os.environ["MXNET_TPU_REMAT_BWD"] = prev

    x_mlp = nd.array(rs.uniform(-1, 1, (256, 512)).astype(np.float32))
    y_mlp = nd.array(rs.randint(0, 64, (256,)), dtype="int32")
    x_cnn = nd.array(rs.uniform(-1, 1, (16, 3, 32, 32)).astype(np.float32))
    y_cnn = nd.array(rs.randint(0, 10, (16,)), dtype="int32")

    fused = run(mlp, x_mlp, y_mlp, remat=False)
    recompute = run(mlp, x_mlp, y_mlp, remat=True)
    rb_fused = run(resnet_block, x_cnn, y_cnn, remat=False)
    rb_recompute = run(resnet_block, x_cnn, y_cnn, remat=True)
    return {
        "metric": "train_step_mlp_steps_s",
        "value": fused["steps_s"],
        "unit": "steps/s",
        # baseline = the recompute-forward backward this refactor replaced
        "vs_baseline": round(fused["steps_s"]
                             / max(recompute["steps_s"], 1e-9), 3),
        "extra": {
            "mlp_fused": fused,
            "mlp_recompute_baseline": recompute,
            "resnet_block_fused": rb_fused,
            "resnet_block_recompute_baseline": rb_recompute,
        },
    }


def bench_telemetry_overhead(steps, warmup):
    """A/B the eager train loop with telemetry disabled vs enabled on the
    CPU artifact bench (MLP, fused-vjp path): proves the instrumented hot
    path (trainer.step metrics + engine FLOPs accounting + kvstore comm
    scopes + memory sampling) stays under ~2% of step time. Artifact-build
    cost capture (cost_analysis lower+compile) happens during warmup, so
    the measured window is pure steady-state overhead."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, telemetry
    from mxnet_tpu import engine

    rs = np.random.RandomState(0)

    def mlp():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(64))
        return net

    x = nd.array(rs.uniform(-1, 1, (256, 512)).astype(np.float32))
    y = nd.array(rs.randint(0, 64, (256,)), dtype="int32")
    net = _make_train_net(mlp())
    net.initialize()
    net(x, y)
    net.hybridize()

    def measure(enabled, trainer=None, reps=3):
        telemetry.enable() if enabled else telemetry.disable()
        # warmup covers compiles AND (enabled) the one-time cost_analysis
        # capture; measured window is steady-state only
        _, trainer = _eager_train_loop(net, x, y, warmup, trainer=trainer)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out, _ = _eager_train_loop(net, x, y, steps, trainer=trainer)
            out.asnumpy()
            best = min(best, time.perf_counter() - t0)
        telemetry.disable()
        return steps / best, trainer

    engine.clear_compilation_cache()
    engine.reset_stats()
    telemetry.reset()
    off1, trainer = measure(False)
    on, trainer = measure(True, trainer)
    off2, trainer = measure(False, trainer)
    off = max(off1, off2)  # best disabled throughput = fair baseline
    overhead_pct = (off / on - 1.0) * 100.0
    scrape = telemetry.scrape()
    return {
        "metric": "telemetry_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%",
        "vs_baseline": round(on / off, 4),  # enabled/disabled steps/s ratio
        "extra": {
            "steps_s_disabled": round(off, 2),
            "steps_s_disabled_runs": [round(off1, 2), round(off2, 2)],
            "steps_s_enabled": round(on, 2),
            "pass_2pct": overhead_pct < 2.0,
            "scrape_bytes": len(scrape),
            "scrape_has_mfu": "mx_mfu" in scrape,
        },
    }


def bench_tracing(steps, warmup):
    """A/B span tracing disarmed vs armed (ISSUE 14) on the two hot paths
    it instruments: the fused train step (per-step dispatch loop — span
    record + watchdog feed) and the serving closed loop (enqueue event +
    queue-wait/dispatch/complete/request spans per request). Measures
    off/on/off with the best disabled run as baseline (same discipline as
    bench_telemetry_overhead); acceptance is <2% armed overhead on both.
    Also reports the ns-scale cost of the DISARMED path: the bare
    `tracing._ENABLED` flag check call sites pay, and building a disarmed
    span() (entered, it is a profiler TraceAnnotation and nothing else).

    The serving model is sized to the regime bench_serving measures
    (ResNet/BERT — ms-scale per batch), not a micro-MLP: armed tracing
    costs a fixed ~10-20us of Python per request, so the overhead ratio
    is meaningful only against a realistic per-request denominator. (On a
    ~100us/request toy model the same fixed cost GIL-interleaves with the
    serializing dispatcher/completer threads and reads as 30%+ — a
    measurement of the toy, not of tracing.)"""
    import threading
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, serving, telemetry
    from mxnet_tpu.telemetry import tracing
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    rs = np.random.RandomState(0)
    telemetry.enable()  # realistic armed config: metrics + tracing

    # -- fused train step: per-step dispatch loop -----------------------
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(1024, activation="relu"),
            gluon.nn.Dense(1024, activation="relu"),
            gluon.nn.Dense(64))
    net.initialize()
    net(nd.zeros((2, 512)))
    trainer = DataParallelTrainer(
        net, _loss_tokens, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05}, mesh=mesh)
    x = nd.array(rs.uniform(-1, 1, (256, 512)).astype(np.float32))
    y = nd.array(rs.randint(0, 64, (256,)), dtype="int32")

    # Paired interleaving: a 2% gate is below this box's run-to-run drift
    # (CPU contention moves whole phases by 10%+), so each rep times a
    # disarmed segment and an armed segment back to back and the best of
    # each arm is compared — drift lands on both arms instead of biasing
    # whichever phase ran during the quiet period.
    reps = int(os.environ.get("BENCH_TRACING_REPS", 5))

    def timed_train():
        t0 = time.perf_counter()
        for _ in range(steps):
            trainer.step(x, y)
        trainer.drain()
        return steps / (time.perf_counter() - t0)

    for _ in range(warmup):
        trainer.step(x, y)
    trainer.drain()
    t_off = t_on = 0.0
    for _ in range(reps):
        tracing.disable()
        t_off = max(t_off, timed_train())
        tracing.enable()
        t_on = max(t_on, timed_train())
    tracing.disable()
    tracing.reset()
    train_pct = (t_off / t_on - 1.0) * 100.0

    # -- serving closed loop --------------------------------------------
    clients = int(os.environ.get("BENCH_TRACING_CLIENTS", 4))
    requests = int(os.environ.get("BENCH_TRACING_REQUESTS", 400))
    net2 = gluon.nn.HybridSequential()
    net2.add(gluon.nn.Dense(2048, activation="relu"),
             gluon.nn.Dense(2048, activation="relu"),
             gluon.nn.Dense(256))
    net2.initialize()
    net2.hybridize()
    net2(nd.zeros((1, 1024)))
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "mlp")
        net2.export(prefix)
        srv = serving.Server(max_wait_ms=1.0)
        try:
            srv.register("mlp", prefix + "-symbol.json",
                         prefix + "-0000.params",
                         input_shapes={"data": (1024,)}, buckets=(4, 16))
            xq = rs.uniform(-1, 1, (4, 1024)).astype(np.float32)
            srv.predict("mlp", data=xq)  # warm all buckets' compiles

            def closed_loop():
                def client(k):
                    for _ in range(requests // clients):
                        srv.predict("mlp", data=xq, timeout=600.0)
                ts = [threading.Thread(target=client, args=(k,))
                      for k in range(clients)]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                return requests / (time.perf_counter() - t0)

            closed_loop()  # warm the batcher + both buckets' compiles
            s_off = s_on = 0.0
            for _ in range(reps):  # paired, same rationale as the train arm
                tracing.disable()
                s_off = max(s_off, closed_loop())
                tracing.enable()
                s_on = max(s_on, closed_loop())
            tracing.disable()
            tracing.reset()
            serving_pct = (s_off / s_on - 1.0) * 100.0
        finally:
            srv.close()

    # -- disarmed path: flag check + span() microbench ------------------
    tracing.disable()
    n = 1_000_000
    t0 = time.perf_counter()
    for _ in range(n):
        if tracing._ENABLED:
            pass
    flag_ns = (time.perf_counter() - t0) / n * 1e9
    t0 = time.perf_counter()
    for _ in range(n):
        tracing.span("x")
    span_ns = (time.perf_counter() - t0) / n * 1e9
    telemetry.disable()
    telemetry.reset()

    worst = max(train_pct, serving_pct)
    return {
        "metric": "tracing_overhead_pct",
        "value": round(worst, 3),
        "unit": "%",
        "vs_baseline": round(min(t_on / t_off, s_on / s_off), 4),
        "extra": {
            "train_overhead_pct": round(train_pct, 3),
            "train_steps_s_disabled": round(t_off, 2),
            "train_steps_s_enabled": round(t_on, 2),
            "serving_overhead_pct": round(serving_pct, 3),
            "serving_req_s_disabled": round(s_off, 2),
            "serving_req_s_enabled": round(s_on, 2),
            "disarmed_flag_check_ns": round(flag_ns, 2),
            "disarmed_span_call_ns": round(span_ns, 2),
            "pass_2pct": train_pct < 2.0 and serving_pct < 2.0,
        },
    }


def bench_goodput(steps, warmup):
    """A/B goodput ledger disarmed vs armed (ISSUE 17) on the fused
    train-step dispatch loop it hooks: telemetry stays enabled in BOTH
    arms so the diff isolates the armed ledger's own cost — one stamp
    snapshot, the waterfall arithmetic, and an NDJSON ring append per
    step. Paired interleaving with best-of-arm comparison, same
    discipline (and same rationale) as bench_tracing: a 2% gate is below
    this box's run-to-run drift, so each rep times a disarmed segment
    and an armed segment back to back.

    Also reports the ns-scale cost of the DISARMED path — the bare
    `goodput._ENABLED` flag check the record_step funnel pays — and a
    reconciliation check over the armed run's own waterfall (the
    compute + sum(badput) - other == wall invariant, other <= 5%)."""
    import tempfile

    import jax
    from mxnet_tpu import nd, gluon, telemetry
    from mxnet_tpu.telemetry import goodput
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    rs = np.random.RandomState(0)
    telemetry.enable()  # both arms: the A/B isolates the ledger itself

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(1024, activation="relu"),
            gluon.nn.Dense(1024, activation="relu"),
            gluon.nn.Dense(64))
    net.initialize()
    net(nd.zeros((2, 512)))
    trainer = DataParallelTrainer(
        net, _loss_tokens, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05}, mesh=mesh)
    x = nd.array(rs.uniform(-1, 1, (256, 512)).astype(np.float32))
    y = nd.array(rs.randint(0, 64, (256,)), dtype="int32")

    reps = int(os.environ.get("BENCH_GOODPUT_REPS", 5))

    def timed_train():
        t0 = time.perf_counter()
        for _ in range(steps):
            trainer.step(x, y)
        trainer.drain()
        return steps / (time.perf_counter() - t0)

    for _ in range(warmup):
        trainer.step(x, y)
    trainer.drain()

    with tempfile.TemporaryDirectory() as root:
        t_off = t_on = 0.0
        for _ in range(reps):
            goodput.disable()
            t_off = max(t_off, timed_train())
            goodput.enable(root=root, rank=0)
            t_on = max(t_on, timed_train())
        # reconcile the armed run's own waterfall before tearing down
        totals = goodput.totals()
        wall = totals["wall_seconds"]
        cats = totals["categories"]
        badput = sum(v for c, v in cats.items()
                     if c not in ("compute", "other"))
        residual = abs(cats["compute"] + badput - cats["other"] - wall)
        other_pct = 100.0 * cats["other"] / wall if wall else 0.0
        ring_bytes = os.path.getsize(goodput.ring_path() or os.devnull)
        goodput.disable()
    overhead_pct = (t_off / t_on - 1.0) * 100.0

    # -- disarmed path: the flag check record_step pays -----------------
    n = 1_000_000
    t0 = time.perf_counter()
    for _ in range(n):
        if goodput._ENABLED:
            pass
    flag_ns = (time.perf_counter() - t0) / n * 1e9
    telemetry.disable()
    telemetry.reset()

    return {
        "metric": "goodput_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%",
        "vs_baseline": round(t_on / t_off, 4),
        "extra": {
            "steps_s_disarmed": round(t_off, 2),
            "steps_s_armed": round(t_on, 2),
            "disarmed_flag_check_ns": round(flag_ns, 2),
            "armed_steps_recorded": totals["steps"],
            "armed_other_pct": round(other_pct, 3),
            "armed_reconcile_residual_s": round(residual, 9),
            "armed_ring_bytes": ring_bytes,
            "pass_2pct": overhead_pct < 2.0,
            "pass_reconcile": residual < 1e-6 and other_pct <= 5.0,
        },
    }


def bench_zero_dp(steps, warmup):
    """A/B: replicated weight update vs the ZeRO-style sharded update
    (DataParallelTrainer(zero_update=True), arXiv:2004.13336) on the
    ResNet-50 and wide-conv configs. Reports per-variant step time,
    per-step collective bytes by kind (ring estimates, the same
    accounting telemetry books), optimizer-state bytes per replica, and
    live device bytes per replica.

    A single chip cannot host >1 data-parallel replica, so the mesh runs
    over virtual host devices (XLA_FLAGS set by main() before backend
    init) unless the process already sees >= BENCH_ZERO_DP real devices;
    the A/B is about the relative update/collective structure, and the
    configs are scaled down (BENCH_ZERO_IMAGE/BENCH_ZERO_BATCH) so the
    CPU mesh finishes in bench time."""
    import gc
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    from mxnet_tpu.parallel import zero as zero_mod
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    ndp = int(os.environ.get("BENCH_ZERO_DP", 8))
    mesh = make_mesh({"dp": ndp}, devices=_lane_devices(ndp))
    rs = np.random.RandomState(0)

    # local batch = batch/dp; keep it >= 4 — the shard_map body runs
    # per-device BatchNorm, and ResNet-50's 50+ BN layers diverge on the
    # statistics of 2-sample tiles (docs/data_parallel.md "when not to")
    image = int(os.environ.get("BENCH_ZERO_IMAGE", 32))
    batch = int(os.environ.get("BENCH_ZERO_BATCH", 32))

    def resnet():
        net = resnet50_v1()
        with mx.cpu():
            net.initialize(ctx=mx.cpu())
            net(nd.zeros((1, 3, image, image), ctx=mx.cpu()))
        x = nd.array(rs.uniform(-1, 1, (batch, 3, image, image))
                     .astype(np.float32))
        y = nd.array(rs.randint(0, 1000, (batch,)), dtype="int32")
        return net, x, y

    def wide_conv(ch=256, hw=14):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(ch, 3, padding=1, activation="relu"),
                gluon.nn.Conv2D(ch, 3, padding=1, activation="relu"),
                gluon.nn.GlobalAvgPool2D(), gluon.nn.Flatten(),
                gluon.nn.Dense(1000))
        with mx.cpu():
            net.initialize(ctx=mx.cpu())
            net(nd.zeros((1, 3, hw, hw), ctx=mx.cpu()))
        x = nd.array(rs.uniform(-1, 1, (batch, 3, hw, hw))
                     .astype(np.float32))
        y = nd.array(rs.randint(0, 1000, (batch,)), dtype="int32")
        return net, x, y

    def run(make_cfg, zero):
        mx.random.seed(0)
        net, x, y = make_cfg()
        # momentum so the sharded state shrink is visible; conservative lr —
        # the shard_map paths normalize BN over each replica's LOCAL batch
        # (2-8 samples here), and an aggressive lr diverges on that noise
        tr = DataParallelTrainer(
            net, _loss_tokens, optimizer="sgd",
            optimizer_params={
                "learning_rate": float(os.environ.get("BENCH_ZERO_LR",
                                                      0.005)),
                "momentum": 0.9},
            mesh=mesh, zero_update=zero,
            comm_dtype=os.environ.get("MXNET_TPU_COMM_DTYPE") or None
            if zero else None)
        float(tr.run_steps(x, y, max(warmup, 1))[-1])
        best = float("inf")
        loss = None
        for _ in range(2):
            t0 = time.perf_counter()
            losses = tr.run_steps(x, y, steps)
            loss = float(losses[-1])
            best = min(best, time.perf_counter() - t0)
        if zero:
            comm = {
                "reduce_scatter": zero_mod.reduce_scatter_wire_bytes(
                    tr._zero_plan, ndp, tr._comm_dtype),
                "all_gather": zero_mod.all_gather_wire_bytes(
                    tr._zero_plan, ndp),
                "buckets": len(tr._zero_plan),
            }
        else:
            comm = {"allreduce": tr._grad_allreduce_bytes()}
        out = {
            "step_ms": round(best / steps * 1e3, 3),
            "collective_bytes_per_step": comm,
            "opt_state_bytes_per_replica": tr._opt_state_replica_bytes(),
            # per-replica live footprint: sharded leaves count their local
            # shard only (same accounting as the telemetry gauge)
            "live_bytes_per_replica": zero_mod.per_replica_state_bytes(
                jax.live_arrays()),
            "final_loss": round(loss, 4),
        }
        del tr, net, x, y
        gc.collect()
        return out

    configs = {"resnet50": resnet, "wide_conv": wide_conv}
    if os.environ.get("BENCH_QUICK") == "1":
        configs.pop("resnet50")
    extra = {"dp": ndp, "batch": batch, "image": image}
    for name, cfg in configs.items():
        rep = run(cfg, zero=False)
        zro = run(cfg, zero=True)
        extra[name] = {
            "replicated": rep,
            "zero": zro,
            "step_time_ratio": round(zro["step_ms"]
                                     / max(rep["step_ms"], 1e-9), 3),
            "opt_state_shrink": round(
                zro["opt_state_bytes_per_replica"]
                / max(rep["opt_state_bytes_per_replica"], 1), 4),
        }
    key = "wide_conv" if "wide_conv" in extra else "resnet50"
    return {
        "metric": "zero_dp_step_time_ratio",
        "value": extra[key]["step_time_ratio"],
        "unit": "zero/replicated",
        "vs_baseline": extra[key]["opt_state_shrink"],  # ~1/dp target
        "extra": extra,
    }


def bench_pipeline(steps, warmup):
    """A/B: GPipe (grad-of-scan transpose) vs the hand-scheduled 1F1B
    pipeline schedule (docs/pipeline_parallel.md) on BERT-base-shaped
    stages over a pp mesh. Reports per-schedule step time, analytic vs
    measured bubble fraction, and the compiled temp/peak memory from
    XLA's memory_analysis — the bounded-activation-memory claim: 1F1B's
    temp allocation stays ~flat as the microbatch count doubles while
    GPipe's residual stash grows with it.

    The measured bubble derives from two microbatch counts per schedule:
    with t(M) ~= (M + k) * t_tick, the slope t_tick = (t(2M) - t(M)) / M
    and bubble(M) = 1 - M * t_tick / t(M). Config is scaled down
    (BENCH_PP_LAYERS/UNITS/SEQ/MB) so the CPU mesh finishes in bench
    time; on a real slice raise them toward BERT-base (12 x 768 x 512)."""
    import gc
    import mxnet_tpu as mx
    from mxnet_tpu import nd, telemetry as telem
    from mxnet_tpu.models.bert import BertModel
    from mxnet_tpu.parallel import PipelineTrainer, make_mesh

    pp = int(os.environ.get("BENCH_PP", 4))
    mesh = make_mesh({"pp": pp}, devices=_lane_devices(pp))
    quick = os.environ.get("BENCH_QUICK") == "1"
    layers = int(os.environ.get("BENCH_PP_LAYERS", 4 if quick else 8))
    units = int(os.environ.get("BENCH_PP_UNITS", 128 if quick else 256))
    seq = int(os.environ.get("BENCH_PP_SEQ", 64 if quick else 128))
    vocab = int(os.environ.get("BENCH_PP_VOCAB", 2048))
    mb = int(os.environ.get("BENCH_PP_MB", 2))       # rows per microbatch
    M = int(os.environ.get("BENCH_PP_MICRO", 2 * pp))
    telem.enable()
    rs = np.random.RandomState(0)

    def run(sched, m):
        mx.random.seed(0)
        net = BertModel(vocab_size=vocab, num_layers=layers, units=units,
                        hidden_size=4 * units,
                        num_heads=max(units // 64, 2), max_length=seq,
                        dropout=0.0)
        with mx.cpu():
            net.initialize(ctx=mx.cpu())
            net(nd.zeros((1, seq), ctx=mx.cpu(), dtype="int32"))
        tr = PipelineTrainer(net, _loss_tokens, optimizer="adamw",
                             optimizer_params={"learning_rate": 1e-4},
                             mesh=mesh, num_microbatch=m, schedule=sched)
        B = mb * m  # fixed microbatch size: B scales with m (weak scaling)
        x = nd.array(rs.randint(0, vocab, (B, seq)), dtype="int32")
        y = nd.array(rs.randint(0, vocab, (B, seq)), dtype="int32")
        pending = None
        for _ in range(max(warmup, 1)):
            pending = tr.step(x, y)
        tr.drain()
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(steps):
                pending = tr.step(x, y)
            tr.drain()
            best = min(best, time.perf_counter() - t0)
        cost = next(iter(tr._program._costs.values()), {}) \
            if tr._program._costs else {}
        out = {
            "step_ms": round(best / steps * 1e3, 3),
            "temp_memory_bytes": cost.get("temp_memory_bytes"),
            "peak_memory_bytes": cost.get("peak_memory_bytes"),
            "final_loss": round(float(pending), 4),
        }
        del tr, net, x, y
        gc.collect()
        return out

    extra = {"pp": pp, "layers": layers, "units": units, "seq": seq,
             "microbatch_rows": mb, "num_microbatch": M}
    for sched, bubble_ticks in (("gpipe", pp - 1), ("1f1b", 2 * (pp - 1))):
        a = run(sched, M)
        b = run(sched, 2 * M)
        t_tick = max((b["step_ms"] - a["step_ms"]) / M, 1e-9)
        extra[sched] = {
            **a,
            "step_ms_2x_microbatches": b["step_ms"],
            "temp_memory_bytes_2x_microbatches": b["temp_memory_bytes"],
            "bubble_analytic": round(bubble_ticks / (M + bubble_ticks), 4),
            "bubble_measured": round(
                max(1 - M * t_tick / a["step_ms"], 0.0), 4),
        }
        if a["temp_memory_bytes"] and b["temp_memory_bytes"]:
            extra[sched]["temp_memory_growth_2x"] = round(
                b["temp_memory_bytes"] / a["temp_memory_bytes"], 3)

    # -- partitioned-tp A/B lane (ISSUE 16) ---------------------------------
    # weight-sharded tp (per-step full-weight all-gather) vs compute-
    # partitioned tp (activation collectives only) vs partitioned +
    # sequence parallelism, all on a pp=2 x tp mesh under 1F1B. The
    # headline columns: per-chip weight-gather bytes (the >= tp-factor
    # reduction claim — the gather op vanishes outright) and the compiled
    # peak/temp activation memory (sequence parallelism shrinks the
    # LN/dropout/residual stash by ~tp in SP regions).
    tp = int(os.environ.get("BENCH_PP_TP", 2))
    if tp > 1 and len(devs) >= 2 * tp:
        from mxnet_tpu.parallel import shard_params_megatron
        from mxnet_tpu.recipes.moe import token_cross_entropy
        mesh_tp = make_mesh({"pp": 2, "tp": tp}, devices=devs[:2 * tp])

        def run_tp(mode, sp):
            mx.random.seed(0)
            net = BertModel(vocab_size=vocab, num_layers=layers, units=units,
                            hidden_size=4 * units,
                            num_heads=max(units // 64, tp), max_length=seq,
                            dropout=0.0)
            with mx.cpu():
                net.initialize(ctx=mx.cpu())
                net(nd.zeros((1, seq), ctx=mx.cpu(), dtype="int32"))
            kw = {}
            if mode == "sharded":
                shard_params_megatron(net, axis="tp")
            else:
                kw = {"tp_mode": "partitioned", "sequence_parallel": sp}
            tr = PipelineTrainer(net, token_cross_entropy, optimizer="adamw",
                                 optimizer_params={"learning_rate": 1e-4},
                                 mesh=mesh_tp, tp_axis="tp",
                                 num_microbatch=M, schedule="1f1b", **kw)
            B = mb * M
            x = nd.array(rs.randint(0, vocab, (B, seq)), dtype="int32")
            y = nd.array(rs.randint(0, vocab, (B, seq)), dtype="int32")
            pending = None
            for _ in range(max(warmup, 1)):
                pending = tr.step(x, y)
            tr.drain()
            telem.reset()
            t0 = time.perf_counter()
            for _ in range(steps):
                pending = tr.step(x, y)
            tr.drain()
            dt = time.perf_counter() - t0
            bytes_c = telem.get_metric("mx_comm_bytes_total")
            cost = next(iter(tr._program._costs.values()), {}) \
                if tr._program._costs else {}
            out = {
                "step_ms": round(dt / steps * 1e3, 3),
                "weight_gather_bytes_per_step": int(
                    (bytes_c.get("tp_weight_all_gather", "mesh")
                     if bytes_c else 0) // steps),
                "tp_lane_bytes_per_step": int(
                    telem.comm_axis_bytes("tp") // steps),
                "sp_lane_bytes_per_step": int(
                    telem.comm_axis_bytes("sp") // steps),
                "temp_memory_bytes": cost.get("temp_memory_bytes"),
                "peak_memory_bytes": cost.get("peak_memory_bytes"),
                "final_loss": round(float(pending), 4),
            }
            del tr, net, x, y
            gc.collect()
            return out

        lane = {"tp": tp}
        for tag, mode, sp in (("weight_sharded", "sharded", False),
                              ("partitioned", "partitioned", False),
                              ("partitioned_sp", "partitioned", True)):
            lane[tag] = run_tp(mode, sp)
        wg_a = lane["weight_sharded"]["weight_gather_bytes_per_step"]
        wg_b = lane["partitioned"]["weight_gather_bytes_per_step"]
        lane["weight_gather_eliminated"] = wg_b == 0 and wg_a > 0
        lane["weight_gather_reduction_factor"] = (
            round(wg_a / wg_b, 2) if wg_b else None)  # None = infinite
        tm_ns, tm_sp = (lane["partitioned"]["temp_memory_bytes"],
                        lane["partitioned_sp"]["temp_memory_bytes"])
        if tm_ns and tm_sp:
            lane["sp_temp_memory_ratio"] = round(tm_sp / tm_ns, 3)
        extra["partitioned_tp"] = lane

    return {
        "metric": "pipeline_1f1b_step_time_ratio",
        "value": round(extra["1f1b"]["step_ms"]
                       / max(extra["gpipe"]["step_ms"], 1e-9), 3),
        "unit": "1f1b/gpipe",
        # the memory headline: 1F1B temp per GPipe temp at the same M
        "vs_baseline": round(
            (extra["1f1b"]["temp_memory_bytes"] or 0)
            / max(extra["gpipe"]["temp_memory_bytes"] or 1, 1), 3),
        "extra": extra,
    }


def bench_async_feed(steps, warmup):
    """A/B: synchronous loop (host batch assembly + inline device_put +
    per-step float(loss)) vs the overlapped loop (DeviceFeed staging
    device-resident batches from a producer thread + bounded in-flight
    dispatch + PendingScalar losses drained at the end) — ISSUE 5's
    wall-clock acceptance. Two model scenarios (MLP and a ResNet-ish conv
    block); reports the speedup, the feed-stall/inflight gauges proving
    the overlap, and 10-step loss-trajectory parity sync-vs-overlapped
    (sgd + adam, single-device and dp)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, telemetry
    from mxnet_tpu.engine.async_feed import DeviceFeed
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    ndp = int(os.environ.get("BENCH_FEED_DP", 4))
    batch = int(os.environ.get("BENCH_FEED_BATCH", 128))
    n_batches = max(steps, warmup, 10) + 2

    def mlp():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(64))
        return net, (512,), 64

    def conv():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(32, 3, padding=1, activation="relu"),
                gluon.nn.Conv2D(32, 3, padding=1, activation="relu"),
                gluon.nn.GlobalAvgPool2D(), gluon.nn.Flatten(),
                gluon.nn.Dense(10))
        return net, (3, 24, 24), 10

    class _AugmentIter:
        """ResNet-ish host input pipeline: per-batch normalize + pad-crop
        + mirror in numpy — the host work a real image feed performs each
        step. Runs inline in the sync loop, inside the producer thread in
        the overlapped loop (seeded, so both draw identical batches)."""

        def __init__(self, x, y, image=False, seed=1):
            self._x, self._y, self._image = x, y, image
            self._seed = seed
            self.batch_size = batch
            self.reset()

        def reset(self):
            self._cur = 0
            self._rng = np.random.RandomState(self._seed)

        def __iter__(self):
            return self

        def __next__(self):
            i = self._cur
            if (i + 1) * batch > len(self._x):
                raise StopIteration
            self._cur += 1
            xb = self._x[i * batch:(i + 1) * batch].astype(np.float32)
            yb = self._y[i * batch:(i + 1) * batch]
            if self._image:
                xb = (xb - 127.0) / 64.0
                p = 2
                padded = np.pad(xb, ((0, 0), (0, 0), (p, p), (p, p)),
                                mode="reflect")
                dy, dx = self._rng.randint(0, 2 * p + 1, 2)
                h, w = xb.shape[2], xb.shape[3]
                xb = padded[:, :, dy:dy + h, dx:dx + w]
                if self._rng.rand() < 0.5:
                    xb = xb[:, :, :, ::-1]
                xb = np.ascontiguousarray(xb)
            else:
                xb = (xb - xb.mean()) / (xb.std() + 1e-6)
            # host numpy out: the sync loop pays the implicit H2D upload
            # inline per step, the overlapped loop's producer device_puts
            # it behind the previous step's compute
            return xb, np.ascontiguousarray(yb)

    def build(make_cfg, opt, ndev):
        mx.random.seed(0)
        rs = np.random.RandomState(0)  # per-build: identical data per config
        mesh = make_mesh({"dp": ndev}, devices=_lane_devices(ndev))
        net, xshape, nclass = make_cfg()
        with mx.cpu():
            net.initialize(ctx=mx.cpu())
            net(nd.zeros((1,) + xshape, ctx=mx.cpu()))
        tr = DataParallelTrainer(
            net, _loss_tokens, optimizer=opt,
            optimizer_params={"learning_rate": 0.01}, mesh=mesh)
        image = len(xshape) == 3
        x = rs.randint(0, 255, (batch * n_batches,) + xshape) \
            .astype(np.uint8) if image else \
            rs.uniform(-1, 1, (batch * n_batches,) + xshape) \
            .astype(np.float32)
        y = rs.randint(0, nclass, (batch * n_batches,)).astype(np.int32)
        return tr, _AugmentIter(x, y, image=image)

    def sync_loop(tr, it, n):
        """The pre-ISSUE-5 loop: host augmentation inline, loss read back
        every step (a host<->device round-trip per iteration)."""
        it.reset()
        losses = []
        for xb, yb in it:
            losses.append(float(tr.step(xb, yb)))
            if len(losses) == n:
                break
        return losses

    def overlapped_loop(tr, it, n):
        """DeviceFeed (producer-thread augmentation + explicit device_put)
        + bounded in-flight dispatch + lazy loss drain at the end."""
        it.reset()
        feed = DeviceFeed.for_trainer(it, tr)
        pend = []
        for xb, yb in feed:
            pend.append(tr.step(xb, yb))
            if len(pend) == n:
                break
        tr.drain()
        return [float(p) for p in pend], feed

    def measure(make_cfg):
        # separate trainers, same seed/config -> same compiled artifact;
        # paired interleaved reps (sync, overlapped, sync, ...) with min
        # aggregation so drift hits both variants alike
        tr_s, it = build(make_cfg, "sgd", 1)
        tr_o, it_o = build(make_cfg, "sgd", 1)
        sync_loop(tr_s, it, warmup)
        overlapped_loop(tr_o, it_o, warmup)[1].close()
        dt_sync = dt_over = float("inf")
        feed = None
        for _ in range(3):
            t0 = time.perf_counter()
            sync_loop(tr_s, it, steps)
            dt_sync = min(dt_sync, time.perf_counter() - t0)
            t0 = time.perf_counter()
            _, fd = overlapped_loop(tr_o, it_o, steps)
            dt = time.perf_counter() - t0
            if dt < dt_over:
                dt_over, feed = dt, fd
            fd.close()
        # gauge wiring proof (outside the timed windows)
        telemetry.enable()
        overlapped_loop(tr_o, it_o, 4)[1].close()
        depth_gauge = telemetry.get_metric("mx_feed_queue_depth").get("feed")
        telemetry.disable()
        return {
            "sync_steps_s": round(steps / dt_sync, 2),
            "overlapped_steps_s": round(steps / dt_over, 2),
            "speedup": round(dt_sync / dt_over, 3),
            "gauges": {
                "mx_feed_stall_seconds_total": round(feed.stall_seconds, 4),
                "mx_feed_queue_depth_last": depth_gauge,
                "mx_inflight_steps_max": tr_o._window.max_inflight,
            },
        }

    def parity(make_cfg):
        """10-step loss trajectory must match the synchronous path exactly
        for the same seed — overlap changes scheduling, never math."""
        out = {}
        for opt in ("sgd", "adam"):
            for ndev in (1, ndp):
                tr_a, it_a = build(make_cfg, opt, ndev)
                ref = sync_loop(tr_a, it_a, 10)
                tr_b, it_b = build(make_cfg, opt, ndev)
                got, feed = overlapped_loop(tr_b, it_b, 10)
                feed.close()
                out[f"{opt}_dp{ndev}"] = bool(ref == got)
        return out

    scenarios = {"mlp": mlp, "conv": conv}
    extra = {"batch": batch, "inflight_depth":
             int(os.environ.get("MXNET_TPU_INFLIGHT_STEPS", 2)),
             # context for CPU-only readings: a single-host-core CPU box
             # conserves total work (compute shares the core with the
             # producer), so the honest A/B there is ~1.0; the overlap
             # pays off against a real accelerator, where each per-step
             # float(loss) is a device sync the overlapped loop removes
             "host_cores": os.cpu_count()}
    for name, cfg in scenarios.items():
        extra[name] = measure(cfg)
        extra[name]["trajectory_match"] = parity(cfg)
    return {
        "metric": "async_feed_overlap_speedup",
        "value": extra["conv"]["speedup"],
        "unit": "sync/overlapped walltime",
        "vs_baseline": extra["mlp"]["speedup"],
        "extra": extra,
    }


def bench_elastic(steps, warmup):
    """A/B: the same training loop with the elastic snapshot writer off vs
    on (save every BENCH_ELASTIC_EVERY steps) — ISSUE 11's acceptance is
    snapshot-on step overhead under 5%, because ``save()`` only dispatches
    async device-side copies and the npz/manifest work runs on a
    background thread behind the next steps' compute. Also times the
    kill-and-resume path itself: the forced final synchronous snapshot a
    preempted job writes, the ``resume_or_init`` restore on a fresh
    trainer, and 5-step post-resume loss parity vs continuing the
    original run (docs/checkpointing.md's runbook numbers)."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, elastic
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    ndp = int(os.environ.get("BENCH_ELASTIC_DP", 4))
    batch = int(os.environ.get("BENCH_ELASTIC_BATCH", 512))
    every = int(os.environ.get("BENCH_ELASTIC_EVERY", 10))

    def build():
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(64))
        net.initialize()
        net(nd.zeros((2, 512)))
        mesh = make_mesh({"dp": ndp}, devices=_lane_devices(ndp))
        return DataParallelTrainer(
            net, _loss_tokens, optimizer="adam",
            optimizer_params={"learning_rate": 1e-3}, mesh=mesh)

    rs = np.random.RandomState(0)
    x = rs.uniform(-1, 1, (batch, 512)).astype(np.float32)
    y = rs.randint(0, 64, (batch,)).astype(np.int32)

    def loop(tr, n, mgr=None):
        """Returns the summed wall time of the save() dispatches — the
        only cost snapshotting adds ON the step path (capture + async
        device-side copies; the npz/manifest work runs on the writer
        thread)."""
        dispatch_s = 0.0
        for _ in range(n):
            tr.step(x, y)
            if mgr is not None and mgr.should_save(tr._t):
                t0 = time.perf_counter()
                elastic.save_trainer(mgr, tr)
                dispatch_s += time.perf_counter() - t0
        tr.drain()
        return dispatch_s

    root = tempfile.mkdtemp(prefix="mx-bench-elastic-")
    try:
        tr_off, tr_on = build(), build()
        loop(tr_off, warmup)
        loop(tr_on, warmup)
        # paired interleaved reps, min aggregation: host drift (the writer
        # shares CPU cores on a host-only box) hits both variants alike
        dt_off = dt_on = float("inf")
        dispatch_s = 0.0
        mgr = None
        for r in range(3):
            t0 = time.perf_counter()
            loop(tr_off, steps)
            dt_off = min(dt_off, time.perf_counter() - t0)
            m = elastic.SnapshotManager(os.path.join(root, f"rep{r}"),
                                        save_interval_steps=every)
            t0 = time.perf_counter()
            ds = loop(tr_on, steps, m)
            dt = time.perf_counter() - t0
            m.wait_until_finished()  # writer tail is NOT step overhead
            if dt < dt_on:
                dt_on, dispatch_s, mgr = dt, ds, m

        # kill-and-resume: forced final sync snapshot, then a fresh boot
        t0 = time.perf_counter()
        elastic.save_trainer(mgr, tr_on, wait=True)
        final_save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _, tr2, start, outcome = elastic.resume_or_init(mgr.directory, build)
        restore_s = time.perf_counter() - t0
        expect = [float(tr_on.step(x, y)) for _ in range(5)]
        got = [float(tr2.step(x, y)) for _ in range(5)]
        parity = bool(np.allclose(got, expect, rtol=1e-6, atol=1e-7))
        # headline: what snapshotting adds ON the step path (capture +
        # async copy dispatch) — the cost the subsystem's design bounds.
        # The total-walltime A/B additionally pays the writer's npz/CRC/
        # disk work wherever the host has no spare core to absorb it (a
        # 1-core CPU box conserves total work, same caveat as the
        # async_feed scenario); that reading is in extra, not the gate.
        overhead = dispatch_s / dt_off
        total_overhead = dt_on / dt_off - 1.0
        return {
            "metric": "elastic_snapshot_step_overhead",
            "value": round(overhead * 100, 2),
            "unit": "% step-path overhead, snapshot on vs off",
            "vs_baseline": round(dt_on / dt_off, 4),
            "extra": {
                "dp": ndp, "batch": batch, "save_every": every,
                "steps_s_off": round(steps / dt_off, 2),
                "steps_s_on": round(steps / dt_on, 2),
                "pass_lt_5pct": overhead < 0.05,
                "save_dispatch_s_total": round(dispatch_s, 4),
                "total_walltime_overhead_pct": round(total_overhead * 100,
                                                     2),
                "async_save_seconds_last": round(mgr.save_seconds, 4),
                "snapshot_bytes": mgr.bytes_written,
                "final_sync_save_s": round(final_save_s, 4),
                "resume_restore_s": round(restore_s, 4),
                "resume_outcome": outcome,
                "resume_start_step": start,
                "post_resume_parity_5step": parity,
                "host_cores": os.cpu_count(),
            },
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_serving():
    """Latency-vs-throughput curves for the continuous-batching serving
    path (mxnet_tpu.serving, docs/serving.md): ResNet-50 and BERT-base
    registered on one serving.Server (per-bucket artifacts warmed at
    registration), then 1/8/64 closed-loop concurrent streams each firing
    single-row requests back-to-back. Reports per-config p50/p99 latency,
    request+row throughput, batch occupancy (real vs padded rows), sampled
    queue-depth peak, and the batch-formation histogram by bucket — the
    numbers the max-wait/bucket-set tuning loop in docs/serving.md reads.

    Model scale is env-tunable so the scenario also runs on CPU hosts:
    BENCH_SERVING_IMAGE (default 224), BENCH_SERVING_SEQ (128),
    BENCH_SERVING_VOCAB (8192), BENCH_SERVING_BUCKETS (1,8,64),
    BENCH_SERVING_STREAMS (1,8,64), BENCH_SERVING_REQUESTS (16/stream),
    BENCH_SERVING_MAX_WAIT_MS (5), BENCH_SERVING_MODELS
    (resnet50,bert_base)."""
    import tempfile
    import threading
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serving, telemetry

    image = int(os.environ.get("BENCH_SERVING_IMAGE", 224))
    seq = int(os.environ.get("BENCH_SERVING_SEQ", 128))
    vocab = int(os.environ.get("BENCH_SERVING_VOCAB", 8192))
    buckets = tuple(int(b) for b in os.environ.get(
        "BENCH_SERVING_BUCKETS", "1,8,64").split(","))
    streams_list = tuple(int(s) for s in os.environ.get(
        "BENCH_SERVING_STREAMS", "1,8,64").split(","))
    reqs_per_stream = int(os.environ.get("BENCH_SERVING_REQUESTS", 16))
    max_wait_ms = float(os.environ.get("BENCH_SERVING_MAX_WAIT_MS", 5.0))
    which = os.environ.get("BENCH_SERVING_MODELS",
                           "resnet50,bert_base").split(",")
    tmp = tempfile.mkdtemp(prefix="mx_serving_bench_")

    def export_resnet50():
        from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
        net = resnet50_v1()
        with mx.cpu():
            net.initialize(ctx=mx.cpu())
            net.hybridize()
            net(nd.zeros((1, 3, image, image), ctx=mx.cpu()))
        prefix = os.path.join(tmp, "resnet50")
        net.export(prefix)
        return prefix, {"data": (3, image, image)}, "float32"

    def export_bert_base():
        from mxnet_tpu.models import bert_base
        net = bert_base(vocab_size=vocab)
        with mx.cpu():
            net.initialize(ctx=mx.cpu())
            net.hybridize()
            net(nd.zeros((1, seq), ctx=mx.cpu(), dtype="int32"))
        prefix = os.path.join(tmp, "bert_base")
        net.export(prefix)
        return prefix, {"data": (seq,)}, "int32"

    exporters = {"resnet50": export_resnet50, "bert_base": export_bert_base}

    def run_config(srv, name, row_shape, dtype, n_streams):
        telemetry.reset()
        telemetry.enable()
        latencies = []
        lat_lock = threading.Lock()
        errors = []

        def client(k):
            rs = np.random.RandomState(k)
            if dtype == "int32":
                x = rs.randint(0, vocab, (1,) + row_shape).astype(np.int32)
            else:
                x = rs.uniform(-1, 1, (1,) + row_shape).astype(np.float32)
            mine = []
            try:
                for _ in range(reqs_per_stream):
                    t0 = time.perf_counter()
                    srv.predict(name, data=x, timeout=600.0)
                    mine.append(time.perf_counter() - t0)
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")
            with lat_lock:
                latencies.extend(mine)

        depth_peak = [0.0]
        stop = threading.Event()

        def monitor():
            while not stop.is_set():
                fam = telemetry.get_metric("mx_serving_queue_depth")
                if fam is not None:
                    depth_peak[0] = max(depth_peak[0], fam.get(name))
                stop.wait(0.002)

        mon = threading.Thread(target=monitor, daemon=True)
        mon.start()
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stop.set()
        mon.join()
        assert not errors, errors[:3]
        latencies.sort()

        def pct(p):
            return latencies[min(int(p * len(latencies)),
                                 len(latencies) - 1)]

        rows_fam = telemetry.get_metric("mx_serving_batch_rows_total")
        pad_fam = telemetry.get_metric("mx_serving_padded_rows_total")
        batch_fam = telemetry.get_metric("mx_serving_batches_total")
        real = sum(s.value for s in rows_fam._series.values()) \
            if rows_fam else 0.0
        padded = sum(s.value for s in pad_fam._series.values()) \
            if pad_fam else 0.0
        by_bucket = {s.label_values[1]: int(s.value)
                     for s in batch_fam._series.values()} \
            if batch_fam else {}
        telemetry.disable()
        n = len(latencies)
        return {
            "streams": n_streams,
            "requests": n,
            "p50_ms": round(pct(0.50) * 1e3, 2),
            "p99_ms": round(pct(0.99) * 1e3, 2),
            "req_s": round(n / wall, 2),
            "occupancy": round(real / max(real + padded, 1.0), 4),
            "queue_depth_peak": int(depth_peak[0]),
            "batches_by_bucket": by_bucket,
        }

    extra = {"buckets": list(buckets), "max_wait_ms": max_wait_ms,
             "requests_per_stream": reqs_per_stream, "host_cores":
             os.cpu_count()}
    for name in which:
        name = name.strip()
        prefix, row_shapes, dtype = exporters[name]()
        srv = serving.Server(max_wait_ms=max_wait_ms)
        try:
            t0 = time.perf_counter()
            srv.register(name, prefix + "-symbol.json",
                         prefix + "-0000.params", input_shapes=row_shapes,
                         buckets=buckets, dtype=dtype)
            warm_s = time.perf_counter() - t0
            row_shape = row_shapes["data"]
            extra[name] = {
                "warmup_s": round(warm_s, 2),
                "curves": [run_config(srv, name, row_shape, dtype, s)
                           for s in streams_list],
            }
        finally:
            srv.close()
    key = which[0].strip()
    mid = extra[key]["curves"][min(1, len(extra[key]["curves"]) - 1)]
    return {
        "metric": "serving_p99_ms",
        "value": mid["p99_ms"],
        "unit": f"ms @ {mid['streams']} streams ({key})",
        "vs_baseline": mid["occupancy"],  # real-row fraction at that load
        "extra": extra,
    }


def bench_roofline(steps, warmup):
    """Per-region roofline ledger for ResNet-50 bs32 and BERT-base
    (ISSUE 7 / ROADMAP item 1): run the model as a CHAIN of hybridized
    sub-blocks — each one its own compiled artifact, hence its own ledger
    region — through a full forward+backward+update loop, then read the
    attribution: achieved-vs-peak FLOPs and bytes per region,
    compute/memory-bound classification against the ridge point, and the
    top-3 underutilized ResNet-50 regions ranked by lost FLOP-seconds (the
    action list for the space-to-depth stem PR). Also asserts the ledger's
    per-region FLOPs sum reconciles with the aggregate flops_executed
    account (<= 5%) and A/Bs the loop with telemetry+ledger off vs on
    (overhead must stay <= 2%).

    Env knobs so the scenario also finishes on CPU hosts:
    BENCH_ROOFLINE_BATCH (32), BENCH_ROOFLINE_IMAGE (224),
    BENCH_ROOFLINE_BERT_BATCH (8), BENCH_ROOFLINE_SEQ (128),
    BENCH_ROOFLINE_VOCAB (8192), BENCH_ROOFLINE_MODELS, and
    BENCH_ROOFLINE_JSON=path to dump the full ledger JSON."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, autograd, telemetry
    from mxnet_tpu import engine
    from mxnet_tpu.telemetry import roofline

    batch = int(os.environ.get("BENCH_ROOFLINE_BATCH", 32))
    image = int(os.environ.get("BENCH_ROOFLINE_IMAGE", 224))
    bert_batch = int(os.environ.get("BENCH_ROOFLINE_BERT_BATCH", 8))
    seq = int(os.environ.get("BENCH_ROOFLINE_SEQ", 128))
    vocab = int(os.environ.get("BENCH_ROOFLINE_VOCAB", 8192))
    which = os.environ.get("BENCH_ROOFLINE_MODELS",
                           "resnet50,bert_base").split(",")
    rs = np.random.RandomState(0)

    def resnet_chain():
        from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
        net = resnet50_v1()
        with mx.cpu():
            net.initialize(ctx=mx.cpu())
            net(nd.zeros((1, 3, image, image), ctx=mx.cpu()))
        net.hybridize()
        blocks = [(f"features[{i}]:{type(b).__name__}", b)
                  for i, b in enumerate(net.features._children.values())]
        blocks.append(("output:Dense", net.output))
        x = nd.array(rs.uniform(-1, 1, (batch, 3, image, image))
                     .astype(np.float32))
        return net, blocks, (x,)

    def bert_chain():
        from mxnet_tpu.models import bert_base
        net = bert_base(vocab_size=vocab)
        with mx.cpu():
            net.initialize(ctx=mx.cpu())
            net(nd.zeros((1, seq), ctx=mx.cpu(), dtype="int32"))
        embed, cells, head = net.pipeline_split()
        blocks = [("embed", embed)]
        blocks += [(f"encoder[{i}]:TransformerEncoderCell", c)
                   for i, c in enumerate(cells)]
        blocks.append(("mlm_head", head))
        for _, b in blocks:
            b.hybridize()
        x = nd.array(rs.randint(0, vocab, (bert_batch, seq)), dtype="int32")
        return net, blocks, (x,)

    def region_of(b, bwd=False):
        # the same row-key formula the gluon cached path uses, so the
        # bench can map ledger regions back onto chain positions
        base = f"gluon:{type(b).__name__}#{b._fingerprint()[:6]}"
        return base + ("/bwd" if bwd else "")

    def run(make_chain):
        telemetry.disable()
        telemetry.reset()
        net, blocks, inputs = make_chain()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01, "momentum": 0.9})
        n_examples = inputs[0].shape[0]

        def chain_step():
            with autograd.record():
                h = inputs[0]
                for _, b in blocks:
                    h = b(h)
                loss = (h * h).mean()
            loss.backward()
            trainer.step(n_examples)
            return loss

        def loop(n):
            loss = None
            for _ in range(n):
                loss = chain_step()
            loss.asnumpy()  # boundary sync for honest wall time

        loop(max(warmup, 2))                      # compiles, telemetry off
        t0 = time.perf_counter()
        loop(steps)
        dt_off = time.perf_counter() - t0         # disabled baseline

        telemetry.enable()
        loop(2)                                   # one-time cost captures
        telemetry.reset()                         # measured ledger only
        flops0 = engine.cache_stats()["flops_executed"]
        t0 = time.perf_counter()
        loop(steps)
        dt_on = time.perf_counter() - t0
        agg_flops = engine.cache_stats()["flops_executed"] - flops0
        ledger = roofline.as_dict()
        report = roofline.report()
        telemetry.disable()

        # map ledger regions back to human chain positions (structurally
        # identical blocks share a row: the name aggregates their count)
        names = {}
        for name, b in blocks:
            for bwd in (False, True):
                key = region_of(b, bwd)
                suffix = "/bwd" if bwd else ""
                if key in names:
                    base, cnt = names[key]
                    names[key] = (base, cnt + (0 if bwd else 1))
                else:
                    names[key] = (name + suffix, 1)
        rows = []
        for r in ledger["regions"]:
            label, cnt = names.get(r["region"], (r["region"], 1))
            rows.append({
                "region": label if cnt == 1 else f"{label} x{cnt}",
                "kind": r["kind"],
                "executions": r["executions"],
                "gflops": round(r["flops"] / 1e9, 3),
                "gbytes": round(r["bytes"] / 1e9, 3),
                "seconds": round(r["seconds"], 4),
                "achieved_flops_ratio": round(r["achieved_flops_ratio"], 4),
                "achieved_bytes_ratio": round(r["achieved_bytes_ratio"], 4),
                "arithmetic_intensity": round(r["arithmetic_intensity"], 2)
                if r["arithmetic_intensity"] != float("inf") else -1,
                "bound": r["bound"],
                "lost_gflop_seconds": round(r["lost_flop_seconds"] / 1e9, 2),
                "estimated": r["estimated"],
            })
        ledger_flops = ledger["total_flops"]
        return {
            "rows": rows,
            "report": report,
            "ledger_flops": ledger_flops,
            "aggregate_flops_executed": agg_flops,
            # acceptance: per-region sum within 5% of the aggregate account
            "flops_sum_ratio": round(ledger_flops / max(agg_flops, 1.0), 4),
            "step_ms_disabled": round(dt_off / steps * 1e3, 2),
            "step_ms_enabled": round(dt_on / steps * 1e3, 2),
            "overhead_pct": round((dt_on / dt_off - 1.0) * 100.0, 2),
            "ridge_point_flops_per_byte":
                ledger["ridge_point_flops_per_byte"],
            "peak_flops": ledger["peak_flops_per_second"],
            "peak_bytes_per_second": ledger["peak_bytes_per_second"],
        }

    chains = {"resnet50": resnet_chain, "bert_base": bert_chain}
    extra = {"batch": batch, "image": image, "bert_batch": bert_batch,
             "seq": seq, "host_cores": os.cpu_count()}
    for name in which:
        name = name.strip()
        extra[name] = run(chains[name])
        print(f"# --- {name} ---\n{extra[name].pop('report')}",
              file=sys.stderr)
    if "resnet50" in extra and isinstance(extra["resnet50"], dict):
        # the action list: top-3 underutilized compute-carrying regions by
        # lost FLOP-seconds (zero-FLOP bookkeeping rows such as the eager
        # optimizer-update slice can't be "underutilized compute")
        extra["resnet50"]["top3_underutilized"] = [
            {k: r[k] for k in ("region", "kind", "achieved_flops_ratio",
                               "bound", "lost_gflop_seconds")}
            for r in extra["resnet50"]["rows"]
            if r["gflops"] > 0 and r["bound"] != "unknown"][:3]
    dump = os.environ.get("BENCH_ROOFLINE_JSON")
    if dump:
        with open(dump, "w") as f:
            json.dump(extra, f, indent=2)
    key = which[0].strip()
    return {
        "metric": "roofline_ledger_vs_aggregate_flops",
        "value": extra[key]["flops_sum_ratio"],
        "unit": "ledger/aggregate (pass: within 5% of 1.0)",
        "vs_baseline": extra[key]["overhead_pct"],  # <= 2% acceptance
        "extra": extra,
    }


def _recipe_run(trainer, x, y, steps, warmup):
    """The recipe-scenario measurement protocol (bench_roofline's A/B):
    warm + time with telemetry off, then enable, let the one-time cost
    captures happen, reset to a measured-only ledger, and time again.
    Returns (dt_off, dt_on, ledger, flops_per_step)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import roofline

    def loop(n):
        last = None
        for _ in range(n):
            last = trainer.step(x, y)
        float(last)                           # device sync
        trainer.drain()

    telemetry.disable()
    telemetry.reset()
    loop(max(warmup, 2))                      # compiles, telemetry off
    t0 = time.perf_counter()
    loop(steps)
    dt_off = time.perf_counter() - t0
    telemetry.enable()
    loop(2)                                   # one-time cost captures
    telemetry.reset()                         # measured ledger only
    t0 = time.perf_counter()
    loop(steps)
    dt_on = time.perf_counter() - t0
    ledger = roofline.as_dict()
    flops_per_step = max((c.get("flops", 0.0)
                          for c in trainer._program._costs.values()),
                         default=0.0)
    telemetry.disable()
    return dt_off, dt_on, ledger, flops_per_step


def moe_train_flops_per_step(batch, seq, layers, units, hidden, experts,
                             top_k, capacity_factor, vocab, shards):
    """Analytic matmul FLOPs of one MoE train step, matching the einsum
    formulation the model executes (gating + one-hot dispatch/combine
    einsums carry real FLOPs): forward terms below, train = 3x."""
    N = batch * seq
    nl = N // shards                          # tokens per gating shard
    cap = max(1, int(capacity_factor * nl * top_k / experts))
    slots = shards * experts * cap            # global expert slots
    attn = 2 * N * units * 3 * units + 4 * N * seq * units \
        + 2 * N * units * units
    gate = 2 * N * units * experts
    dispatch = 2 * 2 * N * experts * cap * units      # dispatch + combine
    expert = 2 * 2 * slots * units * hidden           # w1 + w2
    per_layer = attn + gate + dispatch + expert
    return 3 * (layers * per_layer + 2 * N * units * vocab)


def bench_moe(steps, warmup):
    """Expert-parallel MoE recipe (recipes/moe.py) as a benchmarked
    workload on a dp x ep mesh: fused-step time with telemetry off vs on,
    MFU from the step artifact's cost_analysis FLOPs, the roofline ledger
    row the step writes, exact all_to_all wire bytes per step, and the
    FLOP reconciliation — roofline-ledger sum vs cost_analysis x steps
    (must agree within 5%), with the analytic einsum count reported as an
    independent cross-check.

    Env knobs (CPU-sized defaults): BENCH_MOE_DP (2), BENCH_MOE_EP (2),
    BENCH_MOE_BATCH (16), BENCH_MOE_SEQ (32), BENCH_MOE_EXPERTS (4),
    BENCH_MOE_TOPK (1), BENCH_MOE_VOCAB (256)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, telemetry
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel import moe as pmoe
    from mxnet_tpu.recipes import get_recipe
    from mxnet_tpu.recipes import moe as rmoe

    ndp = int(os.environ.get("BENCH_MOE_DP", 2))
    nep = int(os.environ.get("BENCH_MOE_EP", 2))
    batch = int(os.environ.get("BENCH_MOE_BATCH", 16))
    seq = int(os.environ.get("BENCH_MOE_SEQ", 32))
    experts = int(os.environ.get("BENCH_MOE_EXPERTS", 4))
    top_k = int(os.environ.get("BENCH_MOE_TOPK", 1))
    vocab = int(os.environ.get("BENCH_MOE_VOCAB", 256))
    devs = _lane_devices(ndp * nep)
    mesh = make_mesh({"dp": ndp, "ep": nep}, devices=devs)

    r = get_recipe("moe")
    mx.random.seed(0)
    net = r.build_model(vocab_size=vocab, num_experts=experts, top_k=top_k)
    tr = r.build_trainer(net, mesh)
    rs = np.random.RandomState(0)
    x = nd.array(rs.randint(0, vocab, (batch, seq)), dtype="int32")
    y = nd.array(rs.randint(0, vocab, (batch, seq)), dtype="int32")

    dt_off, dt_on, ledger, flops_step = _recipe_run(tr, x, y, steps, warmup)
    a2a_bytes, a2a_calls = tr._a2a_step_bytes((batch, seq))
    # cost_analysis counts the per-device SPMD program; the analytic
    # count is global — divide by the mesh size to compare
    analytic = moe_train_flops_per_step(
        batch, seq, 2, 64, 128, experts, top_k, 2.0, vocab,
        ndp * nep) / (ndp * nep)
    recon = ledger["total_flops"] / max(flops_step * steps, 1.0)
    tok_s = batch * seq * steps / dt_on
    return {
        "metric": "moe_recipe_flops_reconciliation",
        "value": round(recon, 4),
        "unit": "ledger/cost_analysis (pass: within 5% of 1.0)",
        "vs_baseline": round(dt_on / max(dt_off, 1e-9), 3),
        "extra": {
            "mesh": {"dp": ndp, "ep": nep},
            "batch": batch, "seq": seq, "experts": experts, "top_k": top_k,
            "step_ms_disabled": round(dt_off / steps * 1e3, 2),
            "step_ms_enabled": round(dt_on / steps * 1e3, 2),
            "tokens_per_s": round(tok_s, 1),
            "gflops_per_step_cost": round(flops_step / 1e9, 3),
            "gflops_per_step_analytic": round(analytic / 1e9, 3),
            "analytic_vs_cost": round(analytic / max(flops_step, 1.0), 4),
            **_rates(flops_step * steps / dt_on, devs[0]),
            "all_to_all_bytes_per_step": a2a_bytes,
            "all_to_all_calls_per_step": a2a_calls,
            "dropped_tokens": telemetry.counter(
                "mx_moe_dropped_tokens_total").get("moe"),
            "roofline_regions": [
                {k: rr[k] for k in ("region", "kind", "executions",
                                    "bound")}
                for rr in ledger["regions"]],
            "roofline_total_gflops": round(ledger["total_flops"] / 1e9, 3),
        },
    }


def long_context_train_flops_per_step(batch, seq, layers, units, hidden,
                                      vocab):
    """Analytic matmul FLOPs of one long-context train step: fused qkv +
    scores/values + out proj + FFN per layer, vocab head; train = 3x.
    Ring attention moves kv around but computes the same score FLOPs."""
    N = batch * seq
    per_layer = 2 * N * units * 3 * units + 4 * N * seq * units \
        + 2 * N * units * units + 4 * N * units * hidden
    return 3 * (layers * per_layer + 2 * N * units * vocab)


def bench_long_context(steps, warmup):
    """Long-context recipe (recipes/long_context.py) as a benchmarked
    workload on a dp x sp mesh: ring attention over sequence shards,
    fused-step time, MFU, roofline row, per-step ppermute ring bytes, and
    the same ledger-vs-cost FLOP reconciliation gate as bench_moe.

    Env knobs (CPU-sized defaults): BENCH_LC_DP (2), BENCH_LC_SP (2),
    BENCH_LC_BATCH (4), BENCH_LC_SEQ (512), BENCH_LC_VOCAB (256)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, telemetry
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.recipes import get_recipe

    ndp = int(os.environ.get("BENCH_LC_DP", 2))
    nsp = int(os.environ.get("BENCH_LC_SP", 2))
    batch = int(os.environ.get("BENCH_LC_BATCH", 4))
    seq = int(os.environ.get("BENCH_LC_SEQ", 512))
    vocab = int(os.environ.get("BENCH_LC_VOCAB", 256))
    devs = _lane_devices(ndp * nsp)
    mesh = make_mesh({"dp": ndp, "sp": nsp}, devices=devs)

    r = get_recipe("long_context")
    mx.random.seed(0)
    net = r.build_model(vocab_size=vocab, seq_len=seq)
    tr = r.build_trainer(net, mesh)
    rs = np.random.RandomState(0)
    x = nd.array(rs.randint(0, vocab, (batch, seq)), dtype="int32")
    y = nd.array(rs.randint(0, vocab, (batch, seq)), dtype="int32")

    dt_off, dt_on, ledger, flops_step = _recipe_run(tr, x, y, steps, warmup)
    ring_bytes, ring_calls = tr._ring_step_bytes((batch, seq))
    # cost_analysis counts the per-device SPMD program; the analytic
    # count is global — divide by the mesh size to compare
    analytic = long_context_train_flops_per_step(
        batch, seq, 2, 64, 128, vocab) / (ndp * nsp)
    recon = ledger["total_flops"] / max(flops_step * steps, 1.0)
    tok_s = batch * seq * steps / dt_on
    return {
        "metric": "long_context_recipe_flops_reconciliation",
        "value": round(recon, 4),
        "unit": "ledger/cost_analysis (pass: within 5% of 1.0)",
        "vs_baseline": round(dt_on / max(dt_off, 1e-9), 3),
        "extra": {
            "mesh": {"dp": ndp, "sp": nsp},
            "batch": batch, "seq": seq,
            "step_ms_disabled": round(dt_off / steps * 1e3, 2),
            "step_ms_enabled": round(dt_on / steps * 1e3, 2),
            "tokens_per_s": round(tok_s, 1),
            "gflops_per_step_cost": round(flops_step / 1e9, 3),
            "gflops_per_step_analytic": round(analytic / 1e9, 3),
            "analytic_vs_cost": round(analytic / max(flops_step, 1.0), 4),
            **_rates(flops_step * steps / dt_on, devs[0]),
            "ppermute_bytes_per_step": ring_bytes,
            "ppermute_calls_per_step": ring_calls,
            "roofline_regions": [
                {k: rr[k] for k in ("region", "kind", "executions",
                                    "bound")}
                for rr in ledger["regions"]],
            "roofline_total_gflops": round(ledger["total_flops"] / 1e9, 3),
        },
    }


def bench_lint_walltime():
    """Static-analyzer cost over the whole package (tier-1 runs mxlint via
    tests/test_lint_clean.py, so it must stay well under the suite budget:
    pass bar < 10 s). No accelerator involved — pure AST walking."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.mxlint import run_lint, all_passes
    t0 = time.perf_counter()
    findings = run_lint()
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_lint()
    best = min(warm, time.perf_counter() - t0)
    return {
        "metric": "lint_walltime",
        "value": round(best, 3),
        "unit": "s",
        "vs_baseline": round(best / 10.0, 4),  # fraction of the 10 s budget
        "extra": {
            "pass_10s": best < 10.0,
            "passes": len(all_passes()),
            "pass_names": sorted(all_passes()),
            "findings_total": len(findings),
            "first_run_s": round(warm, 3),
        },
    }


def bench_chaos():
    """The fault-injection plane's two promises, measured (ISSUE 13):

    1. **Free when off.** The headline A/B runs the elastic snapshot hot
       cycle (write_shard -> commit -> load -> SnapshotReader) with the
       plane disarmed vs armed-but-never-firing (every elastic point on
       ``every_nth:10^9`` — strictly MORE work than disarmed: the lock,
       the attempt counters, the schedule call all run). Gate: < 1%.
       The disarmed guard itself (`if _faults._ACTIVE` at a call site)
       is also timed directly, in ns/check.

    2. **Bounded recovery.** Per fault class, the wall-clock cost of one
       injected transient fault absorbed by its recovery path, vs the
       clean run: shard write / manifest commit / manifest read under
       ``first_k:1`` (io_retry), a DeviceFeed producer restart
       (exactly-once redelivery), and the serving admission reject
       latency (how fast an overloaded queue says 503-equivalent).
    """
    import shutil
    import statistics
    import tempfile
    import threading

    from mxnet_tpu import faults
    from mxnet_tpu.elastic import manifest as _manifest
    from mxnet_tpu.engine.async_feed import DeviceFeed
    from mxnet_tpu.serving.batcher import ContinuousBatcher, ServerOverloaded

    os.environ["MXNET_TPU_IO_BACKOFF"] = "0.001"  # recovery lanes: tiny,
    os.environ["MXNET_TPU_IO_BACKOFF_MAX"] = "0.002"  # bounded jitter
    cycles = int(os.environ.get("BENCH_CHAOS_CYCLES", 60))
    reps = int(os.environ.get("BENCH_CHAOS_REPS", 3))
    rs = np.random.RandomState(0)
    arr = rs.uniform(-1, 1, (64, 128)).astype(np.float32)
    entries = [("w", [(0, 64), (0, 128)], arr, arr.shape, arr.dtype)]
    root = tempfile.mkdtemp(prefix="mx-bench-chaos-")
    counter = [0]

    def cycle(tag):
        counter[0] += 1
        step = counter[0]
        sub = os.path.join(root, tag)
        sdir = _manifest.step_path(sub, step)
        _manifest.write_shard(sdir, 0, entries)
        _manifest.commit(sdir, step, {"step": step})
        man = _manifest.load(sub, step)
        with _manifest.SnapshotReader(sub, step, manifest=man) as rd:
            rd("w")

    try:
        faults.clear()
        for _ in range(5):  # warm the fs path + imports
            cycle("warm")
        never = "every_nth:1000000000"
        dt_off = dt_on = float("inf")
        for _ in range(reps):  # paired interleaved reps, min aggregation
            t0 = time.perf_counter()
            for _ in range(cycles):
                cycle("off")
            dt_off = min(dt_off, time.perf_counter() - t0)
            for p in ("elastic.write_shard", "elastic.commit",
                      "elastic.read"):
                faults.inject(p, never)
            t0 = time.perf_counter()
            for _ in range(cycles):
                cycle("on")
            dt_on = min(dt_on, time.perf_counter() - t0)
            faults.clear()
        overhead = dt_on / dt_off - 1.0

        # disarmed call-site guard, ns/check (the TRUE disabled path)
        n = 2_000_000
        t0 = time.perf_counter()
        for _ in range(n):
            if faults._ACTIVE:
                faults.check("elastic.read")
        guard_ns = (time.perf_counter() - t0) / n * 1e9

        def _recover(point, fn, trials=15):
            """Median wall of one clean run vs one run whose FIRST attempt
            is injected and absorbed (first_k:1 + counter reset)."""
            clean, faulty = [], []
            for _ in range(trials):
                t0 = time.perf_counter()
                fn()
                clean.append(time.perf_counter() - t0)
                faults.inject(point, "first_k:1")
                try:
                    t0 = time.perf_counter()
                    fn()
                    faulty.append(time.perf_counter() - t0)
                finally:
                    faults.clear()  # reset attempts so first_k re-fires
            return (statistics.median(clean) * 1e3,
                    statistics.median(faulty) * 1e3)

        wr_clean, wr_fault = _recover(
            "elastic.write_shard",
            lambda: _manifest.write_shard(
                _manifest.step_path(os.path.join(root, "rw"), 1), 0,
                entries))
        cm_state = {"n": 1000}

        def _commit_once():
            cm_state["n"] += 1
            sdir = _manifest.step_path(os.path.join(root, "rc"),
                                       cm_state["n"])
            _manifest.write_shard(sdir, 0, entries)
            faults.clear("elastic.write_shard")
            _manifest.commit(sdir, cm_state["n"], {"step": cm_state["n"]})

        cm_clean, cm_fault = _recover("elastic.commit", _commit_once)
        rd_clean, rd_fault = _recover(
            "elastic.read",
            lambda: _manifest.load(os.path.join(root, "rc"),
                                   cm_state["n"]))

        # DeviceFeed producer restart: exactly-once redelivery cost
        class _Src:
            def __iter__(self):
                return (np.full((4,), float(i), np.float32)
                        for i in range(16))

        def _drain(restarts=0):
            feed = DeviceFeed(_Src(), name="bench-chaos",
                              restarts=restarts)
            t0 = time.perf_counter()
            n = sum(1 for _ in feed)
            dt = time.perf_counter() - t0
            feed.close()
            assert n == 16
            return dt * 1e3

        _drain()  # warm the backend
        fd_clean = statistics.median(_drain() for _ in range(5))
        fd_fault = []
        for _ in range(5):
            faults.inject("feed.produce", "first_k:1")
            try:
                fd_fault.append(_drain(restarts=1))
            finally:
                faults.clear()
        fd_fault = statistics.median(fd_fault)

        # serving admission reject latency (how fast overload says no)
        class _Stub:
            name = "bench"
            input_names = ("data",)
            output_names = ("out",)
            buckets = (1, 4)
            max_bucket = 4

            def input_dtype(self, name):
                return "float32"

            def row_shape(self, name):
                return (2,)

            def smallest_bucket(self, rows):
                return 1 if rows <= 1 else 4

            def place_input(self, name, host):
                return host

            def forward(self, bucket, feed):
                return [feed["data"]]

        b = ContinuousBatcher(_Stub(), max_wait_ms=10_000, max_queue=1)
        try:
            b.submit(data=np.zeros((2,), np.float32))  # fill the bound
            lat = []
            for _ in range(300):
                t0 = time.perf_counter()
                try:
                    b.submit(data=np.zeros((2,), np.float32))
                except ServerOverloaded:
                    lat.append(time.perf_counter() - t0)
            shed_us = statistics.median(lat) * 1e6
        finally:
            b.close()

        return {
            "metric": "chaos_disabled_path_overhead",
            "value": round(overhead * 100, 2),
            "unit": "% snapshot-cycle overhead, plane armed-never-fire "
                    "vs disarmed",
            "vs_baseline": round(dt_on / dt_off, 4),
            "extra": {
                "pass_lt_1pct": overhead < 0.01,
                "cycles": cycles,
                "cycle_ms_disarmed": round(dt_off / cycles * 1e3, 3),
                "cycle_ms_armed_never_fire": round(dt_on / cycles * 1e3, 3),
                "disarmed_guard_ns_per_check": round(guard_ns, 1),
                "recovery_ms": {
                    "elastic.write_shard": {"clean": round(wr_clean, 3),
                                            "one_fault": round(wr_fault, 3)},
                    "elastic.commit": {"clean": round(cm_clean, 3),
                                       "one_fault": round(cm_fault, 3)},
                    "elastic.read": {"clean": round(rd_clean, 3),
                                     "one_fault": round(rd_fault, 3)},
                    "feed.produce_restart_16_batches": {
                        "clean": round(fd_clean, 3),
                        "one_fault": round(fd_fault, 3)},
                },
                "shed_reject_us_p50": round(shed_us, 1),
                "io_backoff_s": float(os.environ["MXNET_TPU_IO_BACKOFF"]),
                "host_cores": os.cpu_count(),
            },
        }
    finally:
        faults.clear()
        shutil.rmtree(root, ignore_errors=True)


def bench_multihost():
    """The multi-host control plane's costs, measured (ISSUE 15):

    1. **Free when idle (the gate).** elastic.run's step path with a
       coordinator ATTACHED but quiet (heartbeats throttled to a
       realistic interval, no stop posted) vs coordinator=None, paired
       interleaved reps, min aggregation (chaos protocol). The hook is
       one clock read + two flag checks per step; gate: < 1%.
    2. **Heartbeat cost**: µs per forced membership-lease write (the
       throttle ceiling — at interval h seconds, a host pays this once
       per h, not per step).
    3. **Commit-barrier latency vs N**: N coordinators over one shared
       directory (threads as hosts — same filesystem protocol, zero
       process-boot noise), marker write -> global manifest visible.
    4. **Kill-and-resume wall-clock**: the real multi-process drill —
       3 spawned hosts, one killed mid-run, survivors coordinate a stop
       and commit; then a 2-host relaunch resumes the trajectory.

    CPU-container caveats: spawned drill hosts each pay a ~0.5 s
    mxnet_tpu import on boot and share one core with the survivors, so
    kill_resume_s is dominated by process boot + lease expiry, not by
    protocol IO; commit-barrier numbers are tmpfs-backed local fs, a
    network filesystem multiplies them by its metadata RTT.
    """
    import shutil
    import statistics
    import tempfile
    import threading

    from mxnet_tpu import elastic
    from mxnet_tpu.elastic import drill
    from mxnet_tpu.elastic import manifest as _manifest
    from mxnet_tpu.elastic.coordinator import Coordinator

    steps = int(os.environ.get("BENCH_MULTIHOST_STEPS", 300))
    reps = int(os.environ.get("BENCH_MULTIHOST_REPS", 5))
    dim, hidden, batch = 96, 192, 64
    rs = np.random.RandomState(0)
    batches = [(rs.uniform(-1, 1, (batch, dim)),
                rs.uniform(-1, 1, (batch, 1))) for _ in range(8)]

    class _Step:
        """Numpy MLP step sized so one step is ~1 ms of real work — the
        scale at which a per-step µs hook is honestly gated at 1%."""

        def __init__(self):
            r = np.random.RandomState(1)
            self.w1 = r.randn(dim, hidden) * 0.3
            self.b1 = np.zeros(hidden)
            self.w2 = r.randn(hidden, 1) * 0.3
            self.b2 = np.zeros(1)
            self._t = 0

        def step(self, x, y):
            h = np.tanh(x @ self.w1 + self.b1)
            p = h @ self.w2 + self.b2
            e = p - y
            g = 2.0 * e / e.size
            gw2 = h.T @ g
            gh = (g @ self.w2.T) * (1.0 - h * h)
            self.w2 -= 0.05 * gw2
            self.b2 -= 0.05 * g.sum(0)
            self.w1 -= 0.05 * (x.T @ gh)
            self.b1 -= 0.05 * gh.sum(0)
            self._t += 1
            return float((e * e).mean())

        def drain(self):
            pass

    class _Feed:
        def __iter__(self):
            return iter(batches)

        def reset(self):
            pass

    root = tempfile.mkdtemp(prefix="mx-bench-multihost-")
    try:
        def run_once(coord, tag):
            tr = _Step()
            mgr = elastic.SnapshotManager(os.path.join(root, tag),
                                          coordinator=coord)
            mgr._last_saved = steps       # step-path A/B: no snapshot IO
            out = elastic.run(tr, _Feed(), steps, manager=mgr,
                              coordinator=coord)
            assert out["step"] == steps and not out["preempted"]

        coord = Coordinator(os.path.join(root, "ab"), 0,
                            lease_timeout=30.0, heartbeat_interval=5.0)
        coord.join()
        run_once(None, "warm-off")        # warm numpy + fs paths
        run_once(coord, "warm-on")
        dt_off = dt_on = float("inf")
        for _ in range(reps):             # paired interleaved, min-of-reps
            t0 = time.perf_counter()
            run_once(None, "off")
            dt_off = min(dt_off, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run_once(coord, "on")
            dt_on = min(dt_on, time.perf_counter() - t0)
        overhead = dt_on / dt_off - 1.0

        # heartbeat: µs per FORCED lease write (the throttle ceiling)
        n = 300
        t0 = time.perf_counter()
        for i in range(n):
            coord.heartbeat(i, force=True)
        hb_us = (time.perf_counter() - t0) / n * 1e6
        coord.leave()
        coord.close()

        # commit-barrier latency vs N (threads as hosts, shared dir)
        def barrier_once(world, tag):
            broot = os.path.join(root, tag)
            coords = [Coordinator(broot, r, lease_timeout=30.0,
                                  straggler_timeout=30.0,
                                  poll_interval=0.002)
                      for r in range(world)]
            for c in coords:
                c.join()
            for c in coords:
                c.view()
            sdir = _manifest.step_path(broot, 1)
            arr = rs.uniform(-1, 1, (32, 32)).astype(np.float32)
            for r in range(world):
                _manifest.write_shard(
                    sdir, r, [(f"w{r}", [(0, 32), (0, 32)], arr,
                               arr.shape, arr.dtype)])
            t0 = time.perf_counter()

            def host(c):
                c.write_marker(sdir, 1, nbytes=arr.nbytes)
                c.commit_snapshot(sdir, 1, {"step": 1})

            ts = [threading.Thread(target=host, args=(c,)) for c in coords]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.perf_counter() - t0
            for c in coords:
                c.leave()
                c.close()
            return dt * 1e3

        barrier_ms = {}
        for world in (2, 3, 4):
            barrier_ms[str(world)] = round(statistics.median(
                barrier_once(world, f"bar{world}-{i}")
                for i in range(3)), 2)

        # kill-and-resume wall-clock: the REAL multi-process drill
        droot = os.path.join(root, "drill")
        t0 = time.perf_counter()
        res = drill.run_drill(droot, world=3, num_steps=120,
                              save_every=20, report_tag="bench",
                              scenario={2: {"die_at_step": 5}},
                              lease_timeout=1.0, straggler_timeout=8.0,
                              step_sleep=0.02, timeout=90.0)
        drill_s = time.perf_counter() - t0
        assert res["exitcodes"][0] == 0 and res["exitcodes"][1] == 0, \
            res["exitcodes"]
        s = res["reports"][0]["final_step"]
        t0 = time.perf_counter()
        res2 = drill.run_drill(droot, world=2, num_steps=s + 10,
                               save_every=1000, report_tag="bench2",
                               lease_timeout=2.0, straggler_timeout=10.0,
                               timeout=60.0)
        resume_s = time.perf_counter() - t0
        assert res2["exitcodes"] == [0, 0], res2["exitcodes"]

        return {
            "metric": "multihost_step_path_overhead",
            "value": round(overhead * 100, 2),
            "unit": "% elastic.run step path, coordinator attached-quiet "
                    "vs none",
            "vs_baseline": round(dt_on / dt_off, 4),
            "extra": {
                "pass_lt_1pct": overhead < 0.01,
                "steps": steps,
                "reps": reps,
                "step_ms_baseline": round(dt_off / steps * 1e3, 4),
                "heartbeat_us_per_forced_beat": round(hb_us, 1),
                "commit_barrier_ms_vs_world": barrier_ms,
                "kill_and_resume_s": {
                    "drill_3hosts_kill1": round(drill_s, 2),
                    "resume_2hosts": round(resume_s, 2),
                    "survivor_final_step": s,
                },
                "host_cores": os.cpu_count(),
            },
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _virtual_cpu_devices(n):
    """Request n virtual host devices BEFORE the CPU backend initializes
    (no-op when real devices suffice or the flag is already set)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _env_int(name, default):
    return int(os.environ.get(name, default))


def _train_steps(steps, warmup):
    return (_env_int("BENCH_TRAIN_STEPS", steps),
            _env_int("BENCH_TRAIN_WARMUP", warmup))


def main():
    from mxnet_tpu import engine
    scenario = os.environ.get("BENCH_SCENARIO")
    if scenario == "lint_walltime":
        _emit(bench_lint_walltime())
        return
    if scenario == "multihost":
        # host-only: coordinator IO, the numpy toy step, and the spawned
        # drill hosts (which never import jax) all land on CPU
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _emit(bench_multihost())
        return
    if scenario == "chaos":
        # host-only: manifest IO, queue policy, and the DeviceFeed lane's
        # device_put land on CPU — the plane's costs are host costs
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _emit(bench_chaos())
        return
    # multi-device lanes: (bench fn, devices needed, default steps/warmup)
    mesh_lanes = {
        "async_feed": (bench_async_feed, _env_int("BENCH_FEED_DP", 4),
                       (40, 8)),
        "zero_dp": (bench_zero_dp, _env_int("BENCH_ZERO_DP", 8), (5, 2)),
        "pipeline": (bench_pipeline, _env_int("BENCH_PP", 4), (5, 2)),
        "elastic": (bench_elastic, _env_int("BENCH_ELASTIC_DP", 4), (40, 8)),
        "moe": (bench_moe,
                _env_int("BENCH_MOE_DP", 2) * _env_int("BENCH_MOE_EP", 2),
                (8, 2)),
        "long_context": (bench_long_context,
                         _env_int("BENCH_LC_DP", 2) * _env_int("BENCH_LC_SP", 2),
                         (8, 2)),
    }
    if scenario in mesh_lanes:
        fn, need, defaults = mesh_lanes[scenario]
        _virtual_cpu_devices(need)
        engine.enable_compile_cache()
        # async_feed times on one device (only its parity arm uses the dp
        # mesh); every other lane times on the mesh it asked for
        _emit(fn(*_train_steps(*defaults)),
              _lane_devices(1 if scenario == "async_feed" else need))
        return
    engine.enable_compile_cache()
    single = {
        "serving": bench_serving,
        "roofline": lambda: bench_roofline(*_train_steps(4, 2)),
        "train_step": lambda: bench_train_step(*_train_steps(50, 10)),
        "telemetry_overhead":
            lambda: bench_telemetry_overhead(*_train_steps(60, 10)),
        "tracing": lambda: bench_tracing(*_train_steps(60, 10)),
        "goodput": lambda: bench_goodput(*_train_steps(60, 10)),
    }
    if scenario in single:
        _emit(single[scenario]())
        return
    headline = bench_resnet(BATCH, IMAGE, STEPS, WARMUP)
    result = {
        "metric": "resnet50_train_throughput_bs32",
        "value": headline["img_s"],
        "unit": "img/s",
        "vs_baseline": round(headline["img_s"] / BASELINE_IMG_S, 3),
    }
    result.update({k: headline[k] for k in ("tflops", "mfu")
                   if k in headline})
    if not QUICK:
        result["extra"] = {
            "resnet50_bs256": bench_resnet(
                _env_int("BENCH_BATCH2", 256), IMAGE, max(STEPS // 4, 3), 1),
            "bert_base_mlm": bench_bert(
                _env_int("BERT_BATCH", 16), _env_int("BERT_SEQ", 512),
                max(STEPS // 3, 3), 1),
            "bert_large_mlm": bench_bert(
                _env_int("BERT_LARGE_BATCH", 8), _env_int("BERT_SEQ", 512),
                max(STEPS // 6, 3), 1, large=True),
            "wide_conv_768": bench_wide_conv(BATCH, max(STEPS // 3, 3), 1),
        }
    _emit(result)


if __name__ == "__main__":
    sys.exit(main())
