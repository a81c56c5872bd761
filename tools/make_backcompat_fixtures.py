"""Generate the committed model back-compat fixtures (reference
tests/nightly/model_backwards_compatibility_check/train_mxnet_legacy_models.sh:
artifacts saved by an OLD version must keep loading bit-exactly in every
NEW version).

Here the "old version" is the round that ran this script; the artifacts
under tests/fixtures/backcompat/ are committed BYTES — never regenerated
in CI — and tests/test_model_backcompat.py asserts the current code
still loads every format and reproduces the recorded outputs. Re-run
this script ONLY to add new artifact families, never to paper over a
loading regression.

Covers every serialization surface:
  gluon save_parameters / load_parameters      (.params, gluon format)
  HybridBlock.export -> SymbolBlock.imports    (symbol.json + arg:/aux:)
  Module.save_checkpoint / Module.load         (+ optimizer states)
  gluon Trainer save_states / load_states
  serialization.save_ndarrays / load_ndarrays  (raw tensor dict)

Run: JAX_PLATFORMS=cpu python tools/make_backcompat_fixtures.py
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# pin the CPU backend exactly the way tests/conftest.py does: the fixtures
# must carry CPU numerics because the CI suite replays them on CPU
import jax  # noqa: E402
jax.config.update("jax_default_device", jax.devices("cpu")[0])

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd, gluon, autograd  # noqa: E402
mx.test_utils.set_default_context(mx.cpu())

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                   "tests", "fixtures", "backcompat")


def build_net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1),
            gluon.nn.BatchNorm(),
            gluon.nn.Activation("relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Dense(16, activation="relu"),
            gluon.nn.Dense(4))
    return net


def main():
    os.makedirs(OUT, exist_ok=True)
    mx.random.seed(1234)
    rng = np.random.RandomState(7)
    x = rng.uniform(-1, 1, (2, 3, 8, 8)).astype(np.float32)

    net = build_net()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    # a few training steps so BN aux state and momentum are non-trivial
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    for i in range(5):
        xb = nd.array(rng.uniform(-1, 1, (4, 3, 8, 8)).astype(np.float32))
        yb = nd.array(rng.randint(0, 4, 4), dtype="int32")
        with autograd.record():
            loss = ce(net(xb), yb).mean()
        loss.backward()
        trainer.step(1)

    expected = net(nd.array(x)).asnumpy()

    # 1. gluon parameter file
    net.save_parameters(os.path.join(OUT, "gluon_cnn.params"))
    # 2. exported symbol + checkpoint params (SymbolBlock.imports surface)
    net.export(os.path.join(OUT, "gluon_cnn_export"), epoch=0)
    # 3. trainer states
    trainer.save_states(os.path.join(OUT, "gluon_cnn.states"))
    # 4. raw tensor dict incl. every dtype the format supports
    tensors = {
        "float32": nd.array(rng.normal(0, 1, (3, 5)).astype(np.float32)),
        "float16": nd.array(rng.normal(0, 1, (4,)).astype(np.float16)),
        "int32": nd.array(rng.randint(-9, 9, (2, 3)), dtype="int32"),
        "int64": nd.array(rng.randint(-9, 9, (6,)).astype(np.int64)),
        "uint8": nd.array(rng.randint(0, 255, (2, 2)).astype(np.uint8)),
        "bool": nd.array(np.array([True, False, True])),
        "scalar": nd.array(np.float32(3.25)),
    }
    from mxnet_tpu.serialization import save_ndarrays
    save_ndarrays(os.path.join(OUT, "tensors.nd"), tensors)

    # 5. Module checkpoint with optimizer states
    import mxnet_tpu.symbol as sym
    data = sym.Variable("data")
    h = sym.FullyConnected(data, num_hidden=8, name="fc1")
    h = sym.Activation(h, act_type="relu", name="relu1")
    out = sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=3, name="fc2"),
                            name="softmax")
    from mxnet_tpu.module import Module
    from mxnet_tpu.io import NDArrayIter
    mod = Module(out, data_names=["data"], label_names=["softmax_label"])
    xs = rng.uniform(-1, 1, (16, 6)).astype(np.float32)
    ys = rng.randint(0, 3, 16).astype(np.float32)
    it = NDArrayIter(xs, ys, batch_size=8, label_name="softmax_label")
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    mod.save_checkpoint(os.path.join(OUT, "module_mlp"), 2,
                        save_optimizer_states=True)
    mod_x = xs[:8]
    mod.forward(mx.io.DataBatch(data=[nd.array(mod_x)]), is_train=False)
    mod_expected = mod.get_outputs()[0].asnumpy()

    np.savez(os.path.join(OUT, "expected.npz"),
             x=x, y=expected, mod_x=mod_x, mod_y=mod_expected)
    with open(os.path.join(OUT, "MANIFEST.json"), "w") as f:
        json.dump({
            "created_round": 5,
            "format_doc": "mxnet_tpu/serialization.py",
            "artifacts": sorted(os.listdir(OUT)),
        }, f, indent=1)
    print("fixtures written to", OUT)
    for a in sorted(os.listdir(OUT)):
        print(" ", a, os.path.getsize(os.path.join(OUT, a)), "bytes")


if __name__ == "__main__":
    main()
