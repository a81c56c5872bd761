"""Deployment story (VERDICT r1 item 8; replaces the reference's
include/mxnet/c_predict_api.h load-and-run-without-training path).

A trained HybridBlock exports to symbol-JSON + params; a FRESH python
process (no access to the model-building code) reloads it with
SymbolBlock.imports and must reproduce the training process's outputs
bit-for-bit-close. ONNX round-trips cover the cross-framework exit."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon
from mxnet_tpu.gluon.model_zoo import vision

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FRESH_PROCESS_SCRIPT = r"""
import json, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import SymbolBlock

prefix, out_path = sys.argv[1], sys.argv[2]
x = np.load(prefix + "-input.npy")
net = SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                          prefix + "-0000.params", ctx=mx.cpu())
y = net(nd.array(x, ctx=mx.cpu()))
np.save(out_path, y.asnumpy())
print("SERVED_OK")
"""


def _export_and_serve(net, x, prefix):
    """Export, reload in a fresh process, return its output."""
    net.export(prefix)
    np.save(prefix + "-input.npy", x)
    out_path = prefix + "-served.npy"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", FRESH_PROCESS_SCRIPT, prefix, out_path],
        capture_output=True, text=True, timeout=560, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SERVED_OK" in proc.stdout
    return np.load(out_path)


@pytest.mark.slow
@pytest.mark.parametrize("factory,in_shape", [
    (lambda: vision.resnet18_v1(classes=10), (2, 3, 32, 32)),
    (lambda: vision.mobilenet_v2_0_25(classes=10), (2, 3, 32, 32)),
    (lambda: vision.squeezenet1_1(classes=10), (2, 3, 64, 64)),
])
def test_export_serves_in_fresh_process(factory, in_shape, tmp_path):
    mx.random.seed(11)
    net = factory()
    net.initialize()
    net.hybridize()
    x = np.random.RandomState(0).uniform(-1, 1, in_shape).astype(np.float32)
    want = net(nd.array(x)).asnumpy()
    served = _export_and_serve(net, x, str(tmp_path / "model"))
    np.testing.assert_allclose(served, want, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_onnx_roundtrip_model_zoo(tmp_path):
    """Model-zoo net -> ONNX -> import -> numerically identical executor."""
    from mxnet_tpu.contrib import onnx as mxonnx

    mx.random.seed(12)
    net = vision.alexnet(classes=10)
    net.initialize()
    net.hybridize()
    x = np.random.RandomState(1).uniform(-1, 1, (2, 3, 224, 224)).astype(np.float32)
    want = net(nd.array(x)).asnumpy()

    prefix = str(tmp_path / "alexnet")
    net.export(prefix)
    sym, args, aux = mx.model.load_checkpoint(prefix, 0)
    onnx_path = str(tmp_path / "alexnet.onnx")
    mxonnx.export_model(sym, {**args, **aux}, [x.shape],
                        onnx_file_path=onnx_path)

    sym2, args2, aux2 = mxonnx.import_model(onnx_path)
    data_name = [n for n in sym2.list_inputs()
                 if n not in args2 and n not in aux2][0]
    e = sym2.bind(mx.cpu(), {**args2, **aux2, data_name: nd.array(x)})
    got = e.forward()[0].asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_symbolblock_collect_params_carries_data(tmp_path):
    """Imported SymbolBlock must expose loaded params with real data
    (re-saveable), not shape-only shells."""
    mx.random.seed(5)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((1, 3)))
    prefix = str(tmp_path / "m")
    net.export(prefix)
    blk = gluon.SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                    prefix + "-0000.params", ctx=mx.cpu())
    pd = blk.collect_params()
    assert len(pd.keys()) == 2
    for p in pd.values():
        assert p.data() is not None and p.data().size > 0


def test_symbolblock_set_data_affects_inference(tmp_path):
    """set_data on collect_params() results must feed subsequent forwards
    (advisor round-2: params were a first-call snapshot before)."""
    mx.random.seed(6)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, use_bias=False))
    net.initialize()
    x = nd.ones((1, 3))
    net(x)
    prefix = str(tmp_path / "m2")
    net.export(prefix)
    blk = gluon.SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                    prefix + "-0000.params", ctx=mx.cpu())
    out1 = blk(x).asnumpy()
    pd = blk.collect_params()
    for p in pd.values():
        p.set_data(p.data() * 2.0)
    out2 = blk(x).asnumpy()
    np.testing.assert_allclose(out2, out1 * 2.0, rtol=1e-5)
    # and after the executor cache is warm, too
    out3 = blk(x).asnumpy()
    np.testing.assert_allclose(out3, out2, rtol=1e-6)


SLIM_PREDICT_SCRIPT = r"""
import json, sys, time
import numpy as np

t0 = time.perf_counter()
from mxnet_tpu.predict import Predictor
t_import = time.perf_counter() - t0

prefix, out_path = sys.argv[1], sys.argv[2]
x = np.load(prefix + "-input.npy")
p = Predictor(prefix + "-symbol.json", prefix + "-0000.params",
              input_shapes={"data": x.shape})
y = p.predict(x)
np.save(out_path, y)

# the c_predict_api contract: serving must not pull training machinery
banned = [m for m in sys.modules
          if m.startswith("mxnet_tpu.") and any(
              m.startswith("mxnet_tpu." + h)
              for h in ("parallel", "optimizer", "gluon", "io", "module",
                        "model", "kvstore", "metric", "image", "contrib"))]
assert not banned, f"slim predict imported training machinery: {banned}"

# shape contract: a different shape must demand reshape()
try:
    p.predict(np.zeros((x.shape[0] + 1,) + x.shape[1:], np.float32))
    raise SystemExit("expected shape error")
except Exception as e:
    assert "reshape" in str(e), e

print(f"SLIM_OK import={t_import:.2f}")
"""


@pytest.mark.slow
def test_slim_predict_runtime(tmp_path):
    """mxnet_tpu.predict (reference c_predict_api.h analog): fresh-process
    serving with NO training imports, bit-close to the training net."""
    mx.random.seed(12)
    net = vision.resnet18_v1(classes=10)
    net.initialize()
    net.hybridize()
    x = np.random.RandomState(1).uniform(-1, 1, (2, 3, 32, 32)).astype(np.float32)
    want = net(nd.array(x)).asnumpy()
    prefix = str(tmp_path / "slim")
    net.export(prefix)
    np.save(prefix + "-input.npy", x)
    out_path = prefix + "-served.npy"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", SLIM_PREDICT_SCRIPT, prefix, out_path],
        capture_output=True, text=True, timeout=560, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SLIM_OK" in proc.stdout
    np.testing.assert_allclose(np.load(out_path), want, rtol=1e-4, atol=1e-5)
