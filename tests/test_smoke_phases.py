"""chip_smoke.py on the CPU: the script refuses to run off the chip, and its
phase functions hold at toy sizes with the Pallas kernels in interpret mode
(the full sizes run on the TPU: `python chip_smoke.py`).

The file name sorts late on purpose: tier-1 is cut at 870 s inside the dense
op sweeps (ROADMAP D8), and these tests compile a dozen small programs."""
import os
import subprocess
import sys

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, gluon, telemetry
from mxnet_tpu.models.bert import BertModel
from mxnet_tpu.parallel import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def test_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def _toy_convnet():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1), gluon.nn.BatchNorm(),
            gluon.nn.Activation("relu"), gluon.nn.GlobalAvgPool2D(),
            gluon.nn.Flatten(), gluon.nn.Dense(10))
    return net


def _toy_bert(**kw):
    return BertModel(vocab_size=64, num_layers=1, units=32, hidden_size=64,
                     num_heads=2, **kw)


@pytest.fixture()
def smoke(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    was_on = telemetry.is_enabled()
    telemetry.enable()
    # the phases look for the HLO audits THEY caused; a configuration an
    # earlier test already compiled would not show up as new
    engine.hlo_audit.reset()
    s = chip_smoke.Smoke(mosaic=False)
    yield s
    s.close()
    if not was_on:
        telemetry.disable()


def _one_cpu():
    return make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])


def test_phases_at_toy_sizes(smoke, monkeypatch):
    mesh = _one_cpu()
    ctx = mx.tpu(0)  # JAX_PLATFORMS=cpu: resolves to the host device
    r = chip_smoke.phase_resnet(smoke, mesh, _toy_convnet, batch=8, image=8,
                                classes=10)
    assert len(r) == 13  # 3 fed steps + 2 x run_steps(5)
    chip_smoke.phase_flash_kernel(smoke, shapes=((1, 2, 100, 64),))
    # the flash branch at a toy length: the crossover is an existing knob
    monkeypatch.setenv("MXNET_FLASH_ATTENTION_MIN_SEQ", "128")
    _, kernels = chip_smoke.phase_bert_flash(
        smoke, mesh, _toy_bert(max_length=128), batch=2, seq=128, vocab=64,
        layers=1)
    assert kernels == 0  # interpret mode: nothing went through Mosaic
    chip_smoke.phase_small_kernels(smoke, ctx)
    chip_smoke.phase_serving(smoke, _toy_convnet(), ctx, row_shape=(3, 8, 8),
                             buckets=(1, 4), requests=6)


@pytest.mark.slow
def test_four_chip_phase_on_virtual_devices(smoke):
    """Phase 7 only ever runs on a four-chip host; this is its rehearsal
    (and phase 2's: phase_bert at toy size)."""
    bert_fn = lambda: _toy_bert(max_length=16)  # noqa: E731
    b = chip_smoke.phase_bert(smoke, _one_cpu(), bert_fn, batch=4, seq=16,
                              vocab=64)
    chip_smoke.phase_four_chips(
        smoke, jax.devices("cpu"), _toy_convnet, bert_fn, b, resnet_batch=16,
        image=8, classes=10, bert_batch=16, seq=16, vocab=64)


def test_imperative_phase_with_donation_on(smoke, monkeypatch):
    """On the chip the update kernels donate the weight buffers; on the CPU
    they do not unless forced. chip_smoke found gluon.Trainer.step admitting
    the weight itself into its in-flight window, which the next step's
    update then deleted: the third step raised on the v5e."""
    from mxnet_tpu.optimizer import optimizer as opt
    monkeypatch.setenv("MXNET_TPU_DONATION", "1")
    for k in vars(opt).values():
        if isinstance(k, opt._UpdateKernel):  # re-resolve donation lazily
            monkeypatch.setattr(k, "_jit", None)
            monkeypatch.setattr(k, "_donating", False)
    before = engine.cache_stats()["donated_updates"]
    chip_smoke.phase_imperative(smoke, mx.tpu(0))
    assert engine.cache_stats()["donated_updates"] > before
