"""bench.py names its device and hides no failure: every line carries
platform/device_kind/n_devices, the headline refuses to run off the TPU
unless JAX_PLATFORMS=cpu was asked for, a CPU line carries no tflops/mfu,
and main() neither retries nor turns a crashed configuration into a field."""
import inspect
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import bench  # noqa: E402


def test_headline_refuses_non_tpu_unless_cpu_is_explicit(monkeypatch):
    assert jax.devices()[0].platform == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="not 'tpu'"):
        bench._chip_device()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._chip_device().platform == "cpu"


def test_cpu_line_is_stamped_and_carries_no_rates(capsys):
    cpu = jax.devices("cpu")[0]
    assert bench._rates(5e13, cpu) == {}
    bench._emit({"metric": "m", "value": 1.0, **bench._rates(5e13, cpu)},
                [cpu])
    line = json.loads(capsys.readouterr().out)
    assert (line["platform"], line["device_kind"], line["n_devices"]) == \
        ("cpu", cpu.device_kind, 1)
    assert not {"mfu", "tflops", "mfu_vs_measured_peak"} & set(line)

    class Tpu:
        platform = "tpu"
    assert bench._rates(98.5e12, Tpu()) == {"tflops": 98.5, "mfu": 0.5}


def test_main_neither_retries_nor_swallows():
    src = inspect.getsource(bench)
    assert "_main_with_retry" not in src and "MEASURED_PEAK" not in src
    assert "except" not in inspect.getsource(bench.main)


@pytest.mark.slow
def test_quick_headline_on_cpu_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_QUICK="1",
               BENCH_BATCH="4", BENCH_IMAGE="32", BENCH_STEPS="2",
               BENCH_WARMUP="1")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["platform"] == "cpu" and "mfu" not in line
