"""Test configuration.

The suite runs on the host: tier-1 sets JAX_PLATFORMS=cpu, and this file
additionally pins jax's default device to CPU (so a run without the variable
on a machine with a chip still stays off it) and requests 8 virtual CPU
devices — the 8-way mesh for sharding/collective tests without hardware
(SURVEY.md §4's N-process local pod pattern, realized as N virtual devices).
The real chip is exercised by chip_smoke.py and bench.py, not the unit suite.
"""
import os

# must be set before the CPU backend initializes
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

_cpu0 = jax.devices("cpu")[0]
jax.config.update("jax_default_device", _cpu0)

import numpy as _np
import pytest

import mxnet_tpu as mx

# default context = cpu so every eager op runs on the local CPU backend
mx.test_utils.set_default_context(mx.cpu())


def cpu_devices():
    return jax.devices("cpu")


def pytest_addoption(parser):
    parser.addoption(
        "--sanitize", action="store_true", default=False,
        help="run under the jax sanitizers: tracer-leak + NaN checks "
             "globally, transfer_guard('disallow') around each fused step "
             "(mxnet_tpu.sanitize; same switches as MXNET_TPU_SANITIZE=1)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (subprocess compiles, trainings)")
    if config.getoption("--sanitize"):
        mx.sanitize.enable()


@pytest.fixture
def host_mesh8():
    """8-way 'dp' mesh over the virtual host devices this conftest spawns
    via ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set above,
    before the CPU backend initializes — it cannot be changed afterwards).
    The multi-device trainer tests (tests/test_zero_dp.py's sharded weight
    update in particular) depend on real cross-device collectives, so fail
    loudly if the flag did not take."""
    devs = jax.devices("cpu")
    assert len(devs) >= 8, (
        "need 8 virtual CPU devices — XLA_FLAGS was set too late "
        f"(have {len(devs)})")
    from mxnet_tpu.parallel import make_mesh
    return make_mesh({"dp": 8}, devices=devs[:8])


@pytest.fixture(autouse=True)
def _seed_everything(request):
    """with_seed parity (reference tests/python/unittest/common.py:161):
    deterministic seeds per test, logged for repro. MXNET_TEST_SEED overrides
    (set by tools/flakiness_checker.py to sweep seeds)."""
    env_seed = os.environ.get("MXNET_TEST_SEED")
    seed = int(env_seed) if env_seed else \
        abs(hash(request.node.nodeid)) % (2 ** 31)
    _np.random.seed(seed)
    mx.random.seed(seed)
    yield
