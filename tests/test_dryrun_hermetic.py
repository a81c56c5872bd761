"""Driver-shaped hermeticity check for __graft_entry__.dryrun_multichip.

An early failure mode: the dryrun touched the *default*
XLA backend (eager jax.random.key at import, default-context resolution), and
on a host whose accelerator runtime was broken (libtpu version mismatch) the
first eager op crashed before the CPU mesh was ever built.

This test re-runs the dryrun the way the driver does — a fresh subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and *no*
``JAX_PLATFORMS`` override — with a guard installed at jax's single compile
chokepoint (``jax._src.compiler.compile_or_get_cached``): any compilation for
a non-cpu backend raises.  The guard is self-validated (an uncommitted
``jnp.ones`` must trip it when an accelerator is the default backend), then
``dryrun_multichip(8)`` must complete without ever compiling for, or leaving
live arrays on, a non-cpu device.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import jax
from jax._src import compiler

real = compiler.compile_or_get_cached

def guarded(backend, *a, **k):
    if backend.platform != "cpu":
        raise RuntimeError(f"compile on non-cpu backend: {backend.platform}")
    return real(backend, *a, **k)

compiler.compile_or_get_cached = guarded

# Self-validate the guard: with an accelerator as the default backend an
# uncommitted op must trip it.  If the default backend is already cpu (no
# accelerator on this host) the hermeticity aspect is vacuous but the dryrun
# itself still runs.
try:
    jax.numpy.ones(3)
    print("GUARD_VACUOUS_DEFAULT_IS_CPU")
except RuntimeError:
    print("GUARD_ACTIVE")

import __graft_entry__
__graft_entry__.dryrun_multichip(8)

bad = [a for a in jax.live_arrays()
       if any(d.platform != "cpu" for d in a.devices())]
assert not bad, f"live non-cpu arrays after dryrun: {bad[:3]}"
print("HERMETIC_DRYRUN_OK")
"""


@pytest.mark.slow
def test_dryrun_multichip_is_hermetic_on_cpu():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # let the accelerator be the default
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, (
        f"dryrun subprocess failed\nstdout:\n{proc.stdout[-4000:]}\n"
        f"stderr:\n{proc.stderr[-4000:]}")
    assert "HERMETIC_DRYRUN_OK" in proc.stdout
