"""Device contexts never hide the device: an accelerator context resolves to
the host only when the process was explicitly held to the CPU."""
import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import context
from mxnet_tpu.base import MXNetError


def test_tpu_context_needs_an_accelerator_or_explicit_cpu(monkeypatch):
    cpu0 = jax.local_devices(backend="cpu")[0]
    # this suite runs under JAX_PLATFORMS=cpu: reference scripts that say
    # ctx=mx.gpu(0) run on the host
    assert mx.tpu(0).jax_device == mx.gpu(0).jax_device == cpu0
    assert mx.num_tpus() == 0 and mx.current_context() == mx.cpu(0)
    # the same process without the explicit pin: asking for tpu(0) raises
    monkeypatch.setattr(context, "_held_to_cpu", lambda: False)
    with pytest.raises(MXNetError, match=r"tpu\(0\) requested"):
        mx.tpu(0).jax_device
    assert mx.cpu(0).jax_device == cpu0
