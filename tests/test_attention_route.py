"""The choice between the flash kernels and the plain attention has one home,
`ops/attention.py: use_flash`: each model site asks it and reads no
environment of its own."""
import importlib
import os

import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import env
from mxnet_tpu.ops import attention

# the package's attribute of that name is the function, not the module
pallas_flash = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")

NAME = "MXNET_FLASH_ATTENTION_MIN_SEQ"
CROSSOVER = 16          # the test's own, far from the default
UNITS, HEADS, BATCH = 32, 2, 2


def _bert(T):
    from mxnet_tpu.models.bert import SelfAttention
    layer = SelfAttention(UNITS, HEADS)
    layer.initialize(mx.init.Xavier())
    return layer(mx.nd.ones((BATCH, T, UNITS)))


def _gqa(T):
    from mxnet_tpu.models.hybrid_decoder import GroupedQueryAttention
    layer = GroupedQueryAttention(UNITS, HEADS, 1)
    layer.initialize(mx.init.Xavier())
    return layer(mx.nd.ones((BATCH, T, UNITS)))


def _megatron(T):
    from mxnet_tpu.models.bert import TransformerEncoderCell
    from mxnet_tpu.parallel import megatron
    cell = TransformerEncoderCell(UNITS, 2 * UNITS, HEADS)
    cell.initialize(mx.init.Xavier())
    plist = list(cell.collect_params().values())
    plan = megatron.plan_cell(cell, plist, 1)
    leaves = [jnp.asarray(p.data().asnumpy()).reshape(
        megatron.view_shape(p.shape, layout))
        for p, layout in zip(plist, plan.layouts)]
    return megatron._attention(plan, megatron.PartitionConfig("tp", 1),
                               jnp.ones((BATCH, T, UNITS)), leaves, None,
                               train=False)


@pytest.mark.parametrize("where", ["below", "at"])
@pytest.mark.parametrize("site", [_bert, _gqa, _megatron],
                         ids=["bert", "gqa", "megatron"])
def test_route(site, where, monkeypatch):
    T = CROSSOVER - 8 if where == "below" else CROSSOVER
    # the environment says the opposite of the patched function: a site that
    # still read it would take the other route
    monkeypatch.setenv(NAME, "1" if where == "below" else "4096")
    asked, kernel = [], []

    def use_flash(seq_len):
        asked.append(seq_len)
        return seq_len >= CROSSOVER

    def flash(q, k, v, **kwargs):
        kernel.append(q.shape)
        return jnp.zeros_like(q)

    monkeypatch.setattr(attention, "use_flash", use_flash)
    monkeypatch.setattr(pallas_flash, "flash_attention", flash)
    out = site(T)
    assert tuple(out.shape) == (BATCH, T, UNITS)
    assert asked == [T]
    assert kernel == ([(BATCH, HEADS, T, UNITS // HEADS)]
                      if where == "at" else [])


def test_min_seq_is_declared(monkeypatch):
    default, typ, _ = env.items()[NAME]
    assert (default, typ) == (1024, int)
    monkeypatch.delenv(NAME, raising=False)
    assert attention.flash_min_seq() == 1024
    assert attention.use_flash(1024) and not attention.use_flash(1023)
    monkeypatch.setenv(NAME, "512")
    assert attention.use_flash(512) and not attention.use_flash(511)
    root = os.path.dirname(os.path.abspath(mx.__file__))
    holders = []
    for folder, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(folder, f)
                with open(path, encoding="utf-8") as fh:
                    if NAME in fh.read():
                        holders.append(os.path.relpath(path, root))
    assert holders == [os.path.join("ops", "attention.py")]
