"""The choice between the flash kernels and the plain attention has one home,
`ops/attention.py: use_flash`: each model site asks it and reads no
environment of its own. Where the trainer's GSPMD step is traced for several
devices, the kernels' call is partitioned over its batch axis, and
`mx_attention_route_total` says which route each traced layer took."""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import env
from mxnet_tpu.ops import attention, registry
from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

# the package's attribute of that name is the function, not the module
pallas_flash = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")

NAME = "MXNET_FLASH_ATTENTION_MIN_SEQ"
CROSSOVER = 16          # the test's own, far from the default
UNITS, HEADS, BATCH = 32, 2, 2


def _bert(T, batch=BATCH, traced=False):
    from mxnet_tpu.models.bert import SelfAttention
    layer = SelfAttention(UNITS, HEADS)
    layer.initialize(mx.init.Xavier())
    x = mx.nd.ones((batch, T, UNITS))
    if traced:
        return jax.eval_shape(lambda a: layer(mx.nd.NDArray(a))._data,
                              x._data)
    return layer(x)


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
    assert (default, typ) == (512, int)
    monkeypatch.delenv(NAME, raising=False)
    assert attention.flash_min_seq() == 512
    assert attention.use_flash(512) and not attention.use_flash(511)
    monkeypatch.setenv(NAME, "1024")
    assert attention.use_flash(1024) and not attention.use_flash(1023)
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


# ---------------------------------------------------------------------------
# the route a traced layer took, and the partitioned route through the trainer
# ---------------------------------------------------------------------------

@pytest.fixture()
def routes(monkeypatch):
    """A call gives what `mx_attention_route_total` counted since the call
    before it; the Pallas kernels run in interpret mode."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    monkeypatch.delenv(NAME, raising=False)
    was_on = telemetry.is_enabled()
    telemetry.enable()
    family = telemetry.counter("mx_attention_route_total", labelnames=("route",))
    seen = {}

    def since_last_read():
        new = {}
        for route in ("plain", "flash", "flash_partitioned"):
            now = int(family.get(route))
            if now != seen.get(route, now):
                new[route] = now - seen[route]
            seen[route] = now
        return new

    since_last_read()
    yield since_last_read
    if not was_on:
        telemetry.disable()


def _dp_mesh(n):
    return make_mesh({"dp": n}, devices=jax.devices("cpu")[:n])


def test_route_counter(routes):
    """BERT's attention at T = 256, at the published 512 on one device, and
    at 512 where the trainer's step is traced for dp = 4."""
    _bert(256, traced=True)
    assert routes() == {"plain": 1}
    _bert(512, traced=True)
    assert routes() == {"flash": 1}
    tok = registry.batch_partition.set((_dp_mesh(4), "dp"))
    try:
        _bert(512, batch=4, traced=True)
    finally:
        registry.batch_partition.reset(tok)
    assert routes() == {"flash_partitioned": 1}


T_TRAINED, VOCAB = 128, 64


def _train(mesh, routes, batch=8, steps=3, **trainer_kw):
    """-> (losses, first gradient by leaf as AdamW's m after step 1, routes
    counted while the trainer traced its step) of a one-layer BERT over
    `mesh`, the same weights and batches every call."""
    from mxnet_tpu.models.bert import BertModel
    mx.random.seed(0)
    net = BertModel(vocab_size=VOCAB, num_layers=1, units=UNITS,
                    hidden_size=2 * UNITS, num_heads=HEADS,
                    max_length=T_TRAINED)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, T_TRAINED), dtype="int32"))
    trainer = DataParallelTrainer(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="adamw",
        optimizer_params={"learning_rate": 1e-3}, mesh=mesh, **trainer_kw)
    rs = np.random.RandomState(0)
    losses, first = [], None
    routes()    # the eager first forward above is not the trainer's
    for _ in range(steps):
        x, y = (mx.nd.array(rs.randint(0, VOCAB, (batch, T_TRAINED)),
                            dtype="int32") for _ in range(2))
        losses.append(float(trainer.step(x, y)))
        if first is None and not trainer_kw:
            first = [np.asarray(s[0]) / 0.1 for s in trainer._opt_state if s]
    return losses, first, routes()


def test_trainer_partitions_the_kernels_over_dp(routes, monkeypatch):
    """`_build_step` for dp = 4 against the one-device run: the same three
    losses and the same first gradient, the layer counted as partitioned;
    `_build_step_zero` traces inside its own shard_map and is not wrapped a
    second time."""
    monkeypatch.setenv(NAME, str(T_TRAINED))
    losses1, grads1, took = _train(_dp_mesh(1), routes)
    assert took == {"flash": 1}
    losses4, grads4, took = _train(_dp_mesh(4), routes)
    assert took == {"flash_partitioned": 1}
    np.testing.assert_allclose(losses4, losses1, rtol=2e-5, atol=2e-5)
    assert len(grads1) == len(grads4) > 10
    for a, b in zip(grads4, grads1):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    losses_zero, _, took = _train(_dp_mesh(4), routes, zero_update=True)
    assert took == {"flash": 1}
    np.testing.assert_allclose(losses_zero, losses1, rtol=2e-5, atol=2e-5)

