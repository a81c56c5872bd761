"""Multi-HOST fused trainer: 2 processes x 4 virtual devices = one global
8-device mesh, dp across the process (DCN) axis, tp inside each process
(ICI). This is the scaling shape of a real TPU pod (SURVEY.md §5h): the
SAME DataParallelTrainer one-jit step runs as multi-controller SPMD, each
process feeding only its local batch shard, XLA lowering the gradient
reduction to cross-process collectives — the reference needed its ps-lite
server plus NCCL tree for this split."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as onp
import jax
import jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu import nd, gluon
from mxnet_tpu.parallel import (make_mesh, P, DataParallelTrainer,
                                shard_params_megatron, column_parallel_spec,
                                row_parallel_spec)

rank = jax.process_index()
assert jax.process_count() == 2
assert len(jax.devices()) == 8, jax.devices()

# dp spans the two PROCESSES, tp spans each process's 4 local devices
devs = onp.array(jax.devices()).reshape(2, 4)
import jax.sharding as jsh
mesh = jsh.Mesh(devs, ("dp", "tp"))

mx.random.seed(123)  # identical init on both workers (rank-0-broadcast analog)
net = gluon.nn.HybridSequential()
net.add(gluon.nn.Dense(32), gluon.nn.Activation("relu"), gluon.nn.Dense(4))
net.initialize()
net(nd.zeros((2, 16)))
n = shard_params_megatron(net, axis="tp", rules={{
    r"0\.weight$": column_parallel_spec("tp"),
    r"0\.bias$": P("tp"),
    r"2\.weight$": row_parallel_spec("tp"),
}})
assert n > 0

def loss_fn(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    return jnp.mean(logz - gold)

tr = DataParallelTrainer(net, loss_fn, optimizer="sgd",
                         optimizer_params={{"learning_rate": 0.1}},
                         mesh=mesh, batch_axis_name="dp")

# global batch 16 -> each process feeds ITS half (8 rows)
rs = onp.random.RandomState(7)
gx = rs.uniform(-1, 1, (16, 16)).astype(onp.float32)
gy = rs.randint(0, 4, (16,)).astype(onp.int64)
lx = gx[rank * 8:(rank + 1) * 8]
ly = gy[rank * 8:(rank + 1) * 8]

losses = [float(tr.step(nd.array(lx), nd.array(ly, dtype="int32")))
          for _ in range(6)]
assert all(onp.isfinite(losses)), losses
assert losses[-1] < losses[0], losses
open(os.path.join({tmp!r}, f"loss_{{rank}}"), "w").write(
    " ".join(f"{{l:.6f}}" for l in losses))
print("worker", rank, "losses", losses)
"""


@pytest.mark.slow
def test_two_process_hybrid_mesh_trainer(tmp_path):
    script = tmp_path / "mh_worker.py"
    script.write_text(WORKER.format(repo=REPO, tmp=str(tmp_path)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    l0 = (tmp_path / "loss_0").read_text().split()
    l1 = (tmp_path / "loss_1").read_text().split()
    # multi-controller SPMD: both workers observe the SAME global loss
    assert l0 == l1, (l0, l1)


COMP_WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as onp
import jax
import jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu import nd, gluon
from mxnet_tpu.parallel import make_mesh, P, DataParallelTrainer

rank = jax.process_index()
mesh = make_mesh({{"dp": 8}}, devices=jax.devices())  # dp spans both hosts

mx.random.seed(77)
net = gluon.nn.HybridSequential()
net.add(gluon.nn.Dense(16), gluon.nn.Activation("relu"), gluon.nn.Dense(4))
net.initialize()
net(nd.zeros((2, 8)))

def loss_fn(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    return jnp.mean(logz - gold)

tr = DataParallelTrainer(net, loss_fn, optimizer="sgd",
                         optimizer_params={{"learning_rate": 0.3}}, mesh=mesh,
                         compression={{"type": "2bit", "threshold": 0.01}})

rs = onp.random.RandomState(5)
gx = rs.uniform(-1, 1, (16, 8)).astype(onp.float32)
gy = rs.randint(0, 4, (16,)).astype(onp.int64)
lx, ly = gx[rank * 8:(rank + 1) * 8], gy[rank * 8:(rank + 1) * 8]
losses = [float(tr.step(nd.array(lx), nd.array(ly, dtype="int32")))
          for _ in range(12)]
assert all(onp.isfinite(losses)), losses
assert losses[-1] < losses[0], losses
open(os.path.join({tmp!r}, f"closs_{{rank}}"), "w").write(
    " ".join(f"{{l:.6f}}" for l in losses))
print("compressed worker", rank, "ok")
"""


@pytest.mark.slow
def test_two_process_compressed_trainer(tmp_path):
    """2-bit in-jit gradient compression over a process-spanning dp mesh:
    the quantized tensors ride the cross-host collective, residuals stay
    host-local, and both controllers see the same global loss."""
    script = tmp_path / "mh_comp_worker.py"
    script.write_text(COMP_WORKER.format(repo=REPO, tmp=str(tmp_path)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    l0 = (tmp_path / "closs_0").read_text().split()
    l1 = (tmp_path / "closs_1").read_text().split()
    assert l0 == l1, (l0, l1)
