"""Retrace-storm guards (VERDICT r2 weak item 6: the reference's CachedOp
motivation — SURVEY.md §3.1 — is that eager dispatch must not recompile
per call). Hooks the XLA compile chokepoint and asserts the jit caches
key correctly: same signature never retraces; new signatures retrace
once each."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon


class _CompileCounter:
    def __init__(self):
        self.count = 0

    def __enter__(self):
        from jax._src import compiler
        self._real = compiler.compile_or_get_cached

        def spy(*a, **k):
            self.count += 1
            return self._real(*a, **k)

        compiler.compile_or_get_cached = spy
        return self

    def __exit__(self, *a):
        from jax._src import compiler
        compiler.compile_or_get_cached = self._real
        return False


def test_eager_op_same_signature_never_retraces():
    x = nd.array(np.ones((4, 5), np.float32))
    nd.exp(x)  # warm the per-op jit cache for this signature
    with _CompileCounter() as c:
        for _ in range(10):
            nd.exp(x)
    assert c.count == 0, f"eager exp retraced {c.count} times"


def test_eager_op_new_shapes_compile_once_each():
    with _CompileCounter() as c:
        for n in (31, 32, 33):
            x = nd.array(np.ones((n,), np.float32))
            nd.tanh(x)
            nd.tanh(x)  # repeat: must hit the cache
    # the lower bound is the POSITIVE CONTROL on the hook itself: fresh
    # shapes are guaranteed to compile, so a silently-dead monkeypatch
    # (jax moving to a direct import) fails here instead of making every
    # upper-bound assertion in this file pass vacuously
    assert 1 <= c.count <= 3, f"tanh compiled {c.count} times for 3 shapes"


def test_scalar_hyperparam_change_does_not_retrace_optimizer():
    """lr changes every step in real training — the update kernels take
    hyperparams as traced scalars precisely so this never retraces."""
    from mxnet_tpu import optimizer as opt_mod
    w = nd.array(np.ones((8,), np.float32))
    g = nd.array(np.ones((8,), np.float32))
    opt = opt_mod.create("sgd", learning_rate=0.1)
    state = opt.create_state(0, w)
    opt.update(0, w, g, state)  # warm
    with _CompileCounter() as c:
        for lr in (0.01, 0.02, 0.03, 0.04):
            opt.lr = lr
            opt.update(0, w, g, state)
    assert c.count == 0, f"optimizer retraced on lr change ({c.count})"


def test_hybridized_block_retraces_only_per_signature():
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net.hybridize()
    net(nd.ones((2, 6)))  # first trace+compile
    x7 = nd.ones((7, 6))  # built OUTSIDE the counter: the ones-fill
    #                       kernel must not inflate the budget
    with _CompileCounter() as c:
        for _ in range(5):
            net(nd.ones((2, 6)))
        same_sig = c.count
        net(x7)
        net(x7)
        new_sig = c.count - same_sig
    assert same_sig == 0, f"hybrid block retraced same signature {same_sig}x"
    assert new_sig == 1, \
        f"new signature compiled {new_sig}x (want exactly one forward)"


def test_fused_trainer_step_never_retraces():
    import jax
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh

    mx.random.seed(1)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 3)))

    def loss(p, y):
        import jax.numpy as jnp
        return jnp.mean((p - y) ** 2)

    mesh = make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])
    tr = DataParallelTrainer(net, loss, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1},
                             mesh=mesh)
    x = nd.ones((4, 3))
    y = nd.ones((4, 4))
    tr.step(x, y)  # compile once
    with _CompileCounter() as c:
        for _ in range(5):
            tr.step(x, y)
    # per-step host scalars (lr, t, key) must be jit arguments, not
    # trace constants — any count here is a silent perf catastrophe
    assert c.count == 0, f"fused step retraced {c.count} times"
    # run_steps feeds its second call the key/step counter its first call
    # returned: they must come back with the type they went in with (found
    # by chip_smoke.py: a plain device_put carried no mesh, the outputs
    # did, and the whole n-step scan compiled twice)
    tr.run_steps(x, y, 3)
    with _CompileCounter() as c:
        tr.run_steps(x, y, 3)
        tr.run_steps(x, y, 3)
    assert c.count == 0, f"run_steps recompiled {c.count} times"


def test_predictor_first_predict_does_not_compile(tmp_path):
    """The bind-time warmup must build the executable predict() uses: the
    engine counters cannot see a jit-level recompile, the XLA chokepoint
    can (found by chip_smoke.py's serving phase)."""
    from mxnet_tpu.predict import Predictor

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    net(nd.zeros((1, 5)))
    sym_file, param_file = net.export(str(tmp_path / "m"))
    p = Predictor(sym_file, param_file, input_shapes={"data": (2, 5)})
    with _CompileCounter() as c:
        p.predict(np.ones((2, 5), np.float32))
    p.close()
    assert c.count == 0, f"first predict compiled {c.count} time(s)"
