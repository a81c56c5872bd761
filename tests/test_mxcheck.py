"""mxcheck: SPMD collective-consistency passes + compiled-HLO hazard audit
(ISSUE 18).

Three layers under test, mirroring the analysis stack:

  1. AST rule fixtures — hand-built divergent/consistent step bodies, one
     positive AND one negative per rule (collective-rank-conditional,
     collective-branch-mismatch, collective-unknown-axis,
     collective-data-loop; pspec-unknown-axis, pspec-duplicate-axis,
     pspec-rank-mismatch), written repo-shaped under tmp_path so path
     seeding behaves exactly as in the live tree.
  2. The LIVE tree — the kvstore `_bigarray_bound` divergence this PR
     fixed stays fixed (pass-level + behavioral regression), and the
     elastic coordinator/snapshot leader paths keep their audited verdict:
     leader-gated branches are pure host IO, NO collective reachable (the
     fixture pair shows what would fire if that regressed).
  3. The compiled-HLO audit — hazard vocabulary on synthetic HLO text, a
     planted host transfer in a real jitted fn caught through the
     estimate_cost funnel, fingerprints for the fused DP step / 1F1B
     partitioned-TP step / a serving artifact, and the
     tools/hlo_audit_gate.py CI gate failing on a planted regression.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu.engine import hlo_audit
from mxnet_tpu import engine as _engine

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.mxlint.core import run_lint  # noqa: E402
from tools.hlo_audit_gate import diff as gate_diff  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    yield
    telemetry.disable()
    telemetry.reset()
    hlo_audit.reset()


# ---------------------------------------------------------------------------
# fixture plumbing (same idiom as tests/test_mxlint.py)
# ---------------------------------------------------------------------------

def _lint(tmp_path, relpath, source, rules=("collective-order",)):
    f = tmp_path / relpath
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return run_lint(f, rules=list(rules), root=tmp_path)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# rule 1: collective-rank-conditional
# ---------------------------------------------------------------------------

def test_rank_conditional_positive(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        import jax
        from jax import lax

        def step_body(x):
            if jax.process_index() == 0:
                x = lax.psum(x, "dp")
            return x
    """)
    assert _rules_of(fs) == ["collective-rank-conditional"], fs
    assert "process_index" in fs[0].message


def test_rank_conditional_negative_uniform_guard(tmp_path):
    # a config flag is not rank identity: no finding
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax import lax

        def step_body(x, use_dp=True):
            if use_dp:
                x = lax.psum(x, "dp")
            return x
    """)
    assert fs == []


def test_rank_conditional_negative_symmetric_sequences(tmp_path):
    # both branches trace the SAME collective sequence — cannot diverge
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        import jax
        from jax import lax

        def step_body(x):
            if jax.process_index() == 0:
                return lax.psum(x, "dp")
            return lax.psum(-x, "dp")
    """)
    assert fs == []


def test_rank_conditional_early_return_fallthrough(tmp_path):
    # the kvstore `_cross` shape: `if <tainted>: return A` guards the
    # collectives in the REMAINDER of the block
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        import os
        from jax import lax
        from jax.experimental import multihost_utils

        class KV:
            def __init__(self):
                self._bound = int(os.environ.get("B", "1"))

            def _build_step(self, x):
                if x.size >= self._bound:
                    return x * 2
                return multihost_utils.process_allgather(x)
    """)
    assert _rules_of(fs) == ["collective-rank-conditional"], fs
    assert "process_allgather" in fs[0].message


def test_rank_conditional_negative_agreed_bound(tmp_path):
    # the fix pattern: the env value is routed through an agreement
    # sanitizer (rank-0 broadcast), so the guard is uniform by construction
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        import os
        from jax import lax
        from jax.experimental import multihost_utils

        class KV:
            def __init__(self):
                self._bound = self._agree_bound(
                    int(os.environ.get("B", "1")))

            def _agree_bound(self, b):
                return int(multihost_utils.broadcast_one_to_all(b))

            def _build_step(self, x):
                if x.size >= self._bound:
                    return x * 2
                return multihost_utils.process_allgather(x)
    """)
    assert fs == []


def test_rank_conditional_transitive_callee(tmp_path):
    # the guarded call has no lexical collective — it TRACES one
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        import jax
        from jax import lax

        def _merge(x):
            return lax.pmean(x, "dp")

        def step_body(x):
            if jax.process_index() == 0:
                x = _merge(x)
            return x
    """)
    assert _rules_of(fs) == ["collective-rank-conditional"], fs
    assert "_merge" in fs[0].message and "pmean" in fs[0].message


# ---------------------------------------------------------------------------
# rule 2: collective-branch-mismatch (lax.cond / lax.switch)
# ---------------------------------------------------------------------------

def test_cond_branch_mismatch_positive(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax import lax

        def step_body(p, x):
            return lax.cond(p,
                            lambda v: lax.psum(v, "tp"),
                            lambda v: v * 2,
                            x)
    """)
    assert _rules_of(fs) == ["collective-branch-mismatch"], fs


def test_cond_branch_axis_symmetric_negative(tmp_path):
    # both branches psum over the SAME axis: consistent schedule, clean
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax import lax

        def step_body(p, x):
            return lax.cond(p,
                            lambda v: lax.psum(v, "tp"),
                            lambda v: lax.psum(-v, "tp"),
                            x)
    """)
    assert fs == []


def test_cond_branch_axis_mismatch_positive(tmp_path):
    # same op, DIFFERENT axis — still a divergent schedule
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax import lax

        def step_body(p, x):
            return lax.cond(p,
                            lambda v: lax.psum(v, "tp"),
                            lambda v: lax.psum(v, "dp"),
                            x)
    """)
    assert _rules_of(fs) == ["collective-branch-mismatch"], fs


def test_switch_branch_mismatch_named_functions(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax import lax

        def _a(v):
            return lax.psum(v, "tp")

        def _b(v):
            return v

        def step_body(i, x):
            return lax.switch(i, [_a, _b], x)
    """)
    assert _rules_of(fs) == ["collective-branch-mismatch"], fs


# ---------------------------------------------------------------------------
# rule 3: collective-unknown-axis
# ---------------------------------------------------------------------------

def test_unknown_axis_positive(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax import lax

        def step_body(x):
            return lax.psum(x, "model")
    """)
    assert _rules_of(fs) == ["collective-unknown-axis"], fs
    assert "'model'" in fs[0].message


def test_unknown_axis_negative_declared(tmp_path):
    # canonical axes + a module-declared Mesh axis are both fine
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax import lax
        from jax.sharding import Mesh

        MESH = Mesh(None, ("rows", "cols"))

        def step_body(x):
            x = lax.psum(x, "tp")
            return lax.pmean(x, "rows")
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# rule 4: collective-data-loop
# ---------------------------------------------------------------------------

def test_data_loop_positive(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        import jax
        from jax import lax

        def step_body(x):
            n = jax.process_index() + 1
            for _ in range(n):
                x = lax.psum(x, "dp")
            return x
    """)
    assert _rules_of(fs) == ["collective-data-loop"], fs


def test_data_loop_negative_static_trip_count(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax import lax

        def step_body(x, num_microbatch=4):
            for _ in range(num_microbatch):
                x = lax.psum(x, "dp")
            return x
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# partition-spec rules
# ---------------------------------------------------------------------------

def test_pspec_unknown_axis_positive_and_negative(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax.sharding import PartitionSpec as P

        GOOD = P("dp", None)
        BAD = P("modle", None)
    """, rules=("partition-spec",))
    assert _rules_of(fs) == ["pspec-unknown-axis"], fs
    assert len(fs) == 1 and "'modle'" in fs[0].message


def test_pspec_duplicate_axis(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from jax.sharding import PartitionSpec as P

        OK = P("dp", "tp")
        DUP = P("dp", "dp")
    """, rules=("partition-spec",))
    assert _rules_of(fs) == ["pspec-duplicate-axis"], fs


def test_pspec_rank_mismatch(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        def _place(mesh):
            ok = jax.device_put(jnp.zeros((4, 2)),
                                NamedSharding(mesh, P("dp")))
            bad = jax.device_put(jnp.zeros((4,)),
                                 NamedSharding(mesh, P("dp", None)))
            return ok, bad
    """, rules=("partition-spec",))
    assert _rules_of(fs) == ["pspec-rank-mismatch"], fs
    assert len(fs) == 1


def test_shard_rules_role_table(tmp_path):
    fs = _lint(tmp_path, "mxnet_tpu/parallel/x.py", """
        from mxnet_tpu.parallel import shard_rules

        OK = shard_rules({"heads": "tp", "seq": None})
        TYPO = shard_rules({"head": "tp"})
        BAD_AXIS = shard_rules({"mlp": "modle"})
    """, rules=("partition-spec",))
    assert _rules_of(fs) == ["pspec-unknown-axis"], fs
    msgs = " | ".join(f.message for f in fs)
    assert "'head'" in msgs and "'modle'" in msgs
    assert len(fs) == 2


# ---------------------------------------------------------------------------
# live tree: the audited verdicts hold
# ---------------------------------------------------------------------------

def test_live_parallel_and_elastic_are_clean():
    """The whole live tree is clean under both new passes — including the
    kvstore fix landing in this PR and the audited elastic leader paths."""
    for rel in ("mxnet_tpu/kvstore/kvstore.py",
                "mxnet_tpu/elastic/coordinator.py",
                "mxnet_tpu/elastic/snapshot.py",
                "mxnet_tpu/parallel/megatron.py",
                "mxnet_tpu/parallel/pipeline.py",
                "mxnet_tpu/parallel/moe.py"):
        fs = run_lint(REPO / rel,
                      rules=["collective-order", "partition-spec"],
                      root=REPO)
        assert fs == [], f"{rel}: {[f.text() for f in fs]}"


def test_leader_gated_host_io_verdict(tmp_path):
    """elastic/coordinator.py + snapshot.py audit verdict, as a fixture
    pair: leader-gated branches doing pure host IO (manifest prune, KV
    writes) are NEGATIVE — no collective is reachable under the rank
    guard. The positive control shows exactly what would fire if a
    collective ever crept into such a branch."""
    negative = """
        import jax

        def _prune(d):
            return d

        def step_body(coord, d):
            if coord.rank == coord.view().leader_rank:
                _prune(d)
            return d
    """
    assert _lint(tmp_path, "mxnet_tpu/elastic/x.py", negative) == []

    positive = """
        import jax
        from jax.experimental import multihost_utils

        def step_body(coord, d):
            if jax.process_index() == 0:
                multihost_utils.sync_global_devices("commit")
            return d
    """
    fs = _lint(tmp_path, "mxnet_tpu/elastic/x.py", positive)
    assert _rules_of(fs) == ["collective-rank-conditional"], fs


def test_kvstore_agreed_bound_behavior(monkeypatch):
    """The real finding's fix: every process adopts rank 0's
    MXNET_KVSTORE_BIGARRAY_BOUND instead of trusting its own env — the
    bound selects WHICH collective `_cross` runs, so divergence is a hang.
    """
    from mxnet_tpu.kvstore.kvstore import KVStoreDist

    # single-process: identity
    assert KVStoreDist._agree_bigarray_bound(123) == 123

    # multi-process: rank 0's value wins via broadcast_one_to_all
    calls = {}

    def fake_broadcast(x):
        calls["arg"] = int(x)
        return onp.asarray(999)  # what rank 0 announced

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    from jax.experimental import multihost_utils
    monkeypatch.setattr(multihost_utils, "broadcast_one_to_all",
                        fake_broadcast)
    assert KVStoreDist._agree_bigarray_bound(123) == 999
    assert calls["arg"] == 123


# ---------------------------------------------------------------------------
# compiled-HLO hazard audit: vocabulary on synthetic HLO
# ---------------------------------------------------------------------------

_HLO_CLEAN = """
HloModule jit_step, input_output_alias={ {0}: (0, {}, may-alias) }
  %p = f32[8,8] parameter(0)
  %ar = f32[8,8] all-reduce-start(%p), replica_groups={}
  %d = f32[8,8] all-reduce-done(%ar)
ROOT %r = f32[8,8] add(%d, %d)
"""

_HLO_HAZARDS = """
HloModule jit_step
  %p = f32[8,8] parameter(0)
  %cb = f32[8,8] custom-call(%p), custom_call_target="xla_ffi_python_cpu_callback"
  %w = f64[8,8] convert(%p)
  %ar = f32[8,8] all-reduce(%p), replica_groups={}
  %out = (f32[8,8], token[]) outfeed(%ar)
"""


def test_audit_text_clean():
    fp = hlo_audit.audit_text(_HLO_CLEAN, kind="dp_step",
                              region="r#1", donation_expected=True)
    assert fp["hazards"] == []
    c = fp["counts"]
    assert c["host_transfers"] == 0 and c["f64_ops"] == 0
    assert c["collectives_async"] == 1 and c["collectives_sync"] == 0
    assert c["alias_pairs"] == 1
    assert fp["collectives"] == {"all-reduce-start": 1}


def test_audit_text_hazards():
    fp = hlo_audit.audit_text(_HLO_HAZARDS, kind="dp_step", region="r#2")
    kinds = {h["kind"]: h["count"] for h in fp["hazards"]}
    assert kinds == {"host_transfer": 2,  # callback + outfeed
                     "f64": 1}
    # a plain all-reduce is a count the gate diffs, not a hazard
    c = fp["counts"]
    assert c["collectives_sync"] == 1 and c["collectives_async"] == 0


# ---------------------------------------------------------------------------
# the estimate_cost funnel: planted host transfer in a real jitted fn
# ---------------------------------------------------------------------------

def test_planted_host_transfer_fires_through_funnel(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HLO_AUDIT_DIR", str(tmp_path / "audit"))
    telemetry.enable()

    def leaky(x):
        jax.debug.callback(lambda v: None, x)  # lowers to a cpu callback
        return x * 2

    cost = _engine.estimate_cost(jax.jit(leaky), jnp.ones((4,)),
                                 kind="dp_step", region="leaky.step#abc")
    assert cost  # the cost capture itself still works
    fp = hlo_audit.fingerprints()["leaky.step#abc"]
    kinds = {h["kind"] for h in fp["hazards"]}
    assert "host_transfer" in kinds
    # persisted next to the compilation cache for the CI gate
    files = list((tmp_path / "audit").glob("*.json"))
    assert len(files) == 1
    on_disk = json.loads(files[0].read_text())
    assert on_disk["label"] == "leaky.step"
    # exported on the Prometheus surface and /statusz
    snap = telemetry.statusz()["hlo_audit"]
    assert any("host_transfer" in k and v >= 1 for k, v in snap.items()), \
        snap


def test_clean_jit_has_no_hazards(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HLO_AUDIT_DIR", str(tmp_path / "audit"))
    telemetry.enable()
    _engine.estimate_cost(jax.jit(lambda x: jnp.sin(x) * 2),
                          jnp.ones((4, 4)), kind="dp_step",
                          region="clean.step#abc")
    assert hlo_audit.fingerprints()["clean.step#abc"]["hazards"] == []


# ---------------------------------------------------------------------------
# artifact fingerprints: fused DP step, 1F1B partitioned-TP step, serving
# ---------------------------------------------------------------------------

def _mse_loss(out, label):
    return ((out - label) ** 2).mean()


def test_dp_step_fingerprint(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HLO_AUDIT_DIR", str(tmp_path / "audit"))
    telemetry.enable()
    from mxnet_tpu.parallel import make_mesh, DataParallelTrainer
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 8)))
    mesh = make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])
    tr = DataParallelTrainer(net, _mse_loss, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.05},
                             mesh=mesh)
    rs = onp.random.RandomState(0)
    x = nd.array(rs.uniform(-1, 1, (2, 8)).astype("float32"))
    y = nd.array(rs.uniform(-1, 1, (2, 4)).astype("float32"))
    tr.step(x, y)
    fps = hlo_audit.fingerprints()
    dp = [fp for fp in fps.values() if fp["kind"] == "dp_step"]
    assert dp, f"no dp_step fingerprint: {sorted(fps)}"
    assert dp[0]["label"].startswith("dp.step"), dp[0]["label"]
    assert dp[0]["hazards"] == [], dp[0]
    assert (tmp_path / "audit").is_dir()


def test_1f1b_partitioned_tp_fingerprint(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HLO_AUDIT_DIR", str(tmp_path / "audit"))
    telemetry.enable()
    from mxnet_tpu.models.bert import BertModel
    from mxnet_tpu.parallel import make_mesh, PipelineTrainer
    from mxnet_tpu.recipes.moe import token_cross_entropy
    V, B, T = 64, 8, 8
    rs = onp.random.RandomState(0)
    x = nd.array(rs.randint(0, V, (B, T)), dtype="int32")
    y = nd.array(rs.randint(0, V, (B, T)), dtype="int32")
    mx.random.seed(3)
    net = BertModel(vocab_size=V, num_layers=4, units=32, hidden_size=64,
                    num_heads=2, max_length=T, dropout=0.0)
    net.initialize()
    net(x)
    tr = PipelineTrainer(net, token_cross_entropy, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.5, "wd": 0.0},
                         schedule="1f1b",
                         mesh=make_mesh({"pp": 2, "tp": 1},
                                        devices=jax.devices("cpu")[:2]),
                         tp_axis="tp", tp_mode="partitioned",
                         num_microbatch=2)
    tr.step(x, y)
    fps = hlo_audit.fingerprints()
    pp = [fp for fp in fps.values() if fp["kind"] == "pp_step"]
    assert pp, f"no pp_step fingerprint: {sorted(fps)}"
    assert pp[0]["hazards"] == [], pp[0]
    # the 1F1B tick body really does run collectives worth auditing
    assert sum(pp[0]["collectives"].values()) > 0, pp[0]


def test_serving_artifact_fingerprint(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HLO_AUDIT_DIR", str(tmp_path / "audit"))
    telemetry.enable()

    class _Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.body = gluon.nn.HybridSequential()
            self.body.add(gluon.nn.Dense(12, activation="relu"),
                          gluon.nn.Dense(3))

        def hybrid_forward(self, F, x):
            return self.body(x).softmax()

    mx.random.seed(11)
    net = _Net()
    net.initialize()
    net.hybridize()
    net(nd.zeros((1, 5)))
    prefix = str(tmp_path / "mlp")
    net.export(prefix)

    from mxnet_tpu.predict import Predictor
    Predictor(prefix + "-symbol.json", prefix + "-0000.params",
              input_shapes={"data": (2, 5)})
    fps = hlo_audit.fingerprints()
    srv = [fp for fp in fps.values() if fp["kind"] == "predict"]
    assert srv, f"no predict fingerprint: {sorted(fps)}"
    assert srv[0]["label"] == "predict"
    assert srv[0]["hazards"] == [], srv[0]


# ---------------------------------------------------------------------------
# the CI gate
# ---------------------------------------------------------------------------

def _run_gate(audit_dir, baseline):
    return subprocess.run(
        [sys.executable, "-m", "tools.hlo_audit_gate",
         "--audit-dir", str(audit_dir), "--baseline", str(baseline),
         "--format=json"],
        capture_output=True, text=True, cwd=REPO, timeout=120)


def test_gate_exits_nonzero_on_planted_regression(tmp_path, monkeypatch):
    """tier-1 exercise of tools/hlo_audit_gate.py: build a clean artifact,
    baseline it, plant a host transfer in the same artifact family,
    rebuild — the gate must fail."""
    audit = tmp_path / "audit"
    baseline = tmp_path / "baseline.json"
    monkeypatch.setenv("MXNET_TPU_HLO_AUDIT_DIR", str(audit))
    telemetry.enable()

    _engine.estimate_cost(jax.jit(lambda x: x * 2), jnp.ones((4,)),
                          kind="dp_step", region="gate.step#v1")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hlo_audit_gate",
         "--audit-dir", str(audit), "--baseline", str(baseline),
         "--write-baseline"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr

    # clean rebuild passes
    proc = _run_gate(audit, baseline)
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # regress the SAME label: a host callback sneaks into the step
    def leaky(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2

    _engine.estimate_cost(jax.jit(leaky), jnp.ones((4,)),
                          kind="dp_step", region="gate.step#v2")
    proc = _run_gate(audit, baseline)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert any("host transfers" in r for r in out["regressions"]), out


def test_gate_fails_new_hazardous_artifact_against_default_baseline():
    """The shipped default baseline (tools/hlo_audit_baseline.json) is
    empty = 'no artifact ships with hazards': a hazard-bearing NEW label
    is a regression, a hazard-free one is a note."""
    fps = {
        "bad.step": hlo_audit.audit_text(_HLO_HAZARDS, kind="dp_step",
                                         region="bad.step#1"),
        "good.step": hlo_audit.audit_text(_HLO_CLEAN, kind="dp_step",
                                          region="good.step#1"),
    }
    regressions, notes = gate_diff(fps, {})
    assert len(regressions) == 1 and "bad.step" in regressions[0]
    assert any("good.step" in n for n in notes)


def test_gate_detects_lost_overlap_and_alias():
    base = {"s.step": {"counts": {"host_transfers": 0, "f64_ops": 0,
                                  "collectives_sync": 0,
                                  "collectives_async": 2,
                                  "alias_pairs": 3, "donated_params": 3}}}
    cur = hlo_audit.audit_text(
        "%ar = f32[4] all-reduce(%p)\n%a2 = f32[4] all-reduce(%ar)\n",
        kind="dp_step", region="s.step#2")
    regressions, _ = gate_diff({"s.step": cur}, base)
    joined = " | ".join(regressions)
    assert "overlap regressed" in joined
    assert "donation stopped aliasing" in joined
