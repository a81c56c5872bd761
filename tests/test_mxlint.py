"""mxlint unit tests: every rule gets true-positive AND false-positive
fixtures (ISSUE 3 satellite). Fixtures are written under tmp_path with
repo-shaped relative paths (host-sync's hot list keys on
``mxnet_tpu/...`` suffixes), and run through the same ``run_lint`` driver
the CLI uses, so waiver parsing and rule selection are covered too."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.mxlint import (Finding, all_passes, diff_baseline,  # noqa: E402
                          load_baseline, run_lint, write_baseline)


def _lint(tmp_path, relpath, source, rules):
    f = tmp_path / relpath
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(source)
    return run_lint(f, rules=rules, root=tmp_path)


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

HOT_TRAINER = "mxnet_tpu/gluon/trainer.py"


def test_host_sync_flags_coercions_in_hot_function(tmp_path):
    src = '''
class Trainer:
    def step(self, batch_size):
        loss = self._run()
        a = float(loss)          # device scalar -> host
        b = loss.item()
        c = loss.asnumpy()
        import numpy as np
        d = np.asarray(loss)
'''
    out = _lint(tmp_path, HOT_TRAINER, src, ["host-sync"])
    assert len(out) == 4, out
    assert _rules_of(out) == {"host-sync"}
    assert all(f.symbol == "Trainer.step" for f in out)


def test_host_sync_ignores_cold_functions_and_python_scalars(tmp_path):
    src = '''
class Trainer:
    def step(self, batch_size):
        lr = float(self._optimizer.learning_rate)   # python scalar: allowed
        n = int(x.shape[0])                          # static shape: allowed
        k = float(3.5)                               # constant
    def save_states(self, fname):
        blob = w.asnumpy()        # checkpoint path is NOT hot-listed
'''
    assert _lint(tmp_path, HOT_TRAINER, src, ["host-sync"]) == []


def test_host_sync_waiver_comment_suppresses(tmp_path):
    src = '''
class Trainer:
    def step(self, batch_size):
        a = float(loss)  # mxlint: disable=host-sync
        b = float(loss)
'''
    out = _lint(tmp_path, HOT_TRAINER, src, ["host-sync"])
    assert len(out) == 1 and out[0].line == 5


def test_host_sync_covers_nested_defs_of_hot_builders(tmp_path):
    src = '''
class DataParallelTrainer:
    def _build_step(self):
        def step(params, x):
            bad = float(params[0])
            return bad
        return step
'''
    out = _lint(tmp_path, "mxnet_tpu/parallel/data_parallel.py", src,
                ["host-sync"])
    assert len(out) == 1
    assert out[0].symbol.endswith("_build_step.step")


@pytest.mark.parametrize("read_back", [
    "float(finite)",
    # (ISSUE 26) the key's read-back serialised every step and passed for a
    # python scalar: `next_key_raw` is off the allowed list
    "_np.asarray(_rng.next_key_raw())",
])
@pytest.mark.parametrize("method,before", [
    ("step", "self._window.admit(lossv)"),
    ("run_steps", "self._window.admit(losses)"),
])
def test_host_sync_reads_the_real_step_bodies(tmp_path, method, before,
                                              read_back):
    """The lint keys on names: the code between `mx.dp.step`'s entry and its
    return has to live in a function the hot list names. A read-back planted
    in the real file's step body is found, under the method's own name."""
    rel = "mxnet_tpu/parallel/data_parallel.py"
    src = (REPO / rel).read_text()
    assert src.count(before) == 1
    src = src.replace(before, read_back + "; " + before)
    needle = read_back.removeprefix("_np.")
    out = [f for f in _lint(tmp_path, rel, src, ["host-sync"])
           if needle in f.message]
    assert [f.symbol for f in out] == ["DataParallelTrainer." + method]


def test_the_key_read_back_is_waived_in_one_place_only():
    """The three fused trainers take their key from `next_step_key`, whose
    multi-process branch holds the one waived `np.asarray` of it."""
    hits = [(path.relative_to(REPO).as_posix(), lineno, line)
            for path in (REPO / "mxnet_tpu").rglob("*.py")
            for lineno, line in enumerate(path.read_text().splitlines(), 1)
            if "asarray(_rng.next_key_raw" in line]
    ((rel, lineno, line),) = hits
    assert rel == "mxnet_tpu/parallel/data_parallel.py"
    assert "mxlint: disable=host-sync" in line
    from tools.mxlint.core import ModuleInfo
    from tools.mxlint.passes import host_sync
    dp = ModuleInfo(REPO / rel)
    (fn,) = [f for f in dp.functions() if dp.qualname(f) == "next_step_key"]
    assert host_sync._is_hot(dp, fn) and fn.lineno <= lineno <= fn.end_lineno


def test_hot_lists_cover_the_always_on_phase_bookkeeping():
    """Every step and every batch passes through tracing.phased armed or
    not, so all of it is held to the no-sync rule by both lints."""
    from tools.mxlint.core import ModuleInfo
    from tools.mxlint.passes import host_sync, sync_in_loop
    mod = ModuleInfo(REPO / "mxnet_tpu/telemetry/tracing.py")
    names = {mod.qualname(fn): fn for fn in mod.functions()}
    per_step = ["span", "phased", "record_span", "_append",
                "_Span.__enter__", "_Span.__exit__", "_Span._complete",
                "_Phased.__enter__", "_Phased.__exit__", "_Phased.phase",
                "_Phased.split", "_Phase.__enter__", "_Phase.__exit__"]
    assert [n for n in per_step if n not in names] == []
    assert [n for n in per_step
            if not host_sync._is_hot(mod, names[n])] == []
    looped = ["record_span", "_Span._complete", "_Phased.__exit__",
              "_Phased.split", "_Phase.__exit__", "step_records"]
    assert [n for n in looped
            if not sync_in_loop._is_hot(mod, names[n])] == []
    # and the trainer's calls are whole bodies, not wrappers round a helper
    # the lists do not name
    dp = ModuleInfo(REPO / "mxnet_tpu/parallel/data_parallel.py")
    phased = [dp.qualname(fn) for fn in dp.functions()
              if ".phase(" in "\n".join(
                  dp.lines[fn.lineno - 1:fn.end_lineno])]
    per_call = ["DataParallelTrainer.run_steps", "DataParallelTrainer.step"]
    # (`__init__` is set-up's `mx.dp.init` record, ISSUE 36: once a trainer,
    # it places the leaves and may wait for them)
    assert sorted(phased) == ["DataParallelTrainer.__init__"] + per_call
    for fn in dp.functions():
        if dp.qualname(fn) in per_call:
            assert host_sync._is_hot(dp, fn) and sync_in_loop._is_hot(dp, fn)


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

def test_retrace_flags_unsorted_dict_in_cache_key(tmp_path):
    src = '''
def make_cache_key(cfg):
    return tuple(cfg.items())
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["retrace-hazard"])
    assert len(out) == 1 and "sorted" in out[0].message


def test_retrace_accepts_sorted_dict_and_non_key_context(tmp_path):
    src = '''
def make_cache_key(cfg):
    return tuple(sorted(cfg.items()))

def export(cfg):
    return list(cfg.items())    # not a key context
'''
    assert _lint(tmp_path, "mxnet_tpu/x.py", src, ["retrace-hazard"]) == []


def test_retrace_flags_id_in_fingerprint(tmp_path):
    src = '''
def fingerprint(block):
    return ("v1", id(block))

def render(block):
    return f"<obj at {id(block)}>"   # debugging repr: not a key context
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["retrace-hazard"])
    assert len(out) == 1 and out[0].line == 3


def test_retrace_flags_value_dependent_static_args(tmp_path):
    src = '''
import jax

def update(w, g, lr):
    return w - lr * g

fast = jax.jit(update, static_argnums=(2,))        # lr static: retraces
ok = jax.jit(update)                               # traced scalars: fine
named = jax.jit(update, static_argnames=("lr",))   # same by name
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["retrace-hazard"])
    assert len(out) == 2, out
    assert all("'lr'" in f.message for f in out)


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

def test_donation_flags_read_after_donate(tmp_path):
    src = '''
import jax

def train(params, state, g):
    step = jax.jit(_impl, donate_argnums=(0, 1))
    new_p, new_s = step(params, state, g)
    return params   # read after donate!
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["donation-safety"])
    assert len(out) == 1 and "`params`" in out[0].message


def test_donation_accepts_rebind_and_set_data(tmp_path):
    src = '''
import jax

def train(params, state, g):
    step = jax.jit(_impl, donate_argnums=(0, 1))
    params, state = step(params, state, g)   # rebound by the call itself
    return params                             # fresh buffer: fine

def eager(weight, grad):
    w2 = _k_sgd(weight._data, grad._data, 0.1)
    weight._set_data(w2)                      # buffer refreshed
    return weight._data

@_update_kernel(0)
def _k_sgd(w, g, lr):
    return w - lr * g
'''
    assert _lint(tmp_path, "mxnet_tpu/x.py", src, ["donation-safety"]) == []


def test_donation_understands_update_kernel_decorator(tmp_path):
    src = '''
@_update_kernel(0, 2)
def _k_sgd_mom(w, g, m, lr):
    return w - lr * (g + m), m * 0.9

def update(self, weight, grad, state):
    w2, m2 = _k_sgd_mom(weight._data, grad._data, state._data, 0.1)
    stale = state._data + 1   # donated (argnum 2) and read back
    weight._set_data(w2)
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["donation-safety"])
    assert len(out) == 1 and "state._data" in out[0].message


def test_donation_understands_sharded_update_kernel(tmp_path):
    # parallel/zero.py's flat-bucket kernels donate like @_update_kernel;
    # a view sliced out of the donated bucket is a read of the bucket
    src = '''
import jax.numpy as jnp

@_sharded_update_kernel(0)
def _k_bucket_reduce(stacked):
    return jnp.sum(stacked, axis=0)

def reduce_bucket(stacked):
    flat = _k_bucket_reduce(stacked)
    view = stacked[0]     # read-after-donate through a bucket view
    return flat + view
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["donation-safety"])
    assert len(out) == 1 and "`stacked`" in out[0].message


def test_donation_sharded_kernel_rebind_is_clean(tmp_path):
    # the safe carry pattern: the donated bucket is rebound by the call
    src = '''
import jax.numpy as jnp

@_sharded_update_kernel(0)
def _k_bucket_reduce(stacked):
    return jnp.sum(stacked, axis=0)

def reduce_bucket(stacked):
    stacked = _k_bucket_reduce(stacked)
    return stacked * 2
'''
    assert _lint(tmp_path, "mxnet_tpu/x.py", src, ["donation-safety"]) == []


def test_donation_donor_names_are_scoped(tmp_path):
    # a donor binding named `fn` in one function must not poison an
    # unrelated local `fn` elsewhere (the false positive the real
    # data_parallel.py exposed)
    src = '''
import jax

def maker(body):
    fn = jax.jit(body, donate_argnums=(0,))
    return fn

def unrelated(update_fn, g, w):
    fn = update_fn
    w2 = fn(w, g)
    return w + w2      # `fn` here donates nothing
'''
    assert _lint(tmp_path, "mxnet_tpu/x.py", src, ["donation-safety"]) == []


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------

def test_purity_flags_time_random_telemetry_in_traced_fns(tmp_path):
    src = '''
import jax, time, random

@jax.jit
def step(w):
    t0 = time.time()
    noise = random.random()
    _telem.record_step(1)
    print("stepping")
    return w * noise * t0

def lossf(p):
    import numpy as np
    return np.random.rand() * p

grads = jax.grad(lossf)
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["jit-purity"])
    msgs = "\n".join(f.message for f in out)
    assert len(out) == 5, out
    assert "time.time" in msgs and "random" in msgs \
        and "telemetry" in msgs and "print" in msgs


def test_purity_accepts_pure_and_untraced_side_effects(tmp_path):
    src = '''
import jax, time

@jax.jit
def step(w, key):
    return w + jax.random.normal(key, w.shape)

def dispatch(w):
    t0 = time.time()             # host side: fine
    out = step(w, make_key())
    _telem.record_step(1)        # around the jit, not inside
    return out, time.time() - t0
'''
    assert _lint(tmp_path, "mxnet_tpu/x.py", src, ["jit-purity"]) == []


def test_purity_flags_global_mutation_in_traced_fn(tmp_path):
    src = '''
import jax

_counter = 0

def body(x):
    global _counter
    _counter += 1      # fires once, at trace time
    return x * 2

fast = jax.jit(body)
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["jit-purity"])
    assert len(out) == 1 and "_counter" in out[0].message


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

LOCK_SRC = '''
import threading

_LOCK = threading.RLock()
_STATS = {"hits": 0}
_peak = 0.0

def good(n):
    with _LOCK:
        _STATS["hits"] += n

def bad(n):
    _STATS["hits"] += n

def bad_peak(v):
    global _peak
    _peak = max(_peak, v)

def helper_locked(v):
    _STATS["hits"] = v       # *_locked naming convention: trusted
'''


def test_lock_discipline_flags_off_lock_mutation(tmp_path):
    out = _lint(tmp_path, "mxnet_tpu/x.py", LOCK_SRC, ["lock-discipline"])
    assert len(out) == 2, out
    assert {f.symbol for f in out} == {"bad", "bad_peak"}


def test_lock_discipline_silent_without_declared_lock(tmp_path):
    src = '''
_CACHE = {}

def put(k, v):
    _CACHE[k] = v      # module declares no lock: presumed single-threaded
'''
    assert _lint(tmp_path, "mxnet_tpu/x.py", src, ["lock-discipline"]) == []


# ---------------------------------------------------------------------------
# mutable-default
# ---------------------------------------------------------------------------

def test_mutable_default_positive_and_negative(tmp_path):
    src = '''
def bad(x, cache={}, items=[]):
    return cache, items

def good(x, cache=None, items=(), n=3):
    return cache or {}, items
'''
    out = _lint(tmp_path, "mxnet_tpu/x.py", src, ["mutable-default"])
    assert len(out) == 2 and _rules_of(out) == {"mutable-default"}


# ---------------------------------------------------------------------------
# sync-in-loop
# ---------------------------------------------------------------------------

LOOP_FILE = "mxnet_tpu/module/base_module.py"


def test_sync_in_loop_flags_sync_on_step_outputs(tmp_path):
    src = '''
import numpy as np

class BaseModule:
    def fit(self, train_data, trainer):
        losses = []
        for batch in train_data:
            loss = trainer.step(batch.data, batch.label)
            losses.append(float(loss))          # sync on the CURRENT step
            a = loss.item()
            b = loss.asnumpy()
            loss.block_until_ready()
            c = np.asarray(loss)
            d = float(trainer.step(batch.data, batch.label))  # direct wrap
'''
    out = _lint(tmp_path, LOOP_FILE, src, ["sync-in-loop"])
    assert len(out) == 6, out
    assert _rules_of(out) == {"sync-in-loop"}
    assert all(f.symbol == "BaseModule.fit" for f in out)


def test_sync_in_loop_allows_pending_and_boundary_drain(tmp_path):
    src = '''
class BaseModule:
    def fit(self, train_data, trainer):
        pending = []
        for batch in train_data:
            loss = trainer.step(batch.data, batch.label)   # stays pending
            pending.append(loss)
            lr = float(trainer.learning_rate)   # python scalar, not a step output
        trainer.drain()                          # boundary: outside the loop
        return [float(p) for p in pending]       # drained after the loop
'''
    assert _lint(tmp_path, LOOP_FILE, src, ["sync-in-loop"]) == []


def test_sync_in_loop_waivable_at_drain_points(tmp_path):
    src = '''
class BaseModule:
    def fit(self, train_data, trainer):
        for epoch in range(2):
            for batch in train_data:
                loss = trainer.step(batch.data, batch.label)
            last = float(loss)  # designed per-epoch drain  # mxlint: disable=sync-in-loop
'''
    assert _lint(tmp_path, LOOP_FILE, src, ["sync-in-loop"]) == []


def test_sync_in_loop_ignores_cold_functions(tmp_path):
    src = '''
class Helper:
    def run(self, train_data, trainer):
        for batch in train_data:
            loss = trainer.step(batch.data, batch.label)
            print(float(loss))   # not a hot-listed loop driver
'''
    assert _lint(tmp_path, LOOP_FILE, src, ["sync-in-loop"]) == []


# ---------------------------------------------------------------------------
# baseline + driver mechanics
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# broad-except
# ---------------------------------------------------------------------------

def test_broad_except_flags_bare_and_base_exception(tmp_path):
    src = '''
def worker(q):
    try:
        q.get()
    except BaseException as e:      # swallows KeyboardInterrupt
        log(e)
    try:
        q.get()
    except (ValueError, BaseException):
        pass

try:
    boot()
except:                             # bare, at module scope
    pass
'''
    out = _lint(tmp_path, "mxnet_tpu/serving/batcher.py", src,
                ["broad-except"])
    assert len(out) == 3, out
    assert _rules_of(out) == {"broad-except"}
    assert {f.symbol for f in out} == {"worker", "<module>"}


def test_broad_except_allows_shutdown_waivers_and_exception(tmp_path):
    src = '''
class Feed:
    def close(self):
        try:
            self._join()
        except Exception:            # narrow containment: fine
            pass
    def __del__(self):
        try:
            self.close()
        except BaseException:        # interpreter teardown: exempt
            pass
    def __exit__(self, *exc):
        try:
            self.close()
        except:                      # teardown scope: exempt
            pass
    def _write(self):
        try:
            self._flush()
        except BaseException as e:  # mxlint: disable=broad-except
            self._error = e
'''
    assert _lint(tmp_path, "mxnet_tpu/engine/async_feed.py", src,
                 ["broad-except"]) == []


def test_baseline_roundtrip_and_diff(tmp_path):
    f1 = Finding("host-sync", "mxnet_tpu/a.py", 10, "A.step", "float() bad")
    f2 = Finding("jit-purity", "mxnet_tpu/b.py", 20, "body", "time.time()")
    bl = tmp_path / "baseline.json"
    write_baseline(bl, [f1])
    new, waived, stale = diff_baseline([f1, f2], load_baseline(bl))
    assert new == [f2] and waived == [f1] and stale == []
    # line drift must not invalidate the baseline entry
    f1_moved = Finding("host-sync", "mxnet_tpu/a.py", 99, "A.step",
                       "float() bad")
    new, waived, stale = diff_baseline([f1_moved], load_baseline(bl))
    assert new == [] and len(waived) == 1
    # fixed finding surfaces as stale
    new, waived, stale = diff_baseline([], load_baseline(bl))
    assert stale and stale[0]["path"] == "mxnet_tpu/a.py"


def test_unknown_rule_raises(tmp_path):
    with pytest.raises(ValueError, match="unknown rule"):
        _lint(tmp_path, "mxnet_tpu/x.py", "x = 1\n", ["no-such-rule"])


def test_all_passes_registered():
    names = set(all_passes())
    assert {"host-sync", "retrace-hazard", "donation-safety", "jit-purity",
            "lock-discipline", "mutable-default", "sync-in-loop",
            "instrumentation", "broad-except",
            "collective-order", "partition-spec"} <= names


def test_cli_json_format_and_exit_codes(tmp_path):
    bad = tmp_path / "mxnet_tpu" / "gluon" / "trainer.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("class Trainer:\n"
                   "    def step(self, n):\n"
                   "        return float(loss)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", str(bad), "--format=json",
         "--baseline=", "--rules=host-sync"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 1, proc.stderr
    data = json.loads(proc.stdout)
    assert len(data["new"]) == 1
    assert data["new"][0]["rule"] == "host-sync"

    proc = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", str(bad), "--format=json",
         "--baseline=", "--rules=mutable-default"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["new"] == []
