"""sync-in-loop: host sync on the current step's outputs inside a fit loop.

The async-dispatch contract (engine/async_feed, docs/input_pipeline.md) is
that a training loop dispatches step i+1 while step i still runs; a
``float()`` / ``.item()`` / ``.asnumpy()`` / ``block_until_ready()`` on the
CURRENT step's outputs inside the loop body re-serializes the pipeline —
every iteration then waits for its own step, and the bounded in-flight
window never fills. Per-step losses belong in ``PendingScalar`` handles
drained at epoch/eval boundaries; designed drain points (``drain()``,
``window.drain()``, metric ``get()`` after the loop) are either outside the
loop body or carry an explicit ``# mxlint: disable=sync-in-loop`` waiver
with rationale.
"""
from __future__ import annotations

import ast
import re

from ..core import Finding, ModuleInfo, call_name, register_pass, unparse

# (path suffix, qualname regex) — training-loop drivers whose loop bodies
# must not sync on their own step's outputs. Nested defs inherit the outer
# qualname, same convention as the host-sync hot list.
LOOP_FUNCTIONS = [
    ("mxnet_tpu/module/base_module.py", r"BaseModule\.(fit|score)\b"),
    ("mxnet_tpu/model.py", r"FeedForward\.(fit|predict)\b"),
    ("mxnet_tpu/gluon/contrib/estimator/estimator.py",
     r"Estimator\.(fit|fit_epoch|_train_loop)\b"),
    ("mxnet_tpu/parallel/data_parallel.py",
     r"DataParallelTrainer\.(run_steps|step)\b"),
    ("mxnet_tpu/parallel/pipeline.py",
     r"PipelineTrainer\.(step|_record_telemetry)\b|\bschedule_1f1b\b"),
    ("mxnet_tpu/parallel/step_program.py",
     r"StepProgram\.(get|region|capture_cost)\b"),
    ("mxnet_tpu/gluon/trainer.py", r"Trainer\.step\b"),
    # serving dispatch loop (ISSUE 6): forming/dispatching batch i+1 must
    # never sync on batch i's outputs — the completion thread owns the one
    # designed host sync (`ContinuousBatcher._complete`)
    ("mxnet_tpu/serving/batcher.py", r"ContinuousBatcher\._dispatch_loop\b"),
    # roofline ledger recording paths (ISSUE 7): timing capture must stay
    # interval-paced — syncing on a step output inside these would turn
    # the observer into a serializer
    ("mxnet_tpu/telemetry/roofline.py", r"\b(record|wrap)\b"),
    ("mxnet_tpu/parallel/data_parallel.py",
     r"DataParallelTrainer\.(_record_telemetry|_region_name)\b"),
    # elastic supervised loop (ISSUE 11): run() interleaves step dispatch
    # with async snapshot saves — syncing on the running step's loss would
    # stall both; losses stay PendingScalar until the caller drains them
    ("mxnet_tpu/elastic/run.py", r"\brun\b"),
    # recipe trainers (ISSUE 12): the traced bodies built by the zero-step
    # builders loop over params/buckets while losses and dropped counts
    # stay device values; `drain()` is the designed drain point and is not
    # listed. LongContextTrainer.step comes from DataParallelTrainer.
    ("mxnet_tpu/recipes/moe.py",
     r"MoETrainer\.(step|_build_step_zero)\b"),
    ("mxnet_tpu/recipes/long_context.py",
     r"LongContextTrainer\._build_step_zero\b"),
    # span tracing (ISSUE 14): the tracer's record/export paths iterate the
    # ring inside loops — syncing on a step output in here would serialize
    # every armed training loop that feeds the watchdog
    ("mxnet_tpu/telemetry/tracing.py",
     r"(\b(record_span|event|watch_step_time|check_loss|dump_chrome_trace|"
     r"dump_flight_recorder|step_records)\b|"
     # (ISSUE 25) the phase bookkeeping runs in every step of every loop,
     # armed or not
     r"_Span\._complete\b|_Phased\.(__exit__|phase|split)\b|"
     r"_Phase\.__(enter|exit)__)"),
    # goodput ledger (ISSUE 17): the waterfall funnel and ring append run
    # inside every armed training loop at step pace — syncing on a step
    # output here would serialize exactly the pipeline whose stalls the
    # ledger attributes
    ("mxnet_tpu/telemetry/goodput.py",
     r"\b(_on_step|note_step|_snapshot_upstream)\b"),
]

# calls whose result is a step output: loss/metric/output handles the loop
# must treat as pending
_STEP_CALLS = {"step", "run_steps", "forward", "forward_backward",
               "get_outputs"}
# receivers/wrappers that force a host sync
_SYNC_ATTRS = {"item", "asnumpy", "block_until_ready"}
_NUMPY_ROOTS = {"np", "_np", "numpy", "onp"}


def _is_hot(mod: ModuleInfo, fn) -> bool:
    qn = mod.qualname(fn)
    for suffix, pattern in LOOP_FUNCTIONS:
        if mod.relpath.endswith(suffix) and re.search(pattern, qn):
            return True
    return False


def _step_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and call_name(node) in _STEP_CALLS


def _loop_step_outputs(loop: ast.AST):
    """Names assigned from a step call anywhere in this loop body."""
    outs = set()
    for n in ast.walk(loop):
        if isinstance(n, ast.Assign) and _step_call(n.value):
            for t in n.targets:
                if isinstance(t, ast.Name):
                    outs.add(t.id)
                elif isinstance(t, ast.Tuple):
                    outs.update(e.id for e in t.elts
                                if isinstance(e, ast.Name))
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)) and \
                n.value is not None and _step_call(n.value) and \
                isinstance(n.target, ast.Name):
            outs.add(n.target.id)
    return outs


@register_pass(
    "sync-in-loop",
    "host sync (float()/.item()/block_until_ready) on the current step's "
    "outputs inside a fit/run_steps loop re-serializes async dispatch")
def check(mod: ModuleInfo):
    seen = set()
    for fn in mod.functions():
        if not _is_hot(mod, fn):
            continue
        for loop in ast.walk(fn):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            outs = _loop_step_outputs(loop)

            def _pending(node):
                # a step-output name, or a step call synced directly
                # (float(tr.step(...)) inside the loop)
                return (isinstance(node, ast.Name) and node.id in outs) \
                    or _step_call(node)

            for n in ast.walk(loop):
                if not isinstance(n, ast.Call) or id(n) in seen:
                    continue
                name = call_name(n)
                hit = None
                if name in ("float", "int") and \
                        isinstance(n.func, ast.Name) and n.args and \
                        _pending(n.args[0]):
                    hit = f"{name}({unparse(n.args[0])[:50]})"
                elif name in _SYNC_ATTRS and \
                        isinstance(n.func, ast.Attribute) and \
                        _pending(n.func.value):
                    hit = f"{unparse(n.func.value)[:50]}.{name}()"
                elif name == "asarray" and \
                        isinstance(n.func, ast.Attribute) and \
                        isinstance(n.func.value, ast.Name) and \
                        n.func.value.id in _NUMPY_ROOTS and n.args and \
                        _pending(n.args[0]):
                    hit = f"asarray({unparse(n.args[0])[:50]})"
                if hit is None:
                    continue
                seen.add(id(n))
                encl = mod.enclosing_function(n)
                qn = mod.qualname(encl) if encl is not None \
                    else mod.qualname(fn)
                yield Finding(
                    "sync-in-loop", mod.relpath, n.lineno, qn,
                    f"host sync on the current step's output inside the "
                    f"loop serializes async dispatch: `{hit}` — keep it "
                    "pending (PendingScalar) and drain at the epoch/eval "
                    "boundary, or waive a designed drain point")
