"""host-sync: device->host synchronization inside hot-path functions.

A stray ``float()`` / ``.asnumpy()`` / ``np.asarray`` on a device array
inside a step function blocks the dispatch queue, serializes the device,
and breaks XLA fusion (arXiv:2301.13062) — on TPU the *whole* point of the
fused train path is that no value crosses the host boundary per step. The
designed sync points (metric ``get()``, checkpoint ``sync()``, the loss
scaler's overflow read) live in functions that are deliberately NOT on the
hot list.
"""
from __future__ import annotations

import ast
import re

from ..core import (Finding, ModuleInfo, call_name, register_pass, unparse)

# (path suffix, qualname regex searched with re.search). Nested defs carry
# the outer function in their qualname (e.g. ``DataParallelTrainer.
# _build_step.step``), so hot-listing a builder covers the traced bodies it
# creates.
HOT_FUNCTIONS = [
    ("mxnet_tpu/gluon/trainer.py",
     r"Trainer\.(step|update|_update|allreduce_grads|_allreduce_grads)\b"),
    ("mxnet_tpu/parallel/data_parallel.py",
     r"DataParallelTrainer\.(step|run_steps|_build_step|"
     r"_build_step_compressed|_get_step|_get_multi|_record_telemetry|"
     r"_loss_raw|_put_batch|_grad_allreduce_bytes)\b"),
    # next_step_key: the per-step RNG key, shared by the three fused
    # trainers; its multi-process branch is the one waived read-back
    ("mxnet_tpu/parallel/data_parallel.py",
     r"\b(_make_apply_fn|next_step_key)\b"),
    ("mxnet_tpu/parallel/pipeline.py",
     r"(PipelineTrainer\.(step|_build_step|_loss_raw|_record_telemetry|"
     r"_record_partitioned_tp_telemetry|_init_zero_state_partitioned)\b"
     r"|\bpipeline_apply\b|\bschedule_1f1b\b)"),
    # compute-partitioned TP program bodies run INSIDE the 1F1B tick scan:
    # any host sync here happens per tick x per microbatch
    ("mxnet_tpu/parallel/megatron.py",
     r"\b(cell_forward|embed_forward|head_loss_forward|_attention|_tp_moe|"
     r"copy_to_tp|reduce_from_tp|gather_from_sp|scatter_to_sp|partial_grad|"
     r"vocab_parallel_embedding|vocab_parallel_cross_entropy)\b"),
    ("mxnet_tpu/parallel/step_program.py",
     r"StepProgram\.(get|region|capture_cost|cost)\b"),
    ("mxnet_tpu/kvstore/kvstore.py",
     r"KVStore(Dist)?\.(push|pull|pushpull|row_sparse_pull|broadcast)\b"),
    ("mxnet_tpu/optimizer/optimizer.py",
     r"(Optimizer\.(update|update_multi_precision|_update_list|_preprocess)"
     r"\b|\w+\.update\b|Updater\.__call__\b)"),
    ("mxnet_tpu/engine/__init__.py",
     r"\b(lookup|insert|record_execution|record_trace)\b"),
    # roofline ledger recording (ISSUE 7): per-region timing capture is
    # interval-based host arithmetic — a block_until_ready/float() here
    # would reintroduce exactly the per-step sync the ledger must observe,
    # not cause. register_cost/export paths included for completeness.
    ("mxnet_tpu/telemetry/roofline.py",
     r"\b(record|register_cost|total_flops|wrap)\b"),
    ("mxnet_tpu/telemetry/__init__.py",
     r"\b(record_step|_trace_tick|record_dispatch_wait)\b"),
    # goodput ledger (ISSUE 17): the per-step waterfall is pure host
    # arithmetic over cumulative stamps the layers already took — a
    # float()/asarray of a device value in the funnel (or any category
    # source it snapshots) would charge every armed step for a sync the
    # ledger exists to expose, not cause
    ("mxnet_tpu/telemetry/goodput.py",
     r"(\b(_on_step|note_step|_snapshot_upstream|_fam_sum|"
     r"_compile_seconds|set_generation|"
     r"set_pipeline_bubble)\b|_Ring\.append\b)"),
    # per-batch metric updates: accumulation must stay on device; the one
    # designed host sync is get()/get_global(), which are not hot-listed
    ("mxnet_tpu/metric.py",
     r"(Accuracy|TopKAccuracy|MAE|MSE|RMSE|CrossEntropy|"
     r"NegativeLogLikelihood|Loss|EvalMetric)\.(update|_update)\b"),
    ("mxnet_tpu/gluon/utils.py", r"\bclip_global_norm\b"),
    # serving hot path (ISSUE 6): the compiled-artifact call and the
    # dispatch loop must stay sync-free; `_complete` (the designed sync)
    # and `_assemble` (host numpy padding) are deliberately NOT hot
    ("mxnet_tpu/serving/batcher.py",
     r"ContinuousBatcher\.(_dispatch_loop|_next_batch)\b"),
    ("mxnet_tpu/serving/registry.py",
     r"RegisteredModel\.(forward|place_input)\b"),
    ("mxnet_tpu/predict.py", r"ForwardArtifact\.__call__\b"),
    # elastic snapshot hot path (ISSUE 11): save() runs BETWEEN step
    # dispatches — capture builds the leaf/meta view and _copy_leaves
    # dispatches async device copies; any host transfer here would
    # serialize the pipeline the async writer exists to protect. The
    # designed syncs (np.asarray of shard data, manifest IO) live on the
    # background writer thread (_write/_commit), deliberately NOT hot.
    ("mxnet_tpu/elastic/snapshot.py",
     r"SnapshotManager\.(save|should_save|_copy_leaves)\b"),
    ("mxnet_tpu/elastic/state.py",
     r"\b(capture|_capture_dp|_capture_pp|_common_meta|_bucket_dict)\b"),
    ("mxnet_tpu/elastic/run.py", r"\b(capture_trainer|save_trainer)\b"),
    # large-model recipes (ISSUE 12): the fused dp x ep / dp x sp step
    # dispatch and the per-step comm byte accounting must stay sync-free —
    # the dropped-token counters ride as device handles until drain. The
    # designed sync (`_flush_dropped`'s int(handle) at the drain boundary)
    # is deliberately NOT hot. LongContextTrainer.step is inherited from
    # DataParallelTrainer and covered by that file's row.
    ("mxnet_tpu/recipes/moe.py",
     r"MoETrainer\.(step|_build_step_zero|_record_telemetry|"
     r"_a2a_step_bytes)\b"),
    ("mxnet_tpu/recipes/long_context.py",
     r"LongContextTrainer\.(_build_step_zero|_record_telemetry|"
     r"_ring_step_bytes)\b"),
    # span tracing record paths (ISSUE 14): spans ride timestamps the
    # instrumented layers already take — a float()/asarray on a device
    # value inside the tracer would turn the observer into a serializer.
    # The watchdog (watch_step_time/check_loss) consumes host floats its
    # callers already materialized; a sync sneaking in here would charge
    # every armed step for it.
    ("mxnet_tpu/telemetry/tracing.py",
     r"(\b(span|phased|record_span|event|attach|new_root|watch_step_time|"
     r"check_loss|_append|_anomaly|_resolve_parent)\b|"
     # (ISSUE 25) the always-on half: every step and every batch passes
     # through these whether tracing is armed or not
     r"_Span\.(__enter__|__exit__|_complete)\b|"
     r"_Phased\.(__enter__|__exit__|phase|split)\b|"
     r"_Phase\.(__enter__|__exit__)\b)"),
]

# host reads of *python* scalars that merely look like syncs. Matched
# against the unparsed argument of float()/int()/bool()/np.asarray().
ALLOWED_ARG = re.compile(
    r"learning_rate|loss_scale|num_update|\.shape\b|\.ndim\b|\.nbytes\b|"
    r"perf_counter|len\(|\blrs?\b|batch_size|wd_mult|"
    r"rescale_grad|\.get\(|self\._t\b|_np\.prod")

_COERCIONS = {"float", "int", "bool"}
_NUMPY_ROOTS = {"np", "_np", "numpy", "onp"}


def _is_hot(mod: ModuleInfo, fn) -> bool:
    qn = mod.qualname(fn)
    for suffix, pattern in HOT_FUNCTIONS:
        if mod.relpath.endswith(suffix) and re.search(pattern, qn):
            return True
    return False


@register_pass(
    "host-sync",
    "device->host sync (float()/.item()/.asnumpy()/np.asarray) on a hot path")
def check(mod: ModuleInfo):
    hot = [fn for fn in mod.functions() if _is_hot(mod, fn)]
    seen = set()
    for fn in hot:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            # findings belong to the INNERMOST enclosing def (a nested
            # step fn inside a hot builder reports as builder.step)
            encl = mod.enclosing_function(node)
            qn = mod.qualname(encl) if encl is not None else mod.qualname(fn)
            name = call_name(node)
            if name == "asnumpy":
                seen.add(id(node))
                yield Finding(
                    "host-sync", mod.relpath, node.lineno, qn,
                    f".asnumpy() blocks on device transfer: "
                    f"`{unparse(node)[:60]}`")
            elif name == "item" and not node.args:
                seen.add(id(node))
                yield Finding(
                    "host-sync", mod.relpath, node.lineno, qn,
                    f".item() blocks on device transfer: "
                    f"`{unparse(node)[:60]}`")
            elif (name in _COERCIONS and isinstance(node.func, ast.Name)
                    and len(node.args) == 1
                    and not isinstance(node.args[0], ast.Constant)):
                arg = unparse(node.args[0])
                if ALLOWED_ARG.search(arg):
                    continue
                seen.add(id(node))
                yield Finding(
                    "host-sync", mod.relpath, node.lineno, qn,
                    f"{name}() on a (potential) device value forces a "
                    f"blocking sync: `{name}({arg[:50]})`")
            elif (name == "asarray" and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in _NUMPY_ROOTS and node.args):
                arg = unparse(node.args[0])
                if ALLOWED_ARG.search(arg):
                    continue
                seen.add(id(node))
                yield Finding(
                    "host-sync", mod.relpath, node.lineno, qn,
                    f"np.asarray() copies device data to host: "
                    f"`asarray({arg[:50]})`")
