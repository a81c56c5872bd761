#!/usr/bin/env python3
"""Static observability lint: every public op-dispatch and collective entry
point must route through the telemetry registry / profiler hook.

Registered as the mxlint ``instrumentation`` pass (tools/mxlint/) and still
runnable standalone — ``python tools/check_instrumentation.py`` remains the
tier-1 entry point tests/test_telemetry.py invokes. The AST walking, parsed
-module model and finding type come from tools/mxlint/core; only the rule
TABLE lives here:

  - kvstore push/pull/pushpull/row_sparse_pull/broadcast (base + dist
    overrides) must carry the `@_telem.instrument_comm(...)` decorator;
  - trainer step paths (gluon.Trainer, DataParallelTrainer, PipelineTrainer,
    BaseModule.fit) must call telemetry's record_step (directly or via a
    helper);
  - the eager op-dispatch path must consult the profiler hook
    (`_profile_hook`) — the reference's IsProfiling() check.

Exit code 0 when clean; nonzero with one line per violation.
"""
from __future__ import annotations

import ast
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PKG = ROOT / "mxnet_tpu"


def _mxlint_core():
    """Shared AST infra (tools/mxlint/core); bootstrap sys.path when run as
    a standalone script (sys.path[0] is tools/ then)."""
    try:
        from tools.mxlint import core
    except ImportError:
        sys.path.insert(0, str(ROOT))
        from tools.mxlint import core
    return core


# (relative file, class name or None for module level, function name,
#  accepted instrumentation names, mode)
#   mode "decorator": one decorator must be <x>.NAME(...) / NAME(...)
#   mode "call":      the body must call one of NAMES (name or attribute)
METHOD_CHECKS = [
    *[("kvstore/kvstore.py", "KVStore", m, {"instrument_comm"}, "decorator")
      for m in ("push", "pull", "pushpull", "row_sparse_pull", "broadcast")],
    *[("kvstore/kvstore.py", "KVStoreDist", m, {"instrument_comm"},
       "decorator")
      for m in ("push", "pull", "pushpull", "row_sparse_pull")],
    ("gluon/trainer.py", "Trainer", "step", {"record_step"}, "call"),
    ("parallel/data_parallel.py", "DataParallelTrainer", "step",
     {"record_step", "_record_telemetry"}, "call"),
    ("parallel/data_parallel.py", "DataParallelTrainer", "run_steps",
     {"record_step", "_record_telemetry"}, "call"),
    # zero-update (ZeRO-style sharded weight update) path: per-kind
    # collective counters + the per-replica optimizer-state gauge must be
    # booked for every step that runs the sharded update
    ("parallel/data_parallel.py", "DataParallelTrainer",
     "_record_zero_telemetry", {"record_comm"}, "call"),
    ("parallel/data_parallel.py", "DataParallelTrainer",
     "_record_telemetry", {"record_optimizer_state"}, "call"),
    ("parallel/pipeline.py", "PipelineTrainer", "step",
     {"record_step", "_record_telemetry"}, "call"),
    # pipeline schedule comm accounting: the per-step ppermute
    # activation-hop volume and the embed/head grad psum must both be
    # booked, plus the per-replica optimizer-state gauge
    ("parallel/pipeline.py", "PipelineTrainer", "_record_telemetry",
     {"record_comm"}, "call"),
    ("parallel/pipeline.py", "PipelineTrainer", "_record_telemetry",
     {"record_optimizer_state"}, "call"),
    ("parallel/tensor_parallel.py", None, "shard_params_megatron",
     {"record_comm", "counter", "gauge"}, "call"),
    ("parallel/tensor_parallel.py", None, "apply_rules",
     {"counter", "gauge"}, "call"),
    # compute-partitioned TP (ISSUE 16): every manual collective in the
    # 1F1B tick body must run under a jax.named_scope region name
    # (mx.tp.* / mx.sp.*) so span traces, the flight recorder, and xplane
    # profiles can attribute its wire time — an unnamed psum here is
    # invisible to every per-region diagnosis tool
    *[("parallel/megatron.py", None, f, {"named_scope"}, "call")
      for f in ("copy_to_tp", "_copy_bwd", "reduce_from_tp",
                "gather_from_sp", "_gather_sp_bwd", "scatter_to_sp",
                "_scatter_sp_bwd", "partial_grad",
                "vocab_parallel_embedding", "vocab_parallel_cross_entropy")],
    # ... and the per-step activation-collective volume must be booked on
    # its per-axis comm lane (the no-weight-gather acceptance signal reads
    # exactly these series)
    ("parallel/pipeline.py", "PipelineTrainer",
     "_record_partitioned_tp_telemetry", {"record_comm"}, "call"),
    ("module/base_module.py", "BaseModule", "fit", {"record_step"}, "call"),
    # async feed + bounded in-flight dispatch (ISSUE 5): the overlap layer
    # must stay observable — feed stalls/queue depth at every delivery,
    # in-flight depth at every window transition
    ("engine/async_feed.py", "DeviceFeed", "next",
     {"record_feed_stall", "record_feed_depth"}, "call"),
    ("engine/async_feed.py", "DispatchWindow", "admit",
     {"record_inflight"}, "call"),
    ("engine/async_feed.py", "DispatchWindow", "drain",
     {"record_inflight"}, "call"),
    # continuous-batching serving (ISSUE 6): every serving entry point —
    # enqueue, dispatch, completion — must route through the SLO telemetry
    # (latency histogram, queue depth, batch occupancy); a serving path
    # that silently skips them is invisible to the p99 dashboards
    ("serving/batcher.py", "ContinuousBatcher", "submit",
     {"record_serving_enqueue"}, "call"),
    ("serving/batcher.py", "ContinuousBatcher", "_dispatch_loop",
     {"record_serving_dispatch"}, "call"),
    ("serving/batcher.py", "ContinuousBatcher", "_complete",
     {"record_serving_completion"}, "call"),
    # roofline ledger (ISSUE 7): every fused-step driver must book its
    # executions through the ONE engine funnel (engine.record_execution
    # with a region), so the per-region ledger always reconciles with the
    # aggregate flops_executed account
    ("parallel/data_parallel.py", "DataParallelTrainer",
     "_record_telemetry", {"record_execution"}, "call"),
    ("parallel/pipeline.py", "PipelineTrainer", "_record_telemetry",
     {"record_execution"}, "call"),
    ("predict.py", "ForwardArtifact", "__call__",
     {"record_execution"}, "call"),
    # elastic fault tolerance (ISSUE 11): the snapshot writer must book
    # its commit (save seconds + bytes) and every worker boot must book
    # its restore outcome — a fleet whose snapshots stop landing or whose
    # relaunches silently boot "fresh" must show on the dashboards
    ("elastic/snapshot.py", "SnapshotManager", "_commit",
     {"record_checkpoint_save"}, "call"),
    ("elastic/run.py", None, "_record_resume",
     {"record_resume"}, "call"),
    # large-model recipes (ISSUE 12): the MoE trainer must book its
    # all_to_all dispatch/combine wire volume per step and its dropped-
    # token count at the drain boundary (capacity starvation must show on
    # mx_moe_dropped_tokens_total, never require a per-step host sync);
    # the long-context trainer must book the ring ppermute volume
    ("recipes/moe.py", "MoETrainer", "step",
     {"record_step", "_record_telemetry"}, "call"),
    ("recipes/moe.py", "MoETrainer", "_record_telemetry",
     {"record_comm"}, "call"),
    ("recipes/moe.py", "MoETrainer", "_flush_dropped",
     {"record_moe_dropped"}, "call"),
    # LongContextTrainer inherits step() from DataParallelTrainer (already
    # checked above); its telemetry override books the ring wire volume
    ("recipes/long_context.py", "LongContextTrainer", "_record_telemetry",
     {"record_comm"}, "call"),
    # reliability plane (ISSUE 13): every fired fault and every transient
    # retry must be booked — chaos runs divide recovery metrics by
    # mx_faults_injected_total, and a nonzero retry rate WITHOUT armed
    # chaos is the flaky-filesystem page; load shedding and producer
    # leaks/restarts are the overload + input-supervision signals
    ("faults/__init__.py", None, "check",
     {"record_fault_injected"}, "call"),
    ("faults/__init__.py", None, "io_retry",
     {"record_io_retry"}, "call"),
    ("serving/batcher.py", "ContinuousBatcher", "_shed",
     {"record_request_shed"}, "call"),
    ("engine/async_feed.py", "DeviceFeed", "_stop_producer",
     {"record_feed_producer_leak"}, "call"),
    ("engine/async_feed.py", "DeviceFeed", "_produce",
     {"record_feed_producer_restart"}, "call"),
    # span tracing (ISSUE 14): the cross-layer funnels — serving request
    # lifecycle, fused-step dispatch, feed produce/put, window admit,
    # snapshot write, fault firings — must each record into the tracing
    # ring when armed; a layer that silently drops its spans breaks the
    # end-to-end trace the flight recorder and Perfetto dump promise
    ("serving/batcher.py", "ContinuousBatcher", "submit",
     {"new_root", "event"}, "call"),
    ("serving/batcher.py", "ContinuousBatcher", "_dispatch_loop",
     {"record_span"}, "call"),
    ("serving/batcher.py", "ContinuousBatcher", "_complete",
     {"record_span"}, "call"),
    # (ISSUE 25) the trainer's calls and the feed's producer go through
    # tracing.phased — the span, its phases and the always-on step / batch
    # record in one body; the window's wait is a real tracing.span
    ("parallel/data_parallel.py", "DataParallelTrainer", "step",
     {"phased"}, "call"),
    ("parallel/data_parallel.py", "DataParallelTrainer", "step",
     {"phase"}, "call"),
    ("parallel/data_parallel.py", "DataParallelTrainer", "run_steps",
     {"phased"}, "call"),
    ("parallel/data_parallel.py", "DataParallelTrainer", "run_steps",
     {"phase"}, "call"),
    ("engine/async_feed.py", "DeviceFeed", "_produce",
     {"phased"}, "call"),
    # (ISSUE 36) set-up on record: the trainer's construction is the span
    # mx.dp.init with its phases, net init and the cold forward go through
    # the one helper that opens mx.block.initialize / mx.block.deferred_init,
    # and the engine's listener is what makes the build records
    ("parallel/data_parallel.py", "DataParallelTrainer", "__init__",
     {"phased"}, "call"),
    ("parallel/data_parallel.py", "DataParallelTrainer", "__init__",
     {"phase"}, "call"),
    ("gluon/block.py", "Block", "initialize",
     {"_outermost"}, "call"),
    ("gluon/block.py", None, "_cold_start",
     {"_outermost"}, "call"),
    ("gluon/block.py", None, "_outermost",
     {"phased"}, "call"),
    ("gluon/block.py", "HybridBlock", "forward",
     {"_cold_start"}, "call"),
    ("gluon/block.py", "HybridBlock", "_forward_cold",
     {"_cold_start"}, "call"),
    ("engine/__init__.py", None, "_listen_to_builds",
     {"register_event_duration_secs_listener"}, "call"),
    ("engine/async_feed.py", "DeviceFeed", "next",
     {"span"}, "call"),
    ("engine/async_feed.py", "DispatchWindow", "admit",
     {"span"}, "call"),
    ("elastic/snapshot.py", "SnapshotManager", "_write",
     {"span", "attach"}, "call"),
    ("faults/__init__.py", None, "check",
     {"event"}, "call"),
    ("faults/__init__.py", None, "io_retry",
     {"record_span"}, "call"),
    ("telemetry/__init__.py", None, "record_step",
     {"watch_step_time"}, "call"),
    # multi-host control plane (ISSUE 15): the group view must book the
    # live-host gauge + generation epoch on every observation, every
    # commit-barrier wait must land in the histogram, and a hang-watchdog
    # firing (an incident by definition) must be counted before the
    # process exits
    ("elastic/coordinator.py", "Coordinator", "view",
     {"record_hosts_live"}, "call"),
    ("elastic/coordinator.py", "Coordinator", "commit_snapshot",
     {"record_commit_barrier"}, "call"),
    ("elastic/coordinator.py", "HangWatchdog", "_fire",
     {"record_hang_watchdog"}, "call"),
    # goodput ledger (ISSUE 17): record_step is THE waterfall funnel —
    # every armed step must flow into goodput._on_step; the dispatch
    # window must book its cumulative wait (the dispatch_backpressure
    # source); restarts must land as run-level downtime; and an eviction
    # must trigger the fleet aggregation + flight-recorder stamp
    ("telemetry/__init__.py", None, "record_step",
     {"_on_step"}, "call"),
    ("engine/async_feed.py", "DispatchWindow", "admit",
     {"record_dispatch_wait"}, "call"),
    ("engine/async_feed.py", "DispatchWindow", "drain",
     {"record_dispatch_wait"}, "call"),
    ("elastic/run.py", None, "_record_resume",
     {"record_restart_downtime"}, "call"),
    ("elastic/coordinator.py", "Coordinator", "step_poll",
     {"on_eviction"}, "call"),
    # compiled-HLO hazard audit (ISSUE 18): estimate_cost is THE audit
    # funnel — every AOT lower+compile must hand its optimized HLO to
    # hlo_audit (a step artifact with a host callback / f64 promotion
    # must fingerprint, never build silently); and every
    # StepProgram cost capture must thread its region so fingerprints
    # carry the same dp.step/pp.step labels the roofline ledger uses
    ("engine/__init__.py", None, "estimate_cost",
     {"audit_compiled"}, "call"),
    ("parallel/step_program.py", "StepProgram", "capture_cost",
     {"region"}, "call"),
]

# (relative file, required substring, rationale)
TEXT_CHECKS = [
    ("ndarray/ndarray.py", "_profile_hook",
     "eager op dispatch must consult the profiler hook (profile_imperative)"),
    ("ops/registry.py", "def set_profile_hook",
     "the op registry must expose the profiler hook installer"),
    ("gluon/block.py", "record_execution",
     "the fused HybridBlock path must account executions with the engine"),
    ("symbol/executor.py", "record_execution",
     "the symbol Executor path must account executions with the engine"),
    ("parallel/pipeline.py", '"ppermute"',
     "the pipeline trainer must book the schedule's activation-hop "
     "ppermute volume under its own comm kind (bubble/ICI accounting — "
     "the grad psum alone undercounts pipeline wire traffic)"),
    ("parallel/pipeline.py", '"tp_act_psum"',
     "the partitioned-tp step must book its activation psum volume under "
     "its own comm kind on the 'tp' lane (the no-weight-gather acceptance "
     "A/B reads this series against tp_weight_all_gather)"),
    ("parallel/pipeline.py", '"tp_act_all_gather"',
     "the sequence-parallel step must book its boundary all_gather volume "
     "on the 'sp' lane"),
    ("parallel/pipeline.py", '"tp_act_psum_scatter"',
     "the sequence-parallel step must book its boundary psum_scatter "
     "volume on the 'sp' lane"),
    ("telemetry/__init__.py", "def comm_axis_bytes",
     "the registry must expose per-mesh-axis comm byte totals (the "
     "dp-vs-tp-vs-sp split the partitioned-tp acceptance reads)"),
    ("telemetry/__init__.py", "def record_optimizer_state",
     "the registry must expose the per-replica optimizer-state gauge "
     "(the zero-update memory acceptance signal)"),
    ("telemetry/__init__.py", "mx_feed_queue_depth",
     "the registry must export the async-feed queue-depth gauge"),
    ("telemetry/__init__.py", "mx_feed_stall_seconds_total",
     "the registry must export the feed-stall accounting metric "
     "(nonzero growth = input-bound, not device-bound)"),
    ("telemetry/__init__.py", "mx_inflight_steps",
     "the registry must export the bounded in-flight window depth gauge"),
    ("telemetry/__init__.py", "DEFAULT_LATENCY_BUCKETS",
     "the registry must declare the documented serving-latency bucket "
     "ladder (docs/serving.md; p50/p99 derive from the cumulative "
     "histogram exposition)"),
    ("telemetry/__init__.py", "mx_serving_request_seconds",
     "the registry must export the end-to-end serving latency histogram"),
    ("telemetry/__init__.py", "mx_serving_queue_depth",
     "the registry must export the serving queue-depth gauge"),
    ("telemetry/__init__.py", "mx_serving_batch_occupancy",
     "the registry must export the batch-occupancy (real vs padded rows) "
     "gauge — the bucket-set tuning signal"),
    # reliability plane (ISSUE 13)
    ("telemetry/__init__.py", "mx_faults_injected_total",
     "the registry must export the injected-fault counter (the chaos "
     "denominator every recovery metric divides by)"),
    ("telemetry/__init__.py", "mx_io_retries_total",
     "the registry must export the transient-IO retry counter (nonzero "
     "without armed chaos = flaky snapshot filesystem, page before "
     "retries exhaust)"),
    ("telemetry/__init__.py", "mx_requests_shed_total",
     "the registry must export the serving shed counter (admission "
     "control / deadline drops — the overload signal)"),
    ("telemetry/__init__.py", "mx_feed_producer_leaks_total",
     "the registry must export the producer-leak counter (abandoned "
     "DeviceFeed producer threads must never be silent)"),
    # roofline ledger + trace capture (ISSUE 7)
    ("telemetry/__init__.py", "def peak_bytes_per_second",
     "the registry must expose the roofline bandwidth peak (env override "
     "-> device_kind HBM table -> documented CPU anchor)"),
    ("telemetry/__init__.py", "def trace_steps",
     "the registry must expose programmatic xplane trace capture "
     "(start_trace + stop after n recorded steps)"),
    ("telemetry/__init__.py", "mx_step_seconds",
     "training must record the step-latency histogram on the documented "
     "DEFAULT_LATENCY_BUCKETS ladder (serving parity)"),
    ("telemetry/roofline.py", "mx_region_achieved_flops_ratio",
     "the roofline ledger must export per-region achieved-vs-peak FLOPs"),
    ("telemetry/roofline.py", "mx_region_bytes_per_second",
     "the roofline ledger must export per-region achieved bandwidth"),
    ("telemetry/roofline.py", "lost_flop_seconds",
     "the ledger report must rank regions by lost FLOP-seconds (the "
     "attribution signal the stem/layout PRs act on)"),
    ("engine/__init__.py", "mx_cost_capture_failures_total",
     "estimate_cost lowering failures must be counted, not swallowed"),
    ("engine/__init__.py", "cost_capture_failures",
     "engine.cache_stats must carry the cost-capture failure count"),
    # elastic fault tolerance (ISSUE 11)
    ("telemetry/__init__.py", "mx_checkpoint_save_seconds",
     "the registry must export the snapshot save-latency gauge (cadence "
     "vs write-bandwidth tuning, docs/checkpointing.md)"),
    ("telemetry/__init__.py", "mx_checkpoint_bytes_total",
     "the registry must export the cumulative snapshot payload counter"),
    ("telemetry/__init__.py", "mx_resume_total",
     "the registry must export the boot-outcome counter "
     "(fresh/resumed/resharded — fresh after a kill means snapshots are "
     "not landing)"),
    # large-model recipes (ISSUE 12)
    ("telemetry/__init__.py", "mx_moe_dropped_tokens_total",
     "the registry must export the MoE capacity-overflow counter "
     "(a silently-dropping router looks like a loss plateau without it)"),
    ("recipes/moe.py", '"all_to_all"',
     "the MoE trainer must book the expert dispatch/combine exchanges "
     "under their own comm kind (the a2a wire is the expert-parallel "
     "scaling limit; folding it into generic comm hides it)"),
    ("recipes/long_context.py", '"ppermute"',
     "the long-context trainer must book the ring-attention kv rotation "
     "volume (sequence-parallel wire accounting, docs/large_models.md)"),
    # span tracing + flight recorder + statusz (ISSUE 14)
    ("telemetry/tracing.py", "mx_anomalies_total",
     "the anomaly watchdog must book detections on the anomaly counter "
     "(EWMA step-time regression / nonfinite loss — the page signal)"),
    ("telemetry/__init__.py", "mx_serving_queue_wait_seconds",
     "the registry must export the serving queue-wait histogram on the "
     "shared latency ladder (queue wait vs total separates admission "
     "pressure from compute)"),
    ("serving/server.py", "X-MX-Trace-Id",
     "the HTTP front door must echo the request's trace id so a client "
     "can join its request to the server-side span timeline"),
    ("elastic/run.py", "dump_flight_recorder",
     "the elastic loop must dump the flight recorder on preemption and "
     "unhandled step exceptions (the black-box postmortem)"),
    ("telemetry/__init__.py", "def statusz",
     "the registry must expose the statusz snapshot the debug endpoints "
     "serve (config fingerprint, cache stats, queue depth, recorder tail)"),
    # multi-host control plane (ISSUE 15)
    ("telemetry/__init__.py", "mx_hosts_live",
     "the registry must export the live-host gauge (below fleet size = "
     "a dead host; the first page of a multi-host incident)"),
    ("telemetry/__init__.py", "mx_coordinator_generation",
     "the registry must export the membership generation epoch (climbing "
     "without deploys = hosts flapping on lease expiry)"),
    ("telemetry/__init__.py", "mx_commit_barrier_seconds",
     "the registry must export the cross-host commit-barrier histogram "
     "(p99 near the straggler deadline predicts the next abort)"),
    ("telemetry/__init__.py", "mx_hang_watchdog_fires_total",
     "the registry must export the hang-watchdog counter (every "
     "increment is an incident with a flight-recorder dump attached)"),
    ("elastic/coordinator.py", '"straggler"',
     "a straggler abort must book mx_snapshot_failures_total under its "
     "own source label — an aborted barrier that books nothing is "
     "indistinguishable from a hang"),
    ("telemetry/__init__.py", '"coordinator"',
     "statusz must carry the coordinator group view (generation, "
     "live/dead, leader) next to the config fingerprint"),
    # goodput ledger (ISSUE 17)
    ("telemetry/goodput.py", "mx_goodput_seconds_total",
     "the ledger must export per-category waterfall seconds (the "
     "Prometheus twin of the on-disk time-series)"),
    ("telemetry/goodput.py", "mx_goodput_ratio",
     "the ledger must export the live goodput ratio gauge (compute "
     "share of wall — the headline fleet-efficiency signal)"),
    ("telemetry/goodput.py", "mx_straggler_score",
     "fleet aggregation must book per-rank straggler scores (median "
     "step-wall skew vs the fleet median) so a slow host pages"),
    ("telemetry/__init__.py", "mx_checkpoint_save_seconds_total",
     "the registry must export cumulative snapshot wall seconds (the "
     "waterfall's snapshot category is a delta of this counter)"),
    ("telemetry/__init__.py", "mx_dispatch_wait_seconds_total",
     "the registry must export the cumulative dispatch-window wait "
     "(the waterfall's dispatch_backpressure fallback source)"),
    ("telemetry/__init__.py", '"goodput"',
     "statusz must carry the goodput waterfall view next to the "
     "coordinator group view"),
    # compiled-HLO hazard audit (ISSUE 18)
    ("engine/hlo_audit.py", "mx_hlo_hazards_total",
     "the HLO audit must book every hazard on the per-kind/per-region "
     "counter — a hazard that only lives in the JSON fingerprint never "
     "pages anyone"),
    ("telemetry/__init__.py", '"hlo_audit"',
     "statusz must carry the compiled-HLO hazard counters next to the "
     "cache stats (the first place to look when a step artifact slows)"),
]


def _called_names(fn):
    core = _mxlint_core()
    return {name for node in ast.walk(fn)
            if isinstance(node, ast.Call)
            and (name := core.call_name(node)) is not None}


def findings(pkg: Path = PKG):
    """Structured results (mxlint Finding objects) — the mxlint
    ``instrumentation`` pass consumes these directly."""
    core = _mxlint_core()
    pkg = Path(pkg)
    out = []
    mods = {}
    for rel, classname, funcname, names, mode in METHOD_CHECKS:
        if rel not in mods:
            try:
                mods[rel] = core.ModuleInfo(pkg / rel, root=pkg.parent)
            except (OSError, SyntaxError, ValueError) as e:
                out.append(core.Finding(
                    "instrumentation", rel, 0, "",
                    f"unreadable/unparseable ({e})"))
                mods[rel] = None
        mod = mods[rel]
        if mod is None:
            continue
        symbol = f"{classname + '.' if classname else ''}{funcname}"
        fn = next((f for f in mod.functions()
                   if mod.qualname(f) == symbol), None)
        if fn is None:
            out.append(core.Finding(
                "instrumentation", mod.relpath, 0, symbol,
                "entry point not found (update tools/check_instrumentation"
                ".py if it moved)"))
            continue
        found = core.decorator_names(fn) if mode == "decorator" \
            else _called_names(fn)
        if not (found & names):
            need = "/".join(sorted(names))
            out.append(core.Finding(
                "instrumentation", mod.relpath, fn.lineno, symbol,
                f"not instrumented — expected "
                f"{'decorator' if mode == 'decorator' else 'a call to'} "
                f"{need} (telemetry must see every "
                f"{'collective' if mode == 'decorator' else 'train step'} "
                "entry point)"))
    for rel, needle, why in TEXT_CHECKS:
        path = pkg / rel
        try:
            text = path.read_text()
        except OSError as e:
            out.append(core.Finding("instrumentation", rel, 0, "",
                                    f"unreadable ({e})"))
            continue
        if needle not in text:
            out.append(core.Finding("instrumentation", rel, 0, "",
                                    f"missing {needle!r} — {why}"))
    return out


def check(pkg: Path = PKG):
    """Back-compat string form (the original standalone API)."""
    out = []
    for f in findings(pkg):
        rel = f.path.split("mxnet_tpu/", 1)[-1] if "mxnet_tpu/" in f.path \
            else f.path
        where = f"{rel}:{f.symbol}" if f.symbol else rel
        out.append(f"{where}: {f.message}")
    return out


def main(argv=None):
    violations = check()
    for v in violations:
        print(f"check_instrumentation: {v}", file=sys.stderr)
    if violations:
        print(f"check_instrumentation: {len(violations)} violation(s)",
              file=sys.stderr)
        return 1
    print("check_instrumentation: all observability entry points "
          "instrumented")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
