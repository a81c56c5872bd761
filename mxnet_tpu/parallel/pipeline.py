"""Pipeline parallelism — 1F1B (default) and circular GPipe schedules.

Capability uplift over the reference (SURVEY.md §2.4: the reference has no
pipeline parallelism; its model-parallel story stops at per-layer ctx
placement, reference example/model-parallel-lstm). TPU-native design:

  - both schedules are ONE `lax.scan` inside `shard_map` over the 'pp' mesh
    axis; activations hop stages with `lax.ppermute` (ICI neighbor traffic);
  - **GPipe** (`pipeline_apply`): backward is NOT hand-written —
    differentiating through the scheduled scan runs the transposed schedule.
    Simple, but the transpose stashes one residual per (stage, microbatch):
    peak activation memory grows O(M) with the microbatch count;
  - **1F1B** (`schedule_1f1b`): warmup / steady 1-forward-1-backward /
    cooldown with hand-scheduled per-tick `jax.vjp` segments (plain
    grad-of-scan would replay GPipe order). A microbatch's backward starts
    as soon as its forward clears the last stage, so each stage keeps at
    most 2·pp·v−1 stashed stage inputs regardless of M — peak live
    activations are bounded O(pp) instead of O(M). The optional interleaved
    variant (`virtual_stages=v>1`) gives each device v non-contiguous layer
    chunks (logical stage c·pp+idx), shrinking the bubble fraction from
    (pp−1)/(M+pp−1) toward (pp−1)/(v·M+pp−1) at the cost of v× ppermute
    traffic.

`PipelineTrainer` fuses embed -> schedule -> head -> loss -> backward ->
optimizer update into one jitted shard_map over a mesh with a 'pp' axis,
optionally composed with:

  - a 'dp' axis (pipeline+data parallelism, with `zero_update=True`
    extending the ZeRO-style sharded update + bf16/int8 comm wire of
    parallel/zero.py over the dp axis of the stacked stage params);
  - a 'tp' axis (manual weight-sharded tensor parallelism: leaves carrying
    `Parameter.sharding` specs over 'tp' are stored sharded, all-gathered
    once per step OUTSIDE the differentiated region, and their — then
    rank-identical — grads sliced back for the local update lane).

Executables live in the process-wide engine cache behind a
`StepProgram` keyed on `engine.config_fingerprint()` (parallel/
step_program.py): same-config trainers share compiles and roofline rows.
"""
from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from .mesh import axis_size as _axis_size, require_axis
from jax.sharding import Mesh, NamedSharding

from ..base import MXNetError, env
from ..ndarray import NDArray
from .. import engine as _engine
from ..engine import async_feed as _feed
from .. import optimizer as opt_mod
from .. import sanitize as _sanitize
from .. import telemetry as _telem
from . import megatron as _mg
from . import zero as _zero
from .data_parallel import next_step_key
from .mesh import current_mesh, P
from .step_program import StepProgram
from .tensor_parallel import gather_tp, slice_tp, tp_shard_dim

__all__ = ["pipeline_spec", "pipeline_apply", "gpipe_schedule",
           "schedule_1f1b", "PipelineTrainer"]

env.declare("MXNET_TPU_PP_SCHEDULE", "1f1b", str,
            "Default PipelineTrainer schedule: '1f1b' (bounded activation "
            "memory) or 'gpipe' (grad-of-scan transpose)")


def pipeline_spec(num_stages: int, axis: str = "pp"):
    return {"num_stages": num_stages, "axis": axis}


def pipeline_apply(stage_fn: Callable, stage_params, x_stack,
                   axis_name: str = "pp", remat: bool = True):
    """Differentiable circular pipeline schedule. Call INSIDE shard_map over
    `axis_name`.

    stage_fn(stage_params, x_mb, tick) -> y_mb must be shape-preserving;
    stage_params is THIS device's stage pytree; `tick` is the schedule step
    (traced int32 — fold it into RNG keys so every microbatch draws fresh
    dropout masks); x_stack is the (M, ...) microbatch stack (only stage 0's
    copy is consumed — other stages receive activations over ppermute).
    Returns the (M, ...) output stack, valid on the LAST stage (finite zeros
    elsewhere — inactive ticks compute on zeros and are masked, so no NaNs
    leak and no gradient flows from them).

    Reverse-mode differentiation through this function yields the reverse
    pipeline schedule with weight-gradient accumulation (see module
    docstring) — callers get pipeline backward for free from jax.grad, at
    GPipe's O(M) residual memory. For the bounded-memory hand-scheduled
    alternative see `schedule_1f1b`.
    """
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = x_stack.shape[0]
    steps = M + n - 1
    f = jax.checkpoint(stage_fn) if remat else stage_fn

    def body(inflight, t):
        x_in = jnp.where(idx == 0, x_stack[jnp.clip(t, 0, M - 1)], inflight)
        y = f(stage_params, x_in, t)
        active = jnp.logical_and(t - idx >= 0, t - idx < M)
        y = jnp.where(active, y, jnp.zeros_like(y))
        perm = [(i, (i + 1) % n) for i in range(n)]
        return lax.ppermute(y, axis_name, perm), y

    _, ys = lax.scan(body, jnp.zeros_like(x_stack[0]), jnp.arange(steps))
    # microbatch m leaves the last stage at tick m + n - 1
    return ys[n - 1:]


def gpipe_schedule(stage_fn: Callable, n_microbatch: int, axis_name: str):
    """Back-compat shim over pipeline_apply for parameterless stage fns."""
    def run(x_stack):
        return pipeline_apply(lambda _, x, t: stage_fn(x), (), x_stack,
                              axis_name=axis_name, remat=False)
    return run


def schedule_1f1b(embed_fn: Callable, stage_fn: Callable,
                  head_loss_fn: Callable, eparams, sparams, hparams,
                  x_stack, y_stack, axis_name: str = "pp",
                  n_chunks: int = 1):
    """Hand-scheduled 1F1B/interleaved pipeline. Call INSIDE shard_map over
    `axis_name` (pp). One `lax.scan` over M + 2(pp·v − 1) combined ticks;
    every tick runs one forward lane and one backward lane per chunk, so a
    microbatch's backward begins the tick after its forward clears the last
    logical stage — the steady state is exactly 1-forward-1-backward.

      embed_fn(eparams, x_mb, m)        -> act        (stage-0 entry)
      stage_fn(chunk_leaves, act, tick) -> act        (shape-preserving)
      head_loss_fn(hparams, act, y_mb, m) -> scalar   (mean over microbatch)

    `sparams` leaves are this device's stacked layers (L_local, ...); with
    `n_chunks=v>1` chunk c (rows [c·L_local/v, (c+1)·L_local/v)) acts as
    logical stage c·pp+idx (interleaved schedule — the trainer's
    `_stack_order` lays cell params out in this order). Backward re-derives
    each tick's vjp from the stashed stage INPUT (ring buffer of
    S = 2·pp·v − 1 slots per chunk), so the scan carries O(pp·v) activations
    independent of M — the bounded-memory property GPipe's transposed scan
    lacks. Gradients are masked `jnp.where` sums over microbatches; inactive
    lanes compute on zeros/clamped indices and contribute nothing.

    Returns (loss_sum, grads_embed, grads_stages, grads_head) as
    MICROBATCH SUMS, nonzero only on the owning stage (loss/head: last
    stage; embed: stage 0; stages: local rows). Caller divides by M and
    psums the replicated groups over pp.
    """
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    v = n_chunks
    nv = n * v
    M = x_stack.shape[0]
    T = M + 2 * (nv - 1)
    S = 2 * nv - 1
    Lc = sparams[0].shape[0] // v

    def chunk(c):
        return [w[c * Lc:(c + 1) * Lc] for w in sparams]

    # activation template: one embed fixes shape/dtype for the carries (the
    # value itself is dead — XLA removes the computation)
    act0 = embed_fn(eparams, x_stack[0], jnp.int32(0))
    zact = jnp.zeros(act0.shape, act0.dtype)

    def tick(carry, t):
        fwd_recv, bwd_recv, stash, ge, gs, gh, lsum = carry
        ys_f, new_stash = [], []
        # ---- forward lane: one microbatch per chunk enters/advances ----
        for c in range(v):
            ls = c * n + idx          # logical stage of this chunk
            mf = t - ls               # microbatch in this chunk's forward
            f_act = jnp.logical_and(mf >= 0, mf < M)
            mf_cl = jnp.clip(mf, 0, M - 1)
            if c == 0:
                h_emb = embed_fn(eparams, x_stack[mf_cl], mf_cl)
                x_in = jnp.where(idx == 0, h_emb, fwd_recv[0])
            else:
                x_in = jnp.where(idx == 0, fwd_recv[c - 1], fwd_recv[c])
            yc = stage_fn(chunk(c), x_in, mf_cl + ls)
            ys_f.append(jnp.where(f_act, yc, jnp.zeros_like(yc)))
            upd = lax.dynamic_update_index_in_dim(stash[c], x_in,
                                                  mf_cl % S, 0)
            new_stash.append(jnp.where(f_act, upd, stash[c]))
        # ---- backward lane (reads new_stash: the last stage turns a
        # microbatch around forward->backward within one tick) ----
        dxs = []
        gs2 = [list(g) for g in gs]
        ge2, gh2, lsum2 = list(ge), list(gh), lsum
        for c in range(v):
            ls = c * n + idx
            mb = t - 2 * (nv - 1) + ls  # microbatch in this chunk's backward
            b_act = jnp.logical_and(mb >= 0, mb < M)
            mb_cl = jnp.clip(mb, 0, M - 1)
            x_saved = lax.dynamic_index_in_dim(new_stash[c], mb_cl % S, 0,
                                               keepdims=False)
            if c == v - 1:
                # last chunk: the head+loss vjp seeds the cotangent on the
                # last stage; other stages take the ring-received cotangent
                lv, pull = jax.vjp(
                    lambda hp, h: head_loss_fn(hp, h, y_stack[mb_cl], mb_cl),
                    hparams, ys_f[v - 1])
                gh_c, seed = pull(jnp.ones_like(lv))
                on_last = jnp.logical_and(b_act, idx == n - 1)
                gh2 = [a + jnp.where(on_last, g, 0)
                       for a, g in zip(gh2, gh_c)]
                lsum2 = lsum2 + jnp.where(on_last, lv, jnp.zeros_like(lv))
                out_cot = jnp.where(idx == n - 1, seed, bwd_recv[v - 1])
            else:
                out_cot = jnp.where(idx == n - 1, bwd_recv[c + 1],
                                    bwd_recv[c])
            _, pull_s = jax.vjp(
                lambda ps, h: stage_fn(ps, h, mb_cl + ls), chunk(c), x_saved)
            gw, dx = pull_s(out_cot)
            gs2[c] = [a + jnp.where(b_act, g, 0) for a, g in zip(gs2[c], gw)]
            dx = jnp.where(b_act, dx, jnp.zeros_like(dx))
            if c == 0:
                # chunk 0 on stage 0 owns the embed: pull dx back through it
                _, pull_e = jax.vjp(
                    lambda ep: embed_fn(ep, x_stack[mb_cl], mb_cl), eparams)
                (ge_c,) = pull_e(dx)
                on_first = jnp.logical_and(b_act, idx == 0)
                ge2 = [a + jnp.where(on_first, g, 0)
                       for a, g in zip(ge2, ge_c)]
            dxs.append(dx)
        perm_f = [(i, (i + 1) % n) for i in range(n)]
        perm_b = [(i, (i - 1) % n) for i in range(n)]
        fwd_next = lax.ppermute(jnp.stack(ys_f), axis_name, perm_f)
        bwd_next = lax.ppermute(jnp.stack(dxs), axis_name, perm_b)
        return (fwd_next, bwd_next, new_stash, ge2, gs2, gh2, lsum2), None

    zrecv = jnp.zeros((v,) + zact.shape, zact.dtype)
    carry0 = (zrecv, zrecv,
              [jnp.zeros((S,) + zact.shape, zact.dtype) for _ in range(v)],
              [jnp.zeros_like(w) for w in eparams],
              [[jnp.zeros_like(w) for w in chunk(c)] for c in range(v)],
              [jnp.zeros_like(w) for w in hparams],
              jnp.float32(0.0))
    (_, _, _, ge, gs, gh, lsum), _ = lax.scan(tick, carry0, jnp.arange(T))
    gs_cat = [jnp.concatenate([gs[c][i] for c in range(v)])
              for i in range(len(sparams))]
    return lsum, ge, gs_cat, gh


class PipelineTrainer:
    """Fused pipeline-parallel trainer (optionally composed with data
    parallelism over a 'dp' axis, weight-sharded tensor parallelism over a
    'tp' axis, and the ZeRO-style sharded update over 'dp').

    `net` must expose `pipeline_split() -> (embed, cells, head)` where
    `cells` are structurally identical stateless HybridBlocks (transformer
    encoder layers — models/bert.py grows this method). Cell parameters are
    stacked layerwise into (n_layers, ...) arrays sharded over 'pp'
    (`_stack_order` permutes rows so each device's v interleaved chunks are
    contiguous); embed and head stay replicated over pp, with their
    gradients psum'd over 'pp' (only stage 0 / the last stage produce
    nonzero contributions — the psum is the sync that keeps the replicas
    identical).

    `schedule='1f1b'` (default, MXNET_TPU_PP_SCHEDULE) runs the
    bounded-memory hand-scheduled 1F1B program; `schedule='gpipe'` keeps the
    grad-of-scan transpose. `virtual_stages=v>1` (1F1B only) interleaves v
    layer chunks per device to shrink the pipeline bubble. Frozen
    (grad_req='null') embed/head/cell params skip their update lanes.

    Composition (docs/pipeline_parallel.md):
      - dp_axis:        grads pmean'd over dp (or reduce-scattered, below)
      - zero_update:    ZeRO sharded update over dp — stage buckets carry
                        per-stage (n_stages, padded) state sharded
                        P(pp, dp); requires dp_axis, excludes tp_axis
      - comm_dtype:     bf16/int8 wire for the zero reduce-scatter
      - tp_axis + tp_mode="sharded" (default): leaves with
                        Parameter.sharding specs over 'tp' are STORED
                        sharded (1/tp weight+state memory), all-gathered
                        once per step outside the differentiated region,
                        grads sliced back for the local update lane. The
                        full weight materializes on every rank each step —
                        layer size stays capped at one chip's HBM.
      - tp_axis + tp_mode="partitioned": compute-partitioned (Megatron)
                        TP inside the 1F1B tick body — weights stay
                        sharded forever, manual activation collectives at
                        the region boundaries (parallel/megatron.py).
                        Composes with zero_update (the optimizer state
                        gains a tp dim). `sequence_parallel=True`
                        additionally shards the layernorm/dropout/residual
                        regions along the sequence axis over the same tp
                        device group, turning boundary psums into
                        all_gather/psum_scatter pairs (docs/
                        tensor_parallel.md for the full rule table).

    One jit computes: embed -> schedule -> head -> loss -> backward ->
    collectives -> optimizer update. `loss` must be a mean-reduction
    callable (pred_raw, label_raw) -> scalar so microbatch splitting leaves
    the math identical to a full-batch step.
    """

    def __init__(self, net, loss, optimizer="sgd", optimizer_params=None,
                 mesh: Optional[Mesh] = None, num_microbatch: Optional[int] = None,
                 pp_axis: str = "pp", dp_axis: Optional[str] = None,
                 tp_axis: Optional[str] = None, tp_mode: str = "sharded",
                 sequence_parallel: bool = False,
                 dtype=None, remat: bool = True,
                 schedule: Optional[str] = None, virtual_stages: int = 1,
                 zero_update: Optional[bool] = None,
                 bucket_bytes: Optional[int] = None, comm_dtype=None):
        from .data_parallel import functional_optimizer, _make_apply_fn
        self.net = net
        self.loss = loss
        self.mesh = mesh if mesh is not None else current_mesh()
        self.n_stages = require_axis(self.mesh, pp_axis, "pipeline stages")
        self.pp_axis, self.dp_axis, self.tp_axis = pp_axis, dp_axis, tp_axis
        self.n_dp = require_axis(self.mesh, dp_axis, "data parallelism") \
            if dp_axis else 1
        self.n_tp = require_axis(self.mesh, tp_axis, "tensor parallelism") \
            if tp_axis else 1
        if tp_mode not in ("sharded", "partitioned"):
            raise MXNetError(f"unknown tp_mode {tp_mode!r}; use 'sharded' "
                             "(per-step weight gather) or 'partitioned' "
                             "(compute-partitioned Megatron collectives)")
        if tp_mode == "partitioned" and tp_axis is None:
            raise MXNetError("tp_mode='partitioned' requires a tp_axis")
        self.tp_mode = tp_mode
        self._partitioned = tp_axis is not None and tp_mode == "partitioned"
        self.sequence_parallel = bool(sequence_parallel)
        if self.sequence_parallel and not self._partitioned:
            raise MXNetError(
                "sequence_parallel shards the non-matmul regions over the "
                "tp device group; it requires tp_mode='partitioned'")
        self.remat = remat

        if schedule is None:
            schedule = env.get("MXNET_TPU_PP_SCHEDULE") or "1f1b"
        if schedule not in ("1f1b", "gpipe"):
            raise MXNetError(f"unknown pipeline schedule {schedule!r}; "
                             "use '1f1b' or 'gpipe'")
        self.schedule = schedule
        self.virtual_stages = int(virtual_stages)
        if self.virtual_stages < 1:
            raise MXNetError("virtual_stages must be >= 1")
        if self.virtual_stages > 1 and schedule != "1f1b":
            raise MXNetError("virtual_stages (interleaved schedule) "
                             "requires schedule='1f1b'")
        if self._partitioned and schedule != "1f1b":
            raise MXNetError(
                "tp_mode='partitioned' runs its manual collectives inside "
                "the 1F1B tick body; schedule='gpipe' (grad-of-scan) only "
                "supports weight-sharded tp")

        if not hasattr(net, "pipeline_split"):
            raise MXNetError(
                f"{type(net).__name__} has no pipeline_split(); implement it "
                "returning (embed_block, identical_cells, head_block)")
        embed, cells, head = net.pipeline_split()
        nv = self.n_stages * self.virtual_stages
        if len(cells) % nv != 0:
            raise MXNetError(
                f"{len(cells)} layers do not divide into {self.n_stages} "
                f"pipeline stages x {self.virtual_stages} virtual chunks")
        self.n_layers = len(cells)
        self.layers_per_stage = self.n_layers // self.n_stages

        def _plist(block):
            ps = list(block.collect_params().values())
            if any(p._data is None for p in ps):
                raise MXNetError("net has uninitialized parameters; run one "
                                 "eager forward before PipelineTrainer")
            return ps

        self._embed_plist = _plist(embed)
        self._head_plist = _plist(head)
        self._cell_plists = [_plist(c) for c in cells]
        ref = self._cell_plists[0]
        for j, cp in enumerate(self._cell_plists[1:], 1):
            if len(cp) != len(ref) or any(
                    a._data._data.shape != b._data._data.shape or
                    a._data._data.dtype != b._data._data.dtype
                    for a, b in zip(cp, ref)):
                raise MXNetError(f"cell {j} is not structurally identical to "
                                 "cell 0; pipeline stages must be homogeneous")
        # frozen (grad_req='null') params skip their update lanes; a stacked
        # cell leaf must be uniformly frozen across cells (one update lane
        # serves all layers of the leaf)
        self._tr_e = [p.grad_req != "null" for p in self._embed_plist]
        self._tr_h = [p.grad_req != "null" for p in self._head_plist]
        self._tr_s = [ref[i].grad_req != "null" for i in range(len(ref))]
        for cp in self._cell_plists[1:]:
            for i, p in enumerate(cp):
                if (p.grad_req != "null") != self._tr_s[i]:
                    raise MXNetError(
                        f"cell param {ref[i].name!r} is frozen in some "
                        "layers but not others; freeze a stacked leaf "
                        "uniformly across cells")

        # compute-partitioned TP: structural layer plans decide each leaf's
        # layout (megatron.plan_*); Parameter.sharding specs are NOT read
        # (they may carry auto-sharding specs naming other axes)
        if self._partitioned:
            self._eplan = _mg.plan_embed(embed, self._embed_plist, self.n_tp)
            self._cplan = _mg.plan_cell(cells[0], ref, self.n_tp)
            self._hplan = _mg.plan_head(head, self._head_plist, self.n_tp)
            self._lay_e = self._eplan.layouts
            self._lay_s = self._cplan.layouts
            self._lay_h = self._hplan.layouts
            self._tp_e = [_mg.view_shard_dim(l) for l in self._lay_e]
            self._tp_s = [_mg.view_shard_dim(l) for l in self._lay_s]
            self._tp_h = [_mg.view_shard_dim(l) for l in self._lay_h]
            self._validate_partitioned_loss()
        # manual weight-sharded TP: which dim of each leaf is sharded
        elif tp_axis is not None:
            self._tp_e = [tp_shard_dim(p.sharding, tp_axis)
                          for p in self._embed_plist]
            self._tp_h = [tp_shard_dim(p.sharding, tp_axis)
                          for p in self._head_plist]
            self._tp_s = [tp_shard_dim(ref[i].sharding, tp_axis)
                          for i in range(len(ref))]
            for cp in self._cell_plists[1:]:
                for i, p in enumerate(cp):
                    if tp_shard_dim(p.sharding, tp_axis) != self._tp_s[i]:
                        raise MXNetError(
                            f"cell param {ref[i].name!r} carries different "
                            "tp specs across cells; stacked leaves must "
                            "shard uniformly")
            for plist, dims in ((self._embed_plist, self._tp_e),
                                (self._head_plist, self._tp_h),
                                (ref, self._tp_s)):
                for p, d in zip(plist, dims):
                    if d is not None and \
                            p._data._data.shape[d] % self.n_tp != 0:
                        raise MXNetError(
                            f"{p.name!r} dim {d} ({p._data._data.shape[d]}) "
                            f"does not divide by tp={self.n_tp}")
        else:
            self._tp_e = [None] * len(self._embed_plist)
            self._tp_h = [None] * len(self._head_plist)
            self._tp_s = [None] * len(ref)

        self._embed_apply = _make_apply_fn(embed, self._embed_plist, train=True)
        self._cell_apply = _make_apply_fn(cells[0], ref, train=True)
        self._head_apply = _make_apply_fn(head, self._head_plist, train=True)

        self.compute_dtype = None
        if dtype is not None and jnp.dtype(dtype) != jnp.dtype(jnp.float32):
            self.compute_dtype = jnp.dtype(dtype)
            if self.compute_dtype != jnp.dtype(jnp.bfloat16):
                raise MXNetError("PipelineTrainer supports float32/bfloat16, "
                                 f"got {dtype!r}")

        self.optimizer = optimizer if isinstance(optimizer, opt_mod.Optimizer) \
            else opt_mod.create(optimizer, **(optimizer_params or {}))
        self._init_fn, self._update_fn = functional_optimizer(self.optimizer)

        if num_microbatch is None:
            num_microbatch = self.n_stages
        self.num_microbatch = num_microbatch

        if zero_update is None:
            zero_update = bool(env.get("MXNET_TPU_ZERO"))
        self._zero = bool(zero_update)
        self._bucket_bytes = int(bucket_bytes if bucket_bytes is not None
                                 else env.get("MXNET_TPU_BUCKET_BYTES"))
        if comm_dtype is None:
            comm_dtype = env.get("MXNET_TPU_COMM_DTYPE") or None
        self._comm_dtype = _zero.canonical_comm_dtype(comm_dtype) \
            if self._zero else None
        if self._zero:
            self._validate_zero()
        if tp_axis is not None:
            from ..optimizer.optimizer import LAMB, LARS
            if isinstance(self.optimizer, (LAMB, LARS)):
                raise MXNetError(
                    f"tensor parallelism does not support "
                    f"{type(self.optimizer).__name__}: per-tensor "
                    "trust-ratio norms are wrong on tp shards")

        # interleaved stacking: global row s*L_dev + c*Lc + j holds the
        # params of logical stage c*pp+s, layer j (identity when v == 1)
        Ld, v = self.layers_per_stage, self.virtual_stages
        Lc = Ld // v
        self._stack_order = [(c * self.n_stages + s) * Lc + j
                             for s in range(self.n_stages)
                             for c in range(v) for j in range(Lc)]

        rep = NamedSharding(self.mesh, P())

        def _leaf_sharding(dim, ndim, stacked):
            spec = [None] * (ndim + (1 if stacked else 0))
            if stacked:
                spec[0] = pp_axis
            if dim is not None:
                spec[dim + (1 if stacked else 0)] = tp_axis
            return NamedSharding(self.mesh, P(*spec))

        # storage (VIEW) shapes: identical to the logical shapes except for
        # partitioned leaves with blocked layouts (the fused qkv's (3C, C)
        # stores as (3, C, C) so the tp shard dim is a plain array dim) —
        # tp-degree-independent globals, which is what lets elastic restore
        # reshard tp=2 -> tp=4 with a plain reinstall
        if self._partitioned:
            self._view_e = [
                _mg.view_shape(p._data._data.shape, l)
                for p, l in zip(self._embed_plist, self._lay_e)]
            self._view_h = [
                _mg.view_shape(p._data._data.shape, l)
                for p, l in zip(self._head_plist, self._lay_h)]
            self._view_s = [
                _mg.view_shape(ref[i]._data._data.shape, l)
                for i, l in enumerate(self._lay_s)]
            for views, dims, plist in (
                    (self._view_e, self._tp_e, self._embed_plist),
                    (self._view_h, self._tp_h, self._head_plist),
                    (self._view_s, self._tp_s, ref)):
                for vshape, d, p in zip(views, dims, plist):
                    if d is not None and vshape[d] % self.n_tp != 0:
                        raise MXNetError(
                            f"{p.name!r} partitioned dim {d} "
                            f"({vshape[d]}) does not divide by "
                            f"tp={self.n_tp}")
        else:
            self._view_e = [tuple(p._data._data.shape)
                            for p in self._embed_plist]
            self._view_h = [tuple(p._data._data.shape)
                            for p in self._head_plist]
            self._view_s = [tuple(ref[i]._data._data.shape)
                            for i in range(len(ref))]
        self._e_sh = [_leaf_sharding(d, len(v), False)
                      for v, d in zip(self._view_e, self._tp_e)]
        self._h_sh = [_leaf_sharding(d, len(v), False)
                      for v, d in zip(self._view_h, self._tp_h)]
        self._s_sh = [_leaf_sharding(d, len(v), True)
                      for v, d in zip(self._view_s, self._tp_s)]
        self._e_raw = [
            jax.device_put(
                jnp.array(p._data._data, copy=True).reshape(v), sh)
            for p, v, sh in zip(self._embed_plist, self._view_e, self._e_sh)]
        self._h_raw = [
            jax.device_put(
                jnp.array(p._data._data, copy=True).reshape(v), sh)
            for p, v, sh in zip(self._head_plist, self._view_h, self._h_sh)]
        # layerwise stack in schedule order: leaf i -> (n_layers, ...)
        self._s_raw = [
            jax.device_put(
                jnp.stack([self._cell_plists[m][i]._data._data
                           for m in self._stack_order])
                .reshape((self.n_layers,) + self._view_s[i]), sh)
            for i, sh in enumerate(self._s_sh)]
        # weight-decay indices follow the optimizer's param-idx convention:
        # embed params first, then the stacked cell leaves, then head
        nE, nS = len(self._e_raw), len(self._s_raw)
        self._wd_e = [self.optimizer._get_wd(i) for i in range(nE)]
        self._wd_s = [self.optimizer._get_wd(nE + i) for i in range(nS)]
        self._wd_h = [self.optimizer._get_wd(nE + nS + i)
                      for i in range(len(self._h_raw))]
        if self._zero:
            self._init_zero_state()
        else:
            def _state(w, sh, tr):
                if not tr:
                    return ()
                return jax.tree_util.tree_map(
                    lambda l: jax.device_put(l, sh), self._init_fn(w))
            self._opt_e = [_state(w, sh, tr) for w, sh, tr in
                           zip(self._e_raw, self._e_sh, self._tr_e)]
            self._opt_h = [_state(w, sh, tr) for w, sh, tr in
                           zip(self._h_raw, self._h_sh, self._tr_h)]
            self._opt_s = [_state(w, sh, tr) for w, sh, tr in
                           zip(self._s_raw, self._s_sh, self._tr_s)]
        self._t = 0
        # bounded in-flight dispatch window (engine/async_feed), same
        # contract as DataParallelTrainer: step() stays non-blocking
        self._window = _feed.DispatchWindow(name="pp")
        self._comm_cache = {}   # sig -> (ppermute bytes, calls)
        self._rs_bytes = None
        self._ag_bytes = None
        self._opt_bytes = None
        # process-wide engine-cache key base: N trainers over one model
        # structure and configuration share compiled step artifacts; any
        # change to schedule/microbatching/parallel axes/zero/precision
        # compiles apart (docs/compilation.md "fused-step fingerprints")
        self._step_key_base = (
            "pp_step",
            _engine.structural_fingerprint(net),
            _engine.config_fingerprint(
                optimizer=type(self.optimizer).__name__,
                opt_conf=tuple(sorted(
                    (k, repr(v)) for k, v in vars(self.optimizer).items()
                    if isinstance(v, (int, float, bool, str, type(None))))),
                wds=tuple(float(w) for w in
                          self._wd_e + self._wd_s + self._wd_h),
                loss=self.loss,
                mesh=tuple(sorted(dict(self.mesh.shape).items())),
                axis_order=tuple(self.mesh.axis_names),
                devices=tuple(int(d.id) for d in self.mesh.devices.flat),
                pp_axis=pp_axis, dp_axis=dp_axis, tp_axis=tp_axis,
                schedule=self.schedule,
                virtual_stages=self.virtual_stages,
                num_microbatch=self.num_microbatch,
                remat=self.remat,
                trainable=(tuple(self._tr_e), tuple(self._tr_s),
                           tuple(self._tr_h)),
                tp_dims=(tuple(self._tp_e), tuple(self._tp_s),
                         tuple(self._tp_h)),
                tp_mode=self.tp_mode,
                sequence_parallel=self.sequence_parallel,
                tp_layouts=((tuple(self._lay_e), tuple(self._lay_s),
                             tuple(self._lay_h))
                            if self._partitioned else None),
                compute_dtype=str(self.compute_dtype),
                zero=self._zero,
                bucket_bytes=self._bucket_bytes if self._zero else None,
                comm_dtype=self._comm_dtype))
        self._program = StepProgram(
            f"pp.step[{type(self.net).__name__}]", self._step_key_base)

    def _validate_partitioned_loss(self):
        """The partitioned head FUSES the decoder matmul into the
        vocab-parallel cross-entropy (the full-vocab logits are never
        materialized), so the trainer must know the loss IS mean token
        cross-entropy — any other callable would silently compute the
        wrong thing against the weight-sharded oracle."""
        from ..gluon.loss import SoftmaxCrossEntropyLoss
        lo = self.loss
        if isinstance(lo, SoftmaxCrossEntropyLoss):
            if (getattr(lo, "_sparse_label", True)
                    and not getattr(lo, "_from_logits", False)
                    and getattr(lo, "_axis", -1) in (-1,)
                    and getattr(lo, "_weight", None) is None):
                return
            raise MXNetError(
                "tp_mode='partitioned' fuses the LM head into a "
                "vocab-parallel softmax cross-entropy; "
                "SoftmaxCrossEntropyLoss must use sparse_label=True, "
                "from_logits=False, axis=-1, weight=None")
        if getattr(lo, "__name__", "") == "token_cross_entropy":
            return
        raise MXNetError(
            "tp_mode='partitioned' supports mean token cross-entropy "
            "losses only (gluon SoftmaxCrossEntropyLoss or "
            "recipes.moe.token_cross_entropy); got "
            f"{type(lo).__name__}")

    # -- ZeRO-over-dp composition -------------------------------------------
    def _validate_zero(self):
        if self.dp_axis is None:
            raise MXNetError("zero_update requires a dp_axis: the sharded "
                             "update distributes over data-parallel replicas")
        if self.tp_axis is not None and self.tp_mode != "partitioned":
            raise MXNetError(
                "zero_update and weight-sharded tp_axis do not compose in "
                "PipelineTrainer (the gathered weights would defeat the "
                "sharded state); tp_mode='partitioned' composes — its "
                "optimizer state gains a tp dim")
        from ..optimizer.optimizer import LAMB, LARS
        if isinstance(self.optimizer, (LAMB, LARS)):
            raise MXNetError(
                f"zero_update does not support "
                f"{type(self.optimizer).__name__}: its per-tensor "
                "trust-ratio norms do not decompose over flat bucket "
                "shards; use sgd/adam/adamw/...")

    def _init_zero_state(self):
        """Fusion-bucket plans + dp-sharded optimizer state for the three
        parameter groups. Embed/head buckets mirror the dp trainer exactly
        ((padded,) state sharded P(dp)); stage buckets are planned over the
        LOCAL stacked shapes (identical plan on every stage) with per-stage
        state stacked into (n_stages, padded) arrays sharded P(pp, dp) —
        each (pp, dp) group holds 1/(dp) of its own stage's state."""
        if self._partitioned:
            self._init_zero_state_partitioned()
            return
        dp_sh = NamedSharding(self.mesh, P(self.dp_axis))
        stg_sh = NamedSharding(self.mesh, P(self.pp_axis, self.dp_axis))
        ndp, Ld = self.n_dp, self.layers_per_stage

        def _plan(params, trainables, shapes=None):
            entries = [(i, shapes[i] if shapes else w.shape, w.dtype)
                       for i, (w, tr) in enumerate(zip(params, trainables))
                       if tr and jnp.issubdtype(w.dtype, jnp.floating)]
            return _zero.plan_buckets(entries, ndp, self._bucket_bytes)

        def _flat_carry(plan, params, wds):
            carry = []
            for b in plan:
                flat_w = _zero.flatten_bucket(b, params)
                state = opt_mod.init_functional_state(self._init_fn, flat_w,
                                                      sharding=dp_sh)
                wd_dev = jax.device_put(_zero.wd_vector(b, wds), dp_sh)
                carry.append((wd_dev, state))
            return tuple(carry)

        self._zplan_e = _plan(self._e_raw, self._tr_e)
        self._zplan_h = _plan(self._h_raw, self._tr_h)
        self._opt_e = _flat_carry(self._zplan_e, self._e_raw, self._wd_e)
        self._opt_h = _flat_carry(self._zplan_h, self._h_raw, self._wd_h)
        local_shapes = [(Ld,) + w.shape[1:] for w in self._s_raw]
        self._zplan_s = _plan(self._s_raw, self._tr_s, shapes=local_shapes)
        carry_s = []
        for b in self._zplan_s:
            rows = [_zero.flatten_bucket(
                        b, [w[s * Ld:(s + 1) * Ld] for w in self._s_raw])
                    for s in range(self.n_stages)]
            w_glob = jax.device_put(jnp.stack(rows), stg_sh)
            state = opt_mod.init_functional_state(self._init_fn, w_glob,
                                                  sharding=stg_sh)
            wd_dev = jax.device_put(_zero.wd_vector(b, self._wd_s), dp_sh)
            carry_s.append((wd_dev, state))
        self._opt_s = tuple(carry_s)

    def _init_zero_state_partitioned(self):
        """ZeRO over dp composed with compute-partitioned tp: every
        (pp, tp) rank updates only its OWN weight shard, so the bucket
        plans cover the tp-LOCAL view shapes and the flat state gains a
        leading tp dim — embed/head (n_tp, padded) sharded P(tp, dp),
        stage (n_stages, n_tp, padded) sharded P(pp, tp, dp). The wd
        vectors depend only on the leaf index (identical across tp ranks)
        and stay P(dp)."""
        dp_sh = NamedSharding(self.mesh, P(self.dp_axis))
        tp_sh = NamedSharding(self.mesh, P(self.tp_axis, self.dp_axis))
        stg_sh = NamedSharding(
            self.mesh, P(self.pp_axis, self.tp_axis, self.dp_axis))
        ndp, ntp, Ld = self.n_dp, self.n_tp, self.layers_per_stage

        def _local(shape, d):
            if d is None:
                return tuple(shape)
            return tuple(shape[:d]) + (shape[d] // ntp,) \
                + tuple(shape[d + 1:])

        def _tp_slice(w, d, r):
            if d is None:
                return w
            sz = w.shape[d] // ntp
            return lax.slice_in_dim(w, r * sz, (r + 1) * sz, axis=d)

        def _plan(params, trainables, dims, stacked=False):
            entries = []
            for i, (w, tr, d) in enumerate(zip(params, trainables, dims)):
                if not (tr and jnp.issubdtype(w.dtype, jnp.floating)):
                    continue
                if stacked:
                    shape = _local((Ld,) + w.shape[1:],
                                   d + 1 if d is not None else None)
                else:
                    shape = _local(w.shape, d)
                entries.append((i, shape, w.dtype))
            return _zero.plan_buckets(entries, ndp, self._bucket_bytes)

        self._zplan_e = _plan(self._e_raw, self._tr_e, self._tp_e)
        self._zplan_h = _plan(self._h_raw, self._tr_h, self._tp_h)

        def _flat_tp(plan, params, dims, wds):
            carry = []
            for b in plan:
                rows = [_zero.flatten_bucket(
                            b, [_tp_slice(w, d, r)
                                for w, d in zip(params, dims)])
                        for r in range(ntp)]
                w_glob = jax.device_put(jnp.stack(rows), tp_sh)
                state = opt_mod.init_functional_state(self._init_fn, w_glob,
                                                      sharding=tp_sh)
                wd_dev = jax.device_put(_zero.wd_vector(b, wds), dp_sh)
                carry.append((wd_dev, state))
            return tuple(carry)

        self._opt_e = _flat_tp(self._zplan_e, self._e_raw, self._tp_e,
                               self._wd_e)
        self._opt_h = _flat_tp(self._zplan_h, self._h_raw, self._tp_h,
                               self._wd_h)
        self._zplan_s = _plan(self._s_raw, self._tr_s, self._tp_s,
                              stacked=True)
        carry_s = []
        for b in self._zplan_s:
            rows = [jnp.stack([
                        _zero.flatten_bucket(
                            b, [_tp_slice(w[s * Ld:(s + 1) * Ld],
                                          d + 1 if d is not None else None,
                                          r)
                                for w, d in zip(self._s_raw, self._tp_s)])
                        for r in range(ntp)])
                    for s in range(self.n_stages)]
            w_glob = jax.device_put(jnp.stack(rows), stg_sh)
            state = opt_mod.init_functional_state(self._init_fn, w_glob,
                                                  sharding=stg_sh)
            wd_dev = jax.device_put(_zero.wd_vector(b, self._wd_s), dp_sh)
            carry_s.append((wd_dev, state))
        self._opt_s = tuple(carry_s)

    # ------------------------------------------------------------------
    def _loss_raw(self, pred_raw, label_raw):
        from .data_parallel import DataParallelTrainer
        return DataParallelTrainer._loss_raw(self, pred_raw, label_raw)

    def _build_step(self):
        embed_apply = self._embed_apply
        cell_apply = self._cell_apply
        head_apply = self._head_apply
        update_fn = self._update_fn
        loss_raw = self._loss_raw
        mesh = self.mesh
        ppax, dpax, tpax = self.pp_axis, self.dp_axis, self.tp_axis
        n_stages, M = self.n_stages, self.num_microbatch
        v = self.virtual_stages
        wd_e, wd_s, wd_h = self._wd_e, self._wd_s, self._wd_h
        tr_e, tr_s, tr_h = self._tr_e, self._tr_s, self._tr_h
        tp_e, tp_s, tp_h = self._tp_e, self._tp_s, self._tp_h
        sched, remat = self.schedule, self.remat
        zero, ndp, comm = self._zero, self.n_dp, self._comm_dtype
        cdt = self.compute_dtype
        part, ntp = self._partitioned, self.n_tp
        if part:
            cfg = _mg.PartitionConfig(
                axis=tpax, n_tp=ntp,
                sp=self.sequence_parallel and ntp > 1)
            eplan, cplan, hplan = self._eplan, self._cplan, self._hplan
            lay_e, lay_s, lay_h = self._lay_e, self._lay_s, self._lay_h

        def _low(a):
            if cdt is not None and jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(cdt)
            return a

        def _no_aux(out_aux, what):
            out, aux = out_aux
            if aux:
                raise MXNetError(
                    f"pipeline {what} emits mutable aux state (BN running "
                    "stats); pipeline stages must be stateless")
            return out

        def body(eparams, sparams, hparams, opt_e, opt_s, opt_h,
                 key, x, y, lr, t):
            # x/y: (M, mb_local, T...) — microbatch stack, batch dim already
            # dp-sliced by shard_map. sparams leaves: (L, ...) local layers.
            idx = lax.axis_index(ppax)
            kk = jax.random.wrap_key_data(key.astype(jnp.uint32),
                                          impl="threefry2x32")
            kk = jax.random.fold_in(kk, idx)
            if dpax is not None:
                kk = jax.random.fold_in(kk, lax.axis_index(dpax))
            # deliberately NOT folded over tp: ranks must draw identical
            # dropout masks so the replicated compute (and the grads being
            # sliced back per rank) stays bitwise identical

            # weight-sharded tp leaves: gather to full size ONCE per step,
            # OUTSIDE the differentiated region — grads w.r.t. the gathered
            # arrays come out rank-identical, no gradient collective needed.
            # (partitioned tp never gathers: the programs below consume the
            # local view shards directly)
            if tpax is not None and not part:
                ep_f = [gather_tp(w, d, tpax) if d is not None else w
                        for w, d in zip(eparams, tp_e)]
                hp_f = [gather_tp(w, d, tpax) if d is not None else w
                        for w, d in zip(hparams, tp_h)]
                sp_f = [gather_tp(w, d + 1, tpax) if d is not None else w
                        for w, d in zip(sparams, tp_s)]
            else:
                ep_f, sp_f, hp_f = eparams, sparams, hparams

            if part:
                def stage_fn(params_local, h, tick):
                    # same (tick, layer) key schedule as the oracle path so
                    # dropout draws line up microbatch-for-microbatch
                    kt = jax.random.fold_in(kk, tick)
                    low = [_low(q) for q in params_local]
                    nloc = params_local[0].shape[0]

                    def cell_body(hc, xs):
                        lp, li = xs
                        klayer = jax.random.fold_in(kt, li)
                        return _mg.cell_forward(cplan, cfg, lp, hc,
                                                klayer), None
                    out, _ = lax.scan(cell_body, h, (low, jnp.arange(nloc)))
                    return out
            else:
                def stage_fn(params_local, h, tick):
                    # fold (tick, layer) so each microbatch draws fresh
                    # dropout masks — tick advances per microbatch in the
                    # schedule
                    kt = jax.random.fold_in(kk, tick)
                    low = [_low(q) for q in params_local]
                    nloc = params_local[0].shape[0]

                    def cell_body(hc, xs):
                        lp, li = xs
                        klayer = jax.random.key_data(
                            jax.random.fold_in(kt, li))
                        return _no_aux(cell_apply(klayer, lp, hc),
                                       "cell"), None
                    out, _ = lax.scan(cell_body, h, (low, jnp.arange(nloc)))
                    return out

            if sched == "1f1b":
                if part:
                    def embed_mb(ep, xm, m):
                        k_e = jax.random.fold_in(
                            jax.random.fold_in(kk, 10_000), m)
                        return _mg.embed_forward(
                            eplan, cfg, [_low(p) for p in ep], xm, k_e)

                    def head_loss_mb(hp, h, ym, m):
                        k_h = jax.random.fold_in(
                            jax.random.fold_in(kk, 10_001), m)
                        return _mg.head_loss_forward(
                            hplan, cfg, [_low(p) for p in hp], h, ym, k_h)
                else:
                    def embed_mb(ep, xm, m):
                        k_e = jax.random.key_data(jax.random.fold_in(
                            jax.random.fold_in(kk, 10_000), m))
                        return _no_aux(embed_apply(k_e,
                                                   [_low(p) for p in ep],
                                                   xm), "embed block")

                    def head_loss_mb(hp, h, ym, m):
                        k_h = jax.random.key_data(jax.random.fold_in(
                            jax.random.fold_in(kk, 10_001), m))
                        logits = _no_aux(head_apply(k_h,
                                                    [_low(p) for p in hp],
                                                    h), "head block")
                        return loss_raw(logits, ym)

                lsum, ge, gs, gh = schedule_1f1b(
                    embed_mb, stage_fn, head_loss_mb, ep_f, sp_f, hp_f,
                    x, y, axis_name=ppax, n_chunks=v)
                # microbatch sums -> batch means (equal microbatch sizes)
                lossv = lsum / M
                ge = [g / M for g in ge]
                gs = [g / M for g in gs]
                gh = [g / M for g in gh]
            else:
                def lossf(ep, sp, hp):
                    k_e = jax.random.key_data(
                        jax.random.fold_in(kk, 10_000))
                    k_h = jax.random.key_data(
                        jax.random.fold_in(kk, 10_001))
                    xf = x.reshape((-1,) + x.shape[2:])
                    h = _no_aux(embed_apply(k_e, [_low(p) for p in ep], xf),
                                "embed block")
                    h = h.reshape((M, -1) + h.shape[1:])
                    out = pipeline_apply(stage_fn, sp, h, axis_name=ppax,
                                         remat=remat)
                    of = out.reshape((-1,) + out.shape[2:])
                    logits = _no_aux(head_apply(k_h, [_low(p) for p in hp],
                                                of), "head block")
                    lossv = loss_raw(logits, y.reshape((-1,) + y.shape[2:]))
                    # only the last stage saw real activations. The mask
                    # must be a plain where — NOT a psum: collectives inside
                    # the differentiated scalar would re-psum the per-device
                    # cotangent seeds and inflate every gradient by
                    # n_stages.
                    return jnp.where(idx == n_stages - 1, lossv, 0.0)

                lossv, (ge, gs, gh) = jax.value_and_grad(
                    lossf, argnums=(0, 1, 2))(ep_f, sp_f, hp_f)
            # loss reporting + replica sync happen OUTSIDE the grad: psum
            # selects the last stage's loss and broadcasts it; embed grads
            # live on stage 0 and head grads on the last stage, so psum over
            # pp is the sync that keeps the replicated copies identical.
            lossv = lax.psum(lossv, ppax)
            if dpax is not None:
                lossv = lax.pmean(lossv, dpax)
            ge = [lax.psum(g, ppax) for g in ge]
            gh = [lax.psum(g, ppax) for g in gh]
            if dpax is not None and not zero:
                # zero mode skips the pmean: the bucket reduce-scatter (+/ndp)
                # below IS the dp mean
                ge = [lax.pmean(g, dpax) for g in ge]
                gs = [lax.pmean(g, dpax) for g in gs]
                gh = [lax.pmean(g, dpax) for g in gh]
            if tpax is not None and not part:
                # grads are rank-identical over tp; each rank updates its
                # own weight shard from its slice — no collective
                ge = [slice_tp(g, d, tpax) if d is not None else g
                      for g, d in zip(ge, tp_e)]
                gh = [slice_tp(g, d, tpax) if d is not None else g
                      for g, d in zip(gh, tp_h)]
                gs = [slice_tp(g, d + 1, tpax) if d is not None else g
                      for g, d in zip(gs, tp_s)]
            elif part and ntp > 1:
                # partial-sum convention (megatron.py docstring): each
                # rank's grad for a REPLICATED leaf is a partial term; one
                # psum over tp completes it. tp-sharded leaves' grads are
                # already the exact local shard — no collective. This runs
                # OUTSIDE the differentiated region, so plain psum is safe.
                ge = [lax.psum(g, tpax) if l is None else g
                      for g, l in zip(ge, lay_e)]
                gh = [lax.psum(g, tpax) if l is None else g
                      for g, l in zip(gh, lay_h)]
                gs = [lax.psum(g, tpax) if l is None else g
                      for g, l in zip(gs, lay_s)]

            if zero:
                pos = lax.axis_index(dpax)

                def zupd(plan, grads, params, carry, lead):
                    # `lead` = number of leading singleton dims carried by
                    # the optimizer-state leaves relative to the plan's flat
                    # buckets: stage states carry the per-stage dim, and the
                    # partitioned-TP variant adds a tp-rank dim in front of
                    # everything (state was built per tp rank over LOCAL view
                    # shapes). Strip them for the update, re-add after.
                    new_p, new_c = list(params), []
                    for b, (wd_vec, st) in zip(plan, carry):
                        stl = st
                        for _ in range(lead):
                            stl = jax.tree_util.tree_map(
                                lambda a: a[0], stl)
                        flat_g = _zero.flatten_bucket(b, grads)
                        g_sh = _zero.reduce_scatter_bucket(
                            flat_g, dpax, ndp, comm) / ndp
                        w_sh = _zero.shard_slice(
                            b, _zero.flatten_bucket(b, params), pos)
                        w2, s2 = update_fn(g_sh.astype(w_sh.dtype), w_sh,
                                           stl, t, lr, wd_vec)
                        full = _zero.all_gather_bucket(
                            w2.astype(w_sh.dtype), dpax)
                        for i, arr in _zero.unflatten_bucket(b, full):
                            new_p[i] = arr.astype(params[i].dtype)
                        for _ in range(lead):
                            s2 = jax.tree_util.tree_map(
                                lambda a: a[None], s2)
                        new_c.append((wd_vec, s2))
                    return new_p, tuple(new_c)

                lead_eh = 1 if part else 0
                eparams, opt_e = zupd(self._zplan_e, ge, eparams, opt_e,
                                      lead_eh)
                hparams, opt_h = zupd(self._zplan_h, gh, hparams, opt_h,
                                      lead_eh)
                sparams, opt_s = zupd(self._zplan_s, gs, sparams, opt_s,
                                      lead_eh + 1)
            else:
                def upd(grads, params, states, wds, trainables):
                    new_p, new_s = [], []
                    for g, w, s, wd, tr in zip(grads, params, states, wds,
                                               trainables):
                        if not tr:
                            new_p.append(w)
                            new_s.append(s)
                            continue
                        w2, s2 = update_fn(g, w, s, t, lr, jnp.float32(wd))
                        new_p.append(w2.astype(w.dtype))
                        new_s.append(s2)
                    return new_p, new_s

                eparams, opt_e = upd(ge, eparams, opt_e, wd_e, tr_e)
                sparams, opt_s = upd(gs, sparams, opt_s, wd_s, tr_s)
                hparams, opt_h = upd(gh, hparams, opt_h, wd_h, tr_h)
            return eparams, sparams, hparams, opt_e, opt_s, opt_h, lossv

        e_in = [sh.spec for sh in self._e_sh]
        s_in = [sh.spec for sh in self._s_sh]
        h_in = [sh.spec for sh in self._h_sh]
        if zero and self._partitioned:
            # partitioned state leaves carry a leading tp-rank dim (plans
            # ran over tp-LOCAL view shapes); wd vectors stay per-dp-shard
            opt_e_in = tuple(
                (P(dpax), jax.tree_util.tree_map(
                    lambda _: P(tpax, dpax), st))
                for (_, st) in self._opt_e)
            opt_h_in = tuple(
                (P(dpax), jax.tree_util.tree_map(
                    lambda _: P(tpax, dpax), st))
                for (_, st) in self._opt_h)
            opt_s_in = tuple(
                (P(dpax), jax.tree_util.tree_map(
                    lambda _: P(ppax, tpax, dpax), st))
                for (_, st) in self._opt_s)
        elif zero:
            opt_e_in = tuple(
                (P(dpax), jax.tree_util.tree_map(lambda _: P(dpax), st))
                for (_, st) in self._opt_e)
            opt_h_in = tuple(
                (P(dpax), jax.tree_util.tree_map(lambda _: P(dpax), st))
                for (_, st) in self._opt_h)
            opt_s_in = tuple(
                (P(dpax), jax.tree_util.tree_map(lambda _: P(ppax, dpax), st))
                for (_, st) in self._opt_s)
        else:
            opt_e_in, opt_s_in, opt_h_in = e_in, s_in, h_in
        data = P(None, dpax) if dpax is not None else P(None)
        rep = P()
        return _zero.shard_map_compat(
            body, mesh=mesh,
            in_specs=(e_in, s_in, h_in, opt_e_in, opt_s_in, opt_h_in,
                      rep, data, data, rep, rep),
            out_specs=(e_in, s_in, h_in, opt_e_in, opt_s_in, opt_h_in, rep))

    def step(self, x, y):
        """One fused pipeline-parallel training step on a global batch."""
        xr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yr = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        M = self.num_microbatch
        B = xr.shape[0]
        # the loss is a mean: grads are already batch-normalized (same
        # contract as DataParallelTrainer.step, data_parallel.py)
        self.optimizer.rescale_grad = 1.0
        if B % (M * self.n_dp) != 0:
            raise MXNetError(
                f"batch {B} must divide by num_microbatch*dp = {M}*{self.n_dp}")
        if (self._partitioned and self.sequence_parallel and self.n_tp > 1
                and xr.ndim >= 2 and xr.shape[1] % self.n_tp != 0):
            raise MXNetError(
                f"sequence_parallel shards the sequence axis over tp: "
                f"seq_len {xr.shape[1]} must divide by n_tp={self.n_tp}")
        xr = xr.reshape((M, B // M) + xr.shape[1:])
        yr = yr.reshape((M, B // M) + yr.shape[1:])
        sig = (xr.shape, str(xr.dtype), yr.shape, str(yr.dtype))
        # engine cache owns the executable: same-config trainers share one
        # compile (engine.cache_stats()["compiles"] stays flat on the 2nd)
        fn = self._program.get(
            (sig,),
            lambda: jax.jit(self._build_step(),
                            donate_argnums=(0, 1, 2, 3, 4, 5)))
        self._t += 1
        self.optimizer.num_update = self._t
        lr = _np.float32(self.optimizer.learning_rate)
        key = next_step_key(host=False)   # single-process trainer
        data = P(None, self.dp_axis) if self.dp_axis else P(None)
        xr = jax.device_put(xr, NamedSharding(
            self.mesh, P(*data, *([None] * (xr.ndim - 2)))))
        yr = jax.device_put(yr, NamedSharding(
            self.mesh, P(*data, *([None] * (yr.ndim - 2)))))
        # explicit placement of the per-step scalars (sanitize mode's
        # transfer guard rejects implicit numpy->device uploads)
        key, lr, t_in = jax.device_put(
            (key, lr, _np.float32(self._t)),
            NamedSharding(self.mesh, P()))
        call_args = (self._e_raw, self._s_raw, self._h_raw, self._opt_e,
                     self._opt_s, self._opt_h, key, xr, yr, lr, t_in)
        self._program.capture_cost(sig, fn, *call_args, kind="pp_step")
        with _telem.annotate("mx.pp.step"), _sanitize.guard():
            (self._e_raw, self._s_raw, self._h_raw, self._opt_e, self._opt_s,
             self._opt_h, lossv) = fn(*call_args)
        # non-blocking dispatch + backpressure on the (i-K)th step;
        # telemetry after admission (completion-paced, sync-free)
        self._window.admit(lossv)
        if _telem._ENABLED:
            self._record_telemetry(sig, B)
        return _feed.PendingScalar(lossv)

    # -- telemetry -----------------------------------------------------------
    def _ppermute_stats(self, sig):
        """Per-step activation-hop volume of the schedule's ppermute rings
        (per-replica wire bytes, both directions). One activation hops
        M + pp·v − 1 ticks per direction under GPipe's scan (+ transpose)
        and M + 2(pp·v − 1) under 1F1B; the interleaved variant moves a
        v-stack per hop. Shapes come from an abstract eval of the embed —
        no device work, cached per signature."""
        st = self._comm_cache.get(sig)
        if st is None:
            x_shape, x_dtype = sig[0], sig[1]
            out, _ = jax.eval_shape(
                self._embed_apply,
                jax.ShapeDtypeStruct((2,), _np.uint32),
                [jax.ShapeDtypeStruct(w.shape, w.dtype)
                 for w in self._e_raw],
                jax.ShapeDtypeStruct(x_shape[1:], x_dtype))
            h = out if not isinstance(out, tuple) else out[0]
            itemsize = self.compute_dtype.itemsize \
                if self.compute_dtype is not None else h.dtype.itemsize
            act_local = int(_np.prod(h.shape)) // self.n_dp * itemsize
            if self._partitioned and self.sequence_parallel and self.n_tp > 1:
                # the residual stream crossing stage boundaries is
                # seq-sharded over tp in SP mode — each ppermute hop moves
                # a T/tp slice (the peak-activation-memory win shows up on
                # the wire too)
                act_local //= self.n_tp
            nv = self.n_stages * self.virtual_stages
            M = self.num_microbatch
            hops = M + 2 * (nv - 1) if self.schedule == "1f1b" \
                else M + nv - 1
            st = (act_local * self.virtual_stages * 2 * hops, 2 * hops)
            self._comm_cache[sig] = st
        return st

    def _record_partitioned_tp_telemetry(self, sig):
        """Per-step activation-collective volume of compute-partitioned TP
        (parallel/megatron.py). Non-SP books psums at region exits/entries
        (axis='tp'); SP books the all_gather/psum_scatter boundary pairs
        (axis='sp' — they shard/unshard the sequence axis). Ring estimate:
        (tp-1)/tp of the full activation per collective; shapes from an
        abstract eval of the embed, cached per signature."""
        st = self._comm_cache.get(("tp", sig))
        if st is None:
            x_shape, x_dtype = sig[0], sig[1]
            out, _ = jax.eval_shape(
                self._embed_apply,
                jax.ShapeDtypeStruct((2,), _np.uint32),
                [jax.ShapeDtypeStruct(w.shape, w.dtype)
                 for w in self._e_raw],
                jax.ShapeDtypeStruct(x_shape[1:], x_dtype))
            h = out if not isinstance(out, tuple) else out[0]
            itemsize = self.compute_dtype.itemsize \
                if self.compute_dtype is not None else h.dtype.itemsize
            act_full = int(_np.prod(h.shape)) // self.n_dp * itemsize
            wire = act_full * (self.n_tp - 1) // self.n_tp
            M = self.num_microbatch
            L = self.n_layers
            if self.sequence_parallel:
                # each region boundary is an all_gather (enter) +
                # psum_scatter (exit) pair, and autodiff mirrors each as
                # its dual: 2L+1 region boundaries (2 per cell, embed exit
                # + head entry share one), ×2 for fwd+bwd
                calls = M * (2 * L + 1) * 2
                st = (("tp_act_all_gather", wire * calls, calls, "sp"),
                      ("tp_act_psum_scatter", wire * calls, calls, "sp"))
            else:
                # per cell: reduce_from_tp fwd psum ×2 regions +
                # copy_to_tp bwd psum ×2 regions; +2 for embed exit psum
                # and the head entry's bwd psum
                calls = M * (4 * L + 2)
                st = (("tp_act_psum", wire * calls, calls, "tp"),)
            self._comm_cache[("tp", sig)] = st
        for op, nbytes, calls, ax in st:
            _telem.record_comm(op, nbytes, store="mesh", calls=calls, axis=ax)

    def _record_zero_telemetry(self):
        if self._rs_bytes is None:
            plans = self._zplan_e + self._zplan_s + self._zplan_h
            self._rs_bytes = _zero.reduce_scatter_wire_bytes(
                plans, self.n_dp, self._comm_dtype)
            self._ag_bytes = _zero.all_gather_wire_bytes(plans, self.n_dp)
        nb = len(self._zplan_e) + len(self._zplan_s) + len(self._zplan_h)
        _telem.record_comm("reduce_scatter", self._rs_bytes, store="mesh",
                           calls=nb, axis="dp")
        _telem.record_comm("all_gather", self._ag_bytes, store="mesh",
                           calls=nb, axis="dp")

    def _opt_state_replica_bytes(self) -> int:
        if self._opt_bytes is None:
            tree = (self._opt_e, self._opt_s, self._opt_h)
            if self._zero:
                # wd vectors riding the bucket carries are hyperparameter
                # constants, not optimizer state
                tree = tuple([st for _, st in grp] for grp in tree)
            self._opt_bytes = _zero.per_replica_state_bytes(tree)
        return self._opt_bytes

    def _record_telemetry(self, sig, examples):
        cost = self._program.cost(sig)
        flops = cost.get("flops")
        if self.n_stages > 1:
            # per-step collective volume: the schedule's activation-hop
            # ppermute rings + the embed/head grad psum over 'pp'
            pp_bytes, pp_calls = self._ppermute_stats(sig)
            _telem.record_comm("ppermute", pp_bytes, store="mesh",
                               calls=pp_calls, axis="pp")
            rep_bytes = sum(int(w.nbytes) for w in
                            self._e_raw + self._h_raw)
            _telem.record_comm("pipeline_grad_psum", rep_bytes, store="mesh",
                               axis="pp")
        if self._zero:
            self._record_zero_telemetry()
        if self.tp_axis is not None and self.n_tp > 1 and not self._partitioned:
            # per-step weight all-gather of the tp-sharded leaves
            # (ring estimate: (tp-1)/tp of the full footprint)
            ag = sum(int(w.nbytes) * (self.n_tp - 1) // self.n_tp
                     for w, d in zip(self._e_raw + self._s_raw + self._h_raw,
                                     self._tp_e + self._tp_s + self._tp_h)
                     if d is not None)
            _telem.record_comm("tp_weight_all_gather", ag, store="mesh",
                               axis="tp")
        elif self._partitioned and self.n_tp > 1:
            # partitioned mode NEVER gathers weights: its collectives move
            # activations only. Booking them under a separate op/axis lane
            # is what lets tests assert "no weight gather" from the ledger.
            self._record_partitioned_tp_telemetry(sig)
        _telem.record_optimizer_state(self._opt_state_replica_bytes(),
                                      source="pipeline")
        # roofline ledger + aggregate flops/bytes through the one engine
        # funnel (after window admission: completion-paced); the region is
        # the fingerprint-derived StepProgram row, like DP
        _engine.record_execution(
            "step", flops or 0.0,
            bytes_accessed=cost.get("bytes_accessed", 0.0),
            region=self._program.region(sig), cost=cost)
        from ..telemetry import goodput as _goodput
        if _goodput._ENABLED and self.n_stages > 1:
            # analytic schedule bubble: idle ticks over total ticks for
            # this schedule's tick count (the same counts _ppermute_stats
            # uses); the ledger multiplies it into the measured
            # device-bound share of each step (the tick slope)
            nv = self.n_stages * self.virtual_stages
            M = self.num_microbatch
            ticks = M + 2 * (nv - 1) if self.schedule == "1f1b" \
                else M + nv - 1
            _goodput.set_pipeline_bubble("pipeline", (ticks - M) / ticks)
        _telem.record_step(examples, source="pipeline", flops_per_step=flops,
                           lr=float(self.optimizer.learning_rate),
                           dispatch_wait_seconds=self._window.wait_seconds)

    def drain(self):
        """Block until every dispatched step completed (epoch/eval
        boundary drain point)."""
        self._window.drain()

    def sync(self):
        """Write device params back into the gluon Parameters (unstacking
        the layerwise cell stacks through `_stack_order`). Row slices are
        device-side views — one (lazy) transfer per leaf at most, never a
        host round-trip per layer."""
        self.drain()
        if self._partitioned:
            # view-shaped storage (blocked qkv etc.) folds back to the
            # Parameters' logical shapes
            for p, w in zip(self._embed_plist, self._e_raw):
                p._data._set_data(w.reshape(p.shape))
            for p, w in zip(self._head_plist, self._h_raw):
                p._data._set_data(w.reshape(p.shape))
            for i, w in enumerate(self._s_raw):
                for k, m in enumerate(self._stack_order):
                    p = self._cell_plists[m][i]
                    p._data._set_data(w[k].reshape(p.shape))
            return
        for p, w in zip(self._embed_plist, self._e_raw):
            p._data._set_data(w)
        for p, w in zip(self._head_plist, self._h_raw):
            p._data._set_data(w)
        for i, w in enumerate(self._s_raw):
            for k, m in enumerate(self._stack_order):
                self._cell_plists[m][i]._data._set_data(w[k])

    # -- elastic fault tolerance ---------------------------------------------
    def state_dict(self):
        """Full training state in the elastic snapshot schema (embed/stage/
        head params with their stacked layout + stack order, per-replica
        ZeRO shards, RNG, step/schedule counters) — see
        mxnet_tpu/elastic/state.py."""
        from ..elastic import state as _estate
        return _estate.capture(self)

    def load_state_dict(self, snapshot):
        """Install a ``state_dict()``/manifest snapshot, permuting stacked
        stage rows when the (pp, virtual_stages) schedule changed and
        resharding onto this trainer's mesh (docs/checkpointing.md)."""
        from ..elastic import state as _estate
        self.drain()
        leaves, meta = snapshot["leaves"], snapshot["meta"]
        _estate.install(self, meta, leaves.__getitem__, set(leaves))
        return self

    @property
    def num_update(self):
        return self._t
