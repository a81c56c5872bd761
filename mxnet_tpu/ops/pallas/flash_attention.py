"""Flash attention as Pallas TPU kernels (forward + backward).

Replaces the reference's cuDNN/hand-CUDA attention path
(src/operator/contrib/transformer.cc:650-819 interleaved_matmul_selfatt_*)
with the TPU equivalent: blocked softmax(QK^T)V with online log-sum-exp,
computed in VMEM with MXU matmuls, O(T) memory. bfloat16 (or whatever the
inputs are) operands, float32 accumulation and softmax statistics.

Schedule. Two Mosaic calls, `mx_flash_fwd` and `mx_flash_bwd`. Blocks are up
to 512 x 512 (`_block`); the walk over blocks is inside the kernel, so a grid
step holds tens of microseconds of products and each operand is one DMA; and
every walk has bounds that are Python ints and unrolls, because the scheduler
then overlaps one block's products with the next block's elementwise work
(worth a third to a half of the time on the v5e: PERF.md, PR 29 and PR 33).
`_plan` cuts a head to make it so, from T, Tk, d, the dtype, `causal` and
`window` alone, and names the cut (`mx_attention_schedule_total` counts it):

- `resident`: a head whose rows fit the VMEM budget in one chunk a side
  (`_chunks`; T <= 4096 at d = 64 in bfloat16) and which is not causal, or of
  up to `_UNROLL_PAIRS` block pairs. Q, K, V (and dO) of G heads are fetched
  once, grid (heads / G,); under `causal` the unrolled walks stop at the
  diagonal, so masked blocks cost nothing. (A longer head that is not causal
  keeps a rolled outer loop: its inner bounds depend on no block.)
- `tiled`: any other head without a narrow window. Chunks of up to 4 x 4
  blocks; grid (heads, q chunks, k chunks) forward and (heads, k chunks,
  q chunks) backward, the last axis carrying the running output, max and sum
  (or dk and dv) in VMEM scratch. Under `causal` the chunk pair on the
  diagonal walks its static triangle and a pair below it every block unmasked;
  a pair above the diagonal is no grid step's work and its index map repeats
  a live pair's block, so it costs no DMA either. The carried max and sum lie
  in scratch replicated along lanes and are read back through a lane
  reduction: a walk that starts from (block, 1) columns loaded as they lie
  ran at half the rate (PERF.md, PR 33).
- `band`: a window that reaches at most three blocks back (below).

Heads a grid step (`_heads_per_step`) are chosen for resident heads so that a
step holds `_STEP_FLOPS` of products; a chunked head takes one.

Width. The backward of a `tiled` head wider than one lane tile (latent
attention's 192) walks blocks of at most `_WIDE_BLOCK` rows (`_wide_blocks`);
its forward walks the blocks any head's does. On the v5e at 32 x 8192 x 192/128 the
backward took 24.8 ms a call in blocks of 512 and 12.3 in blocks of 256, at
256 as at 192, while a head of 128 takes 7.8 ms in blocks of 512: a block
pair of 512 x 512 over a wide head, its float32 dk carry beside the pair's
float32 scores and their gradients, runs at half the rate (PERF.md,
Findings). Its forward stays at 512 (7.1 ms a call at 256, 5.3 at 512). The
backward's chunks may then pad T to another length than the forward's, and
`_bwd` reads `lse` at its own. A head of up to one tile takes the blocks it
always took (`mx_attention_wide_head_total` counts the layers that do not).

Forward: scores as (q rows, k lanes); the running max, sum and output are
loop carries; only blocks that cross the diagonal or the end of the keys are
masked. `lse` leaves as (heads, 1, T): T along lanes.

Backward, one pass: scores are rebuilt transposed, s^T = k q^T (k rows, q
along lanes), so `lse` and `delta = rowsum(dO * O)` broadcast down the rows
as they lie, dv += p^T dO and dk += ds^T q are plain products, and
dq += ds k takes the one transposed operand; dq accumulates in a float32
scratch of T x d. Five products a block pair, every operand read once.
At d = 64 every product half-fills the MXU (its 128-deep contraction or its
128 output columns), and that is what bounds both calls at the cells' shapes.
A head whose scores are one block pair (T, Tk <= 512: `_one_tile`) is handed
no delta and keeps no O between the passes: its kernel holds the whole of
p^T and dp^T and takes delta = colsum(p^T * dp^T), as autodiff of a softmax
does, so every row of ds sums to zero before it is rounded (with delta from
the rounded O it sums to that rounding error, which a bias in front of K
collects over all rows). Longer heads read delta as above.

Window (PR 32; the band PR 33). With `causal` and `window=W` query t sees
the keys t - W < t' <= t. A query block's keys are then its own rows and the
few blocks before them, so a long windowed head streams nothing: the forward
takes a grid (heads, q chunks), and K and V come in twice, as the chunk's own
rows and as a halo of `_reach(W, block)` blocks before it (the same array
under a second BlockSpec whose index is clamped at the first chunk, where the
halo is skipped, not masked into the sums). Every query block visits a count
of key blocks known when the kernel is traced: those the band's lower edge
crosses masked, the diagonal block masked, the blocks between plain
(`_fwd_spans`); nothing is carried between grid steps. The backward mirrors
it over (heads, k chunks) with a halo of q, dO, `lse` and `delta` rows after
the chunk, and stays one pass giving dq, dk and dv. The band's block follows
`_block`'s rule over what a query block visits (`_band_block`: 256 for
W = 512, three blocks of which one is plain, against two half-masked of 512).
A window too wide for a band is tiled: the chunk pairs behind the band are
dead like those above the diagonal. A resident windowed head walks the same
spans. With `window=None` nothing of this is traced: a resident head's
kernels are the parent's operation for operation (tests/test_flash_window.py
holds them to PR 31's text).

Selection (PR 34). With `causal` and `select`, an int8 (B, T, T) that is not
zero where query t keeps key t' (learned sparse attention: `ops/
sparse_select.py` makes it), a key is live where causal and kept, the same
for every head of a batch row. The head takes the schedule a causal head of
its length takes (`resident`, one head a grid step, or `tiled`) with one more
operand: the selection's tile of the chunk pair, one byte a pair, under a
BlockSpec that follows K's chunk (so a dead pair fetches nothing) and maps
head b to row b // H; the backward call reads the selection transposed, keys
by queries, as its scores lie. Every visited block is masked by its tile
(converted to int32 and compared, then the block's own causal mask), in
walks that are Python loops, since the tile is cut along lanes by the key
block. The walk is the causal triangle's: a block pair in which nothing is
kept is masked, not skipped, so the first version costs a dense causal
head's work whatever share of the keys is kept. `_flash_select` is a
`custom_vjp` of its own; with `select=None` nothing of this is traced
(tests/test_sparse_select.py holds every cell's shapes to PR 33's text).

Partitioning. XLA cannot partition a Mosaic call. Where the call is traced
inside `DataParallelTrainer`'s GSPMD step for several devices
(`ops/registry.py: batch_partition`), `flash_attention` wraps the custom_vjp
function in a shard_map over the batch axis: the leading B * H is sharded
with B major, forward and backward run per shard, no collective is added.

Off-TPU (CPU tests) the same kernels run in interpret mode when
MXNET_PALLAS_INTERPRET=1, else we fall back to the lax.scan implementation
in ops/attention.py (identical math).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P
from typing import NamedTuple

from ...base import MXNetError

_NEG = -1e30  # finite mask value: -inf breeds nans in exp(-inf - -inf)
_LANES = 128
# what one grid step's blocks may take of VMEM, both pipeline buffers counted
_VMEM_BLOCK_BYTES = 16 << 20
_VMEM_LIMIT_MAX = 100 << 20     # of v5e's 128 MiB
_STEP_FLOPS = 1e9               # products a grid step should hold at least
_UNROLL_PAIRS = 16              # block pairs of a head that still unroll
_WIDE_BLOCK = 256               # the most rows a wide head's backward block


def pallas_available() -> bool:
    return jax.default_backend() == "tpu"


def _on_tpu(x) -> bool:
    """True when `x` actually lives on a TPU. The TPU plugin registers even
    when tests pin everything to CPU, so jax.default_backend() alone lies —
    check the concrete device when the array has one; for tracers consult
    jax_default_device (set to CPU by the test conftest) before falling back
    to the default backend."""
    try:
        devs = x.devices()
        return all(d.platform == "tpu" for d in devs)
    except Exception:  # tracer — no concrete placement
        from ..registry import exec_platform
        plat = exec_platform.get()
        if plat is not None:
            # the surrounding invoke/compile recorded what backend this
            # computation is actually being built for
            return plat == "tpu"
        dev = jax.config.jax_default_device
        if dev is not None:
            return getattr(dev, "platform", str(dev)) == "tpu"
        return jax.default_backend() == "tpu"


def _use_interpret() -> bool:
    return os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# The schedule, from what the call can see: lengths, head width, dtype,
# `causal` and `window`
# ---------------------------------------------------------------------------

def _block(L, limit):
    """The block, in whole lane tiles up to `limit`, that walks a side of
    length L cheapest: the padded length times (1 + 200 / block), the cost
    of a block's step against its work as read on the v5e (128: 4.0 ms,
    256: 2.4, 512: 2.0 for forward and backward at 192 x 1024 x 64). 512 for
    1000, 1024 or 2400; 384 for 1100. (A band's block: `_band_block`.)"""
    blocks = [_LANES * b for b in range(1, max(limit // _LANES, 1) + 1)]
    return min(blocks, key=lambda b: (_ceil_to(L, b) * (1 + 200 / b), -b))


def _wide_blocks(D, block_q, block_k):
    """The blocks a chunked head's backward walks: at most `_WIDE_BLOCK`
    rows where q and k are wider than one lane tile (the module's
    docstring, "Width"), else the forward's."""
    if D <= _LANES:
        return block_q, block_k
    return min(block_q, _WIDE_BLOCK), min(block_k, _WIDE_BLOCK)


def _row_bytes(D, dtype, Dv=None):
    """VMEM bytes one row of a head costs the backward call (the larger of
    the two): q, k, v, dO in and dq, dk, dv out in both pipeline buffers, and
    the float32 dq accumulator; a head width pads to a lane tile. `Dv`: the
    width of v, dO and dv where it is not q's and k's `D`."""
    Dp = _ceil_to(D, _LANES)
    Dvp = Dp if Dv is None else _ceil_to(Dv, _LANES)
    return 2 * (4 * Dp + 3 * Dvp) * jnp.dtype(dtype).itemsize + 4 * Dp


def _chunks(L, unit, most):
    """(chunk, number of chunks) for a side of length L: equal chunks of
    whole `unit`s, none longer than `most` rows (but one unit at least)."""
    most = max(unit, most // unit * unit)
    n = -(-_ceil_to(L, unit) // most)
    return _ceil_to(-(-L // n), unit), n


def _heads_per_step(BH, head_bytes, head_flops):
    """G: the most heads (a divisor of BH) a grid step can take inside the
    VMEM budget, no more than give it `_STEP_FLOPS` of products."""
    want = max(1, min(_VMEM_BLOCK_BYTES // max(head_bytes, 1),
                      -(-int(_STEP_FLOPS) // max(int(head_flops), 1))))
    return max(g for g in range(1, min(want, BH) + 1) if BH % g == 0)


def _reach(window, b):
    """The farthest block back, of blocks (or chunks) of b rows, that the
    band of a block's first row touches (one at least: a halo is a block)."""
    return max(1, (window + b - 2) // b)


def _band_block(window, limit):
    """The block of a band, by `_block`'s rule: a query block of b rows
    visits reach + 1 key blocks of b, each at (1 + 200 / b) of its work; of
    the blocks whose walk still unrolls the cheapest, None if there is none
    (a window too wide for a band). 256 for W = 512 (three blocks, one
    unmasked, against two half-masked of 512: PR 33's probe read 1.69 and
    3.83 ms against 1.98 and 4.12), 128 for W = 128."""
    blocks = [b for b in range(_LANES, limit + 1, _LANES)
              if _reach(window, b) * (_reach(window, b) + 1) <= _UNROLL_PAIRS]
    return min(blocks, key=lambda b: (
        (_reach(window, b) + 1) * b * (1 + 200 / b), -b), default=None)


class Plan(NamedTuple):
    """What `_plan` chose. `schedule` is `resident` (one chunk a side, G
    heads a grid step), `band` (a window: a grid over chunks of one side,
    each with a `halo` of that many blocks of the other side beside its own
    rows) or `tiled` (a grid over chunk pairs; under a window too wide for a
    band, `halo` is how many chunks back a query chunk's band reaches)."""
    schedule: str
    bq: int
    bk: int
    cq: int
    n_qc: int
    ck: int
    n_kc: int
    G: int
    nbytes: int
    halo: int = 0


def _plan(backward, BH, T, Tk, D, dtype, causal, block_q, block_k,
          window=None, select=False, Dv=None):
    """The cut of a head, so that every walk over blocks has bounds that are
    Python ints. A head whose rows fit the VMEM budget in one chunk a side
    is `resident`: not causal (its bounds depend on no block), or of up to
    `_UNROLL_PAIRS` block pairs (both walks unroll). Any other head is cut
    into chunks that a grid walks: a window whose band reaches few blocks
    back takes `band`, the rest `tiled` in chunk pairs of at most
    `_UNROLL_PAIRS` block pairs. `select`: the call brings a selection; a
    grid step then holds one head (the heads of a step would read different
    rows of it) and its tile of the selection, one byte a pair, in both
    pipeline buffers. `Dv`: the width of v (and of o, dO, dv) where it is
    not q's and k's `D` (latent attention: 192 and 128); the bytes count
    each operand at its own width, and `Dv` None or `D` is the plan of one
    width. A `tiled` backward of a head wider than one lane tile walks the
    smaller blocks of `_wide_blocks`."""
    plan = _plan_unselected(backward, BH, T, Tk, D, dtype, causal, block_q,
                            block_k, window, Dv)
    if not select:
        return plan
    return plan._replace(G=1, nbytes=plan.nbytes // plan.G
                         + 2 * plan.cq * plan.ck)


def _plan_unselected(backward, BH, T, Tk, D, dtype, causal, block_q, block_k,
                     window, Dv=None):
    Dp, isz = _ceil_to(D, _LANES), jnp.dtype(dtype).itemsize
    Dvp = Dp if Dv is None else _ceil_to(Dv, _LANES)
    Dv = D if Dv is None else Dv
    rows = _VMEM_BLOCK_BYTES // _row_bytes(D, dtype, Dv)
    cq, n_qc = _chunks(T, block_q, rows)
    ck, n_kc = _chunks(Tk, block_k, rows)
    pairs = (cq // block_q) * (ck // block_k)
    if n_qc == 1 and n_kc == 1 and (not causal or pairs <= _UNROLL_PAIRS):
        if backward:
            # q, dO, k, v in and dk, dv out in both pipeline buffers; dq
            # whole with its float32 accumulator; lse and delta
            nbytes = (2 * (cq + 2 * ck) * (Dp + Dvp) * isz
                      + cq * Dp * (2 * isz + 4) + 2 * 2 * 8 * cq * 4)
            flops = 2 * T * Tk * (3 * D + 2 * Dv)
        else:
            nbytes = 2 * (cq + ck) * (Dp + Dvp) * isz + 2 * 8 * cq * 4
            flops = 2 * T * Tk * (D + Dv)
        G = _heads_per_step(BH, nbytes, flops // (2 if causal else 1))
        return Plan("resident", block_q, block_k, cq, 1, ck, 1, G, G * nbytes)
    if causal:
        # chunk pairs meet the diagonal squarely: one block for both sides
        block_q = block_k = min(block_q, block_k)
    b = None if window is None else _band_block(window, block_k)
    if b is not None:
        # a chunk is whole halos, so that the halo is a block of its own
        # BlockSpec; as many as still unroll (m * h query blocks of h + 1
        # key blocks each)
        h = _reach(window, b)
        m = max(1, _UNROLL_PAIRS // (h * (h + 1)))
        c, n = _chunks(T, h * b, min(rows, m * h * b))
        own = (6 if backward else 4) * c + 2 * h * b
        nbytes = 2 * own * Dp * isz + 4 * 8 * (c + h * b) * 4 \
            + backward * c * n * Dp * (2 * isz + 4)
        return Plan("band", b, b, c, n, c, n, 1, nbytes, h)
    if backward:
        block_q, block_k = _wide_blocks(D, block_q, block_k)
    side, b = max(1, math.isqrt(_UNROLL_PAIRS)), block_k
    if causal:
        # equal chunks on both sides; Tk may differ from T
        c, _ = _chunks(max(T, Tk), b, min(rows, side * b))
        cq = ck = c
        n_qc, n_kc = -(-T // c), -(-Tk // c)
    else:
        cq, n_qc = _chunks(T, block_q, min(rows, side * block_q))
        ck, n_kc = _chunks(Tk, block_k, min(rows, side * block_k))
    if backward:
        # as a resident head's, with dk and dv carried in float32
        nbytes = (2 * (cq + 2 * ck) * (Dp + Dvp) * isz
                  + cq * n_qc * Dp * (2 * isz + 4) + 2 * 2 * 8 * cq * 4
                  + ck * (Dp + Dvp) * 4)
    else:
        # and the carried output, max and sum
        nbytes = (2 * (cq + ck) * (Dp + Dvp) * isz + 2 * 8 * cq * 4
                  + cq * (Dvp + 2 * _LANES) * 4)
    return Plan("tiled", block_q, block_k, cq, n_qc, ck, n_kc, 1, nbytes,
                0 if window is None else _reach(window, cq))


def _compiler_params(interpret, block_bytes, rank=3):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (rank - 1),
        vmem_limit_bytes=int(min(_VMEM_LIMIT_MAX,
                                 max(32 << 20, block_bytes + (16 << 20)))))}


# ---------------------------------------------------------------------------
# The walks. Every bound is a Python int: positions are counted from the
# first row and the first key that the grid step holds.
# ---------------------------------------------------------------------------

def _clamp(x, hi):
    return min(max(x, 0), hi)


def _fwd_spans(row0, bq, bk, nkb, keys, causal, window):
    """[(first, end, masked)]: the runs of k blocks, in walking order, that
    the q block of rows `row0`.. visits among `nkb` blocks whose first key is
    position 0 and of whose keys `keys` are real. Causal: a block wholly
    above the diagonal is not visited, one wholly on or below it needs no
    mask. Window (the band t - window < t' <= t): blocks wholly behind the
    first row's band are not visited, those that its lower edge crosses for
    some row of the block are masked."""
    n_live, n_plain = min(-(-keys // bk), nkb), min(keys // bk, nkb)
    if causal:
        n_live = min(n_live, (max(row0 + bq, 0) + bk - 1) // bk)
        n_plain = min(n_plain, max(row0 + 1, 0) // bk)
    n_plain = min(n_plain, n_live)
    if window is None:
        return [(0, n_plain, False), (n_plain, n_live, True)]
    back = row0 - window
    n_first = min(max(back + 1, 0) // bk, n_live)
    n_edge = max(_clamp(max(back + bq - 1 + bk, 0) // bk, n_live), n_first)
    n_plain = max(n_plain, n_edge)
    return [(n_first, n_edge, True), (n_edge, n_plain, False),
            (n_plain, n_live, True)]


def _bwd_spans(col0, bq, bk, nqb, causal, window):
    """[(first, end, masked)]: the runs of q blocks that the k block of keys
    `col0`.. visits among `nqb` blocks whose first row is position 0. Causal:
    q blocks wholly above the diagonal are not visited, those wholly on or
    below it need no mask. Window: q blocks wholly past the band of the
    block's last key are not visited, those in which some pair lies a window
    apart are masked."""
    n_dead = n_cross = 0
    if causal:
        n_dead = _clamp(max(col0, 0) // bq, nqb)
        n_cross = max(_clamp((max(col0 + bk - 1, 0) + bq - 1) // bq, nqb),
                      n_dead)
    if window is None:
        return [(n_dead, n_cross, True), (n_cross, nqb, False)]
    ahead = col0 + window
    n_end = max(_clamp(max(ahead + bk - 1 + bq - 1, 0) // bq, nqb), n_dead)
    n_cross = min(n_cross, n_end)
    n_free = max(_clamp(max(ahead, 0) // bq, n_end), n_cross)
    return [(n_dead, n_cross, True), (n_cross, n_free, False),
            (n_free, n_end, True)]


def _inner(lo, hi, body, init):
    """The walk over the inner blocks of a resident head. A trip count of a
    few steps unrolls: the scheduler then overlaps the products of one block
    with the elementwise work of the next (a third of the forward's time on
    the v5e)."""
    if hi <= lo:
        return init
    return lax.fori_loop(lo, hi, body, init, unroll=hi - lo <= 8)


def _outer(n, body, unrolled):
    """The walk over the outer blocks of a resident head: a Python loop
    where the head is short, so that under `causal` every inner bound is
    static."""
    if unrolled:
        for b in range(n):
            body(b)
    else:
        lax.fori_loop(0, n, lambda b, c: body(b), None)


def _start(i, block):
    return i * block if isinstance(i, int) \
        else pl.multiple_of(i * block, block)


def _apart(shape, q_axis, apart, causal, window, keys=None):
    """The mask of a chunked head's block pair from what is static in it:
    `apart` is the first row's position less the first key's (an int, or a
    scalar where only the grid knows it), `keys` how many of the block's keys
    are real (None: all)."""
    k_in = lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    d = apart + lax.broadcasted_iota(jnp.int32, shape, q_axis) - k_in
    conds = ([] if keys is None else [k_in < keys]) \
        + ([d >= 0] if causal else []) \
        + ([] if window is None else [d < window])
    return functools.reduce(jnp.logical_and, conds)


def _kept(sel_ref, g, rows, cols, also=None):
    """The mask of a block pair under a selection: the tile of `sel_ref`
    (one byte a pair, rows and columns as the scores lie) is not zero, and
    `also()` where the pair has a mask of its own."""
    def mask_of():
        kept = sel_ref[g, rows, cols].astype(jnp.int32) != 0
        return kept if also is None else jnp.logical_and(kept, also())
    return mask_of


# ---------------------------------------------------------------------------
# Forward. One q block against one k block: scores as (q rows, k lanes), the
# running max, sum and output carried from block to block.
# ---------------------------------------------------------------------------

def _attend(q, kblk, vblk, carry, scale, mask_of=None):
    m, l, acc = carry
    # matmul operands stay in the input dtype (bf16 on the fast path);
    # preferred_element_type makes the MXU accumulate in f32
    s = lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if mask_of is not None:
        s = jnp.where(mask_of(), s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + lax.dot_general(
        p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


# A band's kernel holds some twenty block bodies in Python loops, and tracing one
# costs the chip's host tens of milliseconds (PERF.md, PR 31 and PR 33: seconds
# of every run's set-up). Jitted, a body is traced once for each kind of block
# (plain; masked at one static distance) and every further block of the kind is
# one equation, which the lowering to Mosaic inlines. `mask` is `_apart`'s
# (apart, causal, window) or None.

@functools.partial(jax.jit, static_argnums=(4, 5))
def _attend_block(q, kblk, vblk, carry, scale, mask, keys=None):
    return _attend(q, kblk, vblk, carry, scale, mask and functools.partial(
        _apart, (q.shape[0], kblk.shape[0]), 0, *mask, keys))


def _fresh(bq, D):
    return (jnp.full((bq, 1), _NEG, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, D), jnp.float32))


def _write_rows(o_ref, lse_ref, g, q0, carry, window):
    m, l, acc = carry
    bq = m.shape[0]
    ls = jnp.maximum(l, 1e-30)
    o_ref[g, pl.ds(q0, bq), :] = (acc / ls).astype(o_ref.dtype)
    # the statistics leave along lanes: (bq, 1) -> (1, bq)
    lse = m + jnp.log(ls)
    if window is not None:
        # a padded row whose band holds no real key has seen nothing
        # (m is still _NEG): exp(s - lse) must stay finite for it in
        # the backward pass, where a one-tile head sums p^T * dp^T
        # over such a row's padded keys too
        lse = jnp.where(m > 0.5 * _NEG, lse, 0.0)
    lse = jnp.broadcast_to(lse, (bq, _LANES))
    lse_ref[g, :, pl.ds(q0, bq)] = lse.T[:1]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                bq, bk, t_k, window=None, sel_ref=None):
    """A resident head: grid (BH / G,), k blocks walked inside. `sel_ref`:
    the head's selection (G is then 1); every visited block is masked by it,
    in a walk that is a Python loop (a selected head is causal, so of few
    block pairs)."""
    G, cq, D = q_ref.shape
    nqb, nkb = cq // bq, k_ref.shape[1] // bk
    k_base = 0          # the one chunk's first key, and its first row

    def q_block(g, qb):
        q0 = _start(qb, bq)
        row0 = 0 * cq + q0
        q = q_ref[g, pl.ds(q0, bq), :]

        def k_block(kb, carry, masked):
            k0 = _start(kb, bk)
            kblk = k_ref[g, pl.ds(k0, bk), :]
            vblk = v_ref[g, pl.ds(k0, bk), :]

            def mask_of():
                k_pos = k_base + k0 + lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                mask = k_pos < t_k
                if causal:
                    q_pos = row0 + lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 0)
                    mask = jnp.logical_and(mask, q_pos >= k_pos)
                    if window is not None:
                        mask = jnp.logical_and(mask, q_pos - k_pos < window)
                return mask

            if sel_ref is not None:
                return _attend(q, kblk, vblk, carry, scale, _kept(
                    sel_ref, g, pl.ds(q0, bq), pl.ds(k0, bk),
                    mask_of if masked else None))
            return _attend(q, kblk, vblk, carry, scale,
                           mask_of if masked else None)

        carry = _fresh(bq, v_ref.shape[-1])
        # not causal: the bounds depend on no block, and q0 may be traced
        carry = _walk(_fwd_spans(row0 if causal else 0, bq, bk, nkb, t_k,
                                 causal, window), k_block, carry,
                      unrolled=sel_ref is not None)
        _write_rows(o_ref, lse_ref, g, q0, carry, window)

    def head(g, _):
        _outer(nqb, functools.partial(q_block, g),
               nqb * nkb <= _UNROLL_PAIRS)

    lax.fori_loop(0, G, head, None)


def _walk(spans, block, carry, unrolled=False):
    """A chunked head's walk over its runs of blocks. A run is a loop that
    unrolls, traced once (a block body costs the chip's host tens of
    milliseconds to trace, and a kernel holds tens of them: PERF.md, PR 31
    and PR 33); `unrolled`: a Python loop, for a band, whose blocks lie in
    two refs."""
    for lo, hi, masked in spans:
        if unrolled:
            for b in range(lo, hi):
                carry = block(b, carry, masked)
        else:
            carry = _inner(lo, hi, functools.partial(block, masked=masked),
                           carry)
    return carry


def _fwd_band_kernel(q_ref, kh_ref, vh_ref, k_ref, v_ref, o_ref, lse_ref, *,
                     scale, window, b, halo, t_k, n_c):
    """A window's q chunk: grid (BH, q chunks). The chunk's keys are its own
    rows and, but in the first chunk, `halo` blocks before them."""
    _, c, D = q_ref.shape
    nb = c // b
    i = pl.program_id(1)
    padded = t_k < c * n_c

    def chunk(n_halo):
        for qb in range(nb):
            q = q_ref[0, pl.ds(qb * b, b), :]
            row0 = (n_halo + qb) * b    # counted from the first key held

            def k_block(kb, carry, masked):
                ref_k, ref_v, k0 = (kh_ref, vh_ref, kb * b) if kb < n_halo \
                    else (k_ref, v_ref, (kb - n_halo) * b)
                keys = t_k - (i * c + (kb - n_halo) * b) \
                    if padded and masked else None
                return _attend_block(
                    q, ref_k[0, pl.ds(k0, b), :], ref_v[0, pl.ds(k0, b), :],
                    carry, scale,
                    (row0 - kb * b, True, window) if masked else None, keys)

            # a padded key lies on or above the diagonal of every real row,
            # so the spans count every key as real and the masked blocks,
            # the diagonal's among them, mask the padded ones
            carry = _walk(_fwd_spans(row0, b, b, n_halo + nb,
                                     (n_halo + nb) * b, True, window),
                          k_block, _fresh(b, D), unrolled=True)
            _write_rows(o_ref, lse_ref, 0, qb * b, carry, window)

    if n_c == 1:
        chunk(0)
    else:
        pl.when(i == 0)(functools.partial(chunk, 0))
        pl.when(i > 0)(functools.partial(chunk, halo))


def _fwd_tiled_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                      acc_ref, *, scale, causal, window, bq, bk, t_k, n_kc,
                      reach, sel_ref=None):
    """A chunk pair: grid (BH, q chunks, k chunks), output, max and sum
    carried in VMEM over the k chunks. Under `causal` the pair on the
    diagonal walks its static triangle and a pair below it every block
    unmasked (under a window: masked, while the band reaches it); pairs
    above the diagonal or behind the band are no step's work, and their
    index repeats a live pair's so that nothing is fetched for them.
    `sel_ref`: the chunk pair's tile of a selection; every visited block is
    then masked by it, in walks that are Python loops."""
    cq, D = q_ref.shape[1:]
    ck = k_ref.shape[1]
    nqb, nkb = cq // bq, ck // bk
    i, j = pl.program_id(1), pl.program_id(2)
    j_last = jnp.minimum(i, n_kc - 1) if causal else n_kc - 1
    j_first = jnp.maximum(i - reach, 0) if window is not None else 0
    tail = t_k - (n_kc - 1) * ck            # real keys of the last k chunk

    @pl.when(j == j_first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        l_ref[:] = jnp.zeros_like(l_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)

    def carried(qb):
        # max and sum lie in scratch replicated along lanes and come back
        # through a lane reduction, in the layout the walk's own reductions
        # give: from (block, 1) columns loaded as they lie the whole walk ran
        # at half the rate (11.65 against 5.65 ms a call: PERF.md, PR 33)
        rows = pl.ds(qb * bq, bq)
        return (jnp.max(m_ref[rows, :], axis=-1, keepdims=True),
                jnp.max(l_ref[rows, :], axis=-1, keepdims=True),
                acc_ref[rows, :])

    def pair(spans_of, mask_of=None):
        """Every q block's walk over the k chunk; `mask_of(apart, k0)` is a
        masked block's mask from its first row's position less its first
        key's and that key's place in the chunk."""
        for qb in range(nqb):
            rows = pl.ds(qb * bq, bq)
            q = q_ref[0, rows, :]

            def k_block(kb, carry, masked):
                k0 = _start(kb, bk)
                kblk, vblk = k_ref[0, pl.ds(k0, bk), :], \
                    v_ref[0, pl.ds(k0, bk), :]
                live = functools.partial(mask_of, qb * bq - k0, k0) \
                    if masked else None
                if sel_ref is not None:
                    live = _kept(sel_ref, 0, rows, pl.ds(k0, bk), live)
                return _attend(q, kblk, vblk, carry, scale, live)

            m, l, acc = _walk(spans_of(qb), k_block, carried(qb),
                              unrolled=sel_ref is not None)
            m_ref[rows, :] = jnp.broadcast_to(m, (bq, _LANES))
            l_ref[rows, :] = jnp.broadcast_to(l, (bq, _LANES))
            acc_ref[rows, :] = acc

    mask = functools.partial(_apart, (bq, bk), 0)
    if causal:
        # on the diagonal; padded keys as in the band's chunk
        padded = t_k < n_kc * ck
        pl.when(j == i)(functools.partial(
            pair, lambda qb: _fwd_spans(qb * bq, bq, bk, nkb, ck, True,
                                        window),
            lambda apart, k0: mask(apart, True, window,
                                   t_k - (j * ck + k0) if padded else None)))
        if window is None:
            pl.when(j < i)(functools.partial(
                pair, lambda qb: [(0, nkb, False)]))
        else:
            pl.when(jnp.logical_and(j < i, j >= j_first))(functools.partial(
                pair, lambda qb: [(0, nkb, True)],
                lambda apart, k0: mask((i - j) * cq + apart, False, window)))
    else:
        def whole():
            pair(lambda qb: [(0, nkb, False)])

        def last():
            pair(lambda qb: _fwd_spans(0, bq, bk, nkb, tail, False, None),
                 lambda apart, k0: mask(0, False, None, tail - k0))

        if tail == ck:
            whole()
        else:
            pl.when(j < n_kc - 1)(whole)
            pl.when(j == n_kc - 1)(last)

    @pl.when(j == j_last)
    def _finalize():
        for qb in range(nqb):
            _write_rows(o_ref, lse_ref, 0, qb * bq, carried(qb), window)


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


# `_fwd` and `_bwd` are jitted so that the layers of a model share one trace of
# the kernel and one lowering to Mosaic: traced anew for every layer, twelve
# layers cost the step's build 2 s more on the v5e's host (PERF.md, PR 31)

def _with_select(kernel, n_in):
    """`kernel` taking the selection's ref as its input number `n_in`, where
    the call hands it on after the other inputs."""
    def run(*refs, **kw):
        return kernel(*refs[:n_in], *refs[n_in + 1:], sel_ref=refs[n_in],
                      **kw)
    return run


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _fwd(q, k, v, causal, scale, block_q, block_k, interpret, window=None,
         select=None):
    """-> o (BH, T, D), lse (BH, 1, Tp): T padded to whole blocks, on lanes.
    `select` int8 (B, T, Tk), BH / B heads a row of it."""
    BH, T, D = q.shape
    Tk, Dv = k.shape[1], v.shape[2]
    plan = _plan(False, BH, T, Tk, D, q.dtype, causal, block_q, block_k,
                 window, select is not None, Dv)
    cq, n_qc, ck, n_kc, G = plan.cq, plan.n_qc, plan.ck, plan.n_kc, plan.G
    Tp, Tkp = cq * n_qc, ck * n_kc
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tkp - Tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tkp - Tk), (0, 0)))
    if select is not None:
        return _fwd_selected(qp, kp, vp, select, plan, scale, interpret,
                             (T, Tk))
    call = functools.partial(
        pl.pallas_call, interpret=interpret, name="mx_flash_fwd",
        out_shape=[jax.ShapeDtypeStruct((BH, Tp, Dv), q.dtype),
                   jax.ShapeDtypeStruct((BH, 1, Tp), jnp.float32)])
    q_spec = _vmem((G, cq, D), lambda b, i, j: (b, i, 0))
    o_spec = _vmem((G, cq, Dv), lambda b, i, j: (b, i, 0))
    row_spec = _vmem((G, 1, cq), lambda b, i, j: (b, 0, i))
    if plan.schedule == "resident":
        k_spec = _vmem((G, ck, D), lambda b, i, j: (b, j, 0))
        v_spec = _vmem((G, ck, Dv), lambda b, i, j: (b, j, 0))
        o, lse = call(
            functools.partial(_fwd_kernel, scale=scale, causal=causal,
                              bq=block_q, bk=block_k, t_k=Tk, window=window),
            grid=(BH // G, n_qc, n_kc),
            in_specs=[q_spec, k_spec, v_spec],
            out_specs=[o_spec, row_spec],
            scratch_shapes=[],
            **_compiler_params(interpret, plan.nbytes),
        )(qp, kp, vp)
    elif plan.schedule == "band":
        hb = plan.halo * plan.bk
        own = _vmem((1, cq, D), lambda b, i: (b, i, 0))
        before = _vmem((1, hb, D), lambda b, i: (
            b, jnp.maximum(i * (cq // hb) - 1, 0), 0))
        o, lse = call(
            functools.partial(_fwd_band_kernel, scale=scale, window=window,
                              b=plan.bk, halo=plan.halo, t_k=Tk, n_c=n_qc),
            grid=(BH, n_qc),
            in_specs=[own, before, before, own, own],
            out_specs=[own, _vmem((1, 1, cq), lambda b, i: (b, 0, i))],
            **_compiler_params(interpret, plan.nbytes, 2),
        )(qp, kp, vp, kp, vp)
    else:
        def k_chunk(b, i, j):
            if causal:
                j = jnp.minimum(j, jnp.minimum(i, n_kc - 1))
            if window is not None:
                j = jnp.maximum(j, i - plan.halo)
            return b, j, 0

        k_spec = _vmem((1, ck, D), k_chunk)
        v_spec = _vmem((1, ck, Dv), k_chunk)
        o, lse = call(
            functools.partial(_fwd_tiled_kernel, scale=scale, causal=causal,
                              window=window, bq=plan.bq, bk=plan.bk, t_k=Tk,
                              n_kc=n_kc, reach=plan.halo),
            grid=(BH, n_qc, n_kc),
            in_specs=[q_spec, k_spec, v_spec],
            out_specs=[o_spec, row_spec],
            scratch_shapes=[pltpu.VMEM((cq, _LANES), jnp.float32),
                            pltpu.VMEM((cq, _LANES), jnp.float32),
                            pltpu.VMEM((cq, Dv), jnp.float32)],
            **_compiler_params(interpret, plan.nbytes),
        )(qp, kp, vp)
    return o[:, :T], lse


def _fwd_selected(qp, kp, vp, select, plan, scale, interpret, lengths):
    """`_fwd` of a causal head under a selection, from the padded operands:
    the resident or the tiled call with the selection's tile of the chunk
    pair as one more input, fetched where K's is (a dead pair repeats a live
    pair's index)."""
    BH, Tp, D = qp.shape
    Tkp, (T, Tk) = kp.shape[1], lengths
    heads = BH // select.shape[0]
    cq, n_qc, ck, n_kc = plan.cq, plan.n_qc, plan.ck, plan.n_kc
    sp = jnp.pad(select, ((0, 0), (0, Tp - T), (0, Tkp - Tk)))

    def k_chunk(i, j):
        return jnp.minimum(j, jnp.minimum(i, n_kc - 1))

    q_spec = _vmem((1, cq, D), lambda b, i, j: (b, i, 0))
    k_spec = _vmem((1, ck, D), lambda b, i, j: (b, k_chunk(i, j), 0))
    sel_spec = _vmem((1, cq, ck),
                     lambda b, i, j: (b // heads, i, k_chunk(i, j)))
    if plan.schedule == "resident":
        kernel = functools.partial(
            _fwd_kernel, scale=scale, causal=True, bq=plan.bq, bk=plan.bk,
            t_k=Tk)
        scratch = []
    else:
        kernel = functools.partial(
            _fwd_tiled_kernel, scale=scale, causal=True, window=None,
            bq=plan.bq, bk=plan.bk, t_k=Tk, n_kc=n_kc, reach=0)
        scratch = [pltpu.VMEM((cq, _LANES), jnp.float32),
                   pltpu.VMEM((cq, _LANES), jnp.float32),
                   pltpu.VMEM((cq, D), jnp.float32)]
    o, lse = pl.pallas_call(
        _with_select(kernel, 3), interpret=interpret, name="mx_flash_fwd",
        out_shape=[jax.ShapeDtypeStruct((BH, Tp, D), qp.dtype),
                   jax.ShapeDtypeStruct((BH, 1, Tp), jnp.float32)],
        grid=(BH, n_qc, n_kc),
        in_specs=[q_spec, k_spec, k_spec, sel_spec],
        out_specs=[q_spec, _vmem((1, 1, cq), lambda b, i, j: (b, 0, i))],
        scratch_shapes=scratch,
        **_compiler_params(interpret, plan.nbytes),
    )(qp, kp, vp, sp)
    return o[:, :T], lse


# ---------------------------------------------------------------------------
# Backward: k blocks outside, q blocks inside, scores transposed (k rows, q
# along lanes); dq accumulates in a float32 scratch of the head's rows
# ---------------------------------------------------------------------------

def _grads(kblk, vblk, qblk, dob, lse, delta, carry, scale, live_of=None):
    """One k block against one q block: (dk, dv) carried on, and ds^T for
    the block's share of dq. `delta` None: a one-tile head computes its
    own."""
    dk, dv = carry
    st = lax.dot_general(kblk, qblk, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32) * scale
    if live_of is not None:
        st = jnp.where(live_of(), st, _NEG)
    # padded rows and keys need no mask here: their q, k, v, dO are
    # zero, so every product they enter adds nothing to a kept row
    pt = jnp.exp(st - lse)
    dv = dv + lax.dot_general(
        pt.astype(dob.dtype), dob, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dpt = lax.dot_general(vblk, dob, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    if delta is None:
        # the tile is the head's whole p^T and dp^T: delta as
        # autodiff of a softmax takes it, so every row of ds sums
        # to zero before it is rounded (sum(dO * o) from the stored
        # o leaves that o's rounding error in the sum, and sum_j dk_j
        # is that error times q)
        delta = jnp.sum(pt * dpt, axis=0, keepdims=True)
    dst = (pt * (dpt - delta)).astype(qblk.dtype)
    dk = dk + lax.dot_general(
        dst, qblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (dk, dv), dst


@functools.partial(jax.jit, static_argnums=(7, 8))
def _grads_block(kblk, vblk, qblk, dob, lse, delta, carry, scale, mask):
    """`_grads` of a band's block, traced once a kind as `_attend_block`."""
    return _grads(kblk, vblk, qblk, dob, lse, delta, carry, scale,
                  mask and functools.partial(
                      _apart, (kblk.shape[0], qblk.shape[0]), 1, *mask))


def _add_dq(dq_acc, g, rows, dst, kblk):
    dq_acc[g, rows, :] = dq_acc[g, rows, :] + lax.dot_general(
        dst, kblk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, *refs, scale, causal,
                bq, bk, own_delta, window=None, sel_ref=None):
    """A resident head: grid (BH / G,). `sel_ref`: the head's selection
    transposed (keys by queries, as the scores lie here)."""
    # a one-tile head (`_one_tile`) is handed no delta: it computes its own
    delta_ref = None if own_delta else refs[0]
    dq_ref, dk_ref, dv_ref, dq_acc = refs[0 if own_delta else 1:]
    G, cq, D = q_ref.shape
    nqb, nkb = cq // bq, k_ref.shape[1] // bk
    q_base = 0
    dq_acc[:] = jnp.zeros_like(dq_acc)

    def k_block(g, kb):
        k0 = _start(kb, bk)
        col0 = 0 * cq + k0
        kblk = k_ref[g, pl.ds(k0, bk), :]
        vblk = v_ref[g, pl.ds(k0, bk), :]

        def q_block(qb, carry, masked):
            q0 = _start(qb, bq)
            qblk = q_ref[g, pl.ds(q0, bq), :]
            dob = do_ref[g, pl.ds(q0, bq), :]
            lse = lse_ref[g, :, pl.ds(q0, bq)]          # (1, bq)
            delta = None if own_delta else delta_ref[g, :, pl.ds(q0, bq)]

            def live_of():
                k_pos = col0 + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
                q_pos = q_base + q0 + lax.broadcasted_iota(
                    jnp.int32, (bk, bq), 1)
                live = q_pos >= k_pos
                if window is not None:
                    live = jnp.logical_and(live, q_pos - k_pos < window)
                return live

            live = live_of if masked else None
            if sel_ref is not None:
                live = _kept(sel_ref, g, pl.ds(k0, bk), pl.ds(q0, bq), live)
            carry, dst = _grads(kblk, vblk, qblk, dob, lse, delta, carry,
                                scale, live)
            _add_dq(dq_acc, g, pl.ds(q_base + q0, bq), dst, kblk)
            return carry

        carry = (jnp.zeros((bk, D), jnp.float32),
                 jnp.zeros((bk, v_ref.shape[-1]), jnp.float32))
        carry = _walk(_bwd_spans(col0 if causal else 0, bq, bk, nqb, causal,
                                 window), q_block, carry,
                      unrolled=sel_ref is not None)
        dk, dv = carry
        dk_ref[g, pl.ds(k0, bk), :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[g, pl.ds(k0, bk), :] = dv.astype(dv_ref.dtype)

    def head(g, _):
        _outer(nkb, functools.partial(k_block, g),
               nqb * nkb <= _UNROLL_PAIRS)

    lax.fori_loop(0, G, head, None)
    dq_ref[:] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_band_kernel(q_ref, qh_ref, do_ref, doh_ref, lse_ref, lseh_ref,
                     delta_ref, deltah_ref, k_ref, v_ref, dq_ref, dk_ref,
                     dv_ref, dq_acc, *, scale, window, b, halo, n_c):
    """A window's k chunk: grid (BH, k chunks). The chunk's rows of q, dO,
    lse and delta are its own and, but in the last chunk, `halo` blocks after
    them; one pass gives dk and dv of the chunk and adds to dq."""
    _, c, D = k_ref.shape
    nb = c // b
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def chunk(n_halo):
        for kb in range(nb):
            kblk = k_ref[0, pl.ds(kb * b, b), :]
            vblk = v_ref[0, pl.ds(kb * b, b), :]

            def q_block(qb, carry, masked):
                own = qb < nb
                q, do, lse, delta = (q_ref, do_ref, lse_ref, delta_ref) \
                    if own else (qh_ref, doh_ref, lseh_ref, deltah_ref)
                rows = pl.ds((qb if own else qb - nb) * b, b)
                carry, dst = _grads_block(
                    kblk, vblk, q[0, rows, :], do[0, rows, :],
                    lse[0, :, rows], delta[0, :, rows], carry, scale,
                    ((qb - kb) * b, True, window) if masked else None)
                _add_dq(dq_acc, 0, pl.ds(pl.multiple_of(j * c + qb * b, b),
                                         b), dst, kblk)
                return carry

            dk, dv = _walk(
                _bwd_spans(kb * b, b, b, nb + n_halo, True, window), q_block,
                (jnp.zeros((b, D), jnp.float32),
                 jnp.zeros((b, D), jnp.float32)), unrolled=True)
            dk_ref[0, pl.ds(kb * b, b), :] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[0, pl.ds(kb * b, b), :] = dv.astype(dv_ref.dtype)

    if n_c == 1:
        chunk(0)
    else:
        pl.when(j < n_c - 1)(functools.partial(chunk, halo))
        pl.when(j == n_c - 1)(functools.partial(chunk, 0))

    @pl.when(j == n_c - 1)
    def _write_dq():
        dq_ref[:] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_tiled_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                      dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale,
                      causal, window, bq, bk, n_qc, n_kc, reach,
                      sel_ref=None):
    """A chunk pair: grid (BH, k chunks, q chunks), dk and dv carried in
    VMEM over the q chunks; the pairs as in the forward's. `sel_ref`: the
    pair's tile of the selection transposed (keys by queries)."""
    cq, D = q_ref.shape[1:]
    ck = k_ref.shape[1]
    nqb, nkb = cq // bq, ck // bk
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _zero_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def pair(spans_of, mask_of=None):
        """Every k block's walk over the q chunk; `mask_of(apart)` is a
        masked block's mask from its first row's position less its first
        key's."""
        for kb in range(nkb):
            keys = pl.ds(kb * bk, bk)
            kblk, vblk = k_ref[0, keys, :], v_ref[0, keys, :]

            def q_block(qb, carry, masked):
                q0 = _start(qb, bq)
                rows = pl.ds(q0, bq)
                operands = (q_ref[0, rows, :], do_ref[0, rows, :],
                            lse_ref[0, :, rows], delta_ref[0, :, rows])
                live = functools.partial(mask_of, q0 - kb * bk) \
                    if masked else None
                if sel_ref is not None:
                    live = _kept(sel_ref, 0, keys, rows, live)
                carry, dst = _grads(kblk, vblk, *operands, carry, scale,
                                    live)
                _add_dq(dq_acc, 0, pl.ds(pl.multiple_of(i * cq + q0, bq), bq),
                        dst, kblk)
                return carry

            dk_acc[keys, :], dv_acc[keys, :] = _walk(
                spans_of(kb), q_block, (dk_acc[keys, :], dv_acc[keys, :]),
                unrolled=sel_ref is not None)

    mask = functools.partial(_apart, (bk, bq), 1)
    if not causal:
        pair(lambda kb: [(0, nqb, False)])
    else:
        pl.when(i == j)(functools.partial(
            pair, lambda kb: _bwd_spans(kb * bk, bq, bk, nqb, True, window),
            lambda apart: mask(apart, True, window)))
        if window is None:
            pl.when(i > j)(functools.partial(
                pair, lambda kb: [(0, nqb, False)]))
        else:
            pl.when(jnp.logical_and(i > j, i - j <= reach))(functools.partial(
                pair, lambda kb: [(0, nqb, True)],
                lambda apart: mask((i - j) * cq + apart, False, window)))

    @pl.when(i == n_qc - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(j == n_kc - 1, i == n_qc - 1))
    def _write_dq():
        dq_ref[:] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_selected(qp, kp, vp, dop, rows, select, plan, scale, interpret,
                  lengths):
    """`_bwd` of a causal head under a selection, from the padded operands:
    the resident or the tiled call with the transposed selection's tile of
    the chunk pair as one more input, fetched where q's is."""
    BH, Tp, D = qp.shape
    Tkp, (T, Tk) = kp.shape[1], lengths
    heads = BH // select.shape[0]
    cq, n_qc, ck, n_kc = plan.cq, plan.n_qc, plan.ck, plan.n_kc
    sp = jnp.pad(jnp.swapaxes(select, 1, 2),
                 ((0, 0), (0, Tkp - Tk), (0, Tp - T)))

    def q_chunk(j, i):
        return jnp.maximum(i, jnp.minimum(j, n_qc - 1))

    q_spec = _vmem((1, cq, D), lambda b, j, i: (b, q_chunk(j, i), 0))
    k_spec = _vmem((1, ck, D), lambda b, j, i: (b, j, 0))
    row_spec = _vmem((1, 1, cq), lambda b, j, i: (b, 0, q_chunk(j, i)))
    sel_spec = _vmem((1, ck, cq),
                     lambda b, j, i: (b // heads, j, q_chunk(j, i)))
    scratch = [pltpu.VMEM((1, Tp, D), jnp.float32)]
    if plan.schedule == "resident":
        kernel = functools.partial(
            _bwd_kernel, scale=scale, causal=True, bq=plan.bq, bk=plan.bk,
            own_delta=len(rows) == 1)
    else:
        kernel = functools.partial(
            _bwd_tiled_kernel, scale=scale, causal=True, window=None,
            bq=plan.bq, bk=plan.bk, n_qc=n_qc, n_kc=n_kc, reach=0)
        scratch += [pltpu.VMEM((ck, D), jnp.float32)] * 2
    n_in = 4 + len(rows)
    dq, dk, dv = pl.pallas_call(
        _with_select(kernel, n_in), interpret=interpret, name="mx_flash_bwd",
        out_shape=[jax.ShapeDtypeStruct((BH, Tp, D), qp.dtype),
                   jax.ShapeDtypeStruct((BH, Tkp, D), kp.dtype),
                   jax.ShapeDtypeStruct((BH, Tkp, D), vp.dtype)],
        grid=(BH, n_kc, n_qc),
        in_specs=[q_spec, k_spec, k_spec, q_spec] + [row_spec] * len(rows)
        + [sel_spec],
        out_specs=[_vmem((1, Tp, D), lambda b, j, i: (b, 0, 0)),
                   k_spec, k_spec],
        scratch_shapes=scratch,
        **_compiler_params(interpret, plan.nbytes),
    )(qp, kp, vp, dop, *rows, sp)
    return dq[:, :T], dk[:, :Tk], dv[:, :Tk]


def _one_tile(T, Tk, block_q, block_k):
    """Whether a head's scores are one block pair: the backward kernel then
    holds the whole of p^T and dp^T and takes `delta` from them."""
    return T <= block_q and Tk <= block_k


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k, interpret,
         window=None, select=None):
    """`o` is None for a one-tile head, whose kernel computes its own delta.
    `select` as `_fwd`'s; the kernels read it transposed."""
    BH, T, D = q.shape
    Tk, Dv = k.shape[1], v.shape[2]
    plan = _plan(True, BH, T, Tk, D, q.dtype, causal, block_q, block_k,
                 window, select is not None, Dv)
    cq, n_qc, ck, n_kc, G = plan.cq, plan.n_qc, plan.ck, plan.n_kc, plan.G
    Tp, Tkp = cq * n_qc, ck * n_kc
    if lse.shape[2] != Tp:
        # a wide head's backward chunks (`_wide_blocks`) pad T otherwise
        # than its forward's; a padded row adds nothing whatever its lse
        # (its q, dO and delta are zero)
        lse = jnp.pad(lse[:, :, :T], ((0, 0), (0, 0), (0, Tp - T)))
    assert lse.shape == (BH, 1, Tp), (lse.shape, Tp)
    own_delta = o is None
    rows = [lse]
    if not own_delta:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        # padded q rows: q = dO = 0 and delta = 0, so ds = p * (0 - 0) = 0
        rows.append(jnp.pad(delta, ((0, 0), (0, Tp - T)))[:, None, :])
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    dop = jnp.pad(do, ((0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tkp - Tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tkp - Tk), (0, 0)))
    scratch = [pltpu.VMEM((G, Tp, D), jnp.float32)]
    if select is not None:
        return _bwd_selected(qp, kp, vp, dop, rows, select, plan, scale,
                             interpret, (T, Tk))
    call = functools.partial(
        pl.pallas_call, interpret=interpret, name="mx_flash_bwd",
        out_shape=[jax.ShapeDtypeStruct((BH, Tp, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, Tkp, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tkp, Dv), v.dtype)])
    if plan.schedule == "resident":
        q_spec = _vmem((G, cq, D), lambda b, j, i: (b, i, 0))
        do_spec = _vmem((G, cq, Dv), lambda b, j, i: (b, i, 0))
        k_spec = _vmem((G, ck, D), lambda b, j, i: (b, j, 0))
        v_spec = _vmem((G, ck, Dv), lambda b, j, i: (b, j, 0))
        row_spec = _vmem((G, 1, cq), lambda b, j, i: (b, 0, i))
        dq, dk, dv = call(
            functools.partial(_bwd_kernel, scale=scale, causal=causal,
                              bq=block_q, bk=block_k, own_delta=own_delta,
                              window=window),
            grid=(BH // G, n_kc, n_qc),
            in_specs=[q_spec, k_spec, v_spec, do_spec]
            + [row_spec] * len(rows),
            out_specs=[_vmem((G, Tp, D), lambda b, j, i: (b, 0, 0)),
                       k_spec, v_spec],
            scratch_shapes=scratch,
            **_compiler_params(interpret, plan.nbytes),
        )(qp, kp, vp, dop, *rows)
    elif plan.schedule == "band":
        hb = plan.halo * plan.bk

        def after(j):
            return jnp.minimum((j + 1) * (ck // hb), Tp // hb - 1)

        own = _vmem((1, ck, D), lambda b, j: (b, j, 0))
        halo = _vmem((1, hb, D), lambda b, j: (b, after(j), 0))
        row = _vmem((1, 1, ck), lambda b, j: (b, 0, j))
        row_halo = _vmem((1, 1, hb), lambda b, j: (b, 0, after(j)))
        dq, dk, dv = call(
            functools.partial(_bwd_band_kernel, scale=scale, window=window,
                              b=plan.bk, halo=plan.halo, n_c=n_kc),
            grid=(BH, n_kc),
            in_specs=[own, halo, own, halo, row, row_halo, row, row_halo,
                      own, own],
            out_specs=[_vmem((1, Tp, D), lambda b, j: (b, 0, 0)), own, own],
            scratch_shapes=scratch,
            **_compiler_params(interpret, plan.nbytes, 2),
        )(qp, qp, dop, dop, rows[0], rows[0], rows[1], rows[1], kp, vp)
    else:
        def q_chunk(j, i):
            if causal:
                i = jnp.maximum(i, jnp.minimum(j, n_qc - 1))
            if window is not None:
                i = jnp.minimum(i, jnp.minimum(j + plan.halo, n_qc - 1))
            return i

        q_spec = _vmem((1, cq, D), lambda b, j, i: (b, q_chunk(j, i), 0))
        do_spec = _vmem((1, cq, Dv), lambda b, j, i: (b, q_chunk(j, i), 0))
        k_spec = _vmem((1, ck, D), lambda b, j, i: (b, j, 0))
        v_spec = _vmem((1, ck, Dv), lambda b, j, i: (b, j, 0))
        row_spec = _vmem((1, 1, cq), lambda b, j, i: (b, 0, q_chunk(j, i)))
        dq, dk, dv = call(
            functools.partial(_bwd_tiled_kernel, scale=scale, causal=causal,
                              window=window, bq=plan.bq, bk=plan.bk,
                              n_qc=n_qc, n_kc=n_kc, reach=plan.halo),
            grid=(BH, n_kc, n_qc),
            in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
            out_specs=[_vmem((1, Tp, D), lambda b, j, i: (b, 0, 0)),
                       k_spec, v_spec],
            scratch_shapes=scratch + [pltpu.VMEM((ck, D), jnp.float32),
                                      pltpu.VMEM((ck, Dv), jnp.float32)],
            **_compiler_params(interpret, plan.nbytes),
        )(qp, kp, vp, dop, *rows)
    return dq[:, :T], dk[:, :Tk], dv[:, :Tk]


# ---------------------------------------------------------------------------
# custom_vjp wrapper, (B, H, T, D) public layout
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q3, k3, v3, causal, scale, block_q, block_k, interpret,
           window=None):
    o, _ = _fwd(q3, k3, v3, causal, scale, block_q, block_k, interpret,
                window)
    return o


def _flash_fwd(q3, k3, v3, causal, scale, block_q, block_k, interpret,
               window=None):
    o, lse = _fwd(q3, k3, v3, causal, scale, block_q, block_k, interpret,
                  window)
    keep = None if _one_tile(q3.shape[1], k3.shape[1], block_q, block_k) \
        else o
    return o, (q3, k3, v3, keep, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q3, k3, v3, o, lse = res
    return _bwd(q3, k3, v3, o, lse, g, causal, scale, block_q, block_k,
                interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


# The same under a selection, a function of its own so that `_flash` stays
# the parent's: `select` int8 (B, T, T) is an operand without a gradient.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_select(q3, k3, v3, select, scale, block_q, block_k, interpret):
    o, _ = _fwd(q3, k3, v3, True, scale, block_q, block_k, interpret, None,
                select)
    return o


def _flash_select_fwd(q3, k3, v3, select, scale, block_q, block_k, interpret):
    o, lse = _fwd(q3, k3, v3, True, scale, block_q, block_k, interpret, None,
                  select)
    keep = None if _one_tile(q3.shape[1], k3.shape[1], block_q, block_k) \
        else o
    return o, (q3, k3, v3, select, keep, lse)


def _flash_select_bwd(scale, block_q, block_k, interpret, res, g):
    q3, k3, v3, select, o, lse = res
    grads = _bwd(q3, k3, v3, o, lse, g, True, scale, block_q, block_k,
                 interpret, None, select)
    return (*grads, None)


_flash_select.defvjp(_flash_select_fwd, _flash_select_bwd)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = 512, block_k: int = 512, window=None,
                    select=None):
    """Flash attention on (B, H, T, D) tensors; differentiable. v (and the
    output) may be of another width than q and k, (B, H, Tk, Dv): latent
    attention's heads of 192 and 128 (the scale then defaults to q's).
    The backward of a long head wider than one lane tile walks blocks of
    at most 256 rows (the module's docstring, "Width").

    `select` (with `causal`; PR 34): int8 (B, T, T), not zero where query t
    keeps key t'; a key is then live where causal and kept, the same for
    every head of a row. The forward and backward kernels mask every block
    they visit by the selection's tile (one more operand, fetched with K's
    chunk); they visit what a causal head visits, so the first version costs
    a dense causal head's work, and a block pair in which nothing is kept is
    masked, not skipped. None is the parent's kernels.

    `window` (with `causal`): query t sees the keys t - window < t' <= t.
    The walks over blocks then start and end at the blocks the band touches
    and mask inside its edge blocks (the module's docstring has the
    schedules); None is the parent's kernels.

    Uses the Pallas kernels on TPU (or in interpret mode when
    MXNET_PALLAS_INTERPRET=1); falls back to the lax.scan blockwise
    implementation elsewhere — same math, same signature. `block_q` and
    `block_k` are upper limits; the kernels walk blocks of whole lane tiles.
    Under the trainer's multi-device GSPMD trace the kernels run per shard of
    the batch (the module's docstring); B must divide over that axis.
    """
    from .. import attention as _attn_ops
    from ..registry import batch_partition
    B, H, T, D = q.shape
    Dv = v.shape[3]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if Dv != D and (window is not None or select is not None):
        raise MXNetError("flash attention: a window or a selection takes v "
                         "as wide as q and k")
    if window is not None:
        if not causal or int(window) < 1 or q.shape[2] != k.shape[2]:
            raise MXNetError("flash attention: a window needs causal "
                             "self-attention and at least one key a query")
        window = int(window)
    if select is not None and (
            not causal or window is not None or q.shape[2] != k.shape[2]
            or select.shape != (B, T, T)):
        raise MXNetError(
            "flash attention: a selection is (B, T, T) over causal "
            "self-attention without a window")
    on_tpu = _on_tpu(q)
    kernels = on_tpu or _use_interpret()
    # XLA cannot partition a Mosaic call by itself. Where the surrounding
    # trace is the trainer's GSPMD step on several devices, each device runs
    # the kernels, forward and backward, on its own rows of the batch: B is
    # the major part of the leading B * H, so no collective is added
    part = batch_partition.get()
    partitioned = kernels and part is not None and part[0].size > 1
    Tk = k.shape[2]
    bq, bk = _block(T, block_q), _block(Tk, block_k)
    if select is not None and partitioned:
        raise MXNetError("flash attention: a selection is not partitioned "
                         "over a mesh axis yet")
    schedule = _plan(False, B * H, T, Tk, D, q.dtype, bool(causal), bq, bk,
                     window, select is not None, Dv).schedule \
        if kernels else None
    wide = _wide_blocks(D, bq, bk)
    _attn_ops.count_route(
        "flash_partitioned" if partitioned else
        "flash_select" if select is not None else
        "flash" if window is None else "flash_window", schedule,
        f"{max(bq, bk)}->{max(wide)}"
        if schedule == "tiled" and wide != (bq, bk) else None)
    if not kernels:
        # The fallback is differentiated by jax AS WRITTEN (no custom_vjp):
        # its gradient contract — matches the dense-softmax VJP at every
        # shape, including T not a multiple of block_size and causal
        # masking — holds because the scan masks via jnp.where against
        # CONSTANT biases (masked lanes contribute zero cotangent), pinned
        # by tests/test_pallas_kernels.py::test_fallback_grad_*.
        # It keeps the 256 keys a block that every caller of the registered
        # op has had: the kernels' limits grew, CPU numerics did not move.
        return _attn_ops.blockwise_attention(
            q, k, v, causal=causal, scale=scale, block_size=min(block_k, 256),
            window=window, select=select)

    if select is not None:
        return _flash_select(
            q.reshape(B * H, T, D), k.reshape(B * H, Tk, D),
            v.reshape(B * H, Tk, D), select.astype(jnp.int8), float(scale),
            int(bq), int(bk), not on_tpu).reshape(B, H, T, D)

    def call(q3, k3, v3):
        return _flash(q3, k3, v3, bool(causal), float(scale), int(bq),
                      int(bk), not on_tpu, window)

    if partitioned:
        mesh, axis = part
        if B % mesh.shape[axis]:
            raise MXNetError(
                f"flash attention: a batch of {B} rows cannot be divided "
                f"over the {mesh.shape[axis]} devices of mesh axis {axis!r}")
        call = jax.shard_map(call, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False)
    out = call(q.reshape(B * H, T, D), k.reshape(B * H, Tk, D),
               v.reshape(B * H, Tk, Dv))
    return out.reshape(B, H, T, Dv)
