"""Flash attention as Pallas TPU kernels (forward + backward).

Replaces the reference's cuDNN/hand-CUDA attention path
(src/operator/contrib/transformer.cc:650-819 interleaved_matmul_selfatt_*)
with the TPU equivalent: blocked softmax(QK^T)V with online log-sum-exp,
computed in VMEM with MXU matmuls, O(T) memory. bfloat16 (or whatever the
inputs are) operands, float32 accumulation and softmax statistics.

Schedule. Two Mosaic calls, `mx_flash_fwd` and `mx_flash_bwd`, each on a grid
(heads / G, q chunks, k chunks) or (heads / G, k chunks, q chunks). A chunk is
a run of blocks that stays in VMEM; the walk over its blocks is a loop inside
the kernel, so a grid step holds tens of microseconds of products and each
operand is one DMA. While a head's rows fit the VMEM budget (`_chunks`;
T <= 4096 at d = 64 in bfloat16) there is one chunk a side: Q, K, V (and dO)
of G heads are resident, fetched once, and under `causal` the loops stop at
the diagonal, so masked blocks cost nothing. Longer sequences stream chunks
along the last grid axis ('arbitrary'), carrying the accumulators in VMEM
scratch. Blocks are up to 512 x 512 (`_block`). A resident head of up to
`_UNROLL_PAIRS` block pairs is walked by unrolled loops with static bounds,
causal or not: the scheduler then overlaps one block's products with the
next block's elementwise work, which is worth a third of the time on the
v5e; longer heads take `fori_loop`s with bounds computed in the kernel.
The choice is made from T, Tk, d, the dtype and `causal` alone.

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
import os

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ...base import MXNetError

_NEG = -1e30  # finite mask value: -inf breeds nans in exp(-inf - -inf)
_LANES = 128
# what one grid step's blocks may take of VMEM, both pipeline buffers counted
_VMEM_BLOCK_BYTES = 16 << 20
_VMEM_LIMIT_MAX = 100 << 20     # of v5e's 128 MiB
_STEP_FLOPS = 1e9               # products a grid step should hold at least
_UNROLL_PAIRS = 16              # block pairs of a head that still unroll


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
# The schedule, from what the call can see: lengths, head width, dtype
# ---------------------------------------------------------------------------

def _block(L, limit):
    """The block, in whole lane tiles up to `limit`, that walks a side of
    length L cheapest: the padded length times (1 + 200 / block), the cost
    of a block's step against its work as read on the v5e (128: 4.0 ms,
    256: 2.4, 512: 2.0 for forward and backward at 192 x 1024 x 64). 512 for
    1000, 1024 or 2400; 384 for 1100."""
    blocks = [_LANES * b for b in range(1, max(limit // _LANES, 1) + 1)]
    return min(blocks, key=lambda b: (_ceil_to(L, b) * (1 + 200 / b), -b))


def _row_bytes(D, dtype):
    """VMEM bytes one row of a head costs the backward call (the larger of
    the two): q, k, v, dO in and dq, dk, dv out in both pipeline buffers, and
    the float32 dq accumulator; the head width pads to a lane tile."""
    Dp = _ceil_to(D, _LANES)
    return 2 * 7 * Dp * jnp.dtype(dtype).itemsize + 4 * Dp


def _chunks(L, block, D, dtype):
    """(chunk, number of chunks) for a side of length L walked in `block`s:
    one chunk while a head's rows fit the VMEM budget, else equal chunks of
    whole blocks."""
    cmax = max(block, _VMEM_BLOCK_BYTES // _row_bytes(D, dtype)
               // block * block)
    n = -(-_ceil_to(L, block) // cmax)
    return _ceil_to(-(-L // n), block), n


def _heads_per_step(BH, head_bytes, head_flops):
    """G: the most heads (a divisor of BH) a grid step can take inside the
    VMEM budget, no more than give it `_STEP_FLOPS` of products."""
    want = max(1, min(_VMEM_BLOCK_BYTES // max(head_bytes, 1),
                      -(-int(_STEP_FLOPS) // max(int(head_flops), 1))))
    return max(g for g in range(1, min(want, BH) + 1) if BH % g == 0)


def _plan(backward, BH, T, Tk, D, dtype, causal, block_q, block_k):
    """(cq, n_qc, ck, n_kc, G, bytes): chunk and number of chunks a side,
    heads a grid step, and the VMEM that step's blocks take."""
    cq, n_qc = _chunks(T, block_q, D, dtype)
    ck, n_kc = _chunks(Tk, block_k, D, dtype)
    Dp, isz = _ceil_to(D, _LANES), jnp.dtype(dtype).itemsize
    if backward:
        # q, dO, k, v in and dk, dv out in both pipeline buffers; dq whole
        # with its float32 accumulator; lse and delta; dk and dv carried in
        # float32 where q streams
        nbytes = (2 * (2 * cq + 4 * ck) * Dp * isz
                  + cq * n_qc * Dp * (2 * isz + 4) + 2 * 2 * 8 * cq * 4
                  + (n_qc > 1) * 2 * ck * Dp * 4)
        flops = 10 * T * Tk * D
    else:
        # q, k, v in and o, lse out; the carried output, max and sum where
        # k streams
        nbytes = (2 * 2 * (cq + ck) * Dp * isz + 2 * 8 * cq * 4
                  + (n_kc > 1) * cq * (Dp + 2 * _LANES) * 4)
        flops = 4 * T * Tk * D
    G = 1
    if n_qc == 1 and n_kc == 1:
        G = _heads_per_step(BH, nbytes, flops // (2 if causal else 1))
    return cq, n_qc, ck, n_kc, G, G * nbytes


def _compiler_params(interpret, block_bytes):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=int(min(_VMEM_LIMIT_MAX,
                                 max(32 << 20, block_bytes + (16 << 20)))))}


# loop bounds are Python ints where the grid has one chunk a side and the
# outer walk is unrolled (or nothing depends on the outer block: not causal)

def _static(*xs):
    return all(isinstance(x, int) for x in xs)


def _imin(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _imax(a, b):
    return max(a, b) if _static(a, b) else jnp.maximum(a, b)


def _idiv(a, b):
    """a // b for a >= 0."""
    return a // b if _static(a) else lax.div(a, jnp.int32(b))


def _clamp(x, hi):
    return _imin(_imax(x, 0), hi)


def _inner(lo, hi, body, init):
    """The walk over the inner blocks. A static trip count of a few steps
    unrolls: the scheduler then overlaps the products of one block with the
    elementwise work of the next (a third of the forward's time on the v5e)."""
    if _static(lo, hi):
        if hi <= lo:
            return init
        return lax.fori_loop(lo, hi, body, init, unroll=hi - lo <= 8)
    return lax.fori_loop(lo, hi, body, init)


def _outer(n, body, unrolled):
    """The walk over the outer blocks of a head: a Python loop where the
    head is resident and short, so that under `causal` too every inner
    bound is static."""
    if unrolled:
        for b in range(n):
            body(b)
    else:
        lax.fori_loop(0, n, lambda b, c: body(b), None)


def _start(i, block):
    return i * block if _static(i) else pl.multiple_of(i * block, block)


# ---------------------------------------------------------------------------
# Forward: grid (BH / G, q chunks, k chunks); k blocks walked inside
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *carried, scale, causal,
                bq, bk, t_k, n_qc, n_kc):
    G, cq, _ = q_ref.shape
    ck = k_ref.shape[1]
    nqb, nkb = cq // bq, ck // bk
    i = pl.program_id(1) if n_qc > 1 else 0
    j = pl.program_id(2) if n_kc > 1 else 0
    k_base = j * ck
    # blocks of this chunk with a real key, and those with real keys only
    keys = _imax(t_k - k_base, 0)
    n_valid = _imin(_idiv(keys + bk - 1, bk), nkb)
    n_whole = _imin(_idiv(keys, bk), nkb)

    def q_block(g, qb):
        q0 = _start(qb, bq)
        row0 = i * cq + q0
        q = q_ref[g, pl.ds(q0, bq), :]

        def k_block(kb, carry, masked):
            m, l, acc = carry
            k0 = _start(kb, bk)
            kblk = k_ref[g, pl.ds(k0, bk), :]
            vblk = v_ref[g, pl.ds(k0, bk), :]
            # matmul operands stay in the input dtype (bf16 on the fast
            # path); preferred_element_type makes the MXU accumulate in f32
            s = lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if masked:
                k_pos = k_base + k0 + lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                mask = k_pos < t_k
                if causal:
                    q_pos = row0 + lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 0)
                    mask = jnp.logical_and(mask, q_pos >= k_pos)
                s = jnp.where(mask, s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        # causal: a block is live unless it sits wholly above the diagonal,
        # and needs no mask once it sits wholly on or below it
        n_live, n_plain = n_valid, n_whole
        if causal:
            n_live = _imin(n_live, _idiv(
                _imax(row0 + bq - k_base, 0) + bk - 1, bk))
            n_plain = _imin(n_plain, _idiv(_imax(row0 + 1 - k_base, 0), bk))
        n_plain = _imin(n_plain, n_live)

        if n_kc > 1:
            acc_ref, m_ref, l_ref = carried
            carry = (m_ref[pl.ds(q0, bq), :], l_ref[pl.ds(q0, bq), :],
                     acc_ref[pl.ds(q0, bq), :])
        else:
            carry = (jnp.full((bq, 1), _NEG, jnp.float32),
                     jnp.zeros((bq, 1), jnp.float32),
                     jnp.zeros((bq, q.shape[-1]), jnp.float32))
        carry = _inner(0, n_plain,
                       functools.partial(k_block, masked=False), carry)
        m, l, acc = _inner(n_plain, n_live,
                           functools.partial(k_block, masked=True), carry)

        def finalize():
            ls = jnp.maximum(l, 1e-30)
            o_ref[g, pl.ds(q0, bq), :] = (acc / ls).astype(o_ref.dtype)
            # the statistics leave along lanes: (bq, 1) -> (1, bq)
            lse = jnp.broadcast_to(m + jnp.log(ls), (bq, _LANES))
            lse_ref[g, :, pl.ds(q0, bq)] = lse.T[:1]

        if n_kc > 1:
            m_ref[pl.ds(q0, bq), :] = m
            l_ref[pl.ds(q0, bq), :] = l
            acc_ref[pl.ds(q0, bq), :] = acc
            pl.when(j == n_kc - 1)(finalize)
        else:
            finalize()

    if n_kc > 1:
        acc_ref, m_ref, l_ref = carried

        @pl.when(j == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            l_ref[:] = jnp.zeros_like(l_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG)

    def head(g, _):
        _outer(nqb, functools.partial(q_block, g),
               _static(i, j) and nqb * nkb <= _UNROLL_PAIRS)

    lax.fori_loop(0, G, head, None)


# `_fwd` and `_bwd` are jitted so that the layers of a model share one trace of
# the kernel and one lowering to Mosaic: traced anew for every layer, twelve
# layers cost the step's build 2 s more on the v5e's host (PERF.md, PR 31)

@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    """-> o (BH, T, D), lse (BH, 1, Tp): T padded to whole blocks, on lanes."""
    BH, T, D = q.shape
    Tk = k.shape[1]
    cq, n_qc, ck, n_kc, G, step_bytes = _plan(
        False, BH, T, Tk, D, q.dtype, causal, block_q, block_k)
    Tp, Tkp = cq * n_qc, ck * n_kc
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tkp - Tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tkp - Tk), (0, 0)))
    scratch = [] if n_kc == 1 else [
        pltpu.VMEM((cq, D), jnp.float32),
        pltpu.VMEM((cq, 1), jnp.float32),
        pltpu.VMEM((cq, 1), jnp.float32)]
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             bq=block_q, bk=block_k, t_k=Tk,
                             n_qc=n_qc, n_kc=n_kc)
    o, lse = pl.pallas_call(
        kern,
        grid=(BH // G, n_qc, n_kc),
        in_specs=[
            pl.BlockSpec((G, cq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((G, ck, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((G, ck, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((G, cq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((G, 1, cq), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tp), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="mx_flash_fwd",
        **_compiler_params(interpret, step_bytes),
    )(qp, kp, vp)
    return o[:, :T], lse


# ---------------------------------------------------------------------------
# Backward: grid (BH / G, k chunks, q chunks); k blocks outside, q blocks
# inside, scores transposed (k rows, q along lanes)
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, *refs, scale, causal,
                bq, bk, n_qc, n_kc, own_delta):
    # a one-tile head (`_one_tile`) is handed no delta: it computes its own
    delta_ref = None if own_delta else refs[0]
    dq_ref, dk_ref, dv_ref, dq_acc, *carried = refs[0 if own_delta else 1:]
    G, cq, D = q_ref.shape
    ck = k_ref.shape[1]
    nqb, nkb = cq // bq, ck // bk
    j = pl.program_id(1) if n_kc > 1 else 0
    i = pl.program_id(2) if n_qc > 1 else 0
    q_base = i * cq
    first = jnp.logical_and(j == 0, i == 0) if not _static(i, j) else True
    last = jnp.logical_and(j == n_kc - 1, i == n_qc - 1) \
        if not _static(i, j) else True

    def when(cond, fn):
        if cond is True:
            fn()
        else:
            pl.when(cond)(fn)

    def _zero_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    when(first, _zero_dq)

    def k_block(g, kb):
        k0 = _start(kb, bk)
        col0 = j * ck + k0
        kblk = k_ref[g, pl.ds(k0, bk), :]
        vblk = v_ref[g, pl.ds(k0, bk), :]

        def q_block(qb, carry, masked):
            dk, dv = carry
            q0 = _start(qb, bq)
            qblk = q_ref[g, pl.ds(q0, bq), :]
            dob = do_ref[g, pl.ds(q0, bq), :]
            lse = lse_ref[g, :, pl.ds(q0, bq)]          # (1, bq)
            delta = None if own_delta else delta_ref[g, :, pl.ds(q0, bq)]
            st = lax.dot_general(kblk, qblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
            if masked:
                k_pos = col0 + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
                q_pos = q_base + q0 + lax.broadcasted_iota(
                    jnp.int32, (bk, bq), 1)
                st = jnp.where(q_pos >= k_pos, st, _NEG)
            # padded rows and keys need no mask here: their q, k, v, dO are
            # zero, so every product they enter adds nothing to a kept row
            pt = jnp.exp(st - lse)
            dv = dv + lax.dot_general(
                pt.astype(dob.dtype), dob, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = lax.dot_general(vblk, dob, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            if own_delta:
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
            rows = pl.ds(q_base + q0, bq)
            dq_acc[g, rows, :] = dq_acc[g, rows, :] + lax.dot_general(
                dst, kblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        # causal: q blocks wholly above the diagonal are skipped, those
        # wholly on or below it need no mask
        n_dead, n_cross = 0, 0
        if causal:
            n_dead = _clamp(_idiv(_imax(col0 - q_base, 0), bq), nqb)
            n_cross = _clamp(_idiv(_imax(col0 + bk - 1 - q_base, 0)
                                   + bq - 1, bq), nqb)
            n_cross = _imax(n_cross, n_dead)

        if n_qc > 1:
            dk_acc, dv_acc = carried
            carry = (dk_acc[pl.ds(k0, bk), :], dv_acc[pl.ds(k0, bk), :])
        else:
            carry = (jnp.zeros((bk, D), jnp.float32),
                     jnp.zeros((bk, D), jnp.float32))
        carry = _inner(n_dead, n_cross,
                       functools.partial(q_block, masked=True), carry)
        dk, dv = _inner(n_cross, nqb,
                        functools.partial(q_block, masked=False), carry)

        def finalize():
            dk_ref[g, pl.ds(k0, bk), :] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[g, pl.ds(k0, bk), :] = dv.astype(dv_ref.dtype)

        if n_qc > 1:
            dk_acc[pl.ds(k0, bk), :] = dk
            dv_acc[pl.ds(k0, bk), :] = dv
            pl.when(i == n_qc - 1)(finalize)
        else:
            finalize()

    if n_qc > 1:
        dk_acc, dv_acc = carried

        @pl.when(i == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    def head(g, _):
        _outer(nkb, functools.partial(k_block, g),
               _static(i, j) and nqb * nkb <= _UNROLL_PAIRS)

    lax.fori_loop(0, G, head, None)

    def _write_dq():
        dq_ref[:] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    when(last, _write_dq)


def _one_tile(T, Tk, block_q, block_k):
    """Whether a head's scores are one block pair: the backward kernel then
    holds the whole of p^T and dp^T and takes `delta` from them."""
    return T <= block_q and Tk <= block_k


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k, interpret):
    """`o` is None for a one-tile head, whose kernel computes its own delta."""
    BH, T, D = q.shape
    Tk = k.shape[1]
    cq, n_qc, ck, n_kc, G, step_bytes = _plan(
        True, BH, T, Tk, D, q.dtype, causal, block_q, block_k)
    Tp, Tkp = cq * n_qc, ck * n_kc
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
    if n_qc > 1:
        scratch += [pltpu.VMEM((ck, D), jnp.float32),
                    pltpu.VMEM((ck, D), jnp.float32)]
    kern = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                             bq=block_q, bk=block_k, n_qc=n_qc, n_kc=n_kc,
                             own_delta=own_delta)
    q_spec = pl.BlockSpec((G, cq, D), lambda b, j, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((G, ck, D), lambda b, j, i: (b, j, 0),
                          memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((G, 1, cq), lambda b, j, i: (b, 0, i),
                            memory_space=pltpu.VMEM)
    dq, dk, dv = pl.pallas_call(
        kern,
        grid=(BH // G, n_kc, n_qc),
        in_specs=[q_spec, k_spec, k_spec, q_spec] + [row_spec] * len(rows),
        out_specs=[
            pl.BlockSpec((G, Tp, D), lambda b, j, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            k_spec, k_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tkp, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tkp, D), v.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="mx_flash_bwd",
        **_compiler_params(interpret, step_bytes),
    )(qp, kp, vp, dop, *rows)
    return dq[:, :T], dk[:, :Tk], dv[:, :Tk]


# ---------------------------------------------------------------------------
# custom_vjp wrapper, (B, H, T, D) public layout
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, scale, block_q, block_k, interpret):
    o, _ = _fwd(q3, k3, v3, causal, scale, block_q, block_k, interpret)
    return o


def _flash_fwd(q3, k3, v3, causal, scale, block_q, block_k, interpret):
    o, lse = _fwd(q3, k3, v3, causal, scale, block_q, block_k, interpret)
    keep = None if _one_tile(q3.shape[1], k3.shape[1], block_q, block_k) \
        else o
    return o, (q3, k3, v3, keep, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q3, k3, v3, o, lse = res
    return _bwd(q3, k3, v3, o, lse, g, causal, scale, block_q, block_k,
                interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = 512, block_k: int = 512):
    """Flash attention on (B, H, T, D) tensors; differentiable.

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
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    on_tpu = _on_tpu(q)
    kernels = on_tpu or _use_interpret()
    # XLA cannot partition a Mosaic call by itself. Where the surrounding
    # trace is the trainer's GSPMD step on several devices, each device runs
    # the kernels, forward and backward, on its own rows of the batch: B is
    # the major part of the leading B * H, so no collective is added
    part = batch_partition.get()
    partitioned = kernels and part is not None and part[0].size > 1
    _attn_ops.count_route("flash_partitioned" if partitioned else "flash")
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
            q, k, v, causal=causal, scale=scale, block_size=min(block_k, 256))
    Tk = k.shape[2]
    bq, bk = _block(T, block_q), _block(Tk, block_k)

    def call(q3, k3, v3):
        return _flash(q3, k3, v3, bool(causal), float(scale), int(bq),
                      int(bk), not on_tpu)

    if partitioned:
        mesh, axis = part
        if B % mesh.shape[axis]:
            raise MXNetError(
                f"flash attention: a batch of {B} rows cannot be divided "
                f"over the {mesh.shape[axis]} devices of mesh axis {axis!r}")
        call = jax.shard_map(call, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False)
    out = call(q.reshape(B * H, T, D), k.reshape(B * H, Tk, D),
               v.reshape(B * H, Tk, D))
    return out.reshape(B, H, T, D)
