"""The indexer's scores and their exact top-k as one Pallas TPU kernel a
group of query chunks (`ops/sparse_select.py: indexer_select` calls it).

A grid step holds `R` queries against the keys up to the group's end, and
keeps everything it computes in VMEM, transposed (keys along sublanes,
queries along lanes, so that every per-query tally is a lane-dense row):

1. **Scores.** Key block by key block, for each of the indexer's heads
   `k (Kb, di) @ q_h^T (di, R)` on the MXU with float32 accumulation, the
   ReLU, times `w[h]`, summed over heads; then the scale, `-inf` past the
   diagonal and past `T`, and the float32 turned into an int32 that orders
   as the floats do (-0.0 as 0.0). Key blocks past the step's last query
   are never computed, read or counted.
2. **The exact top-k.** The k-th largest, bit by bit from the top: a pass
   compares the scratch with a candidate a query and counts a query the
   keys at or above it. The passes stop as soon as every query of the step
   has exactly `top_k` keys at or above its cut (the set is then whole and
   no key ties across the cut); only where a query still has more keys on
   its cut than it wants after all 32 do the passes over the position run
   (`tie_blocks` counts those steps), keeping of the tied keys the lowest
   positions. That is `lax.top_k`'s set, ties and all.
3. **The packed set** is written directly: bit j of byte m is key
   j * Tp / 8 + m, so the eight slabs of the keep mask are shifted and ORed,
   transposed to rows of queries and stored. Beside it, a step's report:
   the keys kept a query in every `chunk` x `chunk` tile, and whether the
   query ran the position passes.

Queries before `top_k` keep every key they see; a group that ends at or
before `top_k` computes no score at all. Off the TPU the same kernels run in
interpret mode."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _VMEM_LIMIT_MAX

_ROWS = 128         # queries a grid step: the lanes of every tally
_KEYS = 512         # keys a step of the score and counting loops
_GROUPS = 8         # calls a set: a group of query chunks against its keys
_INT_MIN = -(1 << 31)
_INT_MAX = (1 << 31) - 1


def _ordered(x):
    """float32 -> int32 that orders as the floats do (-0.0 as 0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _down_rows(x):
    """(n, R) -> (1, R): the sum over the sublanes, as vreg adds first."""
    if x.shape[0] % 8 == 0 and x.shape[0] > 8:
        x = jnp.sum(x.reshape(x.shape[0] // 8, 8, x.shape[1]), axis=0)
    return jnp.sum(x, axis=0, keepdims=True)


def _any(row):
    """bool (1, R) -> a scalar: whether any lane is set."""
    return jnp.max(row.astype(jnp.int32)) > 0


class _Plan:
    """The static sizes of one call: queries a step `R`, keys a loop step
    `Kb`, key rows of a packing slab `L`, tiles of the report."""

    def __init__(self, T, Tp, chunk, top_k, Hi, di, scale):
        self.T, self.Tp, self.chunk, self.top_k = T, Tp, chunk, top_k
        self.Hi, self.di, self.scale = Hi, di, scale
        self.R = math.gcd(chunk, _ROWS)
        self.Kb = math.gcd(chunk, _KEYS)
        self.n = Tp // 8
        self.L = math.gcd(self.n, chunk, _KEYS)
        self.tiles = Tp // chunk
        self.rows = -(-(self.tiles + 1) // 8) * 8    # of the report block


def _select(plan, E, q0, t, qT_ref, k_ref, w_ref, scr, cut_ref):
    """Steps 1 and 2 for the step's queries `t` (1, R) from `q0`: (the signed
    cut a query keeps at or above, the queries that ran the position
    passes), both (1, R)."""
    R, Kb, T, top_k = plan.R, plan.Kb, plan.T, plan.top_k
    live = (q0 + R - 1) // Kb + 1           # key blocks some query sees
    w = w_ref[0].astype(jnp.float32)         # (Hi, R)

    def score_block(j, carry):
        start = pl.multiple_of(j * Kb, Kb)
        kb = k_ref[0, pl.ds(start, Kb), :]
        acc = jnp.zeros((Kb, R), jnp.float32)
        for h in range(plan.Hi):
            s = jnp.dot(kb, qT_ref[0, h], preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w[h:h + 1]
        pos = start + lax.broadcasted_iota(jnp.int32, (Kb, 1), 0)
        seen = jnp.logical_and(pos <= t, t < T)
        scr[pl.ds(start, Kb), :] = _ordered(
            jnp.where(seen, acc * plan.scale, -jnp.inf))
        return carry

    lax.fori_loop(0, live, score_block, 0)

    def count(hit):
        """(1, R): the live keys a query for which `hit(slab, start)`,
        added a sublane tile at a time and over the sublanes once."""
        def body(j, acc):
            start = pl.multiple_of(j * Kb, Kb)
            x = hit(scr[pl.ds(start, Kb), :], start).astype(jnp.int32)
            return acc + jnp.sum(x.reshape(Kb // 8, 8, R), axis=0)
        return jnp.sum(lax.fori_loop(0, live, body,
                                     jnp.zeros((8, R), jnp.int32)),
                       axis=0, keepdims=True)

    # the k-th largest as unsigned bits (the int32 codes shifted by 2**31),
    # and how many keys are at or above the cut so far
    needing = jnp.logical_and(t >= top_k, t < T)

    def unresolved(c):
        return _any(jnp.logical_and(needing, c != top_k))

    def value_bit(carry):
        i, tau, c = carry
        cand = tau | lax.shift_left(jnp.int32(1), 31 - i)
        m = count(lambda s, _: s >= (cand ^ jnp.int32(_INT_MIN)))
        enough = m >= top_k
        return i + 1, jnp.where(enough, cand, tau), jnp.where(enough, m, c)

    _, tau, c = lax.while_loop(
        lambda carry: jnp.logical_and(carry[0] < 32, unresolved(carry[2])),
        value_bit, (jnp.int32(0), jnp.zeros((1, R), jnp.int32),
                    jnp.full((1, R), live * Kb, jnp.int32)))
    tau = jnp.where(needing, tau ^ jnp.int32(_INT_MIN), jnp.int32(_INT_MIN))
    tied = jnp.logical_and(needing, c > top_k)
    cut_ref[...] = jnp.full((1, R), _INT_MAX, jnp.int32)

    @pl.when(_any(tied))
    def _():        # of the keys on the cut, the lowest positions wanted
        want = top_k - count(lambda s, _: s > tau)
        bits = max(E.bit_length(), 1)

        def position_bit(i, m):
            cand = m | lax.shift_left(jnp.int32(1), bits - 1 - i)
            few = count(lambda s, start: jnp.logical_and(
                s == tau, start + lax.broadcasted_iota(
                    jnp.int32, (s.shape[0], 1), 0) < cand)) < want
            return jnp.where(few, cand, m)

        m = lax.fori_loop(0, bits, position_bit, jnp.zeros((1, R), jnp.int32))
        cut_ref[...] = jnp.where(tied, m, _INT_MAX)

    return tau, tied


def _kernel(plan, E, first_row, scored, n_in, *refs):
    R, L, n, chunk = plan.R, plan.L, plan.n, plan.chunk
    o_ref, rep_ref = refs[n_in:n_in + 2]
    q0 = first_row + pl.program_id(1) * R
    t = q0 + lax.broadcasted_iota(jnp.int32, (1, R), 1)
    if scored:
        qT_ref, k_ref, w_ref = refs[:3]
        scr, cut_ref = refs[n_in + 2:]
        tau, tied = _select(plan, E, q0, t, qT_ref, k_ref, w_ref, scr,
                            cut_ref)
        cut = cut_ref[...]
    else:
        tied = jnp.zeros((1, R), bool)

    tiles = {}
    for m in range(n // L):
        byte = jnp.zeros((L, R), jnp.int32)
        for j in range(8):
            start = j * n + m * L
            if start >= E:          # past the group's keys: nothing kept
                continue
            pos = start + lax.broadcasted_iota(jnp.int32, (L, 1), 0)
            keep = jnp.logical_and(pos <= t, t < plan.T)
            if scored:
                s = scr[start:start + L, :]
                keep = jnp.logical_and(keep, jnp.logical_or(
                    s > tau, jnp.logical_and(s == tau, pos <= cut)))
            keep = keep.astype(jnp.int32)
            byte = byte | (keep << j)
            tile = start // chunk
            tiles[tile] = tiles.get(tile, 0) + _down_rows(keep)
        o_ref[0, :, m * L:(m + 1) * L] = byte.T.astype(o_ref.dtype)

    rep_ref[0, 0] = jnp.zeros((plan.rows, R), jnp.int32)
    for tile, kept in tiles.items():
        rep_ref[0, 0, tile:tile + 1, :] = kept
    rep_ref[0, 0, plan.tiles:plan.tiles + 1, :] = tied.astype(jnp.int32)


def _params(interpret, nbytes):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(min(_VMEM_LIMIT_MAX,
                                 max(32 << 20, nbytes + (16 << 20)))))}


def _group(plan, packed, qT, k, w, first, last, interpret):
    """Rows `first` .. `last` of the packed set (B, Tp, Tp / 8) int8 against
    the keys before `last`, written in place into `packed` (None: a new
    array whose other rows hold nothing yet): (the set, the report of the
    group's steps (B, steps, rows, R) int32)."""
    B = qT.shape[0]
    R, E = plan.R, last
    steps, blk0 = (last - first) // R, first // R
    scored = E > plan.top_k
    out_specs = [pl.BlockSpec((1, R, plan.n), lambda b, i: (b, blk0 + i, 0)),
                 pl.BlockSpec((1, 1, plan.rows, R), lambda b, i: (b, i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, plan.Tp, plan.n), jnp.int8),
                 jax.ShapeDtypeStruct((B, steps, plan.rows, R), jnp.int32)]
    nbytes = 2 * R * plan.n + 2 * plan.rows * R * 4
    ins, in_specs, scratch = [], [], []
    if scored:
        ins = [qT, k[:, :E], w]
        in_specs = [
            pl.BlockSpec((1, plan.Hi, plan.di, R),
                         lambda b, i: (b, 0, 0, blk0 + i)),
            pl.BlockSpec((1, E, plan.di), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, plan.Hi, R), lambda b, i: (b, 0, blk0 + i))]
        scratch = [pltpu.VMEM((E, R), jnp.int32),
                   pltpu.VMEM((1, R), jnp.int32)]
        nbytes += E * R * 4 + 2 * (E * 128 * 2 + plan.Hi * plan.di * R * 2
                                   + plan.Hi * R * 4)
    aliases = {}
    if packed is not None:
        aliases = {len(ins): 0}
        ins.append(packed)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    kernel = lambda *refs: _kernel(plan, E, first, scored, len(ins), *refs)
    return pl.pallas_call(
        kernel, name="mx_index_select", interpret=interpret,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(B, steps), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        input_output_aliases=aliases,
        **_params(interpret, nbytes),
    )(*ins)


def select(q, k, w, top_k, chunk, Tp, interpret):
    """q (B, Hi, T, di), k (B, T, di), w (B, T, Hi) -> (the packed set, int8
    (B, Tp, Tp / 8) with Tp = T in whole chunks; the keys kept in each
    chunk x chunk tile, int32 (B, chunks, tiles); the steps that ran the
    position passes, int32). The queries in up to `_GROUPS` groups of
    chunks, a group against the keys up to its end: one call a group."""
    B, Hi, T, di = q.shape
    plan = _Plan(T, Tp, chunk, top_k, Hi, di, (di * Hi) ** -0.5)
    pad = lambda a, axis: jnp.pad(a, [(0, Tp - T if i == axis else 0)
                                      for i in range(a.ndim)])
    qT = jnp.swapaxes(pad(q, 2), 2, 3)          # queries along lanes
    k, wT = pad(k, 1), jnp.swapaxes(pad(w, 1), 1, 2)
    n_chunks = Tp // chunk
    per_group = -(-n_chunks // _GROUPS)
    packed, reports = None, []
    for first in range(0, n_chunks, per_group):
        last = min(first + per_group, n_chunks)
        packed, rep = _group(plan, packed, qT, k, wT, first * chunk,
                             last * chunk, interpret)
        reports.append(rep)
    rep = jnp.concatenate(reports, axis=1)          # (B, Tp / R, rows, R)
    kept = jnp.sum(rep[:, :, :plan.tiles], axis=-1).reshape(
        B, n_chunks, chunk // plan.R, plan.tiles).sum(axis=2)
    ties = jnp.sum(jnp.any(rep[:, :, plan.tiles] > 0, axis=-1),
                   dtype=jnp.int32)
    return packed, kept, ties
