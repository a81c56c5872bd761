"""Learned sparse attention's key selection: an indexer's scores and the
exact top-k of them, as the set of keys each query keeps (PR 34).

No reference counterpart. The mechanism is the lightning indexer of
DeepSeek-V3.2-Exp ("DeepSeek Sparse Attention"): a few small heads score
every key a query may see, and the main attention then runs over the
`top_k` best of them alone. With qI (B, Hi, T, di) the indexer's queries,
kI (B, T, di) its one key head and wI (B, T, Hi) a weight a query and head:

    I[t, s] = scale * sum_j wI[t, j] * relu(qI[t, j] . kI[s]),   s <= t
    S_t     = the top_k keys s <= t of largest I[t, s]; all of them where
              t < top_k; among equal scores the lower s first

The products take their inputs as they come (the compute type) and
accumulate in float32; the sum over heads and every comparison are float32.

What leaves is the set, not the scores: `indexer_select` returns it packed,
one bit a (query, key) pair (T x T / 8 bytes a batch row: 34 MB at 16,384),
and marks it `checkpoint_name("mx.select")`, so that a recomputed block which
keeps that name (`HybridBlock.recompute(keep=("mx.select",))`) carries the
set from its forward pass to its backward pass and computes it once: the set
the backward pass masks by is then the forward pass's, whatever a second
scoring would round to. `unpack_selection` turns it into the int8 (B, T, T)
mask the attention routes take (`flash_attention(select=)`), 268 MB at
16,384, alive for one layer at a time.

How. Queries go in chunks of `chunk` (the published `q_chunk_size`): no
T x T x heads tensor exists, only chunk x keys x heads. Chunks are walked in
up to eight groups, a group's chunks against the keys up to the group's end
(a `lax.map`, so one chunk's scores live at a time): a static extent a
group, five eighths of the T x T scores instead of all. A group that ends at
or before `top_k` keeps every causal key and scores nothing.

The exact top-k without a sort: a float32's bits, the sign folded, order as
an unsigned whole number does. The k-th largest is found bit by bit from
the top, 32 counting passes over the chunk's scores ("how many are at least
this?"); the keys above it are in, and of those equal to it the lowest
positions, as many as are still wanted, found by the same bisection over the
position (a further log2(keys) passes). That is `lax.top_k`'s set, ties and
all (-0.0 counts as 0.0, as a comparison of floats has it);
tests/test_sparse_select.py holds it to `lax.top_k` on planted ties.

Bit j of byte m of a packed row is key j * (Tp / 8) + m, with Tp the keys
padded to whole chunks: eight contiguous runs of the row laid over each
other, so packing and unpacking shift whole lane-aligned slices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .registry import register

# what `indexer_select`'s second output holds, in order
SELECT_REPORT = ("selected_mean", "empty_pairs")
KEEP_NAME = "mx.select"
_GROUPS = 8


def _ceil_to(x, m):
    return -(-x // m) * m


def padded_keys(T, chunk):
    """The keys of a packed row: T in whole chunks (a chunk is whole bytes)."""
    if chunk % 8:
        raise ValueError(f"a chunk of {chunk} queries is not whole bytes")
    return _ceil_to(T, chunk)


def pack_selection(mask):
    """bool (..., Tp) -> uint8 (..., Tp / 8): bit j of byte m is key
    j * Tp / 8 + m."""
    n = mask.shape[-1] // 8
    out = mask[..., :n].astype(jnp.uint8)
    for j in range(1, 8):
        out = out | (mask[..., j * n:(j + 1) * n].astype(jnp.uint8) << j)
    return out


@register("_contrib_selection_unpack", differentiable=False)
def unpack_selection(packed, *, keys):
    """uint8 (B, T, Tp / 8) -> int8 (B, T, keys): 1 where the key is kept."""
    rows = [((packed >> j) & 1).astype(jnp.int8) for j in range(8)]
    return jnp.concatenate(rows, axis=-1)[..., :keys]


def indexer_scores(q, k, w, scale):
    """I (B, n, K) float32 of n queries against K keys: q (B, Hi, n, di),
    k (B, K, di), w (B, n, Hi)."""
    with jax.named_scope("mx.index.score"):
        s = jnp.einsum("bhqd,bkd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * jnp.moveaxis(w.astype(jnp.float32),
                                               2, 1)[..., None]
        return jnp.sum(s, axis=1) * scale


def _ordered(x):
    """float32 -> uint32 that orders as the floats do (-0.0 as 0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(1 << 31)


def top_k_mask(scores, top_k):
    """bool like `scores` (..., K): the `top_k` largest of each row, of equal
    scores the lower positions first: `lax.top_k`'s set, by bisection."""
    K = scores.shape[-1]
    if top_k >= K:
        return jnp.ones(scores.shape, bool)
    with jax.named_scope("mx.index.select"):
        u = _ordered(scores)
        rows = scores.shape[:-1]

        def value_bit(i, tau):      # the k-th largest, from its top bit down
            cand = tau | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
            enough = jnp.sum(u >= cand[..., None], axis=-1,
                             dtype=jnp.int32) >= top_k
            return jnp.where(enough, cand, tau)

        tau = lax.fori_loop(0, 32, value_bit, jnp.zeros(rows, jnp.uint32))
        above = u > tau[..., None]
        tie = (u == tau[..., None]).astype(jnp.int8)
        want = top_k - jnp.sum(above, axis=-1, dtype=jnp.int32)   # >= 1
        pos = jnp.arange(K, dtype=jnp.int32)
        bits = max(K.bit_length(), 1)

        def position_bit(i, m):     # the most positions with too few ties
            cand = m | (jnp.int32(1) << (bits - 1 - i))
            few = jnp.sum(jnp.where(pos < cand[..., None], tie, 0), axis=-1,
                          dtype=jnp.int32) < want
            return jnp.where(few, cand, m)

        cut = lax.fori_loop(0, bits, position_bit, jnp.zeros(rows, jnp.int32))
        return jnp.logical_or(above, jnp.logical_and(tie > 0,
                                                     pos <= cut[..., None]))


@register("_contrib_indexer_select", multi_output=True, differentiable=False)
def indexer_select(q, k, w, *, top_k, chunk=512):
    """q (B, Hi, T, di), k (B, T, di), w (B, T, Hi) -> (the selection packed,
    uint8 (B, T, Tp / 8) with Tp = T in whole chunks; float32 (2,), the
    call's report in the order of `SELECT_REPORT`: keys kept a query (mean)
    and the chunk x chunk tiles on or under the diagonal in which nothing is
    kept). The scores are scaled by (di * Hi) ** -0.5, which moves no
    choice."""
    B, Hi, T, di = q.shape
    top_k, chunk = int(top_k), int(chunk)
    scale = (di * Hi) ** -0.5
    Tp = padded_keys(T, chunk)
    n_chunks = Tp // chunk
    per_group = -(-n_chunks // _GROUPS)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, Tp - T), (0, 0)))
    key_pos = jnp.arange(Tp, dtype=jnp.int32)

    def one_chunk(c, extent, scored):
        """Rows c * chunk .. of the set against the first `extent` keys:
        (packed (B, chunk, Tp / 8), keys kept, empty tiles)."""
        t = c * chunk + jnp.arange(chunk, dtype=jnp.int32)[:, None]
        seen = jnp.logical_and(key_pos[None, :extent] <= t, t < T)
        keep = jnp.broadcast_to(seen, (B, chunk, extent))
        if scored:
            rows = lax.dynamic_slice_in_dim(qp, c * chunk, chunk, axis=2)
            ws = lax.dynamic_slice_in_dim(wp, c * chunk, chunk, axis=1)
            score = jnp.where(seen, indexer_scores(rows, kp[:, :extent], ws,
                                                   scale), -jnp.inf)
            keep = jnp.where(t < top_k, keep, jnp.logical_and(
                keep, top_k_mask(score, top_k)))
        tiles = jnp.any(keep.reshape(B, chunk, extent // chunk, chunk),
                        axis=(1, 3))                # (B, extent / chunk)
        live = jnp.arange(extent // chunk) <= c     # on or under the diagonal
        empty = jnp.sum(jnp.logical_and(live, ~tiles), dtype=jnp.int32)
        keep = jnp.pad(keep, ((0, 0), (0, 0), (0, Tp - extent)))
        return pack_selection(keep), jnp.sum(keep, dtype=jnp.int32), empty

    packed, kept, empty = [], jnp.int32(0), jnp.int32(0)
    for first in range(0, n_chunks, per_group):
        last = min(first + per_group, n_chunks)
        extent = last * chunk
        body = functools.partial(one_chunk, extent=extent,
                                 scored=extent > top_k)
        p, n, e = lax.map(body, jnp.arange(first, last, dtype=jnp.int32))
        packed.append(jnp.moveaxis(p, 0, 1).reshape(
            B, (last - first) * chunk, Tp // 8))
        kept, empty = kept + jnp.sum(n), empty + jnp.sum(e)
    packed = jnp.concatenate(packed, axis=1)[:, :T]
    report = jnp.stack([kept.astype(jnp.float32) / (B * T),
                        empty.astype(jnp.float32)])
    return checkpoint_name(packed, KEEP_NAME), report

