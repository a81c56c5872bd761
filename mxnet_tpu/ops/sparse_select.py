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

How, and where each part runs (PR 37). Queries go in chunks of `chunk` (the
published `q_chunk_size`), and the chunks in up to eight groups, a group's
chunks against the keys up to the group's end: a static extent a group,
five eighths of the T x T scores instead of all. Each group is one call of
the Pallas kernel `mx_index_select` (`ops/pallas/index_select.py`), 128
queries a grid step: the scores of every head, the ReLU, the weighted sum
over heads, the scale and the causal mask are computed key block by key
block into a VMEM scratch and never reach HBM; the exact top-k runs its
counting passes over that scratch; the step writes its rows of the packed
set and a few numbers of report. A group that ends at or before `top_k`
keeps every causal key and scores nothing. What runs in XLA round the calls
is the queries' and head weights' transposes (queries along lanes), the
pads to whole chunks, and the report summed from the steps' tallies.

The exact top-k without a sort: a float32's bits, the sign folded, order as
an int32 does. The k-th largest is found bit by bit from the top, up to 32
counting passes over the step's scores ("how many are at least this?"),
which stop early once every query of the step has exactly `top_k` at or
above its cut; where a query has more keys on its cut than it still wants
after all 32, of those the lowest positions are kept, found by the same
bisection over the position (a further log2(keys) passes, for that step
alone; `tie_blocks` in the report counts such steps). That is
`lax.top_k`'s set, ties and all (-0.0 counts as 0.0, as a comparison of
floats has it); tests/test_sparse_select.py holds it to `lax.top_k` on
planted ties and to `pack_selection` of it byte for byte.

Bit j of byte m of a packed row is key j * (Tp / 8) + m, with Tp the keys
padded to whole chunks: eight contiguous runs of the row laid over each
other, so packing and unpacking shift whole lane-aligned slices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .pallas.flash_attention import _on_tpu
from .pallas import index_select
from .registry import register

# what `indexer_select`'s second output holds, in order; a layer keeps the
# first two as its `selection` state, a leaf whose shape is PR 34's
SELECT_REPORT = ("selected_mean", "empty_pairs", "tie_blocks")
LAYER_REPORT = SELECT_REPORT[:2]
KEEP_NAME = "mx.select"


def _ceil_to(x, m):
    return -(-x // m) * m


def padded_keys(T, chunk):
    """The keys of a packed row: T in whole chunks (a chunk is whole bytes)."""
    if chunk % 8:
        raise ValueError(f"a chunk of {chunk} queries is not whole bytes")
    return _ceil_to(T, chunk)


def pack_selection(mask):
    """bool (..., Tp) -> uint8 (..., Tp / 8): bit j of byte m is key
    j * Tp / 8 + m; the layout the kernel writes, as plain `jax.numpy`."""
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


@register("_contrib_indexer_select", multi_output=True, differentiable=False)
def indexer_select(q, k, w, *, top_k, chunk=512):
    """q (B, Hi, T, di), k (B, T, di), w (B, T, Hi) -> (the selection packed,
    uint8 (B, T, Tp / 8) with Tp = T in whole chunks; float32 (3,), the
    call's report in the order of `SELECT_REPORT`: keys kept a query (mean),
    the chunk x chunk tiles on or under the diagonal in which nothing is
    kept, and the kernel's steps that ran the position passes). The scores
    are scaled by (di * Hi) ** -0.5, which moves no choice."""
    B, Hi, T, di = q.shape
    top_k, chunk = int(top_k), int(chunk)
    q, k, w = (lax.stop_gradient(a) for a in (q, k, w))     # a set: no slope
    with jax.named_scope("mx.index.select"):
        packed, kept, ties = index_select.select(
            q, k, w, top_k, chunk, padded_keys(T, chunk), not _on_tpu(q))
        n_chunks, tiles = kept.shape[1:]
        live = jnp.arange(tiles)[None, :] <= jnp.arange(n_chunks)[:, None]
        empty = jnp.sum(jnp.logical_and(live, kept == 0), dtype=jnp.int32)
        report = jnp.stack([jnp.sum(kept).astype(jnp.float32) / (B * T),
                            empty.astype(jnp.float32),
                            ties.astype(jnp.float32)])
    packed = lax.bitcast_convert_type(packed[:, :T], jnp.uint8)
    return checkpoint_name(packed, KEEP_NAME), report
