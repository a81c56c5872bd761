"""The grouped SwiGLU of the held-experts layer (`parallel/moe.py:
held_moe_ffn`, the sorted path), as Pallas TPU kernels with a `custom_vjp`.

The layer hands over a row buffer sorted by expert: `rows` (n_rows, D), of
which the first `sum(counts)` belong to the experts, `counts[e]` to expert
`e` in turn, and the others to nobody. `grouped_swiglu` returns

    z[r] = w_row[r] * (silu(rows[r] Wg_e) * (rows[r] Wu_e)) Wd_e

for the rows of each expert `e`, `[Wg_e, Wu_e] = w_gate_up[e]`, `Wd_e =
w_down[e]`. **What it costs follows the rows that belong to an expert, not
the buffer**: every kernel walks the row tiles that hold such rows (the tile
and the expert of each visit are scalar-prefetched; a tile shared by two
experts is visited once for each, the store masked to the expert's rows) and
a tile past the last kept row is neither fetched nor multiplied. So rows past
`sum(counts)`, of the result and of every cotangent, **hold whatever was
there** (NaN in interpret mode): the caller selects them away (`jnp.where`,
never a multiplication), on the way in so that their cotangents are
selected too.

Five kernels, each with one expert's weights resident while consecutive
row tiles belong to it, operands in the input's type and products
accumulated in float32:

  `mx_moe_gate_up`    rows [Wg, Wu] with `silu(g) * u` computed on the
                      accumulators: `g` and `u` are written only where the
                      backward pass will want them
  `mx_moe_down`       h Wd, times the row's combine weight
  `mx_moe_down_bwd`   dz Wd^T, with what the SwiGLU and the combine weight
                      ask of it: d[g, u], `w_row * h` (the left operand of
                      Wd's gradient) and the combine weight's gradient
  `mx_moe_gate_up_bwd` d[g, u] [Wg, Wu]^T
  `mx_moe_dweights`   the weights' gradients, left^T right expert by expert
                      (rows outside the expert zeroed in both operands; an
                      expert with no row gets zeros)

`routed_swiglu` is the layer's sorted path whole, from the tokens and back
to them: the rows gathered by token, `grouped_swiglu`, and the rows summed
by token, which is the fifth kernel once more (`_add_by_token`: the kept
rows sorted by token, a group the rows of one tile of tokens, the left
operand a one-hot matrix), so that no step of the path costs by the buffer
but the two gathers. Off the TPU the same kernels run in interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _VMEM_LIMIT_MAX

_ROW_TILE = 512         # rows of a tile at most
_VMEM_BLOCK_BYTES = 40 << 20    # what a grid step's blocks may take

_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b


def _columns(n: int) -> int:
    """The width of the column chunks a kernel cuts `n` columns into: one
    product inside a kernel has 512 columns at most."""
    return next((c for c in (512, 384, 256, 128) if n % c == 0), n)


def _row_tile(n_rows: int, dtype, row_bytes: int, fixed_bytes: int) -> int:
    """Rows of a tile: the largest power of two up to `_ROW_TILE` that
    divides the buffer and whose blocks (`row_bytes` a row, `fixed_bytes`
    beside them, both pipeline buffers counted) fit; the whole buffer where
    no such tile fills the type's sublanes."""
    least = 8 * max(4 // jnp.dtype(dtype).itemsize, 1)
    tm = _ROW_TILE
    while tm > least and (n_rows % tm or
                          tm * row_bytes + fixed_bytes > _VMEM_BLOCK_BYTES):
        tm //= 2
    return n_rows if n_rows % tm else tm


def visits(counts, n_rows: int, tm: int, empty: bool = False):
    """The row tiles the kernels visit, in order: (the expert of visit i, its
    row tile, the number of visits, each expert's first row and the row past
    its last). An expert's tiles are those that hold one of its rows; with
    `empty` an expert without a row is visited once all the same (its
    weights' gradient has to be written). The lists have the static length
    tiles + experts - 1, the most that `sum(counts) <= n_rows` allows;
    entries past the last visit repeat it, so nothing new is fetched."""
    G = counts.shape[0]
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    tiles = jnp.where(counts > 0, (ends - 1) // tm - first + 1,
                      1 if empty else 0)
    upto = jnp.cumsum(tiles)
    n = upto[-1]
    i = jnp.minimum(jnp.arange(n_rows // tm + G - 1, dtype=jnp.int32),
                    jnp.maximum(n - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(upto, i, side="right",
                                       method="compare_all"), G - 1)
    tid = jnp.clip(first[gid] + i - (upto[gid] - tiles[gid]), 0,
                   n_rows // tm - 1)
    return (gid.astype(jnp.int32), tid.astype(jnp.int32), n.reshape(1),
            starts, ends)


def _params(interpret, semantics, block_bytes):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(min(_VMEM_LIMIT_MAX,
                                 max(32 << 20, block_bytes + (24 << 20)))))}


def _nbytes(*shaped):
    return sum(2 * s[0] * s[1] * jnp.dtype(d).itemsize for s, d in shaped)


class _Tile:
    """One visit's rows, inside a kernel: whether the step is a visit at
    all, and the store that leaves the other experts' rows as they were."""

    def __init__(self, meta, i, tm):
        gid, tid, n, starts, ends = meta
        self.active = i < n[0]
        e = gid[i]
        self.first_row, self.tm = tid[i] * tm, tm
        self.start, self.end = starts[e], ends[e]
        self.whole = jnp.logical_and(self.start <= self.first_row,
                                     self.end >= self.first_row + tm)
        self._masks = {}

    def mask(self, width):
        if width not in self._masks:
            row = self.first_row + lax.broadcasted_iota(
                jnp.int32, (self.tm, width), 0)
            self._masks[width] = jnp.logical_and(row >= self.start,
                                                 row < self.end)
        return self._masks[width]

    def put(self, ref, lo, value):
        cols = slice(lo, lo + value.shape[1])
        ref[:, cols] = jnp.where(self.mask(value.shape[1]),
                                 value.astype(ref.dtype), ref[:, cols])


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _sigmoid(g):
    return 1.0 / (1.0 + jnp.exp(-g))


def _by_rows(kernel, name, counts, rows_in, weights, outs, interpret):
    """A kernel over the visited row tiles: every array of `rows_in` and of
    `outs` (n_rows, columns) in tiles of rows, every array of `weights`
    (experts, a, b) one expert at a time."""
    n_rows = rows_in[0].shape[0]
    row_bytes = _nbytes(*(((1, a.shape[1]), a.dtype)
                          for a in (*rows_in, *outs)))
    fixed = _nbytes(*((w.shape[1:], w.dtype) for w in weights))
    tm = _row_tile(n_rows, rows_in[0].dtype, row_bytes, fixed)
    meta = visits(counts, n_rows, tm)

    def body(*refs):
        tile = _Tile(refs[:5], pl.program_id(0), tm)
        pl.when(tile.active)(lambda: kernel(tile, *refs[5:]))

    by_tile = lambda a: pl.BlockSpec(
        (tm, a.shape[1]), lambda i, gid, tid, *_: (tid[i], 0))
    return pl.pallas_call(
        body, name=name, interpret=interpret, out_shape=outs,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(meta[0].shape[0],),
            in_specs=[by_tile(a) for a in rows_in] + [
                pl.BlockSpec((None,) + w.shape[1:],
                             lambda i, gid, *_: (gid[i], 0, 0))
                for w in weights],
            out_specs=[by_tile(o) for o in outs]),
        **_params(interpret, ("arbitrary",), tm * row_bytes + fixed),
    )(*meta, *rows_in, *weights)


def _gate_up(rows, w_gate_up, counts, keep, interpret):
    """h = silu(g) * u of [g, u] = rows w_gate_up[e]; with `keep` also g
    and u, in the rows' type."""
    n_rows, two_f = rows.shape[0], w_gate_up.shape[2]
    F = two_f // 2
    chunk = _columns(F)

    def kernel(tile, x_ref, w_ref, *out_refs):
        x = x_ref[...]
        for lo in range(0, F, chunk):
            g = _dot(x, w_ref[:, lo:lo + chunk])
            u = _dot(x, w_ref[:, F + lo:F + lo + chunk])
            for ref, value in zip(out_refs, (g * _sigmoid(g) * u, g, u)):
                tile.put(ref, lo, value)

    out = jax.ShapeDtypeStruct((n_rows, F), rows.dtype)
    return _by_rows(kernel, "mx_moe_gate_up", counts, [rows], [w_gate_up],
                    [out] * (3 if keep else 1), interpret)


def _down(h, w_down, counts, w_row, interpret):
    """z = w_row * (h w_down[e])."""
    D = w_down.shape[2]
    chunk = _columns(D)

    def kernel(tile, h_ref, s_ref, w_ref, z_ref):
        h, scale = h_ref[...], s_ref[...]
        for lo in range(0, D, chunk):
            tile.put(z_ref, lo, _dot(h, w_ref[:, lo:lo + chunk]) * scale)

    return _by_rows(kernel, "mx_moe_down", counts, [h, w_row[:, None]],
                    [w_down],
                    [jax.ShapeDtypeStruct((h.shape[0], D), h.dtype)],
                    interpret)[0]


def _down_bwd(dz, w_down, g, u, w_row, counts, interpret):
    """From z's cotangent: d[g, u] (n_rows, 2 F), `w_row * h` and the
    combine weight's gradient `sum(dz (h Wd)) = sum((dz Wd^T) h)`."""
    n_rows, F = g.shape
    chunk = _columns(F)

    def kernel(tile, dz_ref, g_ref, u_ref, s_ref, w_ref, dgu_ref, hw_ref,
               ds_ref):
        dz, scale = dz_ref[...], s_ref[...]
        d_scale = jnp.zeros(scale.shape, jnp.float32)
        for lo in range(0, F, chunk):
            cols = slice(lo, lo + chunk)
            t = _dot(dz, w_ref[cols, :], _NT)
            g = g_ref[:, cols].astype(jnp.float32)
            u = u_ref[:, cols].astype(jnp.float32)
            s = _sigmoid(g)
            h = g * s * u
            d_scale = d_scale + jnp.sum(t * h, axis=1, keepdims=True)
            dh = t * scale
            tile.put(dgu_ref, lo, dh * u * s * (1.0 + g * (1.0 - s)))
            tile.put(dgu_ref, F + lo, dh * g * s)
            tile.put(hw_ref, lo, h * scale)
        tile.put(ds_ref, 0, d_scale)

    return _by_rows(
        kernel, "mx_moe_down_bwd", counts, [dz, g, u, w_row[:, None]],
        [w_down],
        [jax.ShapeDtypeStruct((n_rows, 2 * F), g.dtype),
         jax.ShapeDtypeStruct((n_rows, F), g.dtype),
         jax.ShapeDtypeStruct((n_rows, 1), jnp.float32)], interpret)


def _gate_up_bwd(dgu, w_gate_up, counts, interpret):
    """d rows = d[g, u] w_gate_up[e]^T."""
    D = w_gate_up.shape[1]
    chunk = _columns(D)

    def kernel(tile, dgu_ref, w_ref, dx_ref):
        dgu = dgu_ref[...]
        for lo in range(0, D, chunk):
            tile.put(dx_ref, lo, _dot(dgu, w_ref[lo:lo + chunk, :], _NT))

    return _by_rows(kernel, "mx_moe_gate_up_bwd", counts, [dgu],
                    [w_gate_up],
                    [jax.ShapeDtypeStruct((dgu.shape[0], D), dgu.dtype)],
                    interpret)[0]


def _dweights(left, right, counts, dtype, interpret):
    """out[e] = left_e^T right_e over the rows of expert e: (experts,
    left's columns, right's columns)."""
    n_rows, K = left.shape
    N, G = right.shape[1], counts.shape[0]
    tn = _columns(N)
    # both pipeline buffers of the operands' tiles; the accumulator and both
    # buffers of the output's block
    row_bytes = 2 * (K + tn) * jnp.dtype(left.dtype).itemsize
    fixed = K * tn * (4 + 2 * jnp.dtype(dtype).itemsize)
    tm = _row_tile(n_rows, left.dtype, row_bytes, fixed)
    meta = visits(counts, n_rows, tm, empty=True)
    steps = meta[0].shape[0]

    def body(gid, tid, n, starts, ends, l_ref, r_ref, o_ref, acc):
        i = pl.program_id(1)
        tile = _Tile((gid, tid, n, starts, ends), i, tm)
        e = gid[i]
        opens = jnp.logical_or(i == 0, gid[jnp.maximum(i - 1, 0)] != e)
        closes = jnp.logical_or(i == n[0] - 1,
                                gid[jnp.minimum(i + 1, steps - 1)] != e)

        @pl.when(jnp.logical_and(tile.active, opens))
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(jnp.logical_and(tile.active, tile.whole))
        def _():
            acc[...] += _dot(l_ref[...], r_ref[...], _TN)

        @pl.when(jnp.logical_and(tile.active, jnp.logical_not(tile.whole)))
        def _():
            zero = lambda ref: jnp.where(tile.mask(ref.shape[1]), ref[...],
                                         jnp.zeros_like(ref))
            acc[...] += _dot(zero(l_ref), zero(r_ref), _TN)

        @pl.when(jnp.logical_and(tile.active, closes))
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)

    return pl.pallas_call(
        body, name="mx_moe_dweights", interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((G, K, N), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(N // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, K), lambda j, i, gid, tid, *_: (tid[i], 0)),
                pl.BlockSpec((tm, tn),
                             lambda j, i, gid, tid, *_: (tid[i], j))],
            out_specs=pl.BlockSpec((None, K, tn),
                                   lambda j, i, gid, *_: (gid[i], 0, j)),
            scratch_shapes=[pltpu.VMEM((K, tn), jnp.float32)]),
        **_params(interpret, ("parallel", "arbitrary"),
                  tm * row_bytes + fixed),
    )(*meta, left, right)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def grouped_swiglu(rows, w_gate_up, w_down, counts, w_row, interpret=False):
    """z (n_rows, D) in the rows' type: `w_row[r]` times expert e's SwiGLU
    of `rows[r]` for the `counts[e]` rows of each expert in turn; the rows
    past `sum(counts)` hold anything, as do theirs of every cotangent.

    rows (n_rows, D); w_gate_up (experts, D, 2 F); w_down (experts, F, D);
    counts (experts,) int32 with `sum(counts) <= n_rows`; w_row (n_rows,)
    float32."""
    h, = _gate_up(rows, w_gate_up, counts, False, interpret)
    return _down(h, w_down, counts, w_row, interpret)


def _swiglu_fwd(rows, w_gate_up, w_down, counts, w_row, interpret):
    h, g, u = _gate_up(rows, w_gate_up, counts, True, interpret)
    return (_down(h, w_down, counts, w_row, interpret),
            (rows, w_gate_up, w_down, counts, w_row, g, u))


def _swiglu_bwd(interpret, res, dz):
    rows, w_gate_up, w_down, counts, w_row, g, u = res
    dgu, hw, d_w_row = _down_bwd(dz, w_down, g, u, w_row, counts, interpret)
    return (_gate_up_bwd(dgu, w_gate_up, counts, interpret),
            _dweights(rows, dgu, counts, w_gate_up.dtype, interpret),
            _dweights(hw, dz, counts, w_down.dtype, interpret),
            None, d_w_row[:, 0])


grouped_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _add_by_token(z, token, kept, n_tokens, interpret):
    """out[token[r]] += z[r] over the rows r < kept (n_tokens, D), summed in
    float32 and rounded once to z's type, as one more grouped product: the
    kept rows sorted by token, a group the rows of one tile of tokens, and
    each tile of the sum `onehot^T z` with `onehot[r, t]` 1 where row r is
    the tile's token t. So the sums stop at `kept` as the products do, and
    nothing is scattered (XLA's scatter-add takes 3 ms for 32,768 rows of
    2,048 whatever is kept, a third of the memory's rate)."""
    n_rows = z.shape[0]
    tq = math.gcd(n_tokens, 256)        # tokens of a tile
    live = lax.iota(jnp.int32, n_rows) < kept
    by_token, order = lax.sort_key_val(
        jnp.where(live, token, n_tokens), lax.iota(jnp.int32, n_rows))
    edges = jnp.searchsorted(
        by_token, jnp.arange(0, n_tokens + 1, tq, dtype=jnp.int32),
        method="compare_all")
    onehot = (by_token % tq)[:, None] == lax.iota(jnp.int32, tq)[None, :]
    out = _dweights(onehot.astype(z.dtype), z[order], jnp.diff(edges),
                    z.dtype, interpret)
    return out.reshape(n_tokens, z.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def routed_swiglu(x, w_gate_up, w_down, w_row, token, counts,
                  interpret=False):
    """The sorted path of `held_moe_ffn` whole: row r of the buffer is token
    `token[r]`'s, the first `counts[e]` rows expert e's in turn, and

        out[t] = sum over the kept rows r of token t of grouped_swiglu's z[r]

    summed in float32, in x's type (N, D). Rows are multiplied and added
    back as far as `sum(counts)` reaches; `w_row`'s cotangent past it holds
    anything (the caller's `jnp.where` on `w_row` selects it away).
    token (n_rows,) int32 in [0, N); the others as `grouped_swiglu` has
    them."""
    z = grouped_swiglu(x[token], w_gate_up, w_down, counts, w_row, interpret)
    return _add_by_token(z, token, jnp.sum(counts), x.shape[0], interpret)


def _routed_fwd(x, w_gate_up, w_down, w_row, token, counts, interpret):
    z, res = _swiglu_fwd(x[token], w_gate_up, w_down, counts, w_row,
                         interpret)
    out = _add_by_token(z, token, jnp.sum(counts), x.shape[0], interpret)
    return out, (res, token)


def _routed_bwd(interpret, res, d_out):
    res, token = res
    d_rows, d_gate_up, d_down, _, d_w_row = _swiglu_bwd(
        interpret, res, d_out[token])
    dx = _add_by_token(d_rows, token, jnp.sum(res[3]), d_out.shape[0],
                       interpret)
    return dx, d_gate_up, d_down, d_w_row, None, None


routed_swiglu.defvjp(_routed_fwd, _routed_bwd)
