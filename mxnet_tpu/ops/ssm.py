"""State-space (Mamba-2) operators: the chunked scan and the causal depthwise
convolution in front of it.

No counterpart in the reference (MXNet 1.x predates state-space layers); the
equations are those of Dao & Gu, "Transformers are SSMs" (arXiv:2405.21060),
as HF `GraniteMoeHybrid` / `Mamba2` compute them.

Per head h (P channels, state N), with `dt` already positive (softplus),
`A < 0` a scalar per head, `B_t`, `C_t` (N,) shared by the heads of a group:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S: (P, N), S_0 = 0
    y_t = S_t C_t + D x_t

`ssd_scan` computes it in chunks of Q positions (the "state-space dual"
form: matrix products by design, which is what the MXU wants). With
`a_t = dt_t A` and `cum_i = sum_{k<=i} a_k` inside a chunk:

    within a chunk    Y = (L o (C B^T)) (dt x),   L_ij = exp(cum_i - cum_j)
                                                  for i >= j, else 0
    the chunk's end   S_end = exp(cum_Q) S_in + sum_j exp(cum_Q - cum_j)
                                                     dt_j x_j B_j^T
    carried in        y_i += exp(cum_i) (S_in C_i)

The decays (`a`, `cum`, `L`) and the carried state are float32 whatever the
inputs' type; the products take their operands in the inputs' type and
accumulate in float32. Everything is `jax.numpy`, so autodiff gives the
backward pass; a T that is no multiple of Q is padded with `dt = 0`
positions, which neither decay nor feed the state.

`causal_conv1d` is the depthwise convolution of width W over time,
left-padded by W-1: `y_t = bias + sum_w weight[:, w] x_{t-W+1+w}`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


def _chunked(a, n_chunks, chunk):
    return a.reshape(a.shape[:1] + (n_chunks, chunk) + a.shape[2:])


@register("_contrib_ssd_scan")
def ssd_scan(x, dt, A, B, C, D, *, chunk_size=256):
    """x (b, T, H, P), dt (b, T, H) positive, A (H,) negative, B and C
    (b, T, G, N) with H a multiple of G, D (H,). Returns y (b, T, H, P) in
    x's type."""
    b, t, h, p = x.shape
    g = B.shape[2]
    q = min(int(chunk_size), t)
    pad = -t % q
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, B, C))
    nc = (t + pad) // q
    f32 = jnp.float32
    with jax.named_scope("mx.ssd"):
        hg = h // g
        dt32 = dt.astype(f32)
        # the decays, head-major: (b, c, H, q), all <= 0
        a = jnp.moveaxis(_chunked(dt32 * A.astype(f32), nc, q), 2, 3)
        cum = jnp.cumsum(a, axis=3)
        xdt = (x.astype(f32) * dt32[..., None]).astype(x.dtype)
        xg = _chunked(xdt, nc, q).reshape(b, nc, q, g, hg, p)
        # the heads of one group share B and C: (b, c, q, G, N)
        Bc, Cc = _chunked(B, nc, q), _chunked(C, nc, q)

        # within a chunk: (L o C B^T) (dt x)
        i = jnp.arange(q)
        seg = cum[..., :, None] - cum[..., None, :]            # b c H i j
        L = jnp.exp(jnp.where(i[:, None] >= i[None, :], seg, -jnp.inf))
        G = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                       preferred_element_type=f32)
        M = (L.reshape(b, nc, g, hg, q, q) * G[:, :, :, None]).astype(x.dtype)
        y = jnp.einsum("bcghij,bcjghp->bcighp", M, xg,
                       preferred_element_type=f32)

        # every chunk's own end state, then the carry from chunk to chunk
        to_end = jnp.exp(cum[..., -1:] - cum).reshape(b, nc, g, hg, q)
        xend = (xg.astype(f32)
                * jnp.moveaxis(to_end, 4, 2)[..., None]).astype(x.dtype)
        own = jnp.einsum("bcjghp,bcjgn->bcghpn", xend, Bc,
                         preferred_element_type=f32)
        total = jnp.exp(cum[..., -1]).reshape(b, nc, g, hg, 1, 1)

        def carry(s, inp):
            decay, add = inp
            return decay * s + add, s               # emits the state coming in
        _, s_in = lax.scan(carry, jnp.zeros_like(own[:, 0]),
                           (jnp.moveaxis(total, 1, 0), jnp.moveaxis(own, 1, 0)))
        s_in = jnp.moveaxis(s_in, 0, 1)                         # b c g hg P N
        from_start = jnp.moveaxis(jnp.exp(cum).reshape(b, nc, g, hg, q), 4, 2)
        y = y + from_start[..., None] * jnp.einsum(
            "bcign,bcghpn->bcighp", Cc, s_in.astype(x.dtype),
            preferred_element_type=f32)

        y = y.reshape(b, nc * q, h, p)[:, :t]
        y = y + D.astype(f32)[:, None] * x[:, :t].astype(f32)
        return y.astype(x.dtype)


@register("_contrib_causal_conv1d")
def causal_conv1d(x, weight, bias=None):
    """x (b, T, C), weight (C, W), bias (C,) or None -> (b, T, C): each
    channel's own filter over the W latest positions, zeros before the
    sequence's start."""
    with jax.named_scope("mx.conv1d"):
        t, w = x.shape[1], weight.shape[1]
        xp = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
        y = sum(xp[:, k:k + t] * weight[:, k].astype(x.dtype)
                for k in range(w))
        if bias is not None:
            y = y + bias.astype(x.dtype)
        return y
