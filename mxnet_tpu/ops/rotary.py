"""Rotary position embeddings (Su et al., arXiv:2104.09864), as one op.

No reference counterpart (MXNet 1.x has learned and sinusoidal tables only).
Position t turns each pair (x_i, x_{i + r/2}) of the first `rotary_dim` = r
numbers of a head by the angle t * inv_freq_i (the rotate-half convention);
the rest of the head passes through:

    inv_freq_i = base ** (-2 i / r),  i = 0 .. r/2 - 1
    out[..., :r] = x[..., :r] * cos + rotate_half(x[..., :r]) * sin
    rotate_half([x1, x2]) = [-x2, x1];  cos, sin over [t f, t f]

YaRN (Peng et al., arXiv:2309.00071; `yarn_factor` s over an original length
L): a frequency whose wavelength fits the original length `yarn_beta_fast`
times or more keeps inv_freq_i (extrapolated), one that fits
`yarn_beta_slow` times or fewer gets inv_freq_i / s (interpolated), and a
linear ramp over the pair index joins the two, from
low = floor(c(beta_fast)) to high = ceil(c(beta_slow)) with
c(n) = r ln(L / (2 pi n)) / (2 ln base) clipped to [0, r - 1]. cos and sin
are multiplied by `attention_factor`.

Sectioned positions (`sections`, PR 34; multimodal rotary positions with
`rope_type` default and `mrope_section` [s0, s1, s2] in order, not
interleaved): a token has three positions p_0, p_1, p_2 (temporal, height,
width), fed as `positions` (3, B, T), and the frequency pairs are divided
among them in order, pair i turned by the angle p_r[t] * inv_freq_i with
r = 0 for i < s0, r = 1 for s0 <= i < s0 + s1, r = 2 above. The sections sum
to r/2. With `positions` None the three rows are the text's, 0 .. T - 1
each, and the result is the plain op's.

The frequencies are static numbers (numpy, float64 then float32); the table
of T x r/2 angles is built in float32 in the trace and applied in x's type.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register


def rotary_inv_freq(rotary_dim, base=10000.0, yarn_factor=None,
                    yarn_original_length=None, yarn_beta_fast=32.0,
                    yarn_beta_slow=1.0):
    """(rotary_dim / 2,) float32 inverse frequencies, YaRN's where
    `yarn_factor` is given."""
    r = int(rotary_dim)
    pos_freqs = float(base) ** (np.arange(0, r, 2, dtype=np.float64) / r)
    if yarn_factor is None:
        return (1.0 / pos_freqs).astype(np.float32)

    def correction(n_rot):
        return r * math.log(yarn_original_length / (n_rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(yarn_beta_fast)), 0)
    high = min(math.ceil(correction(yarn_beta_slow)), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (yarn_factor * pos_freqs)) * ramp \
        + (1.0 / pos_freqs) * (1.0 - ramp)
    return inv.astype(np.float32)


@register("_contrib_rotary_embedding")
def rotary_embedding(x, positions=None, *, base=10000.0, rotary_dim=None,
                     yarn_factor=None, yarn_original_length=None,
                     yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                     attention_factor=1.0, sections=None):
    """x (B, H, T, d) -> the same shape and type: positions 0 .. T - 1 along
    axis 2, the first `rotary_dim` numbers of each head turned (None: d).
    `sections` (s0, s1, s2) with `positions` (3, B, T) whole numbers: pair i
    is turned by the row its section names (the module's docstring); both
    None is the plain op."""
    with jax.named_scope("mx.rope"):
        d, t = x.shape[-1], x.shape[-2]
        r = d if rotary_dim is None else int(rotary_dim)
        if r % 2 or not 0 < r <= d:
            raise ValueError(f"rotary_dim {r} of a head of {d}")
        inv = jnp.asarray(rotary_inv_freq(
            r, base, yarn_factor, yarn_original_length, yarn_beta_fast,
            yarn_beta_slow))
        if sections is None:
            if positions is not None:
                raise ValueError("positions are fed by sections")
            angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
        else:
            if sum(sections) != r // 2:
                raise ValueError(f"sections {tuple(sections)} of {r // 2} "
                                 "frequency pairs")
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(t, dtype=jnp.int32),
                    (len(sections), x.shape[0], t))
            row = np.repeat(np.arange(len(sections)), sections)   # (r/2,)
            # (B, 1, T, r/2): pair i reads the row of its section
            angle = (jnp.moveaxis(positions.astype(jnp.float32), 0, -1)
                     [..., row] * inv)[:, None]
        cos = (jnp.cos(angle) * attention_factor).astype(x.dtype)
        sin = (jnp.sin(angle) * attention_factor).astype(x.dtype)
        x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
