"""Flash attention as Pallas TPU kernels (forward + backward).

Replaces the reference's cuDNN/hand-CUDA attention path
(src/operator/contrib/transformer.cc:650-819 interleaved_matmul_selfatt_*)
with the TPU equivalent: blocked softmax(QK^T)V with online log-sum-exp,
computed in VMEM with MXU matmuls, O(T) memory. The backward pass is the
standard flash recomputation: delta = rowsum(dO*O); dq from (q-block x
all k-blocks), dk/dv from (k-block x all q-blocks).

Schedule: 3-D grid (batch*heads, outer-block, inner-block) with the inner
axis 'arbitrary' (sequential) — Mosaic double-buffers the inner-axis block
DMAs so HBM traffic overlaps MXU compute; accumulators live in VMEM scratch
that persists across inner iterations. Causal runs skip fully-masked blocks
with pl.when (halves the work).

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

_NEG = -1e30  # finite mask value: -inf breeds nans in exp(-inf - -inf)


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


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


# ---------------------------------------------------------------------------
# Forward: grid (BH, n_q, n_k); k blocks stream along the inner axis
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, l_ref, m_ref, *,
                scale, causal, block_q, block_k, t_k):
    iq, jk = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        l_ref[:] = jnp.zeros_like(l_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)

    # causal: block is live unless it sits entirely above the diagonal
    live = jnp.bool_(True)
    if causal:
        live = jk * block_k <= iq * block_q + (block_q - 1)

    @pl.when(live)
    def _compute():
        # matmul operands stay in the input dtype (bf16 on the fast path);
        # preferred_element_type makes the MXU accumulate in f32
        q = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        s = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = jk * block_k + lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
        mask = k_pos < t_k
        if causal:
            q_pos = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(jk == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l)


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    BH, T, D = q.shape
    Tk = k.shape[1]
    Tp, Tkp = _ceil_to(T, block_q), _ceil_to(Tk, block_k)
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tkp - Tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tkp - Tk), (0, 0)))
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, t_k=Tk)
    o, lse = pl.pallas_call(
        kern,
        grid=(BH, Tp // block_q, Tkp // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="mx_flash_fwd",
        **_params(interpret),
    )(qp, kp, vp)
    return o[:, :T], lse[:, :T, 0]


# ---------------------------------------------------------------------------
# Backward dq: grid (BH, n_q, n_k); k blocks stream inner
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k, t_k):
    iq, jk = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = jnp.bool_(True)
    if causal:
        live = jk * block_k <= iq * block_q + (block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        s = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = jk * block_k + lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
        mask = k_pos < t_k
        if causal:
            q_pos = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[:] = acc_ref[:] + lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(jk == n_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward dk/dv: grid (BH, n_k, n_q); q blocks stream inner
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, t_q):
    jk, iq = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = jnp.bool_(True)
    if causal:  # q block must reach the diagonal: max q_pos >= min k_pos
        live = iq * block_q + (block_q - 1) >= jk * block_k

    @pl.when(live)
    def _compute():
        kb = k_ref[0]
        vb = v_ref[0]
        qb = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        q_pos = iq * block_q + lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
        mask = q_pos < t_q
        if causal:
            k_pos = jk * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG)
        p = jnp.exp(s - lse)
        dv_acc[:] = dv_acc[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[:] = dk_acc[:] + lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k, interpret):
    BH, T, D = q.shape
    Tk = k.shape[1]
    Tp, Tkp = _ceil_to(T, block_q), _ceil_to(Tk, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    dop = jnp.pad(do, ((0, 0), (0, Tp - T), (0, 0)))
    # padded q rows: lse=0, delta=0, p=exp(_NEG-0)=0 -> no contribution
    lsep = jnp.pad(lse, ((0, 0), (0, Tp - T)))[..., None]
    deltap = jnp.pad(delta, ((0, 0), (0, Tp - T)))[..., None]
    kp = jnp.pad(k, ((0, 0), (0, Tkp - Tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tkp - Tk), (0, 0)))

    dq_kern = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                block_q=block_q, block_k=block_k, t_k=Tk)
    dq = pl.pallas_call(
        dq_kern,
        grid=(BH, Tp // block_q, Tkp // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, Tp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="mx_flash_bwd_dq",
        **_params(interpret),
    )(qp, kp, vp, dop, lsep, deltap)

    dkv_kern = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                 block_q=block_q, block_k=block_k, t_q=T)
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(BH, Tkp // block_k, Tp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tkp, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tkp, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="mx_flash_bwd_dkv",
        **_params(interpret),
    )(qp, kp, vp, dop, lsep, deltap)
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
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q3, k3, v3, o, lse = res
    return _bwd(q3, k3, v3, o, lse, g, causal, scale, block_q, block_k,
                interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = 256, block_k: int = 256):
    """Flash attention on (B, H, T, D) tensors; differentiable.

    Uses the Pallas kernels on TPU (or in interpret mode when
    MXNET_PALLAS_INTERPRET=1); falls back to the lax.scan blockwise
    implementation elsewhere — same math, same signature.
    """
    B, H, T, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    on_tpu = _on_tpu(q)
    if not (on_tpu or _use_interpret()):
        # The fallback is differentiated by jax AS WRITTEN (no custom_vjp):
        # its gradient contract — matches the dense-softmax VJP at every
        # shape, including T not a multiple of block_size and causal
        # masking — holds because the scan masks via jnp.where against
        # CONSTANT biases (masked lanes contribute zero cotangent), pinned
        # by tests/test_pallas_kernels.py::test_fallback_grad_*.
        from ..attention import blockwise_attention
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   block_size=block_k)
    Tk = k.shape[2]
    bq = min(block_q, _ceil_to(T, 128))
    bk = min(block_k, _ceil_to(Tk, 128))
    q3 = q.reshape(B * H, T, D)
    k3 = k.reshape(B * H, Tk, D)
    v3 = v.reshape(B * H, Tk, D)
    out = _flash(q3, k3, v3, bool(causal), float(scale), int(bq), int(bk),
                 not on_tpu)
    return out.reshape(B, H, T, D)
