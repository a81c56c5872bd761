"""Operations a training step of the hybrid decoder needs, from the
configuration's shapes (Mamba-2 and grouped-KV attention layers as
`layer_types` lists them, SwiGLU feed-forward, tied head over the rows held).

A multiply-add counts as two operations; the backward pass as twice the
forward; what is recomputed (the layers in the backward pass, a kernel's
own recomputation) is not counted. Causal attention is counted at the half
of the T x T products that the mask leaves. Elementwise work (norms, SiLU,
the width-4 convolution, the decays' exponentials) is not counted."""
from __future__ import annotations


def _scan_forward(cfg):
    """Products of the chunked state-space scan, per token and layer, at the
    published chunk Q: C B^T (2 Q N a group), (L o C B^T)(dt x) (2 Q H P),
    the chunk's own end state and the carried state's part of y (2 H P N
    each). The same work whatever implements it."""
    q, n, g = cfg["mamba_chunk_size"], cfg["mamba_d_state"], \
        cfg["mamba_n_groups"]
    hp = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return 2 * q * n * g + 2 * q * hp + 4 * hp * n


def _forward(cfg, seq):
    """(dense products, the scan's products, attention's T x T products,
    head) per token, forward."""
    c, f = cfg["hidden_size"], cfg["intermediate_size"]
    hp = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = hp + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    kv = cfg["num_key_value_heads"] * (c // cfg["num_attention_heads"])
    n_mamba = sum(k == "mamba" for k in cfg["layer_types"])
    n_attn = len(cfg["layer_types"]) - n_mamba
    ffn = 2 * c * 2 * f + 2 * f * c
    mamba = 2 * c * (hp + conv + cfg["mamba_n_heads"]) + 2 * hp * c
    attn = 2 * (2 * c * c + 2 * c * kv)
    dense = n_mamba * (mamba + ffn) + n_attn * (attn + ffn)
    scores = n_attn * 2 * seq * c          # QK^T and PV under the causal mask
    return dense, n_mamba * _scan_forward(cfg), scores, \
        2 * c * cfg["vocab_size"]


def train_flops_per_item(cfg, traffic):
    """Forward and backward operations per token."""
    return 3 * sum(_forward(cfg, traffic["seq"]))


def mxu_flops_per_item(cfg, traffic, exclude_attention=False):
    """The part of them that the trace's matmul events carry: the scan is
    plain einsums, so its products are in the `kOutput` fusions and are
    counted; where the attention runs in a kernel of its own (custom calls),
    its T x T products are not in those events."""
    dense, scan, scores, head = _forward(cfg, traffic["seq"])
    return 3 * (dense + scan + head + (0 if exclude_attention else scores))


def ssd_scan_work(cfg, traffic):
    """Per step of the cell: (operations, bytes) of the state-space scan in
    all Mamba layers, the chunked algorithm at the published chunk, forward
    and twice that backward, recomputation not counted; bytes of x, B, C, y
    in the compute type (2 B) and dt in float32, and their gradients, once
    each."""
    tokens = traffic["batch"] * traffic["seq"]
    n_mamba = sum(k == "mamba" for k in cfg["layer_types"])
    hp = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    ops = 3 * n_mamba * tokens * _scan_forward(cfg)
    nbytes = 2 * n_mamba * tokens * (2 * (2 * hp + 2 * gn)
                                     + 4 * cfg["mamba_n_heads"])
    return ops, nbytes
