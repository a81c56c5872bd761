"""Operations a training step of the `laguna` decoder needs, from the
configuration's shapes (full and sliding-window attention layers with
per-layer head counts and a per-head gate, a dense SwiGLU in layer 0, then a
router over the published experts, the held experts' share of the routed
products and a shared expert; a head of its own over the rows held).

A multiply-add counts as two operations; the backward pass as twice the
forward; what is recomputed (the layers in the backward pass, a kernel's
own recomputation, the expert layer's second forward) is not counted.
Causal attention is counted at the half of the T x T products that the mask
leaves, window attention at the sum over t of min(t + 1, W) keys. The routed
products are counted at the **expected** share of the assignments that falls
on the experts held here, tokens x top-k x held / published: what uniform
routing sends, whatever the seed's router does. Elementwise work (norms,
SiLU, the rotations, the softmax over 256 scores, the sort) is not counted."""
from __future__ import annotations


def _layers(cfg):
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n]))


def _keys_seen(seq, window):
    """Keys a query sees on average: (T + 1) / 2 under the causal mask alone
    is counted as T / 2, the half of T x T; with a window the sum over t of
    min(t + 1, W), over T."""
    if window is None or window >= seq:
        return seq / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def _routed_rows_per_token(cfg):
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["published"]["num_experts"]


def _forward(cfg, seq):
    """Per token, forward: (dense products outside the held experts, the
    held experts' routed products, full-attention T x T products, window
    T x T products, head)."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * d
    dense = routed = full = window = 0
    for kind, mlp, heads in _layers(cfg):
        # q, k, v, the gate's one number a head, the output projection
        dense += 2 * c * (heads * d + 2 * kv + heads) + 2 * heads * d * c
        if mlp == "dense":
            dense += 6 * c * cfg["intermediate_size"]
        else:
            dense += 2 * c * cfg["published"]["num_experts"] \
                + 6 * c * cfg["shared_expert_intermediate_size"]
            routed += _routed_rows_per_token(cfg) \
                * 6 * c * cfg["moe_intermediate_size"]
        if kind == "sliding_attention":     # QK^T and PV over the keys seen
            window += 4 * heads * d * _keys_seen(seq, cfg["sliding_window"])
        else:
            full += 4 * heads * d * _keys_seen(seq, None)
    return dense, routed, full, window, 2 * c * cfg["vocab_size"]


def train_flops_per_item(cfg, traffic):
    """Forward and backward operations per token."""
    return 3 * sum(_forward(cfg, traffic["seq"]))


def mxu_flops_per_item(cfg, traffic, exclude_attention=False):
    """The part of them that the trace's matmul events carry. Where the
    step has kernels of its own (custom calls: the reader asks with
    `exclude_attention`), the attention's T x T products are in the flash
    kernels and the held experts' routed products in the grouped-product
    kernel that `lax.ragged_dot` compiles to; neither is in those events."""
    dense, routed, full, window, head = _forward(cfg, traffic["seq"])
    own = 0 if exclude_attention else routed + full + window
    return 3 * (dense + head + own)


def _attention_kernel_work(cfg, traffic, kind):
    """(operations, bytes) a step of the attention kernels of one kind of
    layer: forward and twice that backward, recomputation not counted; bytes
    of q, o and their gradients over the layer's query heads and of k, v and
    theirs over the KV heads, once each in the compute type (2 B)."""
    tokens, seq = traffic["batch"] * traffic["seq"], traffic["seq"]
    d, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    ops = nbytes = 0
    for layer_kind, _, heads in _layers(cfg):
        if layer_kind == kind:
            ops += 3 * tokens * 4 * heads * d * _keys_seen(seq, window)
            nbytes += 2 * tokens * d * (4 * heads + 4 * kv)
    return ops, nbytes


def window_attention_kernel_work(cfg, traffic):
    return _attention_kernel_work(cfg, traffic, "sliding_attention")


def full_attention_kernel_work(cfg, traffic):
    return _attention_kernel_work(cfg, traffic, "full_attention")


def moe_experts_work(cfg, traffic):
    """(operations, bytes) a step of the held experts' routed products in
    all sparse layers, at the expected rows: forward and twice that
    backward; bytes of the rows in and out and their gradients in the compute
    type, and of the held experts' weights read forward, read backward and
    their gradient written, 2 B each."""
    tokens = traffic["batch"] * traffic["seq"]
    c, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sparse = sum(mlp == "sparse" for _, mlp, _ in _layers(cfg))
    rows = tokens * _routed_rows_per_token(cfg)
    ops = 3 * sparse * rows * 6 * c * fe
    nbytes = sparse * (4 * rows * c * 2 + 3 * cfg["num_experts"] * 3 * c * fe * 2)
    return ops, nbytes
