"""Operations a training step of the `keye_vl2` decoder needs, from the
configuration's shapes (grouped-KV attention over the keys an indexer
selects, a router over the published experts and the held experts' share of
the routed products, a head of its own over the rows held).

A multiply-add counts as two operations; the backward pass as twice the
forward; what is recomputed (the layers in the backward pass, a kernel's
own recomputation, the expert layer's second forward) is not counted.
**The work is the same whatever implements it.** Attention is counted at the
selected pairs, the sum over t of min(t + 1, topk) keys a query: what a
kernel that gathers the kept keys would compute, so a kernel that walks the
whole causal triangle under a mask reads a low share of it by design. The
indexer's scores are counted at every causal pair (each has to be scored
before any can be chosen), and they and the indexer's projections forward
only: the indexer is not trained. The routed products are counted at the
**expected** share of the assignments that falls on the experts held here,
tokens x top-k x held / published. Elementwise work (norms, SiLU, the
rotations, the ReLU and the weighted sum over the indexer's heads, finding
the top-k, the softmax over 128 scores, the sort) is not counted."""
from __future__ import annotations


def _selected_pairs(seq, top_k):
    """sum_t min(t + 1, top_k): the (query, key) pairs attended to."""
    k = min(top_k, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def _causal_pairs(seq):
    return seq * (seq + 1) // 2


def _routed_rows_per_token(cfg):
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["published"]["num_experts"]


def _forward(cfg, seq):
    """Per token, forward: (dense trained products, the indexer's
    projections, the held experts' routed products, attention's products at
    the selected pairs, the indexer's scores, head)."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    hq, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    n = cfg["num_hidden_layers"]
    dense = n * (2 * c * (hq + 2 * kv) + 2 * hq * c
                 + 2 * c * cfg["published"]["num_experts"])
    index_proj = n * 2 * c * (hi * di + di + hi)
    routed = n * _routed_rows_per_token(cfg) \
        * 6 * c * cfg["moe_intermediate_size"]
    attention = n * 4 * hq * _selected_pairs(seq, sa["topk"]) / seq
    scores = n * 2 * hi * di * _causal_pairs(seq) / seq
    return dense, index_proj, routed, attention, scores, \
        2 * c * cfg["vocab_size"]


def train_flops_per_item(cfg, traffic):
    """Forward and backward operations per token; the indexer forward
    only."""
    dense, index_proj, routed, attention, scores, head = _forward(
        cfg, traffic["seq"])
    return 3 * (dense + routed + attention + head) + index_proj + scores


def mxu_flops_per_item(cfg, traffic, exclude_attention=False):
    """The part of them that the trace's matmul events carry. Where the
    step has kernels of its own (custom calls: the reader asks with
    `exclude_attention`), the attention's products are in the flash kernels
    and the held experts' routed products in the grouped-product kernel that
    `lax.ragged_dot` compiles to; neither is in those events. The indexer's
    scores are plain products and stay."""
    dense, index_proj, routed, attention, scores, head = _forward(
        cfg, traffic["seq"])
    own = 0 if exclude_attention else 3 * (routed + attention)
    return 3 * (dense + head) + index_proj + scores + own


def sparse_attention_kernel_work(cfg, traffic):
    """(operations, bytes) a step of attention at the selected pairs in all
    layers: QK^T and PV forward and twice that backward, recomputation not
    counted; bytes of q, o and their gradients over the query heads and of
    k, v and theirs over the KV heads, once each in the compute type (2 B),
    and of the selection, a bit a causal pair read forward and backward."""
    tokens, seq = traffic["batch"] * traffic["seq"], traffic["seq"]
    d, n = cfg["head_dim"], cfg["num_hidden_layers"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = traffic["batch"] * _selected_pairs(seq, cfg["sa_config"]["topk"])
    ops = n * 3 * 4 * hq * d * pairs
    nbytes = n * (2 * tokens * d * (4 * hq + 4 * kv)
                  + 2 * traffic["batch"] * _causal_pairs(seq) / 8)
    return ops, nbytes


def indexer_work(cfg, traffic):
    """(operations, bytes) a step of the indexer in all layers, forward only:
    its three projections and its scores at every causal pair; bytes of the
    layer's input, the indexer's weights, its queries, key and head weights
    in the compute type, and of the selection written, a bit a pair of
    T x T."""
    tokens, seq = traffic["batch"] * traffic["seq"], traffic["seq"]
    c, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    out = hi * di + di + hi
    ops = n * (tokens * 2 * c * out
               + traffic["batch"] * 2 * hi * di * _causal_pairs(seq))
    nbytes = n * (2 * (tokens * c + c * out + tokens * out)
                  + traffic["batch"] * seq * seq / 8)
    return ops, nbytes
