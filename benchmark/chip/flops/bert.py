"""Operations a BERT training step needs, from the configuration's shapes.

A multiply-add counts as two operations; the backward pass as twice the
forward; what a kernel recomputes is not counted. Copied in substance from
bench.py's `bert_train_flops_per_token` (3 x (L(24H^2 + 4TH) + 2HV)), with
the MLM head's dense layer added."""
from __future__ import annotations


def _forward(cfg, seq):
    c, f = cfg["hidden_size"], cfg["intermediate_size"]
    layer_dense = 2 * (3 * c * c + c * c + 2 * c * f)    # qkv, proj, ffn1+2
    layer_attn = 4 * seq * c                             # QK^T and PV
    head = 2 * c * c + 2 * c * cfg["vocab_size"]         # mlm dense, decoder
    n = cfg["num_hidden_layers"]
    return n * layer_dense, n * layer_attn, head


def train_flops_per_item(cfg, traffic):
    """Forward and backward operations per token."""
    return 3 * sum(_forward(cfg, traffic["seq"]))


def mxu_flops_per_item(cfg, traffic, exclude_attention=False):
    """The part of them that convolution and dot events of the trace carry.
    Where the attention runs in a kernel of its own (custom calls), its
    products are not in those events."""
    dense, attn, head = _forward(cfg, traffic["seq"])
    return 3 * (dense + head + (0 if exclude_attention else attn))


def attention_kernel_work(cfg, traffic):
    """Per step of the cell: (operations, bytes) of the attention kernels,
    forward 4 B H T^2 d and backward 8 B H T^2 d, recomputation not counted;
    bytes of q, k, v, o and their gradients once each in the compute type."""
    b, t, c = traffic["batch"], traffic["seq"], cfg["hidden_size"]
    n = cfg["num_hidden_layers"]
    ops = n * 12 * b * t * t * c
    nbytes = n * 8 * b * t * c * 2
    return ops, nbytes
