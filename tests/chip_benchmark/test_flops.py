"""The flops/ functions against an inventory taken from the real models'
parameter shapes (and, for the convolutions, the shapes of their outputs in
one eager forward pass of the real model)."""
import numpy as np
import pytest

import cells


def _cell(name):
    return cells.Cell(name)


def test_bert_flops_against_the_real_models_matrices():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    cell = _cell("bert_base_train_t512")
    cfg, traffic = cell.config, cell.traffic
    net, _ = cell.module("programs").build(cfg, traffic)
    with mx.cpu():
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, 8), ctx=mx.cpu(), dtype="int32"))
    # every Dense weight is applied once to every token: 2 ops a weight
    dense = sum(2 * int(np.prod(p.shape))
                for n, p in net.collect_params().items()
                if "dense" in n and n.endswith("weight"))
    seq = traffic["seq"]
    attn = cfg["num_hidden_layers"] * 4 * seq * cfg["hidden_size"]
    flops = cell.module("flops")
    assert flops.train_flops_per_item(cfg, traffic) == 3 * (dense + attn)
    assert flops.mxu_flops_per_item(cfg, traffic, exclude_attention=True) \
        == 3 * dense
    ops, nbytes = flops.attention_kernel_work(cfg, traffic)
    assert ops == 3 * attn * traffic["batch"] * seq
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    assert nbytes == (cfg["num_hidden_layers"] * 8 * traffic["batch"]
                      * cfg["num_attention_heads"] * seq * d * 2)
    # the published count: 3 x (L (24 H^2 + 4 T H) + 2 H V), plus the head
    h, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    assert 3 * (dense + attn) == 3 * (n * (24 * h * h + 4 * seq * h)
                                      + 2 * h * v + 2 * h * h)


def test_resnet_flops_against_the_real_models_convolutions():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    cell = _cell("resnet50_train_bs128")
    cfg, traffic = cell.config, cell.traffic
    net, sample = cell.module("programs").build(cfg, traffic)
    total = [0]

    def hook(block, inputs, output):
        w = block.weight.shape
        total[0] += 2 * int(np.prod(w)) * int(np.prod(output.shape[2:]))

    def walk(block):
        if isinstance(block, nn.Conv2D):
            block.register_forward_hook(hook)
        for child in block._children.values():
            walk(child)

    walk(net)
    with mx.cpu():
        net.initialize(ctx=mx.cpu())
        net(sample)
    total[0] += 2 * int(np.prod(net.output.weight.shape))
    flops = cell.module("flops")
    assert flops.train_flops_per_item(cfg, traffic) == 3 * total[0]
    assert 7.6e9 < total[0] < 7.8e9   # He et al. Table 1: 3.8 G multiply-adds
