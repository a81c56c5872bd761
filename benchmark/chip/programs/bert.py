"""The program's BERT, built from a configuration file: what the system under
test trains. The leaves come out in the order `reference/bert.py` lists."""
from __future__ import annotations


def build(cfg, traffic):
    """(net, sample): the uninitialised block and a one-row host sample for
    the deferred shape inference."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.bert import BertModel

    net = BertModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        units=cfg["hidden_size"], hidden_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        max_length=cfg["max_position_embeddings"],
        dropout=cfg["hidden_dropout_prob"])
    return net, nd.zeros((1, traffic["seq"]), ctx=mx.cpu(), dtype="int32")
