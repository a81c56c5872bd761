"""The program's hybrid decoder (Mamba-2 and grouped-KV attention layers),
built from a configuration file: what the system under test trains. The
leaves come out in the order `reference/granite_hybrid.py` lists."""
from __future__ import annotations


def build(cfg, traffic):
    """(net, sample): the uninitialised block and a one-row host sample for
    the deferred shape inference. Every decoder layer is a recomputed block
    (the configuration's `assumed` says so): a property of the model."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.hybrid_decoder import HybridDecoder

    net = HybridDecoder(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"], layer_types=cfg["layer_types"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"], mamba_conv=cfg["mamba_d_conv"],
        mamba_groups=cfg["mamba_n_groups"],
        mamba_chunk=cfg["mamba_chunk_size"],
        mamba_conv_bias=cfg["mamba_conv_bias"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"], epsilon=cfg["rms_norm_eps"],
        recompute=True)
    return net, nd.zeros((1, traffic["seq"]), ctx=mx.cpu(), dtype="int32")
