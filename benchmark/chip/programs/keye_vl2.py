"""The program's decoder for the `keye_vl2` family (grouped-KV attention
over the keys a learned indexer selects, a QK norm, rotary positions in
sections; routed experts without a shared one; its own head), built from a
configuration file: what the system under test trains. The leaves come out
in the order `reference/keye_vl2.py` lists; the indexer's are frozen."""
from __future__ import annotations


def build(cfg, traffic):
    """(net, sample): the uninitialised block and a one-row host sample for
    the deferred shape inference. Every decoder layer is a recomputed block
    that carries its selection to the backward pass (the configuration's
    `assumed` says so): a property of the model."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.hybrid_decoder import HybridDecoder

    rope, sa = cfg["rope_scaling"], cfg["sa_config"]
    if rope["rope_type"] != "default" or cfg["use_sliding_window"] \
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1 \
            or sa["indexer_num_kv_heads"] != 1 or not cfg["norm_topk_prob"]:
        raise ValueError("keye_vl2: only the published layer is built")
    n = cfg["num_hidden_layers"]
    theta = float(cfg["rope_theta"])
    net = HybridDecoder(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        layer_types=("indexed_attention",) * n,
        mlp_layer_types=("sparse",) * n,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        qk_norm=True, sections=rope["mrope_section"],
        rope={"indexed_attention": dict(base=theta)},
        indexer=dict(heads=sa["indexer_num_heads"],
                     head_dim=sa["indexer_head_dim"], top_k=sa["topk"],
                     chunk=sa["q_chunk_size"], rope=dict(base=theta),
                     epsilon=cfg["rms_norm_eps"]),
        moe=dict(expert_hidden=cfg["moe_intermediate_size"],
                 shared_hidden=None, held=cfg["num_experts"],
                 published_experts=cfg["published"]["num_experts"],
                 top_k=cfg["num_experts_per_tok"],
                 first_held=cfg.get("first_held_expert", 0)),
        tie_head=cfg["tie_word_embeddings"], epsilon=cfg["rms_norm_eps"],
        recompute=True)
    return net, nd.zeros((1, traffic["seq"]), ctx=mx.cpu(), dtype="int32")
