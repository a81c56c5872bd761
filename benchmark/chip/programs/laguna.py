"""The program's decoder for the `laguna` family (full and sliding-window
attention with per-layer head counts, gate and rotary positions; a dense
feed-forward and then routed experts beside a shared one; its own head),
built from a configuration file: what the system under test trains. The
leaves come out in the order `reference/laguna.py` lists."""
from __future__ import annotations


def _rope(p, head_dim):
    """`_contrib_rotary_embedding`'s arguments from one of the
    configuration's `rope_parameters` sets."""
    out = dict(base=float(p["rope_theta"]),
               rotary_dim=int(head_dim * p.get("partial_rotary_factor", 1)))
    if p["rope_type"] == "yarn":
        out.update(yarn_factor=float(p["factor"]),
                   yarn_original_length=p["original_max_position_embeddings"],
                   yarn_beta_fast=float(p["beta_fast"]),
                   yarn_beta_slow=float(p["beta_slow"]),
                   attention_factor=float(p["attention_factor"]))
    elif p["rope_type"] != "default":
        raise ValueError(f"rope_type {p['rope_type']!r}")
    return out


def build(cfg, traffic):
    """(net, sample): the uninitialised block and a one-row host sample for
    the deferred shape inference. Every decoder layer is a recomputed block
    (the configuration's `assumed` says so): a property of the model. The
    per-layer lists keep their published length; the first
    `num_hidden_layers` entries are this chip's layers."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.hybrid_decoder import HybridDecoder

    n = cfg["num_hidden_layers"]
    if set(cfg["gating_types"][:n]) != {"per_head"}:
        raise ValueError("gating_types: only 'per_head' is built")
    net = HybridDecoder(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        layer_types=cfg["layer_types"][:n],
        mlp_layer_types=cfg["mlp_layer_types"][:n],
        num_heads=cfg["num_attention_heads_per_layer"][:n],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], gate=True,
        rope={kind: _rope(p, cfg["head_dim"])
              for kind, p in cfg["rope_parameters"].items()},
        moe=dict(expert_hidden=cfg["moe_intermediate_size"],
                 shared_hidden=cfg["shared_expert_intermediate_size"],
                 held=cfg["num_experts"],
                 published_experts=cfg["published"]["num_experts"],
                 top_k=cfg["num_experts_per_tok"],
                 scaling=cfg["moe_routed_scaling_factor"],
                 first_held=cfg.get("first_held_expert", 0)),
        tie_head=cfg["tie_word_embeddings"], epsilon=cfg["rms_norm_eps"],
        recompute=True)
    return net, nd.zeros((1, traffic["seq"]), ctx=mx.cpu(), dtype="int32")
