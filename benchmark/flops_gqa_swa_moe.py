"""Parameters and bytes a token of the ``exaone_moe`` block (K-EXAONE), from
the published keys alone: ``flops.py`` counts Llama-shaped keys (one MLP
width, every layer alike, one KV size a token), and this family has others:
window and full layers by ``layer_types``, a leading dense layer by
``mlp_layer_types``, ``num_experts`` HELD experts of
``moe_intermediate_size`` beside a shared one under a router
``expert_share.router_experts`` wide, and a head over the vocabulary's
slice. ``cfg`` is the configuration file's block, depth, share and slice as
run. Kept with the benchmark so that no PR that claims a gain can change the
count.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    return (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
    )


def layers_of(cfg: dict) -> dict:
    kinds, mlps = cfg["layer_types"], cfg["mlp_layer_types"]
    return {
        "window": kinds.count("sliding_attention"),
        "full": kinds.count("full_attention"),
        "dense": mlps.count("dense"), "sparse": mlps.count("sparse"),
    }


def layer_parameters(cfg: dict) -> dict:
    """One layer's matrices by kind (norm gains left out)."""
    h, hq, hkv, d = _sizes(cfg)
    f = cfg["moe_intermediate_size"]
    share = cfg.get("expert_share") or {}
    return {
        "attention": 2 * h * hq * d + 2 * h * hkv * d,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "held_experts": cfg["num_experts"] * 3 * h * f,
        "shared_expert": cfg["num_shared_experts"] * 3 * h * f,
        "router": h * share.get("router_experts", cfg["num_experts"]),
    }


def stored_weight_bytes(cfg: dict, weight_bytes: float, plain_bytes: float = 2.0) -> float:
    """Bytes a decode step must read of the weights held here: every
    layer's attention projections, the dense layer's MLP, every HELD expert
    (32 rows x 8 picks of 128 touch all 16 in nearly every step) and the
    shared expert of each expert layer, the routers, and the head's slice.
    ``weight_bytes`` a value for what is stored quantised, ``plain_bytes``
    for the routers. The embedding is a lookup."""
    p, n = layer_parameters(cfg), layers_of(cfg)
    return (
        (n["dense"] + n["sparse"]) * p["attention"] * weight_bytes
        + n["dense"] * p["dense_mlp"] * weight_bytes
        + n["sparse"] * (
            (p["held_experts"] + p["shared_expert"]) * weight_bytes
            + p["router"] * plain_bytes
        )
        + cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes
    )


def kv_bytes_per_position(cfg: dict, int8_pool: bool) -> float:
    """K and V (and their scale rows) of one position in ONE layer."""
    _, _, hkv, d = _sizes(cfg)
    return 2 * hkv * (d + 4 if int8_pool else 2 * d)


def kv_bytes_read(cfg: dict, context: float, int8_pool: bool) -> float:
    """KV bytes one decoded token at ``context`` live positions must read,
    by layer kind: a full layer the whole context, a window layer
    ``min(sliding_window, context)``."""
    n = layers_of(cfg)
    return kv_bytes_per_position(cfg, int8_pool) * (
        n["full"] * context
        + n["window"] * min(cfg["sliding_window"], context)
    )
