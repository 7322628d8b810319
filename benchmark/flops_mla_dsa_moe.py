"""Parameters and bytes a token of the ``glm_moe_dsa`` block (GLM-5.2), from
the published keys alone: ``flops_mla_moe.py`` counts a latent with queries
from the hidden state, every layer alike and every expert here, and this
family has others: compressed queries (``q_lora_rank``), an indexer in the
layers ``indexer_types`` marks ``full`` and in no other, attention over
``index_topk`` selected latents in every layer, leading dense layers by
``mlp_layer_types``, ``n_routed_experts`` HELD experts beside a shared one
under a router ``expert_share.router_experts`` wide, and a head over the
vocabulary's slice. ``cfg`` is the configuration file's block, depth, share
and slice as run. Kept with the benchmark so that no PR that claims a gain
can change the count.
"""

from __future__ import annotations


def layers_of(cfg: dict) -> dict:
    kinds, mlps = cfg["indexer_types"], cfg["mlp_layer_types"]
    return {
        "full": kinds.count("full"), "shared": kinds.count("shared"),
        "dense": mlps.count("dense"), "sparse": mlps.count("sparse"),
    }


def layer_parameters(cfg: dict) -> dict:
    """One layer's matrices by kind (norm gains left out): what is stored
    quantised and what stays in the model's dtype, apart."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    f = cfg["moe_intermediate_size"]
    share = cfg.get("expert_share") or {}
    return {
        "attention": h * qr + qr * hq * (dn + dr) + hq * dv * h,
        "attention_plain": h * (rank + dr) + rank * hq * (dn + dv),
        "indexer": qr * hi * di + h * di + h * hi,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "one_expert": 3 * h * f,
        "shared_expert": cfg["n_shared_experts"] * 3 * h * f,
        "router": h * share.get("router_experts", cfg["n_routed_experts"]),
    }


def experts_touched(cfg: dict, rows: float) -> float:
    """Held experts that a decode step of ``rows`` tokens reads, in
    expectation: each of a token's ``num_experts_per_tok`` picks falls on a
    given expert of the router's with probability 1 / router width."""
    held = cfg["n_routed_experts"]
    router = (cfg.get("expert_share") or {}).get("router_experts", held)
    picks = rows * cfg["num_experts_per_tok"]
    return held * (1.0 - (1.0 - 1.0 / router) ** picks)


def stored_weight_bytes(cfg: dict, weight_bytes: float, rows: float,
                        plain_bytes: float = 2.0) -> float:
    """Bytes a decode step of ``rows`` tokens must read of the weights held
    here: every layer's attention projections, an indexer in the ``full``
    layers, the dense layer's MLP, the held experts its tokens' picks fall
    on (:func:`experts_touched`: 16 rows x 8 picks of 256 reach 6 or 7 of
    the 16 held) and the shared expert of each expert layer, the routers,
    and the head's slice. ``weight_bytes`` a value for what is stored
    quantised, ``plain_bytes`` for what stays in the model's dtype. The
    embedding is a lookup."""
    p, n = layer_parameters(cfg), layers_of(cfg)
    layers = n["dense"] + n["sparse"]
    return (
        layers * (p["attention"] * weight_bytes + p["attention_plain"] * plain_bytes)
        + n["full"] * p["indexer"] * plain_bytes
        + n["dense"] * p["dense_mlp"] * weight_bytes
        + n["sparse"] * (
            (experts_touched(cfg, rows) * p["one_expert"] + p["shared_expert"])
            * weight_bytes
            + p["router"] * plain_bytes
        )
        + cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes
    )


def latent_bytes_per_position(cfg: dict, int8_pool: bool) -> float:
    """The stored latent of one position in ONE layer: int8 and one float32
    scale, or float32."""
    w = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return w + 4 if int8_pool else 4 * w


def index_bytes_per_position(cfg: dict, value_bytes: float = 2.0) -> float:
    """The index key of one position in ONE scoring layer (the model's
    dtype)."""
    return cfg["index_head_dim"] * value_bytes


def cache_bytes_read(cfg: dict, context: float, int8_pool: bool,
                     value_bytes: float = 2.0) -> float:
    """Cache bytes one decoded token at ``context`` live positions must
    read: the index keys of the whole context in each ``full`` layer (every
    live position is scored there) and the stored latents of its
    ``min(index_topk, context)`` selected positions in every layer."""
    n = layers_of(cfg)
    return (
        n["full"] * index_bytes_per_position(cfg, value_bytes) * context
        + (n["full"] + n["shared"]) * latent_bytes_per_position(cfg, int8_pool)
        * min(cfg["index_topk"], context)
    )
