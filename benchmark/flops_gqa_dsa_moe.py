"""Parameters, FLOPs and bytes a token of the ``KeyeVL2`` block, from the
published keys alone: ``flops.py`` counts Llama-shaped keys (one MLP width,
every key attended), and this family has others (``num_experts`` of
``moe_intermediate_size``, ``sa_config``'s indexer and its index key beside
K and V, attention over ``topk`` selected rows). ``cfg`` is the
configuration file's published block, depth as run. Kept with the benchmark
so that no PR that claims a gain can change the count.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    sa = cfg["sa_config"]
    return (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        sa["indexer_num_heads"], sa["indexer_head_dim"],
    )


def layer_parameters(cfg: dict) -> dict:
    """One layer's matrices by kind (norm gains left out)."""
    h, hq, hkv, d, hi, di = _sizes(cfg)
    return {
        "experts": cfg["num_experts"] * 3 * h * cfg["moe_intermediate_size"],
        "attention": 2 * h * hq * d + 2 * h * hkv * d,
        "indexer": h * (hi * di + di + hi),
        "router": h * cfg["num_experts"],
    }


def stored_weight_bytes(cfg: dict, weight_bytes: float, plain_bytes: float = 2.0) -> float:
    """Bytes a decode step must read of the weights: every layer's
    projections and EVERY expert (16 rows x 8 picks touch nearly all 128),
    the head. ``weight_bytes`` a value for what is stored quantised (the
    experts, ``wq``/``wk``/``wv``/``wo``, the head), ``plain_bytes`` for the
    router and the indexer's projections. The embedding is a lookup."""
    p = layer_parameters(cfg)
    return (
        cfg["num_hidden_layers"] * (
            (p["experts"] + p["attention"]) * weight_bytes
            + (p["indexer"] + p["router"]) * plain_bytes
        )
        + cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes
    )


def index_bytes_per_token(cfg: dict, value_bytes: float = 2.0) -> float:
    """The index key of one position over all layers (stored in the model's
    dtype beside an int8 K and V too): every live position's is read by a
    decode step."""
    return (
        cfg["num_hidden_layers"] * cfg["sa_config"]["indexer_head_dim"]
        * value_bytes
    )


def kv_bytes_per_token(cfg: dict, int8_pool: bool) -> float:
    """K and V (and their scale rows) of one position over all layers: a
    decode step needs those of its SELECTED positions."""
    _, _, hkv, d, _, _ = _sizes(cfg)
    return cfg["num_hidden_layers"] * 2 * hkv * (d + 4 if int8_pool else 2 * d)


def flops_per_token(cfg: dict, context: float) -> float:
    """FLOPs the architecture needs for one token at ``context`` live
    positions: the projections, the router, 8 experts, the head, the
    indexer against every live position and attention against
    ``min(topk, context)``."""
    h, hq, hkv, d, hi, di = _sizes(cfg)
    p = layer_parameters(cfg)
    active = (
        p["attention"] + p["indexer"] + p["router"]
        + cfg["num_experts_per_tok"] * 3 * h * cfg["moe_intermediate_size"]
    )
    attended = min(cfg["sa_config"]["topk"], context)
    return (
        cfg["num_hidden_layers"] * (
            2.0 * active + 2.0 * hi * di * context + 4.0 * hq * d * attended
        )
        + 2.0 * h * cfg["vocab_size"]
    )
