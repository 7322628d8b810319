"""Bytes the DeepSeek-V2/V3 block needs, from shapes alone: ``flops.py``
counts Llama-shaped keys (``num_local_experts``, one MLP width, K and V a
head), and this family has others (``n_routed_experts`` of
``moe_intermediate_size`` beside ``n_shared_experts``, leading dense layers,
one latent a position). ``cfg`` is the configuration file's published block,
depth as run. Kept with the benchmark so that no PR that claims a gain can
change the count.
"""

from __future__ import annotations


def stored_weight_bytes(cfg: dict, weight_bytes: float, plain_bytes: float = 2.0) -> float:
    """Bytes a decode step must read of the weights: every layer's
    projections, EVERY routed expert (a batch of 32 rows x 6 picks touches
    nearly all 64) and the shared ones, the dense layers' MLP, the head.
    ``weight_bytes`` a value for what is stored quantised (``wq``, ``wo``,
    the MLPs, the experts, the head); ``plain_bytes`` for what stays in the
    model's dtype (``wkv_a``, ``wk_b``, ``wv_b``, the router). Scales, norms
    and the selection bias are left out (under 0.1%); the embedding is a
    lookup."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, dn, dr, dv = (
        cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
        cfg["v_head_dim"],
    )
    layers = cfg["num_hidden_layers"]
    experts = cfg.get("n_routed_experts") or 0
    dense_layers = min(cfg.get("first_k_dense_replace", 0), layers) if experts else layers
    attn = (
        (h * hq * (dn + dr) + hq * dv * h) * weight_bytes
        + (h * (rank + dr) + rank * hq * (dn + dv)) * plain_bytes
    )
    dense = 3 * h * cfg["intermediate_size"] * weight_bytes
    fe = cfg.get("moe_intermediate_size") or 0
    routed = (
        (experts + (cfg.get("n_shared_experts") or 0)) * 3 * h * fe * weight_bytes
        + h * experts * plain_bytes
    )
    return (
        layers * attn + dense_layers * dense + (layers - dense_layers) * routed
        + h * cfg["vocab_size"] * weight_bytes
    )


def latent_bytes_per_token(cfg: dict, int8_pool: bool) -> float:
    """The stored latent of one position over all layers: int8 and one
    float32 scale, or float32."""
    w = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * (w + 4 if int8_pool else 4 * w)
