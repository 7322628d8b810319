"""Operations and bytes the architecture needs, from shapes alone.

``cfg`` is the configuration file's ``hf`` block (the published
``config.json`` keys, depth as run). Only what the mathematics requires is
counted: the top-k experts of a routed layer, the valid tokens of a prompt,
no padding, nothing recomputed. Used for ``mfu_bf16_pct`` and
``hbm_util_pct``; kept here so that no PR that claims a gain can change it.
"""

from __future__ import annotations


def _dims(cfg: dict):
    h = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads") or hq
    d = cfg.get("head_dim") or h // hq
    return h, hq, hkv, d, cfg["intermediate_size"], cfg["num_hidden_layers"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights one token is multiplied with: attention projections, the MLP
    (``num_experts_per_tok`` experts and the router where the layer is
    routed) and the output head. The embedding is a lookup."""
    h, hq, hkv, d, f, layers = _dims(cfg)
    attn = h * (hq * d) + 2 * h * (hkv * d) + (hq * d) * h
    experts = cfg.get("num_local_experts") or 0
    if experts:
        mlp = cfg["num_experts_per_tok"] * 3 * h * f + h * experts
    else:
        mlp = 3 * h * f
    return layers * (attn + mlp) + h * cfg["vocab_size"]


def attention_flops(cfg: dict, q_tokens: int, context: int) -> float:
    """QK^T and PV for ``q_tokens`` queries that each see ``context`` keys
    (the caller applies causality and the window)."""
    _, hq, _, d, _, layers = _dims(cfg)
    return 4.0 * layers * hq * d * q_tokens * context


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` valid tokens, causal, windowed. The head
    runs on the last position only."""
    h = cfg["hidden_size"]
    window = cfg.get("sliding_window") or prompt_len
    # sum over positions of min(position + 1, window)
    full = min(prompt_len, window)
    seen = full * (full + 1) / 2 + max(0, prompt_len - window) * window
    body = matmul_params_per_token(cfg) - h * cfg["vocab_size"]
    return (
        2.0 * body * prompt_len + 2.0 * h * cfg["vocab_size"]
        + attention_flops(cfg, 1, seen)
    )


def decode_token_flops(cfg: dict, context: int) -> float:
    """One generated token that attends to ``context`` cached positions."""
    window = cfg.get("sliding_window") or context
    return 2.0 * matmul_params_per_token(cfg) + attention_flops(
        cfg, 1, min(context, window)
    )


def stored_weight_bytes(cfg: dict, weight_bytes: float) -> float:
    """Bytes a decode step must read of the weights: every layer's
    projections and EVERY expert (a batch of rows touches them all), the
    head, at ``weight_bytes`` per stored value; scales are left out (under
    0.1%)."""
    h, hq, hkv, d, f, layers = _dims(cfg)
    attn = h * (hq * d) + 2 * h * (hkv * d) + (hq * d) * h
    experts = cfg.get("num_local_experts") or 0
    mlp = (experts or 1) * 3 * h * f + h * experts
    return (layers * (attn + mlp) + h * cfg["vocab_size"]) * weight_bytes


def kv_bytes_per_token(cfg: dict, kv_bytes: float) -> float:
    """Stored K and V of one position over all layers (int8 pages carry a
    float32 scale per head and position, counted by the caller's
    ``kv_bytes`` as 1 + 4/head_dim)."""
    _, _, hkv, d, _, layers = _dims(cfg)
    return 2.0 * layers * hkv * d * kv_bytes
