"""Operations and bytes a token of the ``ouro`` block (Ouro-2.6B) needs, from
the published keys alone: the dense block's projections and SwiGLU MLP, run
``total_ut_steps`` times a token; softmax attention over the context in
every one of the ``total_ut_steps x num_hidden_layers`` cache layers (a lap
of a layer attends to its own K and V); the exit gate once a lap; the head
ONCE (it reads the chosen lap's hidden state). The norms' and the
selection's few operations a value are left out, as ``flops.py`` leaves the
dense block's. ``cfg`` is the configuration file's block. Kept with the
benchmark so that no PR that claims a gain can change the count.
"""

from __future__ import annotations


def _dims(cfg: dict):
    h = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads") or hq
    d = cfg.get("head_dim") or h // hq
    return h, hq, hkv, d, cfg["intermediate_size"], cfg["num_hidden_layers"]


def laps(cfg: dict) -> int:
    return int(cfg.get("total_ut_steps", 1))


def cache_layers(cfg: dict) -> int:
    """Layers of K and V a position holds: a lap of a layer has its own."""
    return laps(cfg) * cfg["num_hidden_layers"]


def layer_parameters(cfg: dict) -> int:
    """One layer's matrices: q, k, v, o and the MLP."""
    h, hq, hkv, d, f, _ = _dims(cfg)
    return 2 * h * hq * d + 2 * h * hkv * d + 3 * h * f


def attention_flops(cfg: dict, context: float) -> float:
    """ONE cache layer: QK^T and PV of a token's query heads against
    ``context`` positions."""
    _, hq, _, d, _, _ = _dims(cfg)
    return 4.0 * hq * d * context


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def token_flops(cfg: dict, context: float) -> float:
    """A token that attends to ``context`` positions, through every lap,
    the head left out: the layers' matrices and the attention a cache layer,
    and the gate's ``hidden_size`` products a lap."""
    layers = cfg["num_hidden_layers"]
    return laps(cfg) * (
        layers * (2.0 * layer_parameters(cfg) + attention_flops(cfg, context))
        + 2.0 * cfg["hidden_size"]
    )


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` valid tokens, causal (a token attends to
    itself and those before it: ``(n + 1) / 2`` on average); the head runs
    on the last position only."""
    return prompt_len * token_flops(cfg, (prompt_len + 1) / 2.0) + head_flops(cfg)


def decode_token_flops(cfg: dict, context: float) -> float:
    """One generated token at a context of ``context`` positions."""
    return token_flops(cfg, context) + head_flops(cfg)


def layers_bytes(cfg: dict, weight_bytes: float) -> float:
    """The layers' stored matrices: what ONE lap reads."""
    return cfg["num_hidden_layers"] * layer_parameters(cfg) * weight_bytes


def stored_weight_bytes(cfg: dict, weight_bytes: float) -> float:
    """Bytes a decode step must read of the weights: the layers' matrices
    once a LAP and the head once. The embedding is a lookup; gains and the
    gate are under 0.01%."""
    return (
        laps(cfg) * layers_bytes(cfg, weight_bytes)
        + cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes
    )


def kv_bytes_per_position(cfg: dict, kv_bytes: float) -> float:
    """Bytes one live position holds, and a decode step reads, over every
    cache layer: K and V of every key-value head at ``kv_bytes`` a stored
    value (1 + 4 / head_dim for int8 with a float32 scale a head)."""
    _, _, hkv, d, _, _ = _dims(cfg)
    return cache_layers(cfg) * 2.0 * hkv * d * kv_bytes
