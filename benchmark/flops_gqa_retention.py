"""Operations and bytes a token of the ``brumby`` block (Brumby-14B-Base)
needs, from the published keys alone: the Qwen3 block's projections, SwiGLU
MLP and head, a gate's projection, and power retention by its RECURRENT
form, the same at every context (the model's promise, and the lesser of its
two forms past 4160 positions; a prompt's first 4160 are cheaper pair by
pair, which this count does not take: the mixer is a seventh of a token's
operations): each token's ``Hq`` query heads read a state of ``D x (d + 1)``
a key-value head (the state and its summed keys) and each token is folded
into it once, ``Hkv`` heads of the same; the pairs a tiling attends inside a
chunk or a page are that tiling's and are not counted. ``D = d (d + 1) / 2``,
the distinct products (``kernels/power_retention_decode.py``). ``cfg`` is the configuration file's block,
depth as run. Kept with the benchmark so that no PR that claims a gain can
change the count.
"""

from __future__ import annotations

from benchmark.kernels import power_retention_decode as state


def _dims(cfg: dict):
    h = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads") or hq
    d = cfg.get("head_dim") or h // hq
    return h, hq, hkv, d, cfg["intermediate_size"], cfg["num_hidden_layers"]


def layer_parameters(cfg: dict) -> int:
    """One layer's matrices: q, k, v, o, the gate, the MLP."""
    h, hq, hkv, d, f, _ = _dims(cfg)
    return 2 * h * hq * d + 2 * h * hkv * d + h * hkv + 3 * h * f


def retention_flops_per_token(cfg: dict) -> float:
    """ONE layer: the token's queries against the state, and its fold."""
    _, hq, hkv, d, _, _ = _dims(cfg)
    return 2.0 * (hq + hkv) * state.feature_dim(cfg) * (d + 1)


def token_flops(cfg: dict) -> float:
    """A token through every layer, the head left out."""
    layers = cfg["num_hidden_layers"]
    return layers * (
        2.0 * layer_parameters(cfg) + retention_flops_per_token(cfg)
    )


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` valid tokens; the head runs on the last
    position only."""
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return token_flops(cfg) * prompt_len + head


def decode_token_flops(cfg: dict) -> float:
    """One generated token: the same at every context."""
    return token_flops(cfg) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def stored_weight_bytes(cfg: dict, weight_bytes: float) -> float:
    """Bytes a decode step must read of the weights: every layer's matrices
    and the head at ``weight_bytes`` a stored value (the gate's 41 k values
    a layer among them; scales left out, under 0.1%). The embedding is a
    lookup."""
    return (
        cfg["num_hidden_layers"] * layer_parameters(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"]
    ) * weight_bytes


def state_bytes_per_token(cfg: dict) -> float:
    """Bytes one decoded token must read of its row's state, every layer."""
    return cfg["num_hidden_layers"] * state.state_bytes_per_row(cfg)
