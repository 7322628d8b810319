"""What one call of ``power_retention_decode`` needs, from shapes alone: the
decode kernel of a stack of retention layers (``ops/power_retention.py``).
One call is one layer of one decode step over the rows it walks (the live
ones); ``rows`` is how many, ``pairs`` the unfolded positions a row attends
pair by pair (its open page's and its write-behind tail's, itself among
them), a row's mean. Kept with the benchmark, as ``flops.py`` is, so that no
PR that claims a gain can change the count.

``cfg`` is the configuration file's published block. Counted: every walked
row's state ``[Hkv, D, d]`` and summed keys ``[Hkv, D]`` in float32, ``D = d
(d + 1) / 2``, the DISTINCT products of a key with itself (8256 at 128: what
the mathematics needs; a program that keeps more, as this one's 65 rotations
of 128 do, 8320, reads 0.8% that the count leaves out), and the pairs' keys
and values in the model's two bytes with a float32 gate sum each. Not
counted: the query, the result, and the places of the open page and the tail
that hold nothing yet (the kernel fetches all 80; under 1% of a row's state).
"""

from __future__ import annotations


def _heads(cfg: dict):
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads") or hq
    return hq, hkv, cfg.get("head_dim") or cfg["hidden_size"] // hq


def feature_dim(cfg: dict) -> int:
    """The distinct degree-2 products of a ``d``-wide key."""
    d = _heads(cfg)[2]
    return d * (d + 1) // 2


def state_bytes_per_row(cfg: dict) -> float:
    """A row's state and summed keys in ONE layer."""
    _, hkv, d = _heads(cfg)
    return hkv * feature_dim(cfg) * (d + 1) * 4.0


def bytes_read(cfg: dict, rows: float, pairs: float) -> float:
    _, hkv, d = _heads(cfg)
    return rows * (
        state_bytes_per_row(cfg) + pairs * hkv * (2 * d * 2 + 4)
    )


def operations(cfg: dict, rows: float, pairs: float) -> float:
    """Each query head's features against the state and the summed keys,
    and its squared scores and weighted values over the pairs."""
    hq, _, d = _heads(cfg)
    return rows * hq * (2.0 * feature_dim(cfg) * (d + 1) + 4.0 * pairs * d)
