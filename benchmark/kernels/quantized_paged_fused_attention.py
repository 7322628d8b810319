"""What one call of ``quantized_paged_fused_attention`` needs, from shapes
alone: the decode kernel of every int8 paged engine at a table of 768
positions or more (``ops/paged_attention.py``). One call is one layer of one
decode step over every row of the batch; ``positions`` is the rows' live
context lengths, summed. Kept with the benchmark, as ``flops.py`` is, so
that no PR that claims a gain can change the count.

``cfg`` is the configuration file's published block. Counted: the stored K
and V of the live positions (int8, every kv head) and their float32 scale
rows (one a head and position, for K and for V). Not counted: the page a
row's length only part fills (the kernel fetches whole pages: up to 63
positions a row more than this), the query, the result, and the 16-token
tail, which are under 1% of a live page's bytes.
"""

from __future__ import annotations


def _heads(cfg: dict):
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads") or hq
    return hq, hkv, cfg.get("head_dim") or cfg["hidden_size"] // hq


def bytes_read(cfg: dict, positions: float) -> float:
    _, hkv, d = _heads(cfg)
    return positions * 2 * hkv * (d * 1 + 4)


def operations(cfg: dict, positions: float) -> float:
    """QK^T and PV of every query head against each live position."""
    hq, _, d = _heads(cfg)
    return positions * 4.0 * hq * d
