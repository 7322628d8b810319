"""What one call of ``quantized_ragged_paged_attention`` needs, from shapes
alone: the prefill kernel of every int8 paged engine under the ragged plan
(``ops/ragged_attention.py``). One call is one layer of one prefill dispatch
of ``rows`` prompts that start at position 0 and hold ``valid`` tokens
together; the pad to the dispatch's width is nobody's need and is not
counted. Kept with the benchmark, as ``flops.py`` is, so that no PR that
claims a gain can change the count.

Operations: QK^T and PV of every query head for every (query, key) pair a
causal mask keeps, ``n (n + 1) / 2`` a prompt of ``n`` tokens; a dispatch's
record holds its rows' tokens summed, so rows are taken as equal (exact for
one row, and a lower bound otherwise: the sum of squares is least there).
The window is not counted: no cell's prompt is longer than its model's.
Bytes: each valid position's stored K and V once (int8, every kv head) and
their float32 scale rows (one a head and position, for K and for V), and
the queries in and the results out, ``query_bytes`` a value.
"""

from __future__ import annotations

from benchmark.kernels.quantized_paged_fused_attention import _heads


def causal_pairs(rows: int, valid: float) -> float:
    n = valid / max(rows, 1)
    return rows * n * (n + 1) / 2


def bytes_read(cfg: dict, rows: int, valid: float, query_bytes: float = 2.0) -> float:
    hq, hkv, d = _heads(cfg)
    return valid * (2 * hkv * (d + 4) + 2 * hq * d * query_bytes)


def operations(cfg: dict, rows: int, valid: float) -> float:
    hq, _, d = _heads(cfg)
    return causal_pairs(rows, valid) * 2 * 2.0 * hq * d
