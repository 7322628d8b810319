"""What one call of ``quantized_latent_ragged_paged_attention`` needs, from
shapes alone: the prefill kernel of an int8 latent pool
(``ops/ragged_attention.py``). One call is one layer of one prefill dispatch
of ``rows`` prompts that start at position 0 and hold ``valid`` tokens
together; the pad to the dispatch's width is nobody's need and is not
counted.

Operations: QK^T and PV over the stored width for every (query, key) pair a
causal mask keeps, ``n (n + 1) / 2`` a prompt of ``n`` tokens; a dispatch's
record holds its rows' tokens summed, so rows are taken as equal (exact for
one row, and a lower bound otherwise: the sum of squares is least there).
Bytes: each valid position's stored latent once (int8 and a float32 scale),
and the absorbed queries in and the results out, ``query_bytes`` a value,
``num_attention_heads`` x the stored width a token each way.
"""

from __future__ import annotations

from benchmark.kernels.quantized_latent_paged_attention import stored_width


def causal_pairs(rows: int, valid: float) -> float:
    n = valid / max(rows, 1)
    return rows * n * (n + 1) / 2


def bytes_read(cfg: dict, rows: int, valid: float, query_bytes: float = 2.0) -> float:
    w = stored_width(cfg)
    return valid * (w + 4 + 2 * cfg["num_attention_heads"] * w * query_bytes)


def operations(cfg: dict, rows: int, valid: float) -> float:
    return (
        causal_pairs(rows, valid) * 2 * 2.0
        * cfg["num_attention_heads"] * stored_width(cfg)
    )
