"""What one call of ``window_ragged_paged_attention`` needs, from shapes
alone: the prefill kernel of a WINDOW layer of an int8 two-pool engine
(``cache/paged.py``: the window layers' pool; the body is
``quantized_ragged_paged_attention``'s, under a static window). One call is
one window layer of one prefill-family dispatch (a whole prompt, a group of
prompts, or a chunk). Kept with the benchmark so that no PR that claims a
gain can change the count.

``pairs`` is the (query, key) pairs the window keeps over the dispatch's
valid queries: a query at position ``t`` sees ``min(window, t + 1)`` keys
(``plan.note_dispatch``'s census, which a dispatch's record carries).
Operations: QK^T and PV of every query head for each such pair. Bytes: each
valid position's stored K and V once (int8, every kv head) and their float32
scale rows, the ``window - 1`` positions before the dispatch's first that a
chunk reads back (``rows`` times: a row at position 0 has none, which the
count ignores), and the queries in and the results out, ``query_bytes`` a
value. The pad to the dispatch's width is nobody's need and is not counted.
"""

from __future__ import annotations

from benchmark.kernels.quantized_paged_fused_attention import _heads


def bytes_read(cfg: dict, rows: int, valid: float, reach_back: float,
               query_bytes: float = 2.0) -> float:
    hq, hkv, d = _heads(cfg)
    return (
        (valid + reach_back) * 2 * hkv * (d + 4)
        + valid * 2 * hq * d * query_bytes
    )


def operations(cfg: dict, pairs: float) -> float:
    hq, _, d = _heads(cfg)
    return pairs * 2 * 2.0 * hq * d
