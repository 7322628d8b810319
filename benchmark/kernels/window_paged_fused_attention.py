"""What one call of ``window_paged_fused_attention`` needs, from shapes
alone: the decode kernel of a WINDOW layer of an int8 two-pool engine
(``cache/paged.py``: the window layers' pool; the body is
``quantized_paged_fused_attention``'s, under a static window). One call is
one window layer of one decode step over every row of the batch;
``positions`` is the positions the rows' queries see, summed: a row's
``min(window, context)``. Kept with the benchmark so that no PR that claims
a gain can change the count.

``cfg`` is the configuration file's published block. Counted: the stored K
and V of the in-window positions (int8, every kv head) and their float32
scale rows. Not counted: what the kernel fetches besides, because it fetches
whole pages (a window of 128 over pages of 64 lies in 3 pages two times in
three: up to half as much again), the query, the result and the 16-token
tail. Operations: QK^T and PV of every query head against each in-window
position.
"""

from __future__ import annotations

from benchmark.kernels.quantized_paged_fused_attention import _heads


def bytes_read(cfg: dict, positions: float) -> float:
    _, hkv, d = _heads(cfg)
    return positions * 2 * hkv * (d * 1 + 4)


def operations(cfg: dict, positions: float) -> float:
    hq, _, d = _heads(cfg)
    return positions * 4.0 * hq * d
