"""What one call of ``sparse_ragged_paged_attention`` needs, from shapes
alone: the prefill kernel of an int8 paged engine under a learned key
selection (``ops/ragged_attention.py``'s ragged kernel under the selection's
mask). One call is one layer of one prefill-family dispatch (a whole prompt
or a chunk). ``selected`` is the (query, key) pairs the selection keeps,
summed over the dispatch's valid queries (a query at position ``t`` keeps
``min(topk, t + 1)``), ``live`` the pairs a causal mask alone would keep,
``valid`` the dispatch's valid tokens and ``context`` the live positions of
its rows (earlier chunks included). Kept with the benchmark.

Operations: QK^T and PV of every query head for every selected pair, and
the indexer's heads for every live pair. Bytes: the stored K and V and scale
rows of the rows' live context once (a chunk's queries select among all of
them), their index keys, the queries in and the results out.
"""

from __future__ import annotations

from benchmark.kernels.quantized_paged_fused_attention import _heads
from benchmark.kernels.sparse_paged_fused_attention import index_key_bytes


def bytes_read(cfg: dict, valid: float, context: float, query_bytes: float = 2.0) -> float:
    hq, hkv, d = _heads(cfg)
    return (
        context * (2 * hkv * (d + 4) + index_key_bytes(cfg))
        + valid * 2 * hq * d * query_bytes
    )


def operations(cfg: dict, selected: float, live: float) -> float:
    hq, _, d = _heads(cfg)
    sa = cfg["sa_config"]
    return (
        selected * 2 * 2.0 * hq * d
        + live * 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    )
