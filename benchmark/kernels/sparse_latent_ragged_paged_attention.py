"""What one call of ``sparse_latent_ragged_paged_attention`` needs, from
shapes alone: the prefill kernel of an int8 latent pool under a learned
selection that layers share (``ops/ragged_attention.py``'s ragged kernel
over the one stored plane, under the selection's mask). One call is one
layer of one prefill-family dispatch (a whole prompt or a chunk).
``selected`` is the (query, key) pairs the selection keeps, summed over the
dispatch's valid queries (a query at position ``t`` keeps ``min(index_topk,
t + 1)``), ``live`` the pairs a causal mask alone would keep, ``valid`` the
dispatch's valid tokens, ``context`` the live positions of its rows (earlier
chunks included), and ``scoring`` whether the call is a layer's that scores
its own selection. Kept with the benchmark.

Operations: QK^T over the stored width and PV over the latent's columns of
every query head for every selected pair, and in a scoring layer the
indexer's heads for every live pair. Bytes: the stored latents and scales of
the rows' live context once (a chunk's queries select among all of them),
in a scoring layer their index keys, the absorbed queries in and the
latent-space results out.
"""

from __future__ import annotations

from benchmark.kernels.quantized_latent_paged_attention import stored_width
from benchmark.kernels.sparse_latent_paged_fused_attention import (
    attended_operations, index_key_bytes, index_operations,
)


def bytes_read(cfg: dict, valid: float, context: float, scoring: bool,
               query_bytes: float = 2.0) -> float:
    w = stored_width(cfg)
    return (
        context * (w + 4 + (index_key_bytes(cfg) if scoring else 0.0))
        + valid * 2 * cfg["num_attention_heads"] * w * query_bytes
    )


def operations(cfg: dict, selected: float, live: float, scoring: bool) -> float:
    return selected * attended_operations(cfg) + (
        live * index_operations(cfg) if scoring else 0.0
    )
