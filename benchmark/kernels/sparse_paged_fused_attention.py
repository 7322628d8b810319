"""What one call of ``sparse_paged_fused_attention`` needs, from shapes
alone: the decode kernel of an int8 paged engine under a learned key
selection (``ops/sparse_attention.py``; ``ops/paged_attention.py``'s fused
in-place sweep under the selection's mask). One call is one layer of one
decode step over every row of the batch. ``selected`` is the positions the
rows' queries attend to, summed (a row's ``min(topk, context)``), ``live``
the rows' context lengths, summed. Kept with the benchmark so that no PR
that claims a gain can change the count.

``cfg`` is the configuration file's published block. Counted, as ISSUE 32
defines the selection-and-attention step's need: the stored K and V of the
SELECTED positions (int8, every kv head) and their float32 scale rows, and
the index keys of the LIVE context (``sa_config.indexer_head_dim`` bf16
values a position, as the configuration stores them: every live position is
scored). The kernel as
built fetches every live page and masks what is not selected, and the index
keys are read by the scoring before it: the share reads low for both,
honestly. Operations: QK^T and PV of every query head against each selected
position, and the indexer's heads against each live one.
"""

from __future__ import annotations

from benchmark.kernels.quantized_paged_fused_attention import _heads


def index_key_bytes(cfg: dict) -> float:
    return cfg["sa_config"]["indexer_head_dim"] * 2.0


def bytes_read(cfg: dict, selected: float, live: float) -> float:
    _, hkv, d = _heads(cfg)
    return selected * 2 * hkv * (d * 1 + 4) + live * index_key_bytes(cfg)


def operations(cfg: dict, selected: float, live: float) -> float:
    hq, _, d = _heads(cfg)
    sa = cfg["sa_config"]
    return (
        selected * 4.0 * hq * d
        + live * 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    )
