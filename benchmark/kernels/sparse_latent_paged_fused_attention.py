"""What one call of ``sparse_latent_paged_fused_attention`` needs, from
shapes alone: the decode kernel of an int8 latent pool under a learned
selection that layers share (``ops/paged_attention.py``'s fused one-plane
sweep under the selection's mask; ``cache/latent.py``: the indexed latent
classes). One call is one layer of one decode step over every row of the
batch. ``selected`` is the positions the rows' queries attend to, summed (a
row's ``min(index_topk, context)``), ``live`` the rows' context lengths,
summed; ``scoring`` says whether the call is a layer's that scores its own
selection (``indexer_types`` "full") or one that reuses. Kept with the
benchmark so that no PR that claims a gain can change the count.

``cfg`` is the configuration file's published block. Counted, as
``sparse_paged_fused_attention.py`` counts: the stored latents of the
SELECTED positions (``kv_lora_rank + qk_rope_head_dim`` int8 values and one
float32 scale, read once: the one plane is K and V) and, in a scoring
layer's call only, the index keys of the LIVE context (``index_head_dim``
bf16 values a position: every live position is scored). Operations: QK^T
over the stored width and PV over the latent's ``kv_lora_rank`` columns of
every query head against each selected position (the kernel as built
computes PV over the whole stored width and the model drops the rotary
columns: not counted), and in a scoring layer the indexer's heads against
each live one. At 64 heads x (576 + 512) x 2 operations over 580 bytes a
selected position, 240 operations a byte, the call sits on the ridge of a
v5e (197 TFLOP/s over 819 GB/s). The kernel as built fetches every live page
and masks what is not selected, and the index keys are read by the scoring
before it: the share reads low for both, honestly, and reads the same work
whether the kernel masks or gathers.
"""

from __future__ import annotations

from benchmark.kernels.quantized_latent_paged_attention import stored_width


def index_key_bytes(cfg: dict) -> float:
    return cfg["index_head_dim"] * 2.0


def index_operations(cfg: dict) -> float:
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]


def attended_operations(cfg: dict) -> float:
    """QK^T and PV of every query head against ONE position."""
    return 2.0 * cfg["num_attention_heads"] * (
        stored_width(cfg) + cfg["kv_lora_rank"]
    )


def bytes_read(cfg: dict, selected: float, live: float, scoring: bool) -> float:
    return selected * (stored_width(cfg) + 4) + (
        live * index_key_bytes(cfg) if scoring else 0.0
    )


def operations(cfg: dict, selected: float, live: float, scoring: bool) -> float:
    return selected * attended_operations(cfg) + (
        live * index_operations(cfg) if scoring else 0.0
    )
