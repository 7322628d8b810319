"""What one call of ``quantized_latent_paged_attention`` needs, from shapes
alone: the decode kernel of an int8 latent pool (``ops/paged_attention.py``;
``cache/latent.py``). One call is one layer of one decode step over every row
of the batch; ``positions`` is the rows' live context lengths, summed.

``cfg`` is the configuration file's published block. The stored form is one
latent a position, ``kv_lora_rank + qk_rope_head_dim`` int8 values and one
float32 scale, and it is both K and V (the absorbed form: the query carries
the key up-projection, the value up-projection comes after). Counted ONCE:
the least a kernel could read, and since PR 31 what the fused one-plane
form does read (the grid form before it took the pool as K and again as V).
Operations: QK^T and PV of every query head against each live position,
both over the whole stored width, as the kernel computes them (PV's last
``qk_rope_head_dim`` columns are computed and dropped by the model: 11% of
it). Not counted: the part-filled last page of a row, the query, the result.
"""

from __future__ import annotations


def stored_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def bytes_read(cfg: dict, positions: float) -> float:
    return positions * (stored_width(cfg) * 1 + 4)


def operations(cfg: dict, positions: float) -> float:
    return positions * 2 * 2.0 * cfg["num_attention_heads"] * stored_width(cfg)
