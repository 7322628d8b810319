"""Pallas paged-attention kernel (decode hot path).

The decode-side companion of ``flash_attention.py`` (SURVEY §7 step 4): at
decode the XLA path first gathers every session's pages into a contiguous
``[B, max_len, Hkv, D]`` view (``cache/paged.py:update_and_gather``) — a full
copy of the active KV working set through HBM per layer per token. This kernel
instead reads K/V **in place** from the page pool: the grid walks
``(batch, page)`` with the page table riding as a scalar-prefetch operand, so
each step DMAs one whole physical page (all KV heads — ``[Hkv, PS, D]``, a
megabyte-scale contiguous block) straight from where it lives (the TPU analog
of vLLM's paged attention; the reference's multi-tenancy never got past a
dict of growing tensors,
``/root/reference/distributed_llm_inference/models/llama/cache.py:14-19``).

Bandwidth properties:
* no materialized contiguous copy — pages stream through VMEM once;
* a page block past a row's live length is clamped to the null page 0 in
  the index map, which saves its DMA (the block index does not change) —
  and, in ``paged_attention``, ``quantized_paged_attention`` and the two
  latent wrappers over them, NOTHING ELSE: their grid is ``(slots, table
  width)`` and every step of it runs the whole tile (two matmuls, the
  ``exp``, the accumulator's rescale) and pays the pipeline's fixed cost,
  live or not. The ledger priced such a step at 0.57-0.65 µs where a page's
  bytes take 0.17 (PR 23's breakdown, PERF.md §6), so their cost follows
  slots x table width, not the live tokens. No benchmark cell runs those
  four; the debt is open (PERF.md §7);
* ``quantized_paged_fused_attention`` — the kernel every int8 paged engine
  decodes through past ``INPLACE_CTX``, and (its one-stored-plane form,
  ``quantized_latent_paged_fused_attention``) every int8 latent engine —
  sweeps a row's LIVE pages: its
  grid is over rows only, K and V stay in HBM, and a loop inside the kernel
  fetches (double-buffered async copies, the physical ids from the page
  table in SMEM) and attends to the pages that hold something the query
  sees, a block of live pages as ONE tile of the online softmax (both
  forms: the per-head pools' copies and the latent pool's pipelined
  blocks, whose grid is one axis over the call's live blocks, listed
  once). A page past the row's length, or wholly before its sliding
  window, costs nothing; an empty slot runs the tail tile alone;
* MHA (``G == 1``) uses a VPU multiply-reduce for QK^T and PV — a 1-row MXU
  matmul per head wastes the systolic array; GQA (``G > 1``) uses
  ``Hkv``-batched ``dot_general``.

Online-softmax state (running max / denominator / accumulator) lives in VMEM
scratch carried across the page-grid axis (innermost ⇒ scratch persists
across one row's page sweep). The per-row (m, l) stats are ALSO emitted so
callers can merge this segment with others under one joint softmax — the
write-behind-tail decode (``models/llama.py:multi_decode_apply``) combines
the pool segment with the small tail segment that holds the fused steps' new
tokens.

``q_positions`` decouples the query's absolute position from the pool length:
in the tail regime the query sits ``tail_len`` tokens PAST the pool contents
(sliding-window masking needs the true position; plain causality over the
pool is just slot validity either way).

Runs in interpret mode off-TPU so the CPU test mesh exercises it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _NEG_INF

#: what a device trace calls the latent pool's decode sweep under a learned
#: selection, and the merge of its index tail into the index plane
#: (``cache/latent.py``: the indexed latent classes)
KERNEL_LATENT_DECODE = "sparse_latent_paged_fused_attention"
KERNEL_LATENT_INDEX_FLUSH = "latent_index_tail_flush"

__all__ = [
    "paged_attention",
    "quantized_paged_attention",
    "latent_paged_attention",
    "quantized_latent_paged_attention",
    "quantized_latent_paged_fused_attention",
    "latent_sweep_walk",
    "KERNEL_LATENT_DECODE",
    "KERNEL_LATENT_INDEX_FLUSH",
    "quantized_paged_fused_attention",
]


def _paged_kernel(
    table_ref,  # SMEM [B, T] int32 (scalar prefetch)
    len_ref,    # SMEM [B] int32 (scalar prefetch)
    qpos_ref,   # SMEM [B] int32 (scalar prefetch): query's absolute position
    q_ref,      # [1, Hkv, G, D]
    k_ref,      # [1, Hkv, PS, D]
    v_ref,      # [1, Hkv, PS, D]
    out_ref,    # [1, Hkv, G, D]
    m_out_ref,  # [1, Hkv*G, 128] f32
    l_out_ref,  # [1, Hkv*G, 128] f32
    acc_ref,    # VMEM [Hkv*G, D] f32
    m_ref,      # VMEM [Hkv*G, 128] f32
    l_ref,      # VMEM [Hkv*G, 128] f32
    *,
    scale: float,
    page_size: int,
    num_page_blocks: int,
    sliding_window: Optional[int],
    hkv: int,
    g: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    kv_len = len_ref[b]

    # Live-kv mask for this page's slots (pool slots < kv_len precede the
    # query, so causality ≡ slot validity); the sliding window is measured
    # from the query's true position.
    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1
    )
    valid = pos < kv_len
    if sliding_window is not None:
        valid &= pos > qpos_ref[b] - sliding_window

    q = q_ref[0]  # [Hkv, G, D]
    k = k_ref[0]  # [Hkv, PS, D]
    v = v_ref[0]

    if g == 1:
        # MHA: VPU multiply-reduce; a [1, D] x [D, PS] MXU call per head
        # would waste the systolic array on 1-row matmuls.
        qv = q[:, 0, :][:, None, :].astype(jnp.float32)     # [Hkv, 1, D]
        s = jnp.sum(qv * k.astype(jnp.float32), axis=-1)    # [Hkv, PS]
    else:
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(hkv * g, page_size)                        # [Hkv*G, PS]
    s = s * scale
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)

    l_ref[:] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    if g == 1:
        pv = jnp.sum(p[:, :, None] * v.astype(jnp.float32), axis=1)  # [Hkv, D]
        acc_ref[:] = acc_ref[:] * alpha + pv
    else:
        pg = p.reshape(hkv, g, page_size).astype(v.dtype)
        pv = jax.lax.dot_general(
            pg, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(hkv * g, -1)

    @pl.when(j == num_page_blocks - 1)
    def _finalize():
        # Fully-masked rows (kv_len == 0) have l == 0 → emit zeros.
        l = l_ref[:, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-20)
        out_ref[0] = out.reshape(hkv, g, -1).astype(out_ref.dtype)
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = l_ref[:]


def paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    interpret: Optional[bool] = None,
    q_positions: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
    name: str = "paged_attention",
):
    """Decode attention straight over the page pool.

    ``q``: ``[B, 1, Hq, D]`` (already rotated); ``k_pages``/``v_pages``:
    ``[P, Hkv, page_size, D]`` — one layer's pool, keys stored rotated;
    ``page_table``: ``[B, T]`` int32 physical page ids (slot order = position
    order, 0 = null page); ``kv_lengths``: ``[B]`` int32 live kv count per
    row; ``q_positions``: ``[B]`` absolute query positions (defaults to
    ``kv_lengths - 1`` — the classic decode step attending to itself last).
    Returns ``[B, 1, Hq, D]``, or with ``return_stats`` a tuple
    ``(out, m, l)`` with ``m``/``l`` ``[B, Hkv, G]`` fp32 online-softmax
    stats for joint-softmax merging with other segments.
    """
    b, s, hq, d = q.shape
    if s != 1:
        raise ValueError(f"paged_attention is decode-only (S=1), got S={s}")
    _, hkv, page_size, _ = k_pages.shape
    t = page_table.shape[1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if q_positions is None:
        q_positions = kv_lengths - 1

    qr = q.reshape(b, hkv, g, d)  # kv-head-major grouping, as gqa_attention

    def _page_index(bi, ji, table, lens, qpos):
        # Clamp blocks past the row's live span to the null page: the fetch
        # still happens (BlockSpec semantics) but hits one hot page.
        live = ji * page_size < lens[bi]
        return (jnp.where(live, table[bi, ji], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, t),
        in_specs=[
            pl.BlockSpec(
                (1, hkv, g, d), lambda bi, ji, table, lens, qpos: (bi, 0, 0, 0)
            ),
            pl.BlockSpec((1, hkv, page_size, d), _page_index),
            pl.BlockSpec((1, hkv, page_size, d), _page_index),
        ],
        out_specs=(
            pl.BlockSpec(
                (1, hkv, g, d), lambda bi, ji, table, lens, qpos: (bi, 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, hkv * g, 128),
                lambda bi, ji, table, lens, qpos: (bi, 0, 0),
            ),
            pl.BlockSpec(
                (1, hkv * g, 128),
                lambda bi, ji, table, lens, qpos: (bi, 0, 0),
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv * g, d), jnp.float32),
            pltpu.VMEM((hkv * g, 128), jnp.float32),
            pltpu.VMEM((hkv * g, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel,
        scale=scale,
        page_size=page_size,
        num_page_blocks=t,
        sliding_window=sliding_window,
        hkv=hkv,
        g=g,
    )
    out, m, l = pl.pallas_call(
        kernel,
        name=name,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv * g, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv * g, 128), jnp.float32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_lengths.astype(jnp.int32),
      q_positions.astype(jnp.int32), qr, k_pages, v_pages)
    out = out.reshape(b, 1, hq, d)
    if return_stats:
        return out, m[:, :, 0].reshape(b, hkv, g), l[:, :, 0].reshape(b, hkv, g)
    return out


def _qpaged_kernel(
    table_ref,  # SMEM [B, T] int32
    len_ref,    # SMEM [B] int32
    qpos_ref,   # SMEM [B] int32
    q_ref,      # [1, Hkv, G, D]
    k_ref,      # [1, Hkv, PS, D] int8
    ks_ref,     # [1, Hkv, PS] f32
    v_ref,      # [1, Hkv, PS, D] int8
    vs_ref,     # [1, Hkv, PS] f32
    out_ref,    # [1, Hkv, G, D]
    m_out_ref,  # [1, Hkv*G, 128] f32
    l_out_ref,  # [1, Hkv*G, 128] f32
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    page_size: int,
    num_page_blocks: int,
    sliding_window: Optional[int],
    hkv: int,
    g: int,
):
    """int8 page variant of :func:`_paged_kernel`: the per-(slot, head)
    scales apply to the SCORES/probs (``q·(k·s) = s·(q·k)``), so the int8
    pages stream through VMEM without a dequantized copy."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    kv_len = len_ref[b]
    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1
    )
    valid = pos < kv_len
    if sliding_window is not None:
        valid &= pos > qpos_ref[b] - sliding_window

    q = q_ref[0]                      # [Hkv, G, D]
    k = k_ref[0]                      # [Hkv, PS, D] int8
    ks = ks_ref[0]                    # [Hkv, PS] f32

    if g == 1:
        qv = q[:, 0, :][:, None, :].astype(jnp.float32)
        s = jnp.sum(qv * k.astype(jnp.float32), axis=-1) * ks  # [Hkv, PS]
    else:
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * ks[:, None, :]
        s = s.reshape(hkv * g, page_size)
    s = s * scale
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)

    l_ref[:] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    v = v_ref[0]                      # [Hkv, PS, D] int8
    vs = vs_ref[0]                    # [Hkv, PS] f32
    if g == 1:
        pw = p.reshape(hkv, page_size) * vs
        pv = jnp.sum(pw[:, :, None] * v.astype(jnp.float32), axis=1)
        acc_ref[:] = acc_ref[:] * alpha + pv
    else:
        pw = p.reshape(hkv, g, page_size) * vs[:, None, :]
        pv = jax.lax.dot_general(
            pw, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(hkv * g, -1)

    @pl.when(j == num_page_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-20)
        out_ref[0] = out.reshape(hkv, g, -1).astype(out_ref.dtype)
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = l_ref[:]


def quantized_paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    ks_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    vs_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    interpret: Optional[bool] = None,
    q_positions: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
    name: str = "quantized_paged_attention",
):
    """As :func:`paged_attention` over int8 pages with per-(slot, head)
    scale planes (``ks_pages``/``vs_pages``: ``[P, Hkv, page_size]`` f32)."""
    b, s, hq, d = q.shape
    if s != 1:
        raise ValueError(f"decode-only kernel (S=1), got S={s}")
    _, hkv, page_size, _ = k_pages.shape
    t = page_table.shape[1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if q_positions is None:
        q_positions = kv_lengths - 1

    qr = q.reshape(b, hkv, g, d)

    def _page_index(bi, ji, table, lens, qpos):
        live = ji * page_size < lens[bi]
        return (jnp.where(live, table[bi, ji], 0), 0, 0, 0)

    def _page_index3(bi, ji, table, lens, qpos):
        live = ji * page_size < lens[bi]
        return (jnp.where(live, table[bi, ji], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, t),
        in_specs=[
            pl.BlockSpec(
                (1, hkv, g, d), lambda bi, ji, table, lens, qpos: (bi, 0, 0, 0)
            ),
            pl.BlockSpec((1, hkv, page_size, d), _page_index),
            pl.BlockSpec((1, hkv, page_size), _page_index3),
            pl.BlockSpec((1, hkv, page_size, d), _page_index),
            pl.BlockSpec((1, hkv, page_size), _page_index3),
        ],
        out_specs=(
            pl.BlockSpec(
                (1, hkv, g, d), lambda bi, ji, table, lens, qpos: (bi, 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, hkv * g, 128),
                lambda bi, ji, table, lens, qpos: (bi, 0, 0),
            ),
            pl.BlockSpec(
                (1, hkv * g, 128),
                lambda bi, ji, table, lens, qpos: (bi, 0, 0),
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv * g, d), jnp.float32),
            pltpu.VMEM((hkv * g, 128), jnp.float32),
            pltpu.VMEM((hkv * g, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _qpaged_kernel,
        scale=scale,
        page_size=page_size,
        num_page_blocks=t,
        sliding_window=sliding_window,
        hkv=hkv,
        g=g,
    )
    out, m, l = pl.pallas_call(
        kernel,
        name=name,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv * g, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv * g, 128), jnp.float32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_lengths.astype(jnp.int32),
      q_positions.astype(jnp.int32), qr, k_pages, ks_pages, v_pages, vs_pages)
    out = out.reshape(b, 1, hq, d)
    if return_stats:
        return out, m[:, :, 0].reshape(b, hkv, g), l[:, :, 0].reshape(b, hkv, g)
    return out


def latent_paged_attention(
    q: jnp.ndarray,
    c_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    interpret: Optional[bool] = None,
    q_positions: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
):
    """Absorbed-MLA decode attention over ONE LAYER's float32 latent pool,
    already written, in place: the one-token step of ``cache/latent.py:
    LatentPagedKVCache.attend`` (``c_pages`` ``[P, 1, page_size, lat_dim]``
    fused ``[c ; k_rope]`` latents, ``q`` the absorbed
    ``[B, 1, Hq, lat_dim]`` query, ``K = V =`` stored latents, so the page
    walk is the decompression fusion). The ``(slots, table width)`` grid of
    :func:`paged_attention`; no benchmark cell runs it."""
    return paged_attention(
        q, c_pages, c_pages, page_table, kv_lengths, scale=scale,
        sliding_window=sliding_window, interpret=interpret,
        q_positions=q_positions, return_stats=return_stats,
        name="latent_paged_attention",
    )


def quantized_latent_paged_attention(
    q: jnp.ndarray,
    c_pages: jnp.ndarray,
    cs_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    interpret: Optional[bool] = None,
    q_positions: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
):
    """As :func:`latent_paged_attention` over the int8 latent pool with
    per-token f32 scales (``cs_pages``: ``[P, 1, page_size]``): the
    one-token step of an int8 latent engine that was given ``decode_steps``
    1. The grid form: every tile of ``(slots, table width)``, the pool
    fetched as K and again as V. An engine left to itself decodes through
    :func:`quantized_latent_paged_fused_attention`, which took this
    wrapper's name in a device trace; this one is traced as
    ``quantized_latent_paged_grid_attention``."""
    return quantized_paged_attention(
        q, c_pages, cs_pages, c_pages, cs_pages, page_table, kv_lengths,
        scale=scale, sliding_window=sliding_window, interpret=interpret,
        q_positions=q_positions, return_stats=return_stats,
        name="quantized_latent_paged_grid_attention",
    )


def quantized_latent_paged_fused_attention(
    q: jnp.ndarray,
    c_new: jnp.ndarray,
    pool_c: jnp.ndarray,
    pool_cs: jnp.ndarray,
    tail_c: jnp.ndarray,
    tail_cs: jnp.ndarray,
    layer_idx: jnp.ndarray,
    step_idx: jnp.ndarray,
    page_table: jnp.ndarray,
    base_len: jnp.ndarray,
    tail_valid_len: jnp.ndarray,
    q_positions: jnp.ndarray,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    select=None,
    walk=None,
):
    """A fused-decode step of an int8 latent engine: the one-stored-plane
    form of :func:`quantized_paged_fused_attention` (its body, its sweep of
    a row's live pages, its in-kernel tail) over the WHOLE
    ``[L, P, 1, PS, lat_dim]`` latent pool read in place, each live page
    fetched once and used as K and as V. ``q`` is the absorbed query and
    ``c_new`` ``[B, 1, 1, lat_dim]`` the step's latent in stored form (both
    rotated by the model). Returns ``(out, tail_c', tail_cs')``. Traced as
    ``quantized_latent_paged_attention``: one event is one layer of one
    decode step of a latent engine, as it was under the grid form. Under a
    learned selection (``select``, the pair
    :func:`quantized_paged_fused_attention` describes; ``cache/latent.py``:
    the indexed classes) the same sweep masks what was not chosen and is
    traced as ``sparse_latent_paged_fused_attention``; a call without one
    keeps its operands and its name."""
    planes = (
        q, c_new, None, pool_c, pool_cs, None, None, tail_c, tail_cs, None,
        None,
    )
    rows = dict(
        layer_idx=layer_idx, step_idx=step_idx, page_table=page_table,
        base_len=base_len, tail_valid_len=tail_valid_len,
        q_positions=q_positions, scale=scale, interpret=interpret, walk=walk,
    )
    if select is None:
        return quantized_paged_fused_attention(
            *planes, name="quantized_latent_paged_attention", **rows
        )
    return quantized_paged_fused_attention(
        *planes, name="sparse_latent_paged_fused_attention", select=select,
        **rows,
    )


def latent_sweep_walk(pool_c, k_steps, page_table, base_len, decoding):
    """The ``walk`` of :func:`quantized_latent_paged_fused_attention` (a
    call without a selection) over this pool under a tail of ``k_steps``
    slots (:func:`_sweep_walk`), or ``None`` where the stored row is swept
    by copies. It is a function of
    the table, the pool's lengths and which rows decode (``decoding``
    positive: a row's valid tail slots are then positive at every step of a
    fused window, and zero at every step otherwise): the same for every
    layer of every step of a window, so a caller that scans them builds it
    once, outside the scans (XLA hoists only a part of it out of a loop's
    body: PERF.md §6, PR 48)."""
    _, _, hkv, page_size, d = pool_c.shape
    if not _pages_by_grid(d):
        return None
    n = _pages_per_block(
        page_table.shape[1], hkv, page_size, d, k_steps, planes=1
    )
    lens = base_len.astype(jnp.int32)
    return _sweep_walk(
        page_table.astype(jnp.int32), lens, decoding.astype(jnp.int32),
        lens, n, page_size, None,
    )


# What the in-place kernel's page buffers and a row's operands are CHARGED
# by :func:`_pages_per_block`: K and V of a block of pages, twice (double
# buffering), beside a row's tail blocks and 16 KiB a table slot at 8 kv
# heads (what a row's ``[T, Hkv, PS]`` scale rows took of VMEM as a
# pipelined block, a 64-wide f32 row padded to a 128-lane tile, two planes,
# two buffers, while the wrapper gathered them). Since PR 61 the copies'
# form holds no such block (a page's scale rows arrive with the page, 8 KiB a
# page of the tile), so the slot term is no longer VMEM the kernel takes: it
# is a LOAD budget, kept because of what it decides. It never was what a
# v5e core holds (128 MiB; the call raises Mosaic's scoped limit to 100 MiB,
# and 32 MiB here compiled and ran at every cell's shapes): at 8 kv heads a
# table of 175 slots and more takes 2 pages a block and one of 207 and more
# ONE, the tile it always was. Those are the tables of stacks
# that hold a kernel call a layer in every decode executable
# (``k-exaone-236b-a23b.mixedlen``: 12 calls, 227 slots), and there a
# 4-page tile halved the full layers' kernel time and grew the executables'
# load by a half (39.9 -> 64.3 s, ``setup_s`` 73.7 -> 93.3 s; PERF.md §6,
# PR 42), which no cell may pay (ROADMAP D5, S5 b). tests/test_chip_compile.py
# compiles the cells' shapes; tests/test_paged_attention.py pins the width at
# each cell's.
_SWEEP_VMEM_BUDGET = 4 * 2**20

# The stored bytes (K and V, int8) of the sweep's tile, a block of pages
# attended as one. Wider is cheaper a page only so far: with every page live
# the chip timed a page at 0.36 / 0.27 / 0.24 us in tiles of 2 / 4 / 8 pages
# at 8 kv heads of 128 (a page a tile: 0.54; its bytes take 0.17) and at
# 0.29 / 0.19 / 0.14 at 4 heads, but a row's last tile is padded to the
# width, so at ``mistral-7b.reason``'s 11-12 pages a row 4 and 8 tie (4.70
# against 4.76 ms a 32-layer step), a quarter of the slots live 4 win (1.84
# against 2.00) and so they do at a window layer's 3 pages a row (154 us a
# call against 167; a page a tile: 169). And the tile is code in every
# decode executable: its load grows with the tile's bytes (PERF.md §6,
# PR 42). Half a MiB is 4 pages at 8 kv heads and 8 at 4.
_SWEEP_TILE_BYTES = 512 * 2**10


def _pages_by_grid(d):
    """Whether a fused-decode call takes its pool pages as pipelined blocks
    (the grid walks a row's table, a block of pages a step) and not by async
    copies of its own. Mosaic (jax 0.9.0) refuses EVERY slice of an HBM
    plane whose minor dimension is not whole 128-lane tiles, the whole row
    too ("Slice shape along dimension 4 must be aligned to tiling (128), but
    is 576": the latent pool's stored row), so such a pool cannot be swept
    by copies; a pipelined block of whole rows it takes as stored. A row
    narrower than a tile (no chip runs one) keeps the copies: the CPU suite's
    small pools walk the path the cells' per-head pools walk."""
    return d > 128 and d % 128 != 0


def _scale_rows_by_page(lanes):
    """Whether the copies' form fetches a page's scale rows itself, an async
    copy at ``(layer, physical page)`` beside the page's K and V: where the
    scale plane it is handed holds a page's rows of every stored plane side
    by side in ``lanes`` that are whole 128-lane tiles (two planes at pages
    of 64: :func:`joined_scale_rows`). Mosaic (jax 0.9.0) refuses an async
    copy out of a plane whose minor dimension is anything else ("Slice shape
    along dimension 3 must be aligned to tiling (128), but is 64"); there the
    wrapper gathers the rows of every table slot in XLA, a layer a step."""
    return lanes % 128 == 0


def joined_scale_rows(pool_ks, pool_vs):
    """K's and V's scale rows of a page side by side in ONE plane, ``[L, P,
    Hkv, PS]`` float32 twice -> ``[L, P, Hkv, 2 * PS]``: the form in which
    :func:`quantized_paged_fused_attention` copies them by the live page
    (pass it as ``pool_ks``, with ``pool_vs`` None). None where the kernel
    could not (:func:`_scale_rows_by_page`): the caller keeps the two planes.
    It is a read and a write of both planes (1/32 of the pool's K and V
    bytes), so a caller that scans layers and steps makes it once, outside
    the scans: the pool is read-only through a fused window."""
    if not _scale_rows_by_page(2 * pool_ks.shape[-1]):
        return None
    return jnp.concatenate([pool_ks, pool_vs], axis=-1)


def _pages_per_block(t, hkv, page_size, d, kt, planes=2):
    """Pages of K and V (``planes`` 2; 1 where one stored plane is both) one
    block of the sweep fetches, which is also the width of its tile: the
    most (a power of two, no more than the table is wide, no more than 8)
    whose stored bytes fit :data:`_SWEEP_TILE_BYTES` and whose double
    buffers fit :data:`_SWEEP_VMEM_BUDGET` beside the row's tail and the
    charge a table slot that budget keeps (a load budget: see there). At 8
    kv heads of 128 that is 4 pages up to a table of 174 slots,
    2 up to 206 and 1 past it; 8 at 4 heads (up to 182 slots) and for the
    latent pool's one 576-wide plane, whose blocks are pipelined operands
    and the steps of its grid (:func:`_sweep_walk`): there the width is
    also what a row's steps are counted in, cdiv(live pages, 8) of them."""
    lanes = -(-d // 128) * 128
    heads = -(-hkv // 8) * 8
    page = planes * hkv * -(-page_size // 32) * 32 * lanes     # K + V, int8
    row = 2 * planes * t * heads * max(page_size, 128) * 4     # a slot's charge
    row += 2 * 2 * (planes * hkv * -(-kt // 32) * 32 * lanes   # tail in +
                    + planes * heads * max(kt, 128) * 4)       # out, 2 bufs
    n = 1
    while (
        2 * n <= min(t, 8)
        and 2 * n * page <= _SWEEP_TILE_BYTES
        and 2 * (2 * n) * page + row <= _SWEEP_VMEM_BUDGET
    ):
        n *= 2
    return n


def _live_pages(kv_len, qpos, page_size, width, sliding_window, xp=jnp):
    """A row's live pages ``[lo, hi)``: what lies past its length, or wholly
    before its window, is never fetched and never computed. Both ends are
    held inside the table: a length is the caller's word, and a page id read
    past the table's end would be the source of a DMA. ``xp=numpy`` counts
    on the host what the kernel sweeps (``engine/plan.py``)."""
    hi = xp.clip((kv_len + page_size - 1) // page_size, 0, width)
    if sliding_window is None:
        return 0, hi
    return xp.minimum(
        xp.maximum(qpos - sliding_window + 1, 0) // page_size, hi
    ), hi


def _row_steps(lo, hi, n, xp=jnp):
    """The steps a row of live pages ``[lo, hi)`` takes of a sweep by
    pipelined blocks of ``n`` pages: its blocks that hold a live page,
    ``[lo // n, cdiv(hi, n))``, and one where there is none."""
    return xp.maximum((hi + n - 1) // n - lo // n, 1)


def _sweep_walk(table, lens, vlen, qpos, n, page_size, sliding_window,
                xp=jnp):
    """The steps of a call that sweeps its pool by pipelined blocks
    (:func:`_pages_by_grid`), listed once: ``(steps, rows, blocks, pages)``.
    A row's steps are the blocks of ``n`` table slots that hold one of its
    live pages, ``[lo // n, cdiv(hi, n))`` by :func:`_live_pages`, in the
    table's order, and the rows follow each other; a row with no such block
    (not decoding: ``vlen`` 0) keeps ONE step, its block its range's first,
    because its tail tile, its division and its results' write are a step's.
    ``rows`` and ``blocks`` ``[B * cdiv(T, n)]`` name a step's row and block,
    ``pages`` (flat, ``n`` a step) the physical page of each of the block's
    places, the null page where a place is dead; past the ``steps`` the call
    walks all three repeat the last step, and nothing reads them.
    ``xp=numpy`` lists on the host what the kernel walks
    (``engine/plan.py`` counts it). A row's numbers reach its steps as sums
    under the steps' one-hot rows, and a step's pages as ONE row of the
    table cut in blocks: a gather an entry cost the chip more than all the
    rest (46 us a list; PERF.md §6, PR 48)."""
    b, t = table.shape
    nb = -(-t // n)
    lo, hi = _live_pages(
        xp.where(vlen > 0, lens, 0), qpos, page_size, t, sliding_window, xp
    )
    lo = lo + 0 * hi
    count = _row_steps(lo, hi, n, xp)
    end = xp.cumsum(count)
    step = xp.minimum(xp.arange(b * nb), end[-1] - 1)[:, None]
    own = (step >= (end - count)[None, :]) & (step < end[None, :])  # [W, B]

    def of_row(x):
        return xp.sum(xp.where(own, x[None, :], 0), axis=1)

    rows = of_row(xp.arange(b))
    blocks = of_row(lo // n - (end - count)) + step[:, 0]
    by_block = xp.pad(table, ((0, 0), (0, nb * n - t))).reshape(b * nb, n)
    place = blocks[:, None] * n + xp.arange(n)[None, :]
    live = (place >= of_row(lo)[:, None]) & (place < of_row(hi)[:, None])
    # (a row with no live page may name the block past its table: all dead)
    pages = by_block[rows * nb + xp.minimum(blocks, nb - 1)]
    return end[-1], rows, blocks, xp.where(live, pages, 0).reshape(-1)


def quantized_paged_fused_attention(
    q: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: Optional[jnp.ndarray],
    pool_k: jnp.ndarray,
    pool_ks: jnp.ndarray,
    pool_v: Optional[jnp.ndarray],
    pool_vs: Optional[jnp.ndarray],
    tail_k: jnp.ndarray,
    tail_ks: jnp.ndarray,
    tail_v: Optional[jnp.ndarray],
    tail_vs: Optional[jnp.ndarray],
    layer_idx: jnp.ndarray,
    step_idx: jnp.ndarray,
    page_table: jnp.ndarray,
    base_len: jnp.ndarray,
    tail_valid_len: jnp.ndarray,
    q_positions: jnp.ndarray,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    sliding_window: Optional[int] = None,
    name: str = "quantized_paged_fused_attention",
    select=None,
    walk=None,
):
    """ONE kernel for a fused-decode step over the int8 page pool IN PLACE:
    the WHOLE ``[L, P, Hkv, PS, D]`` K and V planes stay in HBM
    (``pl.ANY``: zero-copy — the r2 per-layer pool slices materialized a
    full pool copy per (layer, step), and the r3 gather-per-window fix held
    a second contiguous copy of the live KV alive, halving the admissible
    batch at long contexts) and the kernel fetches ``(layer, physical
    page)`` itself; the step's fresh K/V quantizes in-kernel into the
    io-aliased write-behind tail, which joins the page sweep as the final
    online-softmax tile.

    **One stored plane or two.** A pool whose stored plane is both K and V
    (the latent cache: ``K = V = [c ; k_rope]``) passes ``None`` for
    ``v_new``, ``pool_v``, ``pool_vs``, ``tail_v`` and ``tail_vs``: the
    same body then has ONE pool operand, one page buffer, one fetch a live
    page and one tail plane with its scale row, and every tile uses the
    fetched page as K and as V. That is a static branch on what the caller
    stores, not a second kernel; the two-plane call traces to the program
    it always did.

    The grid is over rows. A row's sweep is a loop INSIDE the kernel over
    its own live pages — page ``lo`` (0, or under a sliding window the first
    page that holds a position the query still sees) to
    ``cdiv(base_len, page_size)`` — fetched a block of
    :func:`_pages_per_block` pages at a time with double-buffered async
    copies, the physical ids read from the page table in SMEM. A page past
    the row's length, or wholly before its window, is neither fetched nor
    computed, and a slot that is not decoding (``tail_valid_len`` 0: the
    engine leaves a released row's length stale until its next admission)
    runs the tail tile alone, whatever its length says: a call costs what
    the live tokens cost, not slots x table width.

    **A block of live pages is ONE tile.** A block's pages land side by
    side in its buffer (``[Hkv, n, PS, D]``, which is ``[Hkv, n * PS, D]``
    as it lies), their scale rows (and a selection's) are put side by side,
    and the block is one tile of the online softmax: the scores of ``n *
    PS`` positions, one masked maximum, exponent and sum, one PV product,
    one update of the running state, where a page a tile chained ``n`` of
    each (0.54 us a live page against 0.27, PERF.md §6, PR 42). A row's
    last block may hold fewer than ``n`` live pages: it is the same tile,
    its other places (whatever the buffer held, under the scale rows of
    whatever the table names there) masked as positions and their V scales
    SELECTED away, since ``p * scale`` with ``p == 0`` and a NaN scale is
    NaN. :func:`_pages_per_block` keeps the tile narrow enough (half a MiB
    stored) that a short row's padding costs less than its pages' chain
    did: a window layer's 3 pages in a tile of 4 take 154 us a call where
    three tiles took 169. The same pages are read once, in the same bf16
    operands into float32 sums; what differs from a page a tile is the
    ORDER of the float32 sums (one maximum over a block where ``n`` were
    chained), so a row's results are the whole-grid walk's to float32
    rounding, not bit for bit. Skipping a dead page changes no sum.

    **Where Mosaic cannot copy a page** (:func:`_pages_by_grid`: a stored
    row that is not whole 128-lane tiles, the latent pool's 576) the block's
    pages come as pipelined operands, the whole pool behind each, and the
    grid is ONE axis over the call's live steps, its bound the count of
    them (a dynamic grid dimension: what is not live is not a step the
    chip executes, where a dead step of a ``(rows, table blocks)`` grid
    costs 0.65 us and a trailing step of a static bound 0.31; under a
    selection the grid is still that one, rows x every block of the table,
    its index maps deriving a row's live range a step: see ``walked``
    below). The steps are
    listed once (:func:`_sweep_walk`; ``walk``, where the caller built the
    list outside its scans: :func:`latent_sweep_walk`) and ride as
    scalar-prefetch operands: a step's row, its block of the row's table,
    and the physical page of each of the block's places, the null page
    where a place is dead (one fetch, then none while the index stands), so
    an index map is a read of SMEM. A row's steps are its blocks that hold a
    live page, in the table's order, and they are ONE tile of the online
    softmax each, the pages side by side under the positions' mask (a page
    cost 0.54 us as a tile of its own and costs 0.17 in a block of eight,
    PERF.md §6, PR 31 and PR 48); a row with no live page keeps one step.
    The accumulator is cleared at a row's first step and lives across its
    steps; the tail, the division and the results' write are its last
    step's. Still no slice and no copy of the pool, each live page read
    once; what it pays over the copies is the pipeline's fixed cost a LIVE
    step (1.23 us a block of eight pages whose bytes take 0.36) and the
    dead pages beside a row's last live one.

    **The scale rows arrive with the page** in the copies' form, where the
    caller hands them over as ONE plane of whole 128-lane tiles: K's and
    V's rows of a page side by side, ``pool_ks`` ``[L, P, Hkv, 2 * PS]``
    with ``pool_vs`` None (:func:`joined_scale_rows`, made once a window
    outside the caller's scans; the one plane of a one-plane pool as it is
    stored, where its pages are whole tiles wide). A third async copy at
    ``(layer, physical page)`` is started and waited beside that page's K
    and V, into a double-buffered ``[2, N, Hkv, 2 * PS]`` scratch, for the
    row's LIVE pages only; a tile's scale rows are the lane halves of its
    pages' rows put side by side. Mosaic (jax 0.9.0) refuses that copy out
    of a plane whose minor dimension is one page (``[.., Hkv, 64]`` f32:
    "Slice shape along dimension 3 must be aligned to tiling (128), but is
    64"), so handed the planes as they are stored (``pool_vs`` given: a
    page size whose two rows are no whole tiles, :func:`_scale_rows_by_page`)
    the wrapper gathers the layer's scale rows of EVERY table slot in XLA
    (``[B, T, Hkv, PS]`` f32 a plane, a layer a step, live or not, out of a
    slice of the layer's whole plane: 0.72 ms of a 17.0 ms step at 32 rows x
    38 slots over 1280 pages, and more in what the two crowded out of VMEM,
    PERF.md §6, PR 61) and a row's come as one pipelined block. The values and the order of the sums are the
    same: the two forms' results are bit for bit each other's. The
    pipelined blocks' form (``by_grid``) always takes them gathered.

    Shapes: ``q`` ``[B, 1, Hq, D]`` (rotated); ``k_new``/``v_new``
    ``[B, 1, Hkv, D]`` (k rotated); pool planes ``[L, P, Hkv, PS, D]`` int8
    (+ ``[L, P, Hkv, PS]`` f32 scales, or the one joined plane above); tail
    planes ``[L, B, Hkv, KT, D]`` (+ scales, io-aliased). Returns ``(out, tail_k', tail_ks', tail_v',
    tail_vs')``, or ``(out, tail_k', tail_ks')`` of one stored plane.
    ``name`` is what a device trace calls the kernel.

    **Under a selection** (``select``: learned sparse attention,
    ``ops/sparse_attention.py``) a row attends only to the positions its
    indexer chose: ``select`` is ``(pool [B, T, 1, PS], tail [B, 1, KT])``
    float32, positive where a pool position (by table slot) or a tail slot
    is selected. They are two more pipelined blocks a row, and one more
    term of each tile's mask: every live page is still fetched, and a call
    without a selection traces to the program it always did. Both forms of
    the sweep take it: the copies' and the pipelined page blocks' (the
    latent pool's).

    ``walk``: the list :func:`_sweep_walk` gives for these rows, built by
    the caller; the copies' form and a call under a selection never read it.
    """
    b, s, hq, d = q.shape
    if s != 1:
        raise ValueError(f"decode-only kernel (S=1), got S={s}")
    shared = pool_v is None
    if shared and any(
        x is not None for x in (v_new, pool_vs, tail_v, tail_vs)
    ):
        raise ValueError("one stored plane takes no V operand at all")
    num_l, _, hkv, page_size, _ = pool_k.shape
    kt = tail_k.shape[3]
    t = page_table.shape[1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    planes = 1 if shared else 2
    n = _pages_per_block(t, hkv, page_size, d, kt, planes)
    by_grid = _pages_by_grid(d)
    # One scale plane that holds a page's rows of every stored plane side
    # by side, whole tiles wide: the copies' form fetches them by the page.
    lanes = pool_ks.shape[-1]
    by_page = (
        not by_grid and pool_vs is None and lanes == planes * page_size
        and _scale_rows_by_page(lanes)
    )
    if not shared and pool_vs is None and not by_page:
        raise ValueError(
            f"one scale plane of {lanes} lanes is not K's and V's rows of a "
            f"{page_size}-token page side by side in whole tiles, or this "
            "pool is swept by pipelined blocks: pass both planes"
        )
    # Pipelined blocks walk the call's list, but not yet under a selection:
    # that call keeps the (rows, table blocks) grid, its program the one it
    # was. Walked, ``glm-5.2.codebase``'s decode step was 16% shorter and
    # its judged ``tpot_ms_p50`` read 2.4% WORSE (a median of 21 requests,
    # half of them decoding under the lead-in's prefills, that moved with
    # the schedule's trajectory: PERF.md §6 and §7, PR 48).
    walked = by_grid and select is None

    qr = q.reshape(b, hkv, g, d)
    # Per stored plane, in the kernel's operand order: the step's fresh
    # values, the tail (values + scale row, io-aliased), the pool (whole)
    # with its scale plane (gathered to the row's table slots below; where
    # the kernel fetches them by the page, the one joined plane whole, after
    # the pools).
    fresh = [jnp.moveaxis(k_new, 1, 2)]                  # [B, Hkv, 1, D]
    tails = [tail_k, tail_ks]
    pools = [(pool_k, pool_ks)]
    if not shared:
        fresh += [jnp.moveaxis(v_new, 1, 2)]
        tails += [tail_v, tail_vs]
        pools += [(pool_v, pool_vs)]
    lref = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    sref = jnp.asarray(step_idx, jnp.int32).reshape(1)
    table = page_table.astype(jnp.int32)

    def _scale_rows(plane):  # [L, P, Hkv, PS] -> this layer's [B, T, Hkv, PS]
        layer = jax.lax.dynamic_index_in_dim(plane, lref[0], keepdims=False)
        # A table holds page ids; "clip" spares the default mode's bounds
        # test and fill (a quarter of this gather's time on the chip).
        return jnp.take(layer, table, axis=0, mode="clip")

    lens = base_len.astype(jnp.int32)
    vlen = tail_valid_len.astype(jnp.int32)
    qpos = q_positions.astype(jnp.int32)
    # The scalar operands: layer, step, page ids, lengths, valid tail slots,
    # query positions. A row's page ids are its table's; the walk's (the
    # call's list, ``_sweep_walk``) take the table's place, and its rows and
    # blocks follow.
    grid, scalars = (b,), (lref, sref, table, lens, vlen, qpos)
    if walked:
        steps, rows, blocks, pages = walk or _sweep_walk(
            table, lens, vlen, qpos, n, page_size, sliding_window
        )
        grid = (steps,)
        scalars = (lref, sref, pages, lens, vlen, qpos, rows, blocks)
    elif by_grid:
        grid = (b, -(-t // n))

    # An index map takes the grid's ids, then the scalar operands.
    def _at(a):  # the layer and the row of a grid step
        s = a[len(grid):]
        return s[0][0], s[6][a[0]] if walked else a[0]

    def _tail_index(*a):
        return (*_at(a), 0, 0, 0)

    def _tail_index3(*a):
        return (*_at(a), 0, 0)

    def _row_index(*a):
        return (_at(a)[1], 0, 0, 0)

    def _page_index(i):
        if walked:  # a read of SMEM is all it computes
            return lambda at, *s: (s[0][0], s[2][at * n + i], 0, 0, 0)

        def index(bi, ji, lidx, step, table, lens, vlen, qpos):
            page = ji * n + i
            lo, hi = _live_pages(
                jnp.where(vlen[bi] > 0, lens[bi], 0), qpos[bi], page_size,
                t, sliding_window,
            )
            live = (page >= lo) & (page < hi)
            slot = jnp.minimum(page, t - 1)
            return (lidx[0], jnp.where(live, table[bi, slot], 0), 0, 0, 0)

        return index

    def _tail_specs():
        return [
            pl.BlockSpec((1, 1, hkv, kt, d), _tail_index),
            pl.BlockSpec((1, 1, hkv, kt), _tail_index3),
        ] * planes

    if by_grid:
        pool_specs = [
            pl.BlockSpec((1, 1, hkv, page_size, d), _page_index(i))
            for i in range(n)
        ]
        page_bufs = []
    else:
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        page_bufs = [
            *[pltpu.VMEM((2, hkv, n, page_size, d), pool_k.dtype)] * planes,
            *([pltpu.VMEM((2, n, hkv, lanes), jnp.float32)] if by_page
              else []),
            pltpu.SemaphoreType.DMA((2, n)),
        ]
    if by_page:
        pool_in = [pl.BlockSpec(memory_space=pl.ANY)] * (planes + 1)
        pool_args = [*(plane for plane, _ in pools), pool_ks]
    else:
        pool_in = [
            *pool_specs,
            pl.BlockSpec((1, t, hkv, page_size), _row_index),
        ] * planes
        pool_args = [
            x for plane, sc in pools
            for x in (*[plane] * len(pool_specs), _scale_rows(sc))
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), _row_index),
            *[pl.BlockSpec((1, hkv, 1, d), _row_index)] * planes,
            *_tail_specs(),
            *pool_in,
            *([] if select is None else [
                pl.BlockSpec((1, t, 1, page_size), _row_index),
                pl.BlockSpec((1, 1, kt), lambda *a: (_at(a)[1], 0, 0)),
            ]),
        ],
        out_specs=(
            pl.BlockSpec((1, hkv, g, d), _row_index),
            *_tail_specs(),
        ),
        scratch_shapes=[
            *page_bufs,
            pltpu.VMEM((hkv * g, d), jnp.float32),
            pltpu.VMEM((hkv * g, 128), jnp.float32),
            pltpu.VMEM((hkv * g, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _qpaged_fused_kernel,
        planes=planes,
        by_grid=by_grid,
        by_page=by_page,
        walked=walked,
        scale=scale,
        page_size=page_size,
        width=t,
        pages_per_block=n,
        sliding_window=sliding_window,
        hkv=hkv,
        g=g,
        kt=kt,
        selected=select is not None,
    )
    # Tail planes update in place; an alias's index counts every flattened
    # input, the scalar-prefetch operands and q and the fresh values too.
    first_tail = len(scalars) + 1 + planes
    out, *new_tails = pl.pallas_call(
        kernel,
        name=name,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            *[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in tails],
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        input_output_aliases={
            first_tail + i: 1 + i for i in range(len(tails))
        },
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                ("arbitrary",) if walked
                else ("parallel", "arbitrary") if by_grid else ("parallel",)
            ),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(*scalars, qr, *fresh, *tails, *pool_args,
      *(() if select is None else (
          select[0].astype(jnp.float32), select[1].astype(jnp.float32),
      )))
    return (out.reshape(b, 1, hq, d), *new_tails)


def _qpaged_fused_kernel(
    lidx_ref,   # SMEM [1] int32 (layer)
    step_ref,   # SMEM [1] int32 (tail write slot)
    table_ref,  # SMEM [B, T] int32 (physical page ids; ``walked`` the
                # walk's, flat, N a step: the index maps read them)
    len_ref,    # SMEM [B] int32 (live pool tokens)
    vlen_ref,   # SMEM [B] int32 (valid tail slots incl. this write)
    qpos_ref,   # SMEM [B] int32 (query positions)
    *refs,
    planes: int,
    by_grid: bool,
    by_page: bool,
    walked: bool,
    scale: float,
    page_size: int,
    width: int,
    pages_per_block: int,
    sliding_window: Optional[int],
    hkv: int,
    g: int,
    kt: int,
    selected: bool = False,
):
    """``refs``, for the stored planes K and V (``planes`` 2) or the one
    plane that is both (``planes`` 1), a plane after the other in each group:

    * ``walked``: the walk's row and block a step, SMEM ``[steps]`` int32
      (:func:`_sweep_walk`), the last two scalar operands;
    * the queries ``[1, Hkv, G, D]``;
    * fresh values ``[1, Hkv, 1, D]``;
    * tail in: values ``[1, 1, Hkv, KT, D]`` int8, scale row
      ``[1, 1, Hkv, KT]`` f32;
    * pool: HBM ``[L, P, Hkv, PS, D]`` int8 (the whole pool) or, ``by_grid``,
      the N pages of this step's block ``[1, 1, Hkv, PS, D]`` each; then the
      row's scale rows by table slot ``[1, T, Hkv, PS]`` f32; or,
      ``by_page``, the pools and after them ONE scale plane, HBM ``[L, P,
      Hkv, planes * PS]`` f32 (a page's rows side by side, plane by plane);
    * ``selected``: the row's selection, by table slot ``[1, T, 1, PS]`` and
      by tail slot ``[1, 1, KT]`` f32 (positive = attend);
    * ``out_ref`` ``[1, Hkv, G, D]``, then the aliased tail outputs;
    * scratch: unless ``by_grid``, a plane's VMEM ``[2, Hkv, N, PS, D]`` int8
      (two blocks of N pages, a head's pages side by side), ``by_page``
      the pages' scale rows ``[2, N, Hkv, planes * PS]`` f32, and DMA
      semaphores ``[2, N]`` (one a page buffer, its planes and its scale
      rows together);
      ``acc`` ``[Hkv*G, D]``, ``m`` and ``l`` ``[Hkv*G, 128]`` f32.
    """
    refs = list(refs)
    n = pages_per_block

    def _take(count):
        taken = refs[:count]
        del refs[:count]
        return taken

    row_ref, blk_ref = _take(2) if walked else (None, None)
    (q_ref,) = _take(1)
    new_refs = _take(planes)
    tail_in = _take(2 * planes)
    per = n + 1 if by_grid else 1 if by_page else 2
    pool = _take(per * planes)
    scale_hbm = _take(1)[0] if by_page else None
    sel_pool, sel_tail = _take(2) if selected else (None, None)
    (out_ref,) = _take(1)
    tail_out = _take(2 * planes)
    bufs = [] if by_grid else _take(planes)
    scale_buf = _take(1)[0] if by_page else None
    sems = None if by_grid else _take(1)[0]
    acc_ref, m_ref, l_ref = refs
    scale_rows = [] if by_page else pool[per - 1 :: per]

    b = row_ref[pl.program_id(0)] if walked else pl.program_id(0)
    layer = lidx_ref[0]
    # A row with no valid tail slot is not decoding (an empty slot, a
    # released one, one parked mid-prefill: a decoding row's tail holds at
    # least the token this step writes). Its length is whatever its last
    # tenant left there, so it is not believed: the row sweeps nothing.
    kv_len = jnp.where(vlen_ref[b] > 0, len_ref[b], 0)
    qpos = qpos_ref[b]
    lo, hi = _live_pages(kv_len, qpos, page_size, width, sliding_window)

    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _accumulate(s, valid):
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        return p, alpha

    def _tile(stored, valid, width):
        """One tile of the online softmax over ``stored``, a plane's
        ``(values [Hkv, width, D], scale row)`` after the other: K then V,
        or the one plane that is both (its bf16 form is then made once)."""
        (kk, kks), (vv, vvs) = stored[0], stored[-1]
        # q from its block every tile: held across the page loop it cost
        # 3% of the kernel's time on the chip (PERF.md §6, PR 24).
        qb = q_ref[0].astype(jnp.bfloat16).reshape(hkv, g, -1)
        kb = kk.astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            qb, kb.reshape(hkv, width, -1),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                        # [Hkv, G, W]
        s = (s * kks[:, None, :] * scale).reshape(hkv * g, width)
        p, alpha = _accumulate(s, valid)
        pw = p.reshape(hkv, g, width) * vvs[:, None, :]
        pv = jax.lax.dot_general(
            pw.astype(jnp.bfloat16),
            kb if vv is kk else vv.astype(jnp.bfloat16),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(hkv * g, -1)

    def _valid(first_page, width):
        """Which of ``width`` positions from ``first_page`` on the query
        sees: those the row holds, inside its window."""
        pos = first_page * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, width), 1
        )
        valid = pos < kv_len
        if sliding_window is not None:
            valid &= pos > qpos - sliding_window
        return valid

    def _finish():
        """Once a row, after its pages: the step's fresh values into the
        tail (absmax / 127 a token a head, ``cache/dense.py:_quantize_kv``'s
        rule), the tail as the last tile, the division."""
        step = step_ref[0]
        new = [ref[0].astype(jnp.float32) for ref in new_refs]  # [Hkv, 1, D]
        new_sc = [
            jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0
            for x in new
        ]
        new_q = [
            jnp.clip(jnp.round(x / sc[..., None]), -127, 127).astype(jnp.int8)
            for x, sc in zip(new, new_sc)
        ]
        # Two iotas, not ``hit3[..., 0]``: Mosaic (jax 0.9.0) refuses the
        # squeeze of a mask's lane dim ("Invalid vector register cast").
        hit3 = jax.lax.broadcasted_iota(jnp.int32, (1, kt, 1), 1) == step
        hit2 = jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1) == step
        tail_vals = [
            jnp.where(hit3, xq, ref[0, 0])                    # [Hkv, KT, D]
            for xq, ref in zip(new_q, tail_in[0::2])
        ]
        tail_scs = [
            jnp.where(hit2, sc, ref[0, 0])                    # [Hkv, KT]
            for sc, ref in zip(new_sc, tail_in[1::2])
        ]
        for ref, x in zip(tail_out[0::2], tail_vals):
            ref[0, 0] = x
        for ref, x in zip(tail_out[1::2], tail_scs):
            ref[0, 0] = x

        pos1 = jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1)
        tail_valid = pos1 < vlen_ref[b]
        if sliding_window is not None:
            tail_valid &= kv_len + pos1 > qpos - sliding_window
        if selected:
            tail_valid &= sel_tail[0] > 0
        _tile(list(zip(tail_vals, tail_scs)), tail_valid, kt)

        out = acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-20)
        out_ref[0] = out.reshape(hkv, g, -1).astype(out_ref.dtype)

    if by_grid:
        # The block of the row's table this step of the walk holds, its
        # pages the step's pipelined operands: put side by side they are ONE
        # tile, if any of them is live (a page a tile is a chain of matmul,
        # maximum, exponent, sum, matmul that the next page's waits for:
        # 0.54 us a live page against 0.20-0.25, PERF.md §6, PR 31). A dead
        # page beside a live one is the null page under a mask (its scale
        # row any row the table has). A row's steps are the blocks from its
        # range's first to its last, one where it has none (``_sweep_walk``);
        # or, not ``walked``, every block of its table (grid axis 1).
        if walked:
            blk = blk_ref[pl.program_id(0)]
            first = lo // n
            finish = first + _row_steps(lo, hi, n) - 1
        else:
            blk, first = pl.program_id(1), 0
            finish = pl.num_programs(1) - 1
        pl.when(blk == first)(_init)
        last = width - 1

        @pl.when((blk * n < hi) & (blk * n + n > lo))
        def _block_tile():
            stored = [
                (jnp.concatenate(
                    [pool[plane * per + i][0, 0] for i in range(n)], 1),
                 jnp.concatenate(
                    [scale_rows[plane][0, jnp.minimum(blk * n + i, last)]
                     for i in range(n)], -1))
                for plane in range(planes)
            ]
            valid = _valid(blk * n, n * page_size)
            if selected:  # by table slot [1, T, 1, PS], as the scale rows
                valid &= jnp.concatenate(
                    [sel_pool[0, jnp.minimum(blk * n + i, last)]
                     for i in range(n)], -1,
                ) > 0
            _tile(stored, valid, n * page_size)

        pl.when(blk == finish)(_finish)
        return

    hbms = pool[0::per]
    num_blocks = (hi - lo + n - 1) // n
    last = width - 1

    def _page_copies(slot, i, page):
        phys = table_ref[b, page]
        pairs = [(hbm, buf.at[slot, :, i]) for hbm, buf in zip(hbms, bufs)]
        if by_page:  # the page's scale rows, plane beside plane
            pairs.append((scale_hbm, scale_buf.at[slot, i]))
        return [
            pltpu.make_async_copy(src.at[layer, phys], dst, sems.at[slot, i])
            for src, dst in pairs
        ]

    def _block_pages(blk, body):
        """``body(i, page)`` for the live pages of block ``blk``: a loop,
        not ``n`` copies of the body (the engine traces this kernel once an
        executable, and one executable a table width)."""
        first = lo + blk * n

        def step(i, carry):
            body(i, first + i)
            return carry

        jax.lax.fori_loop(0, jnp.minimum(n, hi - first), step, 0)

    def _start_block(blk, slot):
        def start(i, page):
            for copy in _page_copies(slot, i, page):
                copy.start()

        _block_pages(blk, start)

    @pl.when(num_blocks > 0)
    def _first_block():
        _start_block(0, 0)

    _init()

    def _block(blk, carry):
        """A block of the row's live pages as ONE tile of the online
        softmax: its pages lie side by side in the buffer (``[Hkv, n, PS,
        D]`` is the bytes of ``[Hkv, n * PS, D]``), their scale rows (and a
        selection's) are put side by side, and the positions' mask, the
        window's term in it, covers them all. The row's last block may
        hold fewer than ``n`` live pages: the places past them keep what
        the buffer held (int8: finite) under scale rows of whatever the
        table names there (``by_page``: whatever the scale buffer held),
        so their positions are masked and their V scales
        SELECTED away (``p * scale`` with ``p == 0`` and a NaN scale is
        NaN)."""
        slot = blk % 2

        @pl.when(blk + 1 < num_blocks)
        def _prefetch():
            _start_block(blk + 1, 1 - slot)

        def wait(i, page):
            for copy in _page_copies(slot, i, page):
                copy.wait()

        _block_pages(blk, wait)
        first = lo + blk * n

        def side_by_side(rows):  # [1, T, H, PS] by table slot -> [H, n * PS]
            return jnp.concatenate(
                [rows[0, jnp.minimum(first + i, last)] for i in range(n)], -1
            )

        width = n * page_size
        valid = _valid(first, width)
        if n > 1:  # (a block of one page is a live page: nothing is padded)
            fetched = first * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, width), 1
            ) < hi * page_size
            valid &= fetched
        if selected:
            valid &= side_by_side(sel_pool) > 0
        # ``by_page``: what came with the pages, [Hkv, planes * PS] each
        came = [scale_buf[slot, i] for i in range(n)] if by_page else None

        def scales_of(plane):  # its scale rows of the block's places
            if not by_page:
                return side_by_side(scale_rows[plane])
            at = plane * page_size
            return jnp.concatenate(
                [rows[:, at : at + page_size] for rows in came], -1
            )

        stored = [
            (buf[slot].reshape(hkv, width, -1), scales_of(plane))
            for plane, buf in enumerate(bufs)
        ]
        if n > 1:
            vv, vvs = stored[-1]
            stored[-1] = (vv, jnp.where(fetched, vvs, 0.0))
        _tile(stored, valid, width)
        return carry

    jax.lax.fori_loop(0, num_blocks, _block, 0)
    _finish()


def paged_tail_flush(
    pool_k: jnp.ndarray,
    pool_ks: Optional[jnp.ndarray],
    pool_v: Optional[jnp.ndarray],
    pool_vs: Optional[jnp.ndarray],
    tail_k: jnp.ndarray,
    tail_ks: Optional[jnp.ndarray],
    tail_v: Optional[jnp.ndarray],
    tail_vs: Optional[jnp.ndarray],
    page_table: jnp.ndarray,
    base_len: jnp.ndarray,
    tail_len: jnp.ndarray,
    interpret: Optional[bool] = None,
    name: str = "paged_tail_flush",
):
    """Merge the fused window's int8 tail into the page pool by
    read-modify-writing ONLY the pages each row's window touches.

    Why a kernel: the XLA scatter (``cache/paged.py:_scatter_planes``)
    prefers a transposed pool layout, so XLA inserts a whole-pool relayout
    copy into the fused-decode executable feeding the Pallas attention's
    default-layout operand — a 2x3.2 GB HLO temp at b24/1k-ctx 7B shapes
    that OOMs the chip (and silently taxes smaller batches). Here each
    (layer, row) round-trips at most ``ceil(KT/PS)+1`` physical pages
    through VMEM with position-based composition (idempotent under clamped
    duplicate visits), and the pool keeps its default layout end to end.

    ``tail_*``: ``[L, B, Hkv, KT, D]`` int8 (+ ``[L, B, Hkv, KT]`` f32
    scales), KT <= page_size. Rows must have table slots mapped through
    ``base_len + tail_len`` (engine growth contract); clamped visits hit
    the null page 0 and compose no changes. Returns the updated pool
    planes, one a plane given (inputs consumed — aliased). A pool of ONE
    stored plane (the latent cache's: the same array cannot be aliased
    twice) passes ``None`` for the four V arguments and gets its two planes
    back; a value plane with no scales (an index plane in the model's
    dtype, ``cache/paged.py``; ``name`` is then its own) passes ``None``
    for those too. Each plane is blocked by its own head count and width.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ps = pool_k.shape[3]
    num_l = pool_k.shape[0]
    b = page_table.shape[0]
    t = page_table.shape[1]
    kt = tail_k.shape[3]
    if kt > ps:
        raise ValueError(f"tail ({kt}) must fit one page ({ps})")
    nj = -(-kt // ps) + 1  # straddle: at most 2 pages per row's window
    given = [
        (tail, pool) for tail, pool in zip(
            (tail_k, tail_ks, tail_v, tail_vs),
            (pool_k, pool_ks, pool_v, pool_vs),
        ) if pool is not None
    ]
    tails = [tail for tail, _ in given]
    pools = [pool for _, pool in given]

    def _slot(bi, ji, table, lens):
        return table[bi, jnp.minimum(lens[bi] // ps + ji, t - 1)]

    def _pidx(li, bi, ji, table, lens, tl):
        return (li, _slot(bi, ji, table, lens), 0, 0, 0)

    def _pidx4(li, bi, ji, table, lens, tl):
        return (li, _slot(bi, ji, table, lens), 0, 0)

    def _tidx(li, bi, ji, table, lens, tl):
        return (li, bi, 0, 0, 0)

    def _tidx3(li, bi, ji, table, lens, tl):
        return (li, bi, 0, 0)

    def kernel(table_ref, lens_ref, tl_ref, *refs):
        # A plane's (values, scale row) after the other in each group.
        tail_refs = refs[: len(tails)]
        pool_in = refs[len(tails) : 2 * len(tails)]
        pool_out = refs[2 * len(tails) :]
        bi = pl.program_id(1)
        ji = pl.program_id(2)
        start = lens_ref[bi]
        tl = tl_ref[bi]
        slot = jnp.minimum(start // ps + ji, t - 1)

        def compose(pool_ref, tail_ref, out_ref):
            """Values ``[Hkv, PS, D]`` or a scale row ``[Hkv, PS]``."""
            values = len(pool_ref.shape) == 5
            pos = slot * ps + jax.lax.broadcasted_iota(
                jnp.int32, (1, ps, 1) if values else (1, ps), 1
            )
            cur = pool_ref[0, 0]
            tail = tail_ref[0, 0]
            for i in range(kt):
                hit = (pos == start + i) & (i < tl)
                cur = jnp.where(hit, tail[:, i : i + 1], cur)
            out_ref[0, 0] = cur

        for rank in (5, 4):  # the value planes, then the scale rows
            for i, pool in enumerate(pools):
                if pool.ndim == rank:
                    compose(pool_in[i], tail_refs[i], pool_out[i])

    def _specs(arrays, idx5, idx4):
        return [
            pl.BlockSpec((1, 1, *a.shape[2:]), idx5 if a.ndim == 5 else idx4)
            for a in arrays
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_l, b, nj),
        in_specs=[
            *_specs(tails, _tidx, _tidx3), *_specs(pools, _pidx, _pidx4),
        ],
        out_specs=tuple(_specs(pools, _pidx, _pidx4)),
        scratch_shapes=[],
    )
    return pl.pallas_call(
        kernel,
        name=name,
        out_shape=tuple(
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pools
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        # Inputs counting scalars: table 0, lens 1, tl 2, then the tails,
        # then the pools, each aliased to its output.
        input_output_aliases={
            3 + len(tails) + i: i for i in range(len(pools))
        },
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(page_table.astype(jnp.int32), base_len.astype(jnp.int32),
      tail_len.astype(jnp.int32), *tails, *pools)


def paged_piece_write(
    pools,
    tiles,
    layer: jnp.ndarray,
    page_table: jnp.ndarray,
    start: jnp.ndarray,
    num_new: jnp.ndarray,
    interpret: Optional[bool] = None,
    name: str = "paged_piece_write",
):
    """Write a prefill piece into the WHOLE page stacks at ``(layer, page)``,
    by the pages it fills, in place.

    ``pools``: the carried stacks, values ``[L, P, Hkv, PS, D]`` and scale
    rows ``[L, P, Hkv, PS]`` in any order; ``tiles``: the piece's planes in
    the same order, each LAID OUT AS THE PAGES IT FILLS, ``[B, N, Hkv, PS(,
    D)]``: tile ``i`` of row ``b`` holds what the piece has of table slot
    ``start[b] // PS + i`` at the offsets it has it (elsewhere anything);
    ``layer``: the cache layer's index, a traced scalar; ``start`` /
    ``num_new`` ``[B]``: the row's first position of the piece and its valid
    tokens. Grid step ``(b, i)`` round-trips one physical page through VMEM:
    positions in ``[start, start + num_new)`` take the tile's value, every
    other keeps the page's (a first or last page that the piece fills in
    part, a continuation chunk that starts inside a page), so a position a
    per-position scatter with ``mode="drop"`` would not write is not written.
    A step past the row's last page (``num_new`` 0, pad width past the
    prompt, a slot past the table) visits the null page 0 and writes back
    what it read. The stacks keep their layout and are aliased to the
    results: no operation has a layer's plane as its value
    (:func:`paged_tail_flush` is the same round trip for a decode window's
    tail, every layer in one call)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ps = pools[0].shape[3]
    b, t = page_table.shape
    n = tiles[0].shape[1]

    def _page(bi, ji, table, first, nnew):
        slot = first[bi] // ps + ji
        live = (slot * ps < first[bi] + nnew[bi]) & (nnew[bi] > 0) & (slot < t)
        return slot, live, jnp.where(live, table[bi, jnp.minimum(slot, t - 1)], 0)

    def _pool_index(rank):
        def index(bi, ji, lay, table, first, nnew):
            page = _page(bi, ji, table, first, nnew)[2]
            return (lay[0], page) + (0,) * (rank - 2)
        return index

    def _tile_index(rank):
        def index(bi, ji, lay, table, first, nnew):
            return (bi, ji) + (0,) * (rank - 2)
        return index

    def kernel(lay_ref, table_ref, first_ref, nnew_ref, *refs):
        tile_refs = refs[: len(tiles)]
        pool_in = refs[len(tiles) : 2 * len(tiles)]
        pool_out = refs[2 * len(tiles) :]
        bi = pl.program_id(0)
        ji = pl.program_id(1)
        slot, live, _ = _page(bi, ji, table_ref, first_ref, nnew_ref)
        lo = first_ref[bi]
        hi = lo + nnew_ref[bi]
        for tile_ref, in_ref, out_ref in zip(tile_refs, pool_in, pool_out):
            values = len(in_ref.shape) == 5
            pos = slot * ps + jax.lax.broadcasted_iota(
                jnp.int32, (1, ps, 1) if values else (1, ps), 1
            )
            hit = live & (pos >= lo) & (pos < hi)
            out_ref[0, 0] = jnp.where(hit, tile_ref[0, 0], in_ref[0, 0])

    def _specs(arrays, index):
        return [
            pl.BlockSpec((1, 1, *a.shape[2:]), index(a.ndim)) for a in arrays
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n),
        in_specs=[*_specs(tiles, _tile_index), *_specs(pools, _pool_index)],
        out_specs=tuple(_specs(pools, _pool_index)),
        scratch_shapes=[],
    )
    return pl.pallas_call(
        kernel,
        name=name,
        out_shape=tuple(
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pools
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        # inputs counting scalars: layer 0, table 1, start 2, num_new 3, then
        # the tiles, then the stacks, each aliased to its result
        input_output_aliases={
            4 + len(tiles) + i: i for i in range(len(pools))
        },
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(jnp.asarray(layer, jnp.int32).reshape(1), page_table.astype(jnp.int32),
      start.astype(jnp.int32), num_new.astype(jnp.int32), *tiles, *pools)
