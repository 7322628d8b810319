"""Pallas ragged mixed-phase paged attention (one grid for every phase).

``paged_attention.py`` killed the decode-side gather; prefill and chunked
prefill still route through ``cache/paged.py:update_and_gather`` — a full
contiguous ``[B, max_len, Hkv, D]`` copy of every row's pages per layer —
and through per-bucket padded dispatches (``engine/engine.py:_bucket_for``),
one executable a bucket.

This kernel serves rows with PER-ROW true lengths in ONE grid call:

* ``num_new[b]`` query tokens for row ``b`` start at absolute position
  ``q_start[b]`` and attend causally over that row's first ``kv_lengths[b]``
  pool slots. A full prefill row (``q_start == 0``), a chunked-prefill row
  (``q_start > 0``, ``num_new == C``), and a decode row (``num_new == 1``)
  are the SAME cell of the same grid — phase is data, not shape, so mixed
  prefill/decode batches never recompile.
* K/V stream IN PLACE from the page pool: the grid walks ``(batch, q-block,
  page)`` with the page table scalar-prefetched. A tile is LIVE if some
  valid query of its block may see some live key of its page
  (:func:`_tile_live`: under the row's query count, under its live span, at
  or before the block's causal frontier, inside the window). A dead tile is
  neither fetched nor computed: the index maps clamp it to the null page 0
  (an unchanged block index is not fetched again) and the kernel bodies run
  their matmuls, mask and softmax update under ``pl.when(live)``, both by
  that one predicate. What a dead step still costs is the grid step itself:
  the index maps of its operands and the pipeline's bookkeeping (0.08-0.14
  us over int8 K/V, 0.15-0.21 us over the latent pool, against 3.7 us for a
  live tile at Mistral-7B widths: PERF.md, PR 27); ``_init`` and
  ``_finalize`` stay unconditional, so a q-block past the row's query count
  still writes its zeros.
* The query tile ``[BQ, Hkv, G, D]`` rides the MXU as an ``Hkv``-batched
  ``[BQ*G, D] x [D, PS]`` ``dot_general`` (prefill has real row counts; the
  1-row VPU special case in ``_paged_kernel`` only pays off at ``BQ*G == 1``).

Online-softmax state is VMEM scratch carried across the page axis (innermost,
so one (row, q-block)'s sweep owns it), in the exact idiom of
``paged_attention._paged_kernel``. Runs in interpret mode off-TPU so tier-1
CPU tests exercise the same code path as the chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _NEG_INF

#: what a device trace calls the int8 latent pool's ragged kernel under a
#: learned selection (``cache/latent.py``: the indexed latent classes)
KERNEL_LATENT_PREFILL = "sparse_latent_ragged_paged_attention"

__all__ = [
    "KERNEL_LATENT_PREFILL",
    "ragged_paged_attention",
    "quantized_ragged_paged_attention",
    "latent_ragged_paged_attention",
    "quantized_latent_ragged_paged_attention",
    "ragged_attention_reference",
]


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _tile_live(qi, j, q_start, num_new, kv_len, *, block_q, page_size,
               sliding_window):
    """Whether tile (q-block ``qi``, table slot ``j``) of a row holds a
    (query, key) pair that the kernels' element mask keeps: exactly
    ``valid.any()``, from scalars. The block's valid queries are
    ``[qi*BQ, min(qi*BQ + BQ, num_new))`` past ``q_start`` and the page's
    live keys ``[j*PS, min(j*PS + PS, kv_len))``; ``key - query`` takes
    every value between the corners of that rectangle, so a causal (and
    windowed) pair exists iff the corners straddle the band. THE one
    predicate: the index maps fetch by it, the kernel bodies compute by it
    and ``engine/plan.py`` counts by it. Comparisons and ``&`` only, so
    ints, numpy arrays and traced scalars all pass through."""
    q_lo = q_start + qi * block_q       # first query of the block
    q_end = q_start + num_new           # one past the row's last query
    k_lo = j * page_size
    live = (
        (q_lo < q_end) & (k_lo < kv_len)
        & (k_lo < q_lo + block_q) & (k_lo < q_end)
    )
    if sliding_window is not None:
        floor = q_lo - sliding_window   # keys at or under it are too old
        live = live & (k_lo + page_size - 1 > floor) & (kv_len - 1 > floor)
    return live


def _ragged_kernel(
    table_ref,   # SMEM [B, T] int32 (scalar prefetch)
    len_ref,     # SMEM [B] int32: live kv per row (incl. this call's tokens)
    qstart_ref,  # SMEM [B] int32: absolute position of the row's first query
    nnew_ref,    # SMEM [B] int32: valid query rows in this call
    q_ref,       # [1, BQ, Hkv, G, D]
    k_ref,       # [1, Hkv, PS, D]
    v_ref,       # [1, Hkv, PS, D]
    out_ref,     # [1, BQ, Hkv, G, D]
    acc_ref,     # VMEM [Hkv*BQ*G, D] f32
    m_ref,       # VMEM [Hkv*BQ*G, 128] f32
    l_ref,       # VMEM [Hkv*BQ*G, 128] f32
    *,
    scale: float,
    page_size: int,
    num_page_blocks: int,
    block_q: int,
    sliding_window: Optional[int],
    hkv: int,
    g: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    kv_len = len_ref[b]
    live = _tile_live(
        qi, j, qstart_ref[b], nnew_ref[b], kv_len, block_q=block_q,
        page_size=page_size, sliding_window=sliding_window,
    )

    # A tile with no valid pair would leave m, l and acc as they are
    # (m_new = m_prev, alpha = 1, p = 0): skipping it is bit-exact.
    @pl.when(live)
    def _update():
        rows = hkv * block_q * g

        # Flat scratch row r covers (head = r // (BQ*G), query = (r % (BQ*G))
        # // G); its query's position inside the dispatch and in the sequence:
        ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        q_rel = qi * block_q + (ridx % (block_q * g)) // g
        q_pos = qstart_ref[b] + q_rel

        # Per-(query, slot) mask: slot live, causal vs the query's absolute
        # position, and the query itself valid (pad rows past num_new mask to
        # all-dead → l == 0 → zeros at finalize).
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        valid = (pos < kv_len) & (pos <= q_pos) & (q_rel < nnew_ref[b])
        if sliding_window is not None:
            valid &= pos > q_pos - sliding_window

        # [BQ, Hkv, G, D] -> kv-head-major [Hkv, BQ*G, D] so QK^T/PV batch over
        # kv heads with real MXU row counts.
        q = jnp.transpose(q_ref[0], (1, 0, 2, 3)).reshape(hkv, block_q * g, -1)
        k = k_ref[0]  # [Hkv, PS, D]
        v = v_ref[0]

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(rows, page_size)
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

        pg = p.reshape(hkv, block_q * g, page_size).astype(v.dtype)
        pv = jax.lax.dot_general(
            pg, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(rows, -1)

    @pl.when(j == num_page_blocks - 1)
    def _finalize():
        # Fully-masked rows (pad queries, kv_len == 0) have l == 0 → zeros.
        l = l_ref[:, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-20)
        out = out.reshape(hkv, block_q, g, -1)
        out_ref[0] = jnp.transpose(out, (1, 0, 2, 3)).astype(out_ref.dtype)


def _qragged_kernel(
    table_ref,   # SMEM [B, T] int32
    len_ref,     # SMEM [B] int32
    qstart_ref,  # SMEM [B] int32
    nnew_ref,    # SMEM [B] int32
    q_ref,       # [1, BQ, Hkv, G, D]
    k_ref,       # [1, Hkv, PS, D] int8
    ks_ref,      # [1, Hkv, PS] f32
    v_ref,       # [1, Hkv, PS, D] int8
    vs_ref,      # [1, Hkv, PS] f32
    *refs,       # (sel_ref [1, 1, BQ, PS] int8 if selected,) out, acc, m, l
    scale: float,
    page_size: int,
    num_page_blocks: int,
    block_q: int,
    sliding_window: Optional[int],
    hkv: int,
    g: int,
    selected: bool = False,
):
    """int8 page variant of :func:`_ragged_kernel`: per-(slot, head) scales
    apply to the SCORES/probs (``q·(k·s) = s·(q·k)``), so the int8 pages
    stream through VMEM without a dequantized copy. ``selected``: one more
    operand, the (query, key) pairs a learned selection keeps
    (``ops/sparse_attention.py``), a term of the tile's mask."""
    sel_ref = refs[0] if selected else None
    out_ref, acc_ref, m_ref, l_ref = refs[-4:]
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    kv_len = len_ref[b]
    live = _tile_live(
        qi, j, qstart_ref[b], nnew_ref[b], kv_len, block_q=block_q,
        page_size=page_size, sliding_window=sliding_window,
    )

    @pl.when(live)  # as _ragged_kernel: a dead tile changes nothing
    def _update():
        rows = hkv * block_q * g

        ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        q_rel = qi * block_q + (ridx % (block_q * g)) // g
        q_pos = qstart_ref[b] + q_rel

        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        valid = (pos < kv_len) & (pos <= q_pos) & (q_rel < nnew_ref[b])
        if sliding_window is not None:
            valid &= pos > q_pos - sliding_window
        if selected:
            # The block's [BQ, PS] selection, a row a query, to the scratch's
            # rows (head, query, group member): a 0/1 matmul repeats each
            # query's row G times (Mosaic has no sublane repeat), and the
            # heads share it.
            bg = block_q * g
            spread = (
                jax.lax.broadcasted_iota(jnp.int32, (bg, block_q), 0) // g
                == jax.lax.broadcasted_iota(jnp.int32, (bg, block_q), 1)
            ).astype(jnp.float32)
            sel = jax.lax.dot_general(
                spread, sel_ref[0, 0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            valid &= jnp.broadcast_to(
                sel[None], (hkv, bg, page_size)
            ).reshape(rows, page_size) > 0.5

        q = jnp.transpose(q_ref[0], (1, 0, 2, 3)).reshape(hkv, block_q * g, -1)
        k = k_ref[0]   # [Hkv, PS, D] int8
        ks = ks_ref[0]  # [Hkv, PS] f32

        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * ks[:, None, :]
        s = s.reshape(rows, page_size) * scale
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

        v = v_ref[0]    # [Hkv, PS, D] int8
        vs = vs_ref[0]  # [Hkv, PS] f32
        pw = p.reshape(hkv, block_q * g, page_size) * vs[:, None, :]
        pv = jax.lax.dot_general(
            pw, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(rows, -1)

    @pl.when(j == num_page_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-20)
        out = out.reshape(hkv, block_q, g, -1)
        out_ref[0] = jnp.transpose(out, (1, 0, 2, 3)).astype(out_ref.dtype)


# Mosaic's default scoped-VMEM limit on a v5e core is 16 MiB. The estimate
# in :func:`_block_q` counts only what this file allocates per grid step;
# the compiler's own temporaries (the in-kernel q transpose, the sublane
# padding of the G axis) come on top, so the budget keeps a third of the
# limit free. Calibrated by compiling for a described v5e: at 32 query
# heads of 128 both kernels are refused at block_q 128 (18-23 MiB asked, in
# bf16, f32 and over int8 pages) and accepted at 64, for 8 and for 32 kv
# heads; tests/test_chip_compile.py holds the line.
_VMEM_BUDGET = 10 * 2**20


def _block_q(s, hq, hkv, d, page_size, q_itemsize, kv_itemsize):
    """Largest power-of-two q block (<= 128, <= S rounded up) whose VMEM
    footprint fits :data:`_VMEM_BUDGET`. Every term but the K/V page
    blocks scales with ``rows = block_q * Hq``, so wide-head or f32 models
    get a shorter block instead of a compile-time RESOURCE_EXHAUSTED."""
    lanes = -(-d // 128) * 128
    kv_blocks = 2 * 2 * hkv * page_size * lanes * kv_itemsize  # K+V, 2 bufs
    per_row = (
        (lanes + 2 * 128) * 4            # acc/m/l scratch (f32)
        + 2 * 2 * lanes * q_itemsize     # q + out blocks, double-buffered
        + 2 * max(page_size, 128) * 4    # scores and probs (f32)
    )
    bq = min(128, _next_pow2(s))
    while bq > 8 and bq * hq * per_row + kv_blocks > _VMEM_BUDGET:
        bq //= 2
    return bq


def _prep(q, k_pages, block_q):
    b, s, hq, d = q.shape
    hkv, page_size = k_pages.shape[-3:-1]   # a layer's plane or a whole stack
    if block_q is None:
        block_q = _block_q(
            s, hq, hkv, d, page_size, q.dtype.itemsize,
            k_pages.dtype.itemsize,
        )
    s_pad = -(-s // block_q) * block_q
    return b, s, hq, d, block_q, s_pad


def _index_maps(block_q, page_size, sliding_window):
    """The grid's index maps: a dead tile (:func:`_tile_live`) is clamped to
    the null page 0, so consecutive dead steps name one block and nothing
    is fetched for them (BlockSpec semantics skip an unchanged block); the
    kernel bodies skip the same tiles by the same predicate. Over whole
    ``[L, P, ...]`` stacks the cache layer's index is one more scalar-prefetch
    operand (``layer``) and the page maps name ``(layer, page)``, as
    ``paged_attention.quantized_paged_fused_attention``'s do."""

    def _page(bi, qi, ji, table, lens, qstart, nnew, *layer):
        live = _tile_live(
            qi, ji, qstart[bi], nnew[bi], lens[bi], block_q=block_q,
            page_size=page_size, sliding_window=sliding_window,
        )
        return (*(ref[0] for ref in layer), jnp.where(live, table[bi, ji], 0))

    def _page_index(*at):
        return (*_page(*at), 0, 0, 0)

    def _page_index3(*at):
        return (*_page(*at), 0, 0)

    def _q_index(bi, qi, ji, table, lens, qstart, nnew, *layer):
        return (bi, qi, 0, 0, 0)

    return _page_index, _page_index3, _q_index


def _select_index(block_q, page_size, sliding_window):
    """Index map of a selection ``[B, T, S, PS]``: the (page, q-block) tile
    of a live step, and one unchanged tile through a run of dead ones."""

    def _sel_index(bi, qi, ji, table, lens, qstart, nnew, *layer):
        live = _tile_live(
            qi, ji, qstart[bi], nnew[bi], lens[bi], block_q=block_q,
            page_size=page_size, sliding_window=sliding_window,
        )
        return (bi, jnp.where(live, ji, 0), jnp.where(live, qi, 0), 0)

    return _sel_index


def ragged_paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    num_new: jnp.ndarray,
    q_start: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_q: Optional[int] = None,
    interpret: Optional[bool] = None,
    name: str = "ragged_paged_attention",
):
    """Ragged mixed-phase attention straight over the page pool.

    ``q``: ``[B, S, Hq, D]`` (already rotated; rows ragged — row ``b``'s
    first ``num_new[b]`` tokens are real, the rest pad); ``k_pages`` /
    ``v_pages``: ``[P, Hkv, page_size, D]`` one layer's pool, keys stored
    rotated; ``page_table``: ``[B, T]`` int32 physical page ids (slot order
    = position order, 0 = null page); ``kv_lengths``: ``[B]`` int32 live kv
    per row INCLUDING this call's scattered tokens; ``num_new``: ``[B]``
    int32 valid query count per row (1 = decode row, C = chunk row, full
    prompt = prefill row — one grid serves all three); ``q_start``: ``[B]``
    absolute position of each row's first query (defaults to
    ``kv_lengths - num_new`` — queries are the newest tokens). Returns
    ``[B, S, Hq, D]`` with pad query rows zeroed.
    """
    _, hkv, page_size, _ = k_pages.shape
    b, s, hq, d, bq, s_pad = _prep(q, k_pages, block_q)
    t = page_table.shape[1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if q_start is None:
        q_start = kv_lengths - num_new

    qr = q.reshape(b, s, hkv, g, d)
    if s_pad != s:
        qr = jnp.pad(qr, ((0, 0), (0, s_pad - s), (0, 0), (0, 0), (0, 0)))

    _page_index, _, _q_index = _index_maps(bq, page_size, sliding_window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, s_pad // bq, t),
        in_specs=[
            pl.BlockSpec((1, bq, hkv, g, d), _q_index),
            pl.BlockSpec((1, hkv, page_size, d), _page_index),
            pl.BlockSpec((1, hkv, page_size, d), _page_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hkv, g, d), _q_index),
        scratch_shapes=[
            pltpu.VMEM((hkv * bq * g, d), jnp.float32),
            pltpu.VMEM((hkv * bq * g, 128), jnp.float32),
            pltpu.VMEM((hkv * bq * g, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel,
        scale=scale,
        page_size=page_size,
        num_page_blocks=t,
        block_q=bq,
        sliding_window=sliding_window,
        hkv=hkv,
        g=g,
    )
    out = pl.pallas_call(
        kernel,
        name=name,
        out_shape=jax.ShapeDtypeStruct((b, s_pad, hkv, g, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_lengths.astype(jnp.int32),
      q_start.astype(jnp.int32), num_new.astype(jnp.int32),
      qr, k_pages, v_pages)
    return out[:, :s].reshape(b, s, hq, d)


def quantized_ragged_paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    ks_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    vs_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    num_new: jnp.ndarray,
    q_start: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_q: Optional[int] = None,
    interpret: Optional[bool] = None,
    name: str = "quantized_ragged_paged_attention",
    select: Optional[jnp.ndarray] = None,
    layer: Optional[jnp.ndarray] = None,
):
    """As :func:`ragged_paged_attention` over int8 pages with per-(slot,
    head) scale planes (``ks_pages``/``vs_pages``: ``[P, Hkv, page_size]``
    f32). ``select`` ``[B, T, S, page_size]`` int8 (a learned selection,
    ``ops/sparse_attention.py``): nonzero where query ``s`` of the dispatch
    attends to the position at table slot ``t``, offset ``p``; one more
    pipelined block a tile and one more term of its mask. Without it the
    call traces to the program it always did.

    The pool's two forms are told apart by the operands' rank. A layer's
    planes (``k_pages`` 4-D): the program above. The WHOLE stacks
    (``[L, P, Hkv, page_size, D]`` / ``[L, P, Hkv, page_size]``) with
    ``layer``, the cache layer's index (a traced scalar): the index is one
    more scalar-prefetch operand and every page block is fetched at
    ``(layer, page)`` of the stack, so no caller slices a layer's plane out
    of the pool to feed the call (a slice is a copy of the plane through HBM,
    a layer: ``cache/paged.py``'s prefill hands the stacks over); a tile
    computes what it computed."""
    stacked = k_pages.ndim == 5
    if stacked != (layer is not None):
        raise ValueError(
            "whole [L, P, ...] stacks come with the cache layer's index, a "
            "layer's [P, ...] planes without one"
        )
    hkv, page_size = k_pages.shape[-3:-1]
    b, s, hq, d, bq, s_pad = _prep(q, k_pages, block_q)
    t = page_table.shape[1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if q_start is None:
        q_start = kv_lengths - num_new

    qr = q.reshape(b, s, hkv, g, d)
    if s_pad != s:
        qr = jnp.pad(qr, ((0, 0), (0, s_pad - s), (0, 0), (0, 0), (0, 0)))

    _page_index, _page_index3, _q_index = _index_maps(
        bq, page_size, sliding_window
    )

    # a stack's layer axis is squeezed out of its blocks: the kernel sees a
    # page's ``[1, Hkv, PS(, D)]`` in both forms
    at = (None,) if stacked else ()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if stacked else 4,
        grid=(b, s_pad // bq, t),
        in_specs=[
            pl.BlockSpec((1, bq, hkv, g, d), _q_index),
            pl.BlockSpec((*at, 1, hkv, page_size, d), _page_index),
            pl.BlockSpec((*at, 1, hkv, page_size), _page_index3),
            pl.BlockSpec((*at, 1, hkv, page_size, d), _page_index),
            pl.BlockSpec((*at, 1, hkv, page_size), _page_index3),
            *([] if select is None else [pl.BlockSpec(
                (1, 1, bq, page_size),
                _select_index(bq, page_size, sliding_window),
            )]),
        ],
        out_specs=pl.BlockSpec((1, bq, hkv, g, d), _q_index),
        scratch_shapes=[
            pltpu.VMEM((hkv * bq * g, d), jnp.float32),
            pltpu.VMEM((hkv * bq * g, 128), jnp.float32),
            pltpu.VMEM((hkv * bq * g, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _qragged_kernel,
        scale=scale,
        page_size=page_size,
        num_page_blocks=t,
        block_q=bq,
        sliding_window=sliding_window,
        hkv=hkv,
        g=g,
        selected=select is not None,
    )
    extra = ()
    if select is not None:
        if s_pad != s:
            select = jnp.pad(
                select, ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
            )
        extra = (select.astype(jnp.int8),)
    scalars = (
        page_table.astype(jnp.int32), kv_lengths.astype(jnp.int32),
        q_start.astype(jnp.int32), num_new.astype(jnp.int32),
    )
    if stacked:
        body = kernel

        def kernel(table_ref, len_ref, qstart_ref, nnew_ref, layer_ref, *refs):
            # the layer is spent in the index maps
            body(table_ref, len_ref, qstart_ref, nnew_ref, *refs)

        scalars += (jnp.asarray(layer, jnp.int32).reshape(1),)
    out = pl.pallas_call(
        kernel,
        name=name,
        out_shape=jax.ShapeDtypeStruct((b, s_pad, hkv, g, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(*scalars, qr, k_pages, ks_pages, v_pages, vs_pages, *extra)
    return out[:, :s].reshape(b, s, hq, d)


def latent_ragged_paged_attention(
    q: jnp.ndarray,
    c_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    num_new: jnp.ndarray,
    q_start: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_q: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Absorbed-MLA ragged attention reading the latent pool in place.

    ``c_pages``: ``[P, 1, page_size, lat_dim]`` — one layer's pool of
    fused ``[c ; k_rope]`` latents (f32, rope pre-applied to the rope
    slice by the model); ``q``: the absorbed query ``[B, S, Hq,
    lat_dim]``. Because the key up-projection is folded into ``q`` and
    the value up-projection is deferred past the softmax
    (``models/llama.py:_latent_attention``), attention runs with
    ``K = V =`` the STORED latent: the kernel's existing page-table walk
    IS the latent→K/V decompression fusion — no per-token K/V ever
    materializes, on-chip or off. Output: ``[B, S, Hq, lat_dim]`` whose
    first ``rank`` dims are the latent-space attention result.
    """
    return ragged_paged_attention(
        q, c_pages, c_pages, page_table, kv_lengths, num_new,
        q_start=q_start, scale=scale, sliding_window=sliding_window,
        block_q=block_q, interpret=interpret,
        name="latent_ragged_paged_attention",
    )


def quantized_latent_ragged_paged_attention(
    q: jnp.ndarray,
    c_pages: jnp.ndarray,
    cs_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    num_new: jnp.ndarray,
    q_start: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_q: Optional[int] = None,
    interpret: Optional[bool] = None,
    select: Optional[jnp.ndarray] = None,
):
    """As :func:`latent_ragged_paged_attention` over the int8 latent pool
    (``cs_pages``: ``[P, 1, page_size]`` per-token f32 scales); the int8
    pages stream through VMEM as-is and dequantize on the scores. Under a
    learned selection (``select``: :func:`quantized_ragged_paged_attention`'s
    ``[B, T, S, page_size]`` int8) it is one more term of the tile's mask,
    traced as ``sparse_latent_ragged_paged_attention``; a call without one
    keeps its operands and its name."""
    rows = dict(
        q_start=q_start, scale=scale, sliding_window=sliding_window,
        block_q=block_q, interpret=interpret,
    )
    if select is None:
        return quantized_ragged_paged_attention(
            q, c_pages, cs_pages, c_pages, cs_pages, page_table, kv_lengths,
            num_new, name="quantized_latent_ragged_paged_attention", **rows
        )
    return quantized_ragged_paged_attention(
        q, c_pages, cs_pages, c_pages, cs_pages, page_table, kv_lengths,
        num_new, name="sparse_latent_ragged_paged_attention", select=select,
        **rows,
    )


def ragged_attention_reference(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    num_new: jnp.ndarray,
    ks_pages: Optional[jnp.ndarray] = None,
    vs_pages: Optional[jnp.ndarray] = None,
    q_start: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
):
    """XLA oracle for the ragged kernels: gathers the table span into a
    contiguous view (the exact copy the kernel exists to avoid) and runs a
    masked f32 softmax. Tests compare against this; dequantizes int8 pools
    when scale planes are given."""
    b, s, hq, d = q.shape
    _, hkv, page_size, _ = k_pages.shape
    t = page_table.shape[1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if q_start is None:
        q_start = kv_lengths - num_new

    k = jnp.take(k_pages, page_table, axis=0)  # [B, T, Hkv, PS, D]
    v = jnp.take(v_pages, page_table, axis=0)
    k = jnp.moveaxis(k, 2, 3).reshape(b, t * page_size, hkv, d)
    v = jnp.moveaxis(v, 2, 3).reshape(b, t * page_size, hkv, d)
    if ks_pages is not None:
        ks = jnp.take(ks_pages, page_table, axis=0)  # [B, T, Hkv, PS]
        vs = jnp.take(vs_pages, page_table, axis=0)
        ks = jnp.moveaxis(ks, 2, 3).reshape(b, t * page_size, hkv)
        vs = jnp.moveaxis(vs, 2, 3).reshape(b, t * page_size, hkv)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]

    qr = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum(
        "bshgd,bthd->bhgst", qr.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale

    q_pos = q_start[:, None] + jnp.arange(s)[None, :]          # [B, S]
    kv_pos = jnp.arange(t * page_size)[None, :]                # [1, KV]
    valid = (
        (kv_pos[:, None, :] <= q_pos[:, :, None])
        & (kv_pos[:, None, :] < kv_lengths[:, None, None])
    )                                                          # [B, S, KV]
    if sliding_window is not None:
        valid &= kv_pos[:, None, :] > q_pos[:, :, None] - sliding_window
    scores = jnp.where(valid[:, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    q_valid = jnp.arange(s)[None, :] < num_new[:, None]        # [B, S]
    out = jnp.where(q_valid[..., None, None, None], out, 0.0)
    return out.reshape(b, s, hq, d).astype(q.dtype)
