"""Paged KV cache: fixed page pool + per-session page tables.

The TPU-native realization of the reference's multi-tenancy goal: its
``PartialLlamaSinkCache`` keys Python dicts of growing tensors by
``generation_id``
(``/root/reference/distributed_llm_inference/models/llama/cache.py:14-19``),
which cannot live under ``jit``. Here the per-``generation_id`` state becomes
integer indexing into a preallocated page pool (PagedAttention-style): sessions
own rows of a ``page_table``; pages are allocated/freed host-side by the
scheduler (``engine/engine.py``) and the device computation only ever sees
static shapes.

Layout:
    ``k_pages``/``v_pages``: ``[L, num_pages, Hkv, page_size, D]`` (keys
    rotated; head-major within a page so the Pallas paged kernel's per-head
    block is a contiguous ``[page_size, D]`` tile — TPU Pallas requires the
    last two block dims to be tiling-aligned)
    ``page_table``: ``[B, max_pages_per_session]`` int32 page ids
    ``lengths``: ``[B]`` tokens currently cached per session row

Page 0 is the NULL page: never allocated to a session, absorbing writes from
padding tokens and unallocated table slots, so a misconfigured row can never
corrupt another session's pages.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..ops.attention import causal_mask
from ..ops.rotary import RopeAngles, apply_rope
from ..ops.sparse_attention import (
    KERNEL_DECODE, KERNEL_INDEX_FLUSH, KERNEL_PREFILL, selection_mask,
)
from .base import GatherAttendMixin, flash_prefill_fn


@jax.jit
def _table_write(table, pages_row, row, start):
    """One cached executable for every page-table install (per pages-row
    length): see :meth:`PagedKVCache.assign_pages`."""
    return jax.lax.dynamic_update_slice(table, pages_row, (row, start))


@jax.jit
def _table_write_batch(table, rows, slots, pages):
    """N (row, slot) ← page installs in ONE dispatch (scatter over the tiny
    int32 table; padded entries carry out-of-range rows and drop).

    Why: sequential :func:`_table_write` calls CHAIN (each consumes the
    previous table), so a growth tick where every row crosses a page
    boundary pays one dispatch per row (the cost of that chain is not
    measured on a directly attached chip). One batched executable per
    padded length replaces the chain."""
    return table.at[rows, slots].set(pages, mode="drop")


@jax.jit
def _page_copy(pool, dst, src):
    """Device-side page duplicate (copy-on-write split): pool[:, dst] ←
    pool[:, src]. ``dst``/``src`` are TRACED — one executable per pool
    shape/dtype, not per page pair."""
    tile = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(pool, tile, dst, axis=1)


@jax.jit
def _page_read(pool, page):
    """One page's tile ``[L, heads, PS(, D)]`` (traced index — cached
    executable per pool shape; the host copy happens at np.asarray time)."""
    return jax.lax.dynamic_slice_in_dim(pool, page, 1, axis=1)[:, 0]


@jax.jit
def _page_write(pool, tile, page):
    """Install a host-provided page tile at ``pool[:, page]`` (traced
    index; spill-tier reload path)."""
    return jax.lax.dynamic_update_slice_in_dim(
        pool, tile[:, None], page, axis=1
    )


def _page_chunks(a, cap, ps):
    """Chunk contiguous 1-row ring KV ``[L, 1, S, ...]`` into per-page
    tiles ``[L, ceil(S / PS), heads, PS(, D)]``, cropped at ``cap``
    positions (shared by the bf16 and int8 pool ingests so the layout
    cannot drift between them)."""
    a = a[:, 0]
    s = min(a.shape[1], cap)
    a = jax.lax.slice_in_dim(a, 0, s, axis=1)
    widths = [(0, 0)] * a.ndim
    widths[1] = (0, -s % ps)
    a = jnp.pad(a, widths)
    a = a.reshape(a.shape[0], -1, ps, *a.shape[2:])
    return jnp.swapaxes(a, 2, 3)


def _row_spans(pages, table):
    """Every row's table span of a pool plane, contiguous and head-major:
    ``[L, P, H, PS, D]`` by ``table [B, T]`` to ``[L, B, H, T*PS, D]`` (the
    fused window's read-only big segment where no kernel reads the pages in
    place; the int8 class's ``tail_big_stacks`` is the same read of its four
    planes, the whole stack at once). Unmapped slots read the null page:
    masked by ``pos < base_len``. A layer at a time: the whole stack in one
    gather holds two more copies of its result while it is transposed (11.7
    GB of temporaries at a 64-slot table of the tp=4 cell, where this form
    holds 6.8: described-v5e compiles, PR 49) and takes 24.6 ms where this
    takes 14.6 (one chip at that cell's shard, 2.55 GB gathered: my chip
    run, PR 49)."""
    def layer(plane):                             # [P, H, PS, D]
        v = jnp.swapaxes(jnp.take(plane, table, axis=0), 1, 2)
        b, h, t, ps, d = v.shape                  # [B, H, T, PS, D]
        return v.reshape(b, h, t * ps, d)

    return jax.lax.map(layer, pages)


class PagedKVCache(GatherAttendMixin, struct.PyTreeNode):
    k_pages: jax.Array
    v_pages: jax.Array
    page_table: jax.Array
    lengths: jax.Array
    page_size: int = struct.field(pytree_node=False)
    # Use the Pallas paged-attention kernel for decode steps (reads pages in
    # place instead of gathering a contiguous per-row view).
    use_kernel: bool = struct.field(pytree_node=False, default=False)
    # Serve multi-token rows (prefill / chunked prefill) through the ragged
    # mixed-phase kernel (ops/ragged_attention.py) — pages read in place
    # with per-row true lengths, replacing update_and_gather's contiguous
    # [B, max_len, Hkv, D] copy. Set by the engine's AttentionPlan (TPU
    # only; interpret mode is test-grade).
    use_ragged: bool = struct.field(pytree_node=False, default=False)

    # Generic-consumer layout (see DenseKVCache): the page pool is batch-free;
    # only the table/lengths have session rows. Pool fields carry the layer
    # axis and are passed through whole on row slices (SHARED_FIELDS).
    BATCH_AXES = {"page_table": 0, "lengths": 0}
    LAYER_FIELDS = ("k_pages", "v_pages")
    SHARED_FIELDS = ("k_pages", "v_pages")
    # Stored-form plane name -> pool field (export/spill/reload share this
    # map so the host-facing naming cannot drift between them).
    PLANE_FIELDS = {"k": "k_pages", "v": "v_pages"}
    #: per-row fields ``[B, slots]`` that widen and narrow with the table
    TABLE_FIELDS = ("page_table",)
    #: role ("decode" | "prefill" | "flush" | "write") -> the name a class
    #: gives its kernel calls in a device trace; a role left out keeps the
    #: kernel's own (the window pool of a two-pool cache names all four)
    KERNEL_NAMES = {}

    def _kernel_name(self, role: str) -> Dict[str, str]:
        name = self.KERNEL_NAMES.get(role)
        return {} if name is None else {"name": name}

    def resize_table(self, slots: int) -> "PagedKVCache":
        """The same cache over a table ``slots`` wide: zero (null page)
        columns padded on, or the last columns dropped. The pool never
        moves."""
        pad = slots - self.page_table.shape[1]
        return self.replace(**{
            f: (
                jnp.pad(getattr(self, f), ((0, 0), (0, pad))) if pad > 0
                else getattr(self, f)[:, :slots]
            )
            for f in self.TABLE_FIELDS
        })

    @staticmethod
    def create(
        num_layers: int,
        batch: int,
        num_pages: int,
        page_size: int,
        max_pages_per_session: int,
        num_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
        use_kernel: bool = False,
        use_ragged: bool = False,
    ) -> "PagedKVCache":
        shape = (num_layers, num_pages, num_kv_heads, page_size, head_dim)
        return PagedKVCache(
            k_pages=jnp.zeros(shape, dtype),
            v_pages=jnp.zeros(shape, dtype),
            page_table=jnp.zeros((batch, max_pages_per_session), jnp.int32),
            lengths=jnp.zeros((batch,), jnp.int32),
            page_size=page_size,
            use_kernel=use_kernel,
            use_ragged=use_ragged,
        )

    @property
    def max_len(self) -> int:
        return self.page_table.shape[1] * self.page_size

    @property
    def layer_stacks(self):
        return (self.k_pages, self.v_pages)

    def with_layer_stacks(self, new_k, new_v) -> "PagedKVCache":
        return self.replace(k_pages=new_k, v_pages=new_v)

    def q_positions(self, seq_len: int) -> jnp.ndarray:
        return self.lengths[:, None] + jnp.arange(seq_len, dtype=jnp.int32)[None, :]

    def rope_positions(self, seq_len: int, num_new: jnp.ndarray) -> jnp.ndarray:
        return self.q_positions(seq_len)

    def fits(self, num_new) -> jnp.ndarray:
        """Scheduler contract as in ``DenseKVCache.fits`` — additionally the
        scheduler must have mapped enough pages in ``page_table``."""
        return self.lengths + num_new <= self.max_len

    def _slot_pages(self, q_pos: jnp.ndarray, num_new: jnp.ndarray):
        """Map incoming tokens' absolute positions ``[B, S]`` →
        ``(physical page, in-page offset)``, both ``[B, S]``.

        Inactive rows / padding positions (``>= num_new``) and out-of-range
        table slots divert to the NULL page 0 — an inactive slot's old pages
        may already belong to ANOTHER session (freed + reallocated), so a
        write there would corrupt it. Shared by the bf16 and int8 pool
        scatters so the safety mapping cannot drift between them.
        """
        s = q_pos.shape[1]
        table_slot = q_pos // self.page_size
        offset = q_pos % self.page_size
        in_range = (
            jnp.arange(s, dtype=jnp.int32)[None, :] < num_new[:, None]
        ) & (table_slot < self.page_table.shape[1])
        phys = jnp.take_along_axis(
            self.page_table,
            jnp.minimum(table_slot, self.page_table.shape[1] - 1),
            axis=1,
        )
        return jnp.where(in_range, phys, 0), offset

    def _scatter(
        self,
        layer_k: jnp.ndarray,
        layer_v: jnp.ndarray,
        k_rot: jnp.ndarray,
        v_new: jnp.ndarray,
        q_pos: jnp.ndarray,
        num_new: jnp.ndarray,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Scatter rotated k / raw v into the page pool at each incoming
        token's (physical page, offset) per the row's page table."""
        b, s, hkv, d = k_rot.shape
        phys_page, offset_bs = self._slot_pages(q_pos, num_new)
        if s == 1:
            # Decode: one (page, offset) per row. A sequential per-row
            # dynamic_update_slice chain updates the donated pool in place;
            # the general scatter below costs ~2x a decode step at 7B shapes
            # (measured: XLA rewrites the pool).
            page = phys_page[:, 0]
            offset = offset_bs[:, 0]

            def body(r, bufs):
                bk, bv = bufs
                kv = k_rot[r, 0][:, None, :].astype(bk.dtype)  # [Hkv, 1, D]
                vv = v_new[r, 0][:, None, :].astype(bv.dtype)
                start = (page[r], 0, offset[r], 0)
                return (
                    jax.lax.dynamic_update_slice(bk, kv[None], start),
                    jax.lax.dynamic_update_slice(bv, vv[None], start),
                )

            return jax.lax.fori_loop(0, b, body, (layer_k, layer_v))
        flat_page = phys_page.reshape(-1)
        flat_off = offset_bs.reshape(-1)
        # Pool is [P, Hkv, PS, D]: advanced indices (page, offset) around the
        # head slice put the broadcast dim first → writes are [N, Hkv, D].
        new_k = layer_k.at[flat_page, :, flat_off].set(
            k_rot.reshape(b * s, hkv, d), mode="drop"
        )
        new_v = layer_v.at[flat_page, :, flat_off].set(
            v_new.reshape(b * s, hkv, d), mode="drop"
        )
        return new_k, new_v

    def attend(
        self,
        layer_state,
        q,
        k_new,
        v_new,
        rope,
        q_pos,
        num_new,
        sliding_window,
        attention_fn,
        scale=None,
    ):
        """Decode steps with ``use_kernel``: scatter into the pool, then run
        the Pallas paged kernel over the pages in place — no contiguous
        gather. Multi-token rows with ``use_ragged`` go through the ragged
        mixed-phase kernel the same way (per-row true lengths, phase is
        data). Everything else uses the default gather+``attention_fn``
        (``GatherAttendMixin``)."""
        if self.use_ragged and q.shape[1] > 1:
            from ..ops.ragged_attention import ragged_paged_attention

            layer_k, layer_v = layer_state
            q_rot = apply_rope(q, rope.cos, rope.sin)
            k_rot = apply_rope(k_new, rope.cos, rope.sin)
            new_k, new_v = self._scatter(
                layer_k, layer_v, k_rot, v_new, q_pos, num_new
            )
            out = ragged_paged_attention(
                q_rot, new_k, new_v, self.page_table,
                self.lengths + num_new, num_new,
                scale=scale, sliding_window=sliding_window,
                **self._kernel_name("prefill"),
            )
            return out, (new_k, new_v)
        if not self.use_kernel or q.shape[1] != 1:
            return super().attend(
                layer_state, q, k_new, v_new, rope, q_pos, num_new,
                sliding_window, attention_fn, scale,
            )
        from ..ops.paged_attention import paged_attention

        layer_k, layer_v = layer_state
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        new_k, new_v = self._scatter(
            layer_k, layer_v, k_rot, v_new, q_pos, num_new
        )
        out = paged_attention(
            q_rot, new_k, new_v, self.page_table, self.lengths + num_new,
            scale=scale, sliding_window=sliding_window,
        )
        return out, (new_k, new_v)

    # -- write-behind tail (fused multi-step decode) --------------------------
    #
    # The page POOL stays read-only through all K steps and new tokens live
    # in a small dense tail merged into pages once per K steps. With the
    # kernel the pool rides the layer scan as a sliced operand (the
    # carry-slice version costs two full pool copies plus relayouts per layer
    # per step, ~4x the kernel's own time at 7B shapes) and the paged kernel
    # sweeps it in place. Without the kernel (a mesh engine, the CPU) the big
    # segment is the int8 class's gathered form: every row's table span made
    # contiguous ONCE a window (``tail_big_stacks``) and read under one
    # softmax with the tail, in pure XLA. The one-token path pays that gather
    # a layer a step, and carries the pool through the layer scan to write
    # one position a row into it.

    #: the fused window's protocol (``tail_init`` / ``tail_attend`` /
    #: ``tail_flush``) is implemented: the engine's tail gate asks this. A
    #: subclass whose stored form the protocol does not cover says False.
    has_tail = True

    def tail_big_stacks(self):
        """Read-only stacks for the fused window: with the kernel the pool
        planes; without it ``(k, v) [L, B, Hkv, Tmax, D]`` in the pool's
        dtype (:func:`_row_spans`)."""
        if self.use_kernel:
            return (self.k_pages, self.v_pages)
        return (
            _row_spans(self.k_pages, self.page_table),
            _row_spans(self.v_pages, self.page_table),
        )

    def tail_init(self, k_steps: int):
        l = self.k_pages.shape[0]
        b = self.page_table.shape[0]
        hkv, d = self.k_pages.shape[2], self.k_pages.shape[4]
        # time-major beside the kernel's stats merge, head-major beside the
        # gathered stacks (the contraction's batch(B, Hkv) structure)
        shape = (
            (l, b, k_steps, hkv, d) if self.use_kernel
            else (l, b, hkv, k_steps, d)
        )
        z = jnp.zeros(shape, self.k_pages.dtype)
        return (z, z)

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None):
        tk, tv = tail_state
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        if not self.use_kernel:
            from ..ops.attention import gqa_attention_segments
            from .dense import segment_valids

            gk, gv = big_state                    # [B, Hkv, Tmax, D]
            tk = jax.lax.dynamic_update_slice_in_dim(
                tk, jnp.moveaxis(k_rot, 1, 2).astype(tk.dtype), step_idx,
                axis=2,
            )
            tv = jax.lax.dynamic_update_slice_in_dim(
                tv, jnp.moveaxis(v_new, 1, 2).astype(tv.dtype), step_idx,
                axis=2,
            )
            big_valid, tail_valid = segment_valids(
                base_len, tail_len, num_new, gk.shape[2], tk.shape[2],
                sliding_window,
            )
            out = gqa_attention_segments(
                q_rot, [(gk, gv, big_valid), (tk, tv, tail_valid)], scale,
                head_major=True,
            )
            return out, (tk, tv)
        from ..ops.attention import merge_softmax_segments
        from ..ops.paged_attention import paged_attention

        pool_k, pool_v = big_state
        tk = jax.lax.dynamic_update_slice_in_dim(tk, k_rot, step_idx, axis=1)
        tv = jax.lax.dynamic_update_slice_in_dim(tv, v_new, step_idx, axis=1)

        q_pos = base_len + tail_len  # [B]
        out_pool, m_pool, l_pool = paged_attention(
            q_rot, pool_k, pool_v, self.page_table, base_len,
            scale=scale, sliding_window=sliding_window,
            q_positions=q_pos, return_stats=True,
        )

        kk = tk.shape[1]
        tail_pos = base_len[:, None] + jnp.arange(kk, dtype=jnp.int32)[None, :]
        tail_valid = (
            jnp.arange(kk, dtype=jnp.int32)[None, :]
            < (tail_len + num_new)[:, None]
        )
        if sliding_window is not None:
            tail_valid &= tail_pos > (q_pos[:, None] - sliding_window)
        out = merge_softmax_segments(
            q_rot, out_pool, m_pool, l_pool, tk, tv, tail_valid, scale
        )
        return out, (tk, tv)

    def tail_flush(self, tail, tail_len):
        """Merge the tail into the page pool, once per K fused steps: with
        the kernel the prefill scatter path, batched over layers via vmap;
        without it :meth:`_flush_rows`."""
        if not self.use_kernel:
            return self._flush_rows(tail, tail_len)
        wk, wv = tail  # [L, B, K, Hkv, D]
        kk = wk.shape[2]
        q_pos = (
            self.lengths[:, None] + jnp.arange(kk, dtype=jnp.int32)[None, :]
        )
        num_new = tail_len
        new_k, new_v = jax.vmap(
            lambda lk, lv, tkl, tvl: self._scatter(
                lk, lv, tkl, tvl, q_pos, num_new
            )
        )(self.k_pages, self.v_pages, wk, wv)
        return self.replace(
            k_pages=new_k, v_pages=new_v, lengths=self.lengths + tail_len
        )

    def _flush_rows(self, tail, tail_len):
        """The head-major tail ``[L, B, Hkv, K, D]`` into the pages, a row
        at a time, as a read-modify-write of the pages the row's K positions
        lie in (at most ``(K + PS - 2) // PS + 1``): what :meth:`_scatter`
        writes, in place in the donated pool. The XLA scatter wants the pool
        in a layout of its own: two whole-pool relayout copies a plane in
        the decode executable, 9.3 ms a window where this takes 2.0 (one
        chip at the tp=4 cell's shard of a 1.25 GB pool: my chip run,
        PR 49). A page's new values are one contiguous run of the tail,
        padded by a page on each side so that the run may start before the
        tail or end past it; positions outside ``[base, base + tail_len)``
        keep the page's own, and a row that wrote nothing, or a slot past
        the table, rewrites the null page with itself (``_slot_pages``'s
        diversion)."""
        kk, ps = tail[0].shape[3], self.page_size
        slots = self.page_table.shape[1]
        touched = (kk + ps - 2) // ps + 1
        in_page = jnp.arange(ps, dtype=jnp.int32)
        pad = ((0, 0), (0, 0), (0, 0), (ps, touched * ps), (0, 0))
        padded = tuple(jnp.pad(t, pad) for t in tail)

        def row(r, pools):
            base, n = self.lengths[r], tail_len[r]
            runs = tuple(
                jax.lax.dynamic_index_in_dim(t, r, 1, keepdims=False)
                for t in padded
            )                                     # [L, Hkv, PS + K + .., D]
            for j in range(touched):
                slot = base // ps + j
                page = jnp.where(
                    (slot < slots) & (n > 0),
                    self.page_table[r, jnp.minimum(slot, slots - 1)], 0,
                )
                first = slot * ps - base          # tail slot of the page's first
                src = first + in_page
                mine = ((src >= 0) & (src < n))[None, None, :, None]

                def merged(pool, run):
                    old = jax.lax.dynamic_index_in_dim(
                        pool, page, 1, keepdims=False
                    )
                    new = jax.lax.dynamic_slice_in_dim(
                        run, first + ps, ps, axis=2
                    )
                    return jax.lax.dynamic_update_index_in_dim(
                        pool, jnp.where(mine, new.astype(pool.dtype), old),
                        page, 1,
                    )

                pools = tuple(map(merged, pools, runs))
            return pools

        new_k, new_v = jax.lax.fori_loop(
            0, self.page_table.shape[0], row, (self.k_pages, self.v_pages)
        )
        return self.replace(
            k_pages=new_k, v_pages=new_v, lengths=self.lengths + tail_len
        )

    def update_and_gather(
        self,
        layer_state: Tuple[jnp.ndarray, ...],
        q: jnp.ndarray,
        k_new: jnp.ndarray,
        v_new: jnp.ndarray,
        rope: RopeAngles,
        q_pos: jnp.ndarray,
        num_new: jnp.ndarray,
        sliding_window: Optional[int] = None,
    ) -> Tuple[jnp.ndarray, ...]:
        """Scatter new k/v into pages; gather each row's pages for attention.

        Which rows come here: a prefill with history (a prefix hit, a later
        chunk, the tail of a chunked prompt), the batched ``_prefill_rows``
        and a one-token step outside the fused window, wherever no kernel
        reads the pages in place. A FRESH row's one-piece prompt does not
        (``fresh_install``): every position it may attend to is in the
        dispatch's own ``k_new`` / ``v_new``, so the engine prefills it over
        a scratch dense cache and installs whole pages (:meth:`ingest_row`)
        where this scatters position by position (XLA rewrites the pool in a
        layout of its own for that) and attends the whole table span.

        ``layer_state``: ``(layer_k, layer_v)``, each ``[P, Hkv, page_size,
        D]`` (one layer). The gather materializes
        ``[B, max_pages_per_session * page_size, …]`` per layer — the
        XLA-fused correctness baseline. The Pallas paged kernel
        (``ops/paged_attention.py``) reads pages in place instead.
        """
        layer_k, layer_v = layer_state
        b, s, hkv, d = k_new.shape
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        new_k, new_v = self._scatter(
            layer_k, layer_v, k_rot, v_new, q_pos, num_new
        )

        # Gather this row's pages into a contiguous view. Slot i of the view
        # holds absolute position i because table slots are position-ordered.
        # [B, T, Hkv, PS, D] → [B, T, PS, Hkv, D] → [B, max_len, Hkv, D].
        k_all = jnp.take(new_k, self.page_table, axis=0).transpose(
            0, 1, 3, 2, 4
        ).reshape(b, self.max_len, hkv, d)
        v_all = jnp.take(new_v, self.page_table, axis=0).transpose(
            0, 1, 3, 2, 4
        ).reshape(b, self.max_len, hkv, d)

        kv_pos = jnp.broadcast_to(
            jnp.arange(self.max_len, dtype=jnp.int32)[None, :], (b, self.max_len)
        )
        kv_valid = kv_pos < (self.lengths + num_new)[:, None]
        mask = causal_mask(q_pos, kv_pos, kv_valid, sliding_window)
        return q_rot, k_all, v_all, mask, (new_k, new_v)

    def advance(self, num_new: jnp.ndarray) -> "PagedKVCache":
        return self.replace(lengths=self.lengths + num_new)

    def reset_rows(self, row_mask: jnp.ndarray) -> "PagedKVCache":
        """Clear sessions (host frees their pages via the allocator)."""
        return self.replace(
            lengths=jnp.where(row_mask, 0, self.lengths),
            page_table=jnp.where(row_mask[:, None], 0, self.page_table),
        )

    def select_row(self, row) -> "PagedKVCache":
        """Batch-1 view: row-local page table/length over the SHARED page
        pool, so a single-row prefill writes straight into the pool."""
        return self.replace(
            page_table=jax.lax.dynamic_slice_in_dim(self.page_table, row, 1, axis=0),
            lengths=jax.lax.dynamic_slice_in_dim(self.lengths, row, 1),
        )

    def merge_row(self, sub: "PagedKVCache", row) -> "PagedKVCache":
        return self.replace(
            **{name: getattr(sub, name) for name in self.SHARED_FIELDS},
            page_table=jax.lax.dynamic_update_slice_in_dim(
                self.page_table, sub.page_table, row, axis=0
            ),
            lengths=jax.lax.dynamic_update_slice_in_dim(
                self.lengths, sub.lengths, row, axis=0
            ),
        )

    def select_rows(self, rows) -> "PagedKVCache":
        """Compact multi-row view for the batched-admission prefill (see
        ``cache/dense.py`` — padding entries are out-of-range rows, clamped
        here and dropped on merge): row-local tables/lengths over the
        SHARED page pool, so the sub-prefill writes straight into the
        pool. A clamped padding row's table is harmless: its ``num_new=0``
        prefill diverts every write to the null page."""
        return self.replace(
            page_table=jnp.take(self.page_table, rows, axis=0, mode="clip"),
            lengths=jnp.take(self.lengths, rows, axis=0, mode="clip"),
        )

    def merge_rows(self, sub, rows):
        updated = {
            name: getattr(sub, name) for name in self.SHARED_FIELDS
        }
        return self.replace(
            page_table=self.page_table.at[rows].set(
                sub.page_table, mode="drop"
            ),
            lengths=self.lengths.at[rows].set(sub.lengths, mode="drop"),
            **updated,
        )

    @classmethod
    def fresh_install(cls) -> bool:
        """Whether a FRESH row (length 0) whose whole prompt is one piece
        may prefill against the dispatch's own K/V (a scratch
        ``DenseKVCache``) and install it through :meth:`ingest_row`: the
        engine's fresh-row prefill asks this where no kernel reads the pages
        in place (``use_ragged`` False). True for THIS class alone, whose
        stored form is the model's rotated K and raw V; every subclass says
        False until it says otherwise itself (int8 and its scales, a latent,
        an index plane, two pools, a retention state: ``ingest_row`` makes
        none of those from a dense scratch's K and V, or the prefill over
        one would not read what the table path reads)."""
        return cls is PagedKVCache

    def ingest_row(self, ks, vs, n_valid, first_slot=0):
        """Install a row's contiguous K/V into the page pool (cf.
        ``DenseKVCache.ingest_row``; 1-row ``select_row`` view — the pool
        is SHARED, so the pages land in place and ``merge_row`` writes the
        table/length back): the contiguous ``[L, 1, S, Hkv, D]`` K/V (keys
        rotated) is cut into page tiles and written to this row's table
        slots, the ``ceil(n_valid / PS)`` it owns and no other. Whose K/V:
        ring prefill's, a disaggregated admission's shipped copy, and a
        fresh row's own prefill where :meth:`fresh_install` holds (a row
        with history, a later chunk, a prefix hit write position by
        position instead: :meth:`update_and_gather`). Positions past
        ``n_valid`` in the last page take the K/V's own (never read:
        validity derives from ``lengths``).

        ``first_slot`` > 0 additionally diverts the HEAD of the run: slots
        below it map SHARED prefix pages whose content is already resident
        (disaggregated admission with a local prefix hit) and must not be
        overwritten with the shipped copy."""
        return self._ingest_planes(
            {"k_pages": ks, "v_pages": vs}, n_valid, first_slot
        )

    def _ingest_planes(self, planes, n_valid, first_slot=0):
        """Shared ring-ingest write pattern (bf16 values and int8+scale
        planes alike): chunk each contiguous plane into page tiles and
        write them to this row's table slots, then set lengths. Batch-1 views
        ONLY — a multi-row cache would broadcast ``n_valid`` into rows
        whose pages received nothing (silent corruption), so fail loudly."""
        if self.lengths.shape[0] != 1:
            raise ValueError(
                "paged ingest_row needs a batch-1 select_row view, got "
                f"batch {self.lengths.shape[0]}"
            )
        ps = self.page_size
        slots = self.page_table.shape[1]
        # Write ONLY slots [first_slot, ceil(n_valid/page_size)) — the run
        # this ingest actually owns: past it the table holds the null page,
        # and below ``first_slot`` it maps shared prefix pages that must not
        # be overwritten with this ingest's copy of the same content. A page
        # at a time, each a contiguous ``[L, heads, PS(, D)]`` slab written
        # into the donated pool in place, as ``_flush_rows`` writes its
        # pages. (One scatter over all the table's slots would pad the K/V
        # to the table's span first and write the unowned slots' tiles to
        # the null page: 40 MB a plane a chip where a 512-token piece owns
        # 8, at the tp=4 cell's 38 slots.)
        names = tuple(planes)
        tiles = tuple(
            _page_chunks(planes[f], slots * ps, ps).astype(getattr(self, f).dtype)
            for f in names
        )
        n_owned = jnp.minimum(
            (jnp.asarray(n_valid, jnp.int32) + ps - 1) // ps,
            tiles[0].shape[1],
        )

        def install(carry):
            slot, pools = carry
            page = self.page_table[0, slot]
            return slot + 1, tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    pool, jax.lax.dynamic_slice_in_dim(t, slot, 1, axis=1),
                    page, axis=1,
                )
                for pool, t in zip(pools, tiles)
            )

        _, pools = jax.lax.while_loop(
            lambda carry: carry[0] < n_owned, install,
            (jnp.asarray(first_slot, jnp.int32),
             tuple(getattr(self, f) for f in names)),
        )
        return self.replace(
            lengths=jnp.broadcast_to(
                jnp.asarray(n_valid, jnp.int32), self.lengths.shape
            ),
            **dict(zip(names, pools)),
        )

    def copy_page(self, dst: int, src: int) -> "PagedKVCache":
        """Duplicate page ``src`` into ``dst`` across every pool plane —
        the device half of a copy-on-write split. Pure page-pool op: the
        table/lengths are untouched (the scheduler remaps the splitting
        session's slot to ``dst`` itself)."""
        dst = jnp.int32(dst)
        src = jnp.int32(src)
        return self.replace(**{
            f: _page_copy(getattr(self, f), dst, src)
            for f in self.PLANE_FIELDS.values()
        })

    def read_page(self, page: int) -> Dict[str, np.ndarray]:
        """Host copies of one page's tiles in STORED form, keyed by plane
        name (``{"k": [L, Hkv, PS, D], "v": …}``, plus ``ks``/``vs``
        ``[L, Hkv, PS]`` scales on the quantized pool). ``np.asarray``
        blocks until pending device writes to the page have completed, so
        the spill tier always captures settled content."""
        p = jnp.int32(page)
        return {
            name: np.asarray(_page_read(getattr(self, f), p))
            for name, f in self.PLANE_FIELDS.items()
        }

    def write_page(self, page: int, tiles: Dict[str, np.ndarray]) -> "PagedKVCache":
        """Install :meth:`read_page`-form tiles at ``page`` (spill-tier
        reload). Validates plane names, shapes, and dtypes and raises
        ``ValueError`` on any mismatch — a corrupted arena entry must be
        rejected here, before it can poison the pool."""
        want = set(self.PLANE_FIELDS)
        if set(tiles) != want:
            raise ValueError(
                f"page tiles {sorted(tiles)} do not match this pool "
                f"(want {sorted(want)})"
            )
        out = {}
        for name, f in self.PLANE_FIELDS.items():
            pool = getattr(self, f)
            tile = np.asarray(tiles[name])
            expect = pool.shape[:1] + pool.shape[2:]
            if tuple(tile.shape) != tuple(expect):
                raise ValueError(
                    f"page tile {name!r} shape {tile.shape} != {tuple(expect)}"
                )
            if tile.dtype.name != pool.dtype.name:
                raise ValueError(
                    f"page tile {name!r} dtype {tile.dtype.name} != "
                    f"{pool.dtype.name}"
                )
            out[f] = _page_write(pool, jnp.asarray(tile), jnp.int32(page))
        return self.replace(**out)

    def assign_pages(self, row: int, pages, start_slot: int = 0) -> "PagedKVCache":
        """Host-side helper: install allocator-chosen page ids for a row.

        ``row``/``start_slot`` go in TRACED (via the jitted helper): baked-in
        constants would compile a fresh executable per (row, slot) pair —
        measured as a ~2 s stall the first time a serving tick crosses a page
        boundary. As NUMPY values: built with ``jnp`` each is a small device
        program of its own before the write (2.7 ms apiece under a mesh)."""
        return self.replace(
            page_table=_table_write(
                self.page_table, np.asarray(pages, np.int32)[None, :],
                np.int32(row), np.int32(start_slot),
            )
        )

    def assign_pages_batch(self, rows, slots, pages,
                           pad_to: int = 0) -> "PagedKVCache":
        """Install N (row, slot) ← page mappings in ONE device dispatch.

        Sequential :meth:`assign_pages` calls chain (each consumes the
        previous table); the batched scatter replaces the chain on ticks
        where many rows grow at once. ``pad_to`` pads the arrays to a
        fixed length so a few bucketed lengths cover every tick with cached
        executables; padded entries use a past-the-end row (negative would
        WRAP) and drop."""
        n = max(len(rows), pad_to)
        r = np.full((n,), self.page_table.shape[0], np.int32)
        s = np.zeros((n,), np.int32)
        p = np.zeros((n,), np.int32)
        r[: len(rows)] = rows
        s[: len(rows)] = slots
        p[: len(rows)] = pages
        return self.replace(
            page_table=_table_write_batch(
                self.page_table, jnp.asarray(r), jnp.asarray(s),
                jnp.asarray(p),
            )
        )


class PageAllocator:
    """Host-side page allocator (page 0 reserved as the null page) with
    refcounts and a prompt-prefix registry for automatic prefix caching.

    Plays the role hivemind's runtime state played for the reference's server:
    pure Python, not traced — only its *outputs* (page tables) reach the
    device. Guarded by the engine's scheduler lock (SURVEY §5.2).

    Prefix caching (vLLM-style): a page holding a FULL page-sized chunk of a
    session's prompt is content-addressed by the hash chain of the prompt up
    to and including that chunk. On release such pages are ``register``-ed
    instead of freed; a later session with the same prompt prefix ``lookup``s
    the chain and maps the cached pages into its table read-only (refcounted;
    writes never touch them — the session's write offset starts past the
    shared span). Unreferenced registered pages form an LRU that ``alloc``
    evicts from under pool pressure.
    """

    def __init__(self, num_pages: int):
        self._free = list(range(num_pages - 1, 0, -1))  # pop() yields low ids first
        self._free_set = set(self._free)
        self.num_pages = num_pages
        self._refs: Dict[int, int] = {}
        self._registry: Dict[bytes, int] = {}      # chain key -> page
        self._page_key: Dict[int, bytes] = {}      # page -> chain key
        self._lru: "collections.OrderedDict[int, None]" = collections.OrderedDict()
        # Eviction hook (prefixstore spill tier): called with (page, key)
        # BEFORE the page returns to the free list, while its content is
        # still valid — the engine snapshots the tiles to its host arena.
        # Runs under the engine's scheduler lock like every allocator call;
        # a hook failure must not wedge eviction (callers catch their own).
        self.on_evict = None

    @property
    def free_count(self) -> int:
        """Pages obtainable right now (free list + evictable cached pages)."""
        return len(self._free) + len(self._lru)

    @staticmethod
    def chain_keys(tokens, page_size: int) -> List[bytes]:
        """Hash-chain keys of every FULL page-sized chunk of ``tokens``."""
        keys, h = [], hashlib.sha1()
        for i in range(len(tokens) // page_size):
            chunk = tokens[i * page_size : (i + 1) * page_size]
            h.update(np.asarray(chunk, np.int64).tobytes())
            keys.append(h.digest())
        return keys

    def _evict_one(self) -> None:
        page, _ = self._lru.popitem(last=False)  # oldest
        key = self._page_key.pop(page)
        del self._registry[key]
        del self._refs[page]
        if self.on_evict is not None:
            self.on_evict(page, key)
        self._free.append(page)
        self._free_set.add(page)

    def alloc(self, n: int):
        """n fresh (private, refcount-1) pages; evicts cached pages if needed."""
        while len(self._free) < n and self._lru:
            self._evict_one()
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: want {n}, have {len(self._free)}"
            )
        pages = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(pages)
        for p in pages:
            self._refs[p] = 1
        return pages

    def lookup(self, keys: Sequence[bytes]) -> List[int]:
        """Longest cached run of prefix pages for ``keys``; each returned
        page's refcount is incremented (caller owns a reference)."""
        pages: List[int] = []
        for key in keys:
            page = self._registry.get(key)
            if page is None:
                break
            self._refs[page] += 1
            self._lru.pop(page, None)  # referenced: not evictable
            pages.append(page)
        return pages

    def lookup_one(self, key: bytes) -> Optional[int]:
        """One registered page by key, refcounted like :meth:`lookup`
        (caller owns a reference), or ``None`` when the key is not cached —
        the spill-reload walk checks the device registry page-by-page."""
        page = self._registry.get(key)
        if page is None:
            return None
        self._refs[page] += 1
        self._lru.pop(page, None)
        return page

    def peek(self, key: bytes) -> Optional[int]:
        """Registered page for ``key`` WITHOUT taking a reference — for
        match-length probes (routing) that must not pin pages."""
        return self._registry.get(key)

    def registered_keys(self, limit: int = 0) -> List[bytes]:
        """Registered chain keys, oldest first (dict insertion order);
        ``limit`` > 0 keeps only the NEWEST that many — the bounded set a
        node advertises to the directory."""
        keys = list(self._registry)
        return keys[-limit:] if limit > 0 else keys

    def register(self, page: int, key: bytes) -> None:
        """Content-address ``page`` (a full prompt-prefix page) under ``key``.
        If ``key`` is already registered to a different page, the existing
        entry wins (first writer; duplicates just stay private)."""
        if key in self._registry or page in self._page_key:
            return
        self._registry[key] = page
        self._page_key[page] = key

    def free(self, pages) -> None:
        """Drop one reference per page; unreferenced pages return to the free
        list, or to the evictable LRU if they are registered prefixes.

        Iterates in REVERSE so a prefix chain's deepest chunks enter the LRU
        first (oldest): eviction then trims chains from the tail, keeping a
        usable shorter prefix — evicting the chain root first would orphan
        every deeper cached page.

        The whole list is validated BEFORE any state changes: a bad id must
        raise with the pool untouched, not after earlier pages in the list
        were already freed/decref'd (a caught exception would otherwise
        leave refcounts inconsistent with the caller's page lists)."""
        pages = list(pages)
        drops: Dict[int, int] = {}
        for p in pages:
            if not 0 < p < self.num_pages:
                raise ValueError(
                    f"page {p} outside pool (1..{self.num_pages - 1}; 0 is the "
                    "reserved null page)"
                )
            drops[p] = drops.get(p, 0) + 1
            refs = self._refs.get(p)
            if (
                refs is None
                or refs == 0
                or p in self._free_set
                or drops[p] > refs  # duplicates within ONE call over-release
            ):
                raise ValueError(f"double free of page {p}")
        for p in reversed(pages):
            refs = self._refs[p]
            if refs > 1:
                self._refs[p] = refs - 1
                continue
            if p in self._page_key:  # cached prefix: evictable, not freed
                self._lru[p] = None
                self._refs[p] = 0
            else:
                del self._refs[p]
                self._free.append(p)
                self._free_set.add(p)


class QuantizedPagedKVCache(PagedKVCache):
    """Page pool with int8 K/V + per-(slot, head) fp32 scale planes.

    The paged counterpart of :class:`cache.dense.QuantizedDenseKVCache`:
    decode reads every live page each step, so int8 pages halve the pool's
    HBM traffic. Scales ride separate ``[L, P, Hkv, PS]`` planes (≈1.5%
    byte overhead at D=128); the Pallas kernel dequantizes ON THE SCORES
    (``q·(k·s) = s·(q·k)``) so the int8 pages stream through VMEM as-is,
    and the XLA gather fallback dequantizes its contiguous view.
    """

    # Dataclass inheritance: fields after the parent's defaulted ones need
    # defaults; create() always supplies real arrays.
    ks_pages: jax.Array = None
    vs_pages: jax.Array = None

    BATCH_AXES = {"page_table": 0, "lengths": 0}
    LAYER_FIELDS = ("k_pages", "v_pages", "ks_pages", "vs_pages")
    SHARED_FIELDS = ("k_pages", "v_pages", "ks_pages", "vs_pages")
    PLANE_FIELDS = {
        "k": "k_pages", "v": "v_pages", "ks": "ks_pages", "vs": "vs_pages",
    }

    @staticmethod
    def create(
        num_layers: int,
        batch: int,
        num_pages: int,
        page_size: int,
        max_pages_per_session: int,
        num_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,  # interface parity; values are int8
        use_kernel: bool = False,
        use_ragged: bool = False,
    ) -> "QuantizedPagedKVCache":
        shape = (num_layers, num_pages, num_kv_heads, page_size, head_dim)
        return QuantizedPagedKVCache(
            k_pages=jnp.zeros(shape, jnp.int8),
            v_pages=jnp.zeros(shape, jnp.int8),
            ks_pages=jnp.zeros(shape[:-1], jnp.float32),
            vs_pages=jnp.zeros(shape[:-1], jnp.float32),
            page_table=jnp.zeros((batch, max_pages_per_session), jnp.int32),
            lengths=jnp.zeros((batch,), jnp.int32),
            page_size=page_size,
            use_kernel=use_kernel,
            use_ragged=use_ragged,
        )

    @property
    def layer_stacks(self):
        return (self.k_pages, self.v_pages, self.ks_pages, self.vs_pages)

    def with_layer_stacks(self, k, v, ks, vs) -> "QuantizedPagedKVCache":
        return self.replace(k_pages=k, v_pages=v, ks_pages=ks, vs_pages=vs)

    def ingest_row(self, ks, vs, n_valid, first_slot=0):
        """Ring-prefill ingest, quantized pool form: per-(token, head)
        int8 + scale planes (cf. ``QuantizedDenseKVCache.ingest_row``)."""
        from .dense import _quantize_kv

        k_q, k_s = _quantize_kv(ks)  # [L, 1, S, H, D] / [L, 1, S, H]
        v_q, v_s = _quantize_kv(vs)
        return self.ingest_planes_row(k_q, v_q, k_s, v_s, n_valid, first_slot)

    def ingest_planes_row(self, k_q, v_q, k_s, v_s, n_valid, first_slot=0):
        """Install ALREADY-quantized planes (int8 values ``[L, 1, S, H, D]``
        + f32 scales ``[L, 1, S, H]``) without requantizing — disaggregated
        decode imports the prefill pool's STORED planes bit-exact (cf.
        ``QuantizedDenseKVCache.ingest_planes_row``)."""
        return self._ingest_planes(
            {"k_pages": k_q, "v_pages": v_q,
             "ks_pages": k_s, "vs_pages": v_s},
            n_valid,
            first_slot,
        )

    def _scatter_q(self, layer_k, layer_v, layer_ks, layer_vs, k_rot, v_new,
                   q_pos, num_new):
        """Quantize incoming k/v, then the :meth:`_scatter` write pattern
        over the four planes."""
        from .dense import _quantize_kv

        b, s, hkv, d = k_rot.shape
        k_q, k_s = _quantize_kv(k_rot)
        v_q, v_s = _quantize_kv(v_new)
        if s > 1:
            return self._scatter_planes(
                layer_k, layer_v, layer_ks, layer_vs, k_q, v_q, k_s, v_s,
                q_pos, num_new,
            )
        # s == 1 from here (the s > 1 path returned above).
        phys_page, offset_bs = self._slot_pages(q_pos, num_new)
        page = phys_page[:, 0]
        offset = offset_bs[:, 0]

        def body(r, bufs):
            bk, bv, bks, bvs = bufs
            kv = k_q[r, 0][:, None, :]
            vv = v_q[r, 0][:, None, :]
            ks1 = k_s[r, 0][:, None]
            vs1 = v_s[r, 0][:, None]
            start = (page[r], 0, offset[r], 0)
            start3 = (page[r], 0, offset[r])
            return (
                jax.lax.dynamic_update_slice(bk, kv[None], start),
                jax.lax.dynamic_update_slice(bv, vv[None], start),
                jax.lax.dynamic_update_slice(bks, ks1[None], start3),
                jax.lax.dynamic_update_slice(bvs, vs1[None], start3),
            )

        return jax.lax.fori_loop(
            0, b, body, (layer_k, layer_v, layer_ks, layer_vs)
        )

    def _scatter_planes(self, layer_k, layer_v, layer_ks, layer_vs,
                        k_q, v_q, k_s, v_s, q_pos, num_new):
        """Scatter PRE-QUANTIZED ``[B, S, Hkv(, D)]`` values + scales into
        the pool (the fused kernel quantizes in-kernel; its tail flushes
        through here without a second quantization)."""
        b, s, hkv, d = k_q.shape
        phys_page, offset_bs = self._slot_pages(q_pos, num_new)
        flat_page = phys_page.reshape(-1)
        flat_off = offset_bs.reshape(-1)
        return (
            layer_k.at[flat_page, :, flat_off].set(
                k_q.reshape(b * s, hkv, d), mode="drop"
            ),
            layer_v.at[flat_page, :, flat_off].set(
                v_q.reshape(b * s, hkv, d), mode="drop"
            ),
            layer_ks.at[flat_page, :, flat_off].set(
                k_s.reshape(b * s, hkv), mode="drop"
            ),
            layer_vs.at[flat_page, :, flat_off].set(
                v_s.reshape(b * s, hkv), mode="drop"
            ),
        )

    @property
    def ragged_reads_whole_stacks(self) -> bool:
        """Whether a prefill-family dispatch (S > 1) over this cache takes
        the WHOLE carried stacks and the cache layer's index as its layer
        state (``models/llama.py:block_apply`` asks, as
        ``multi_decode_apply`` asks :attr:`tail_reads_whole_big` for decode)
        and hands the updated stacks back: :meth:`attend` then writes the
        piece by whole pages at ``(layer, page)`` (:meth:`_write_pages`) and
        the ragged kernel fetches its blocks there, so that no operation of
        the layer body has a layer's plane as its result. Slicing a layer
        out of the carry to scatter into it position by position costs four
        plane-sized copies a plane a layer (the slice, the relayout XLA's
        scatter wants, the relayout back, the write-back): the pool's size,
        whatever the piece's. True where the ragged kernel serves the
        dispatch and ``attend`` is THIS class's: a subclass that attends its
        own way (an index plane's selection) keeps a layer's planes until it
        says otherwise itself."""
        return self.use_ragged and (
            type(self).attend is QuantizedPagedKVCache.attend
        )

    def _piece_tiles(self, a, offset):
        """A piece's plane ``[B, S, Hkv(, D)]`` laid out as the pages it
        fills, ``[B, N, Hkv, PS(, D)]``: with ``offset[b]`` the offset of the
        row's first position in its page, token ``s`` stands at tile
        ``(offset[b] + s) // PS``, offset ``(offset[b] + s) % PS`` (what
        :func:`paged_piece_write` takes). ``N`` covers the piece wherever in
        a page it starts; offsets the piece has nothing for hold zeros and
        are never written."""
        ps = self.page_size
        b, s = a.shape[:2]
        n = (s + 2 * ps - 2) // ps
        blank = jnp.zeros((n * ps, *a.shape[2:]), a.dtype)
        rows = jnp.stack([
            jax.lax.dynamic_update_slice_in_dim(blank, a[r], offset[r], axis=0)
            for r in range(b)
        ])
        return jnp.swapaxes(rows.reshape(b, n, ps, *a.shape[2:]), 2, 3)

    def _write_pages(self, stacks, layer, k_rot, v_new, q_pos, num_new):
        """:meth:`_scatter_q` over the WHOLE stacks ``(k, v, ks, vs) [L, P,
        ...]`` at cache layer ``layer``, by the pages the piece fills and in
        place: every page but the null page holds afterwards, bit for bit,
        what the scatter leaves there (the null page is where the scatter
        diverts a pad position's write, in no defined order; nothing is
        diverted here, and nothing reads it unmasked)."""
        from ..ops.paged_attention import paged_piece_write
        from .dense import _quantize_kv

        k_q, k_s = _quantize_kv(k_rot)
        v_q, v_s = _quantize_kv(v_new)
        start = q_pos[:, 0]
        offset = start % self.page_size
        return paged_piece_write(
            stacks,
            tuple(self._piece_tiles(a, offset) for a in (k_q, v_q, k_s, v_s)),
            layer, self.page_table, start, num_new,
            **self._kernel_name("write"),
        )

    def attend(self, layer_state, q, k_new, v_new, rope, q_pos, num_new,
               sliding_window, attention_fn, scale=None):
        if self.use_ragged and q.shape[1] > 1:
            from ..ops.ragged_attention import (
                quantized_ragged_paged_attention,
            )

            q_rot = apply_rope(q, rope.cos, rope.sin)
            k_rot = apply_rope(k_new, rope.cos, rope.sin)
            if layer_state[0].ndim == 5:
                # the whole stacks and the cache layer's index
                # (``ragged_reads_whole_stacks``): written and read at
                # (layer, page), the updated stacks handed back
                *stacks, layer = layer_state
                new = self._write_pages(
                    tuple(stacks), layer, k_rot, v_new, q_pos, num_new
                )
            else:
                layer = None
                new = self._scatter_q(
                    *layer_state, k_rot, v_new, q_pos, num_new
                )
            out = quantized_ragged_paged_attention(
                q_rot, new[0], new[2], new[1], new[3], self.page_table,
                self.lengths + num_new, num_new,
                scale=scale, sliding_window=sliding_window, layer=layer,
                **self._kernel_name("prefill"),
            )
            return out, tuple(new)
        if not self.use_kernel or q.shape[1] != 1:
            # Long prefill: flash over the dequantized pool view (see
            # cache/base.py flash_prefill_fn — the full-score path
            # dominates at S >~ 1k).
            flash = flash_prefill_fn(q.shape[1], self.max_len, attention_fn)
            if flash is not None:
                attention_fn = flash
            return super(PagedKVCache, self).attend(
                layer_state, q, k_new, v_new, rope, q_pos, num_new,
                sliding_window, attention_fn, scale,
            )
        from ..ops.paged_attention import quantized_paged_attention

        lk, lv, lks, lvs = layer_state
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        new = self._scatter_q(lk, lv, lks, lvs, k_rot, v_new, q_pos, num_new)
        out = quantized_paged_attention(
            q_rot, new[0], new[2], new[1], new[3], self.page_table,
            self.lengths + num_new, scale=scale,
            sliding_window=sliding_window,
        )
        return out, new

    def update_and_gather(self, layer_state, q, k_new, v_new, rope, q_pos,
                          num_new, sliding_window=None):
        """Gather fallback: contiguous int8 view dequantized to the model
        dtype (prefill / non-kernel decode)."""
        lk, lv, lks, lvs = layer_state
        b, s, hkv, d = k_new.shape
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        new = self._scatter_q(lk, lv, lks, lvs, k_rot, v_new, q_pos, num_new)
        nk, nv, nks, nvs = new
        dt = q.dtype

        def view(pages, scales):
            g = jnp.take(pages, self.page_table, axis=0).astype(dt)
            sc = jnp.take(scales, self.page_table, axis=0).astype(dt)
            return (g * sc[..., None]).transpose(0, 1, 3, 2, 4).reshape(
                b, self.max_len, hkv, d
            )

        k_all = view(nk, nks)
        v_all = view(nv, nvs)
        kv_pos = jnp.broadcast_to(
            jnp.arange(self.max_len, dtype=jnp.int32)[None, :],
            (b, self.max_len),
        )
        kv_valid = kv_pos < (self.lengths + num_new)[:, None]
        mask = causal_mask(q_pos, kv_pos, kv_valid, sliding_window)
        return q_rot, k_all, v_all, mask, new

    # -- write-behind tail ----------------------------------------------------
    #
    # r3 redesign: the fused K-step window GATHERS each row's live pages to
    # contiguous head-major buffers once (``tail_big_stacks``) and runs the
    # same two-segment int8 attention as the quantized dense cache. The
    # previous design read pages in place via the Pallas kernel per layer per
    # step, but (profiled, b64 7B) the per-layer pool slices the scan feeds a
    # kernel operand MATERIALIZE a full pool copy each (~9.6 ms/step of pure
    # copies) and the per-page kernel grid pays ~3x the dense attention in
    # fixed per-step cost. Amortized over K steps the gather is ~2% of a
    # step; the pool itself stays read-only until ``tail_flush`` scatters the
    # window back.
    #
    # r4: PAST the context threshold below, the fused window reads the pool
    # in place again — but through ``quantized_paged_fused_attention``, which
    # takes the WHOLE ``[L, P, …]`` pool with the layer resolved in its block
    # index map (zero-copy, the mechanism r2 lacked) and the tail io-aliased
    # in-kernel. At long contexts the r3 gather's second contiguous copy of
    # the live KV was the binding constraint: it halved the admissible batch
    # (paged_kvq_1k capped at b8 while dense served b24) and re-copied the
    # whole working set every window.

    #: TABLE CAPACITY (``max_len`` = table width x page size) at/above which
    #: the fused window switches from gather-per-window to the in-place
    #: whole-pool kernel. The switch must be static per executable, so it
    #: keys on capacity — a faithful proxy for live context under the
    #: engine's growth ladder, which widens the table bucket-by-bucket as
    #: sessions lengthen (grow-disabled mesh configs sit at full capacity
    #: and always take the in-place form, a conservative choice). Below the
    #: threshold the gather is cheap and row-blocked 256-wide tiles stand
    #: against per-page DMAs; above it the gather is a second copy of the
    #: live KV. The value is not measured on the chip end to end and no
    #: cell is on the other side (ROADMAP D5); the kernels alone: PERF.md §7.
    INPLACE_CTX = 768

    @property
    def _fused_inplace(self) -> bool:
        return self.use_kernel and self.max_len >= self.INPLACE_CTX

    def tail_big_stacks(self):
        """Read-only stacks for the fused window: past ``INPLACE_CTX`` the
        whole pool planes (in-place kernel), the two scale planes as ONE
        with a page's K and V rows side by side where the kernel can then
        copy them by the live page (``joined_scale_rows``: a read and a
        write of both, once a window, here outside both scans; the planes
        as they are stored where it cannot); below it a contiguous
        head-major gather of every row's table span:
        ``(k [L,B,Hkv,Tmax,D] int8, v, ks [L,B,Hkv,Tmax] f32, vs)``. Unmapped
        table slots read the null page — masked by ``pos < base_len``."""
        if self._fused_inplace:
            from ..ops.paged_attention import joined_scale_rows

            joined = joined_scale_rows(self.ks_pages, self.vs_pages)
            if joined is not None:
                return (self.k_pages, self.v_pages, joined)
            return (self.k_pages, self.v_pages, self.ks_pages, self.vs_pages)
        table = self.page_table  # [B, T]

        def g(pages):  # [L, P, H, PS, D] → [L, B, H, T*PS, D]
            v = jnp.take(pages, table, axis=1)       # [L, B, T, H, PS, D]
            v = v.transpose(0, 1, 3, 2, 4, 5)        # [L, B, H, T, PS, D]
            l, b, h, t, ps, d = v.shape
            return v.reshape(l, b, h, t * ps, d)

        def gs(scales):  # [L, P, H, PS] → [L, B, H, T*PS]
            v = jnp.take(scales, table, axis=1).transpose(0, 1, 3, 2, 4)
            l, b, h, t, ps = v.shape
            return v.reshape(l, b, h, t * ps)

        return (
            g(self.k_pages), g(self.v_pages),
            gs(self.ks_pages), gs(self.vs_pages),
        )

    @property
    def _kernel_tail_ok(self) -> bool:
        """The gathered fused path feeds ``quantized_fused_decode_attention``
        whose io-aliased operands cannot pad — its time axis (= table
        capacity here) must be a 32 multiple, like the dense cache's gate;
        the in-place whole-pool kernel tiles by page instead and has no
        such constraint. Odd capacities (e.g. page_size 8 x 5 slots) keep
        the XLA segments path."""
        return self.use_kernel and (
            self._fused_inplace or self.max_len % 32 == 0
        )

    @property
    def tail_reads_whole_big(self) -> bool:
        """Kernel mode: the GATHERED contiguous stacks pass to the fused
        kernel whole (+ layer index) — slicing a layer out of them to feed
        the custom call would copy it through HBM every (layer, step)."""
        return self._kernel_tail_ok

    @property
    def tail_in_kernel(self) -> bool:
        return self._kernel_tail_ok

    def tail_init(self, k_steps: int):
        l = self.k_pages.shape[0]
        b = self.page_table.shape[0]
        hkv, d = self.k_pages.shape[2], self.k_pages.shape[4]
        if self._kernel_tail_ok:
            # int8 + scale planes, quantized IN-KERNEL with the same
            # symmetric absmax scheme ``_scatter_q`` uses — the flush
            # scatters these planes into the pool directly, so pool
            # contents are bit-identical to the per-step write path.
            # Distinct buffers: the kernel aliases each operand.
            return (
                jnp.zeros((l, b, hkv, k_steps, d), jnp.int8),
                jnp.zeros((l, b, hkv, k_steps, d), jnp.int8),
                jnp.zeros((l, b, hkv, k_steps), jnp.float32),
                jnp.zeros((l, b, hkv, k_steps), jnp.float32),
            )
        # bf16 head-major tail (quantized into pages only at flush, exactly
        # like the per-step path quantizes on write — pool contents match).
        z = jnp.zeros((l, b, hkv, k_steps, d), jnp.bfloat16)
        return (z, z)

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None, select=None):
        """``select``: a learned selection's masks of the big and the tail
        segment (:class:`IndexedQuantizedPagedKVCache`), in the form the
        path taken wants them; None attends to every live key."""
        from ..ops.attention import gqa_attention_quantized_segments
        from .dense import segment_valids

        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        if self._kernel_tail_ok and q.shape[1] == 1:
            # whole [L, ...] + layer idx; in place, K's and V's scale rows
            # may be one joined plane (``tail_big_stacks``)
            gk, gv, gks, *gvs, lidx = big_state
            gvs = gvs[0] if gvs else None
            tk, tv, tks, tvs = tail_state
            if self._fused_inplace:
                from ..ops.paged_attention import (
                    quantized_paged_fused_attention,
                )

                out, ntk, ntks, ntv, ntvs = quantized_paged_fused_attention(
                    q_rot, k_rot, v_new,
                    gk, gks, gv, gvs,
                    tk, tks, tv, tvs,
                    layer_idx=lidx, step_idx=step_idx,
                    page_table=self.page_table, base_len=base_len,
                    tail_valid_len=tail_len + num_new,
                    q_positions=base_len + tail_len,
                    scale=scale, sliding_window=sliding_window,
                    **self._kernel_name("decode"),
                    **({} if select is None else {
                        "select": select, "name": KERNEL_DECODE,
                    }),
                )
                return out, (ntk, ntv, ntks, ntvs)
            if select is not None:
                raise ValueError("a selection decodes over the pool in place")
            from ..ops.quant_attention import (
                quantized_fused_decode_attention,
            )

            out, ntk, ntks, ntv, ntvs = quantized_fused_decode_attention(
                q_rot, k_rot, v_new,
                gk, gks, gv, gvs,
                tk, tks, tv, tvs,
                layer_idx=lidx, step_idx=step_idx,
                base_len=base_len, tail_valid_len=tail_len + num_new,
                q_positions=base_len + tail_len,
                scale=scale, sliding_window=sliding_window,
            )
            return out, (ntk, ntv, ntks, ntvs)
        gk, gv, gks, gvs = big_state   # [B, Hkv, Tmax, D] int8 / f32 scales
        tk, tv = tail_state            # [B, Hkv, K, D] bf16
        tk = jax.lax.dynamic_update_slice_in_dim(
            tk, jnp.moveaxis(k_rot, 1, 2).astype(tk.dtype), step_idx, axis=2
        )
        tv = jax.lax.dynamic_update_slice_in_dim(
            tv, jnp.moveaxis(v_new, 1, 2).astype(tv.dtype), step_idx, axis=2
        )
        big_valid, tail_valid = segment_valids(
            base_len, tail_len, num_new, gk.shape[2], tk.shape[2],
            sliding_window,
        )
        if select is not None:
            big_valid &= select[0]
            tail_valid &= select[1]
        ones = jnp.ones(tk.shape[:3], jnp.float32)
        out = gqa_attention_quantized_segments(
            q_rot,
            [(gk, gks, gv, gvs, big_valid), (tk, ones, tv, ones, tail_valid)],
            scale,
        )
        return out, (tk, tv)

    def tail_flush(self, tail, tail_len):
        kk = tail[0].shape[3]
        q_pos = (
            self.lengths[:, None] + jnp.arange(kk, dtype=jnp.int32)[None, :]
        )
        num_new = tail_len
        if len(tail) == 4:  # kernel mode: pre-quantized int8 + scales
            wk, wv, wks, wvs = tail  # [L, B, Hkv, K, D] / [L, B, Hkv, K]
            if kk <= self.page_size:
                # Blocked page RMW (Pallas): the XLA scatter below prefers a
                # transposed pool layout, making XLA insert a whole-pool
                # relayout copy into the fused-decode executable (2x3.2 GB
                # HLO temp at b24 1k-ctx 7B — an OOM; a silent bandwidth tax
                # below that).
                from ..ops.paged_attention import paged_tail_flush

                new_k, new_ks, new_v, new_vs = paged_tail_flush(
                    self.k_pages, self.ks_pages, self.v_pages, self.vs_pages,
                    wk, wks, wv, wvs,
                    self.page_table, self.lengths, tail_len,
                    **self._kernel_name("flush"),
                )
                return self.replace(
                    k_pages=new_k, v_pages=new_v,
                    ks_pages=new_ks, vs_pages=new_vs,
                    lengths=self.lengths + tail_len,
                )
            new_k, new_v, new_ks, new_vs = jax.vmap(
                lambda lk, lv, lks, lvs, tkl, tvl, tksl, tvsl:
                self._scatter_planes(
                    lk, lv, lks, lvs,
                    jnp.moveaxis(tkl, 1, 2), jnp.moveaxis(tvl, 1, 2),
                    jnp.swapaxes(tksl, 1, 2), jnp.swapaxes(tvsl, 1, 2),
                    q_pos, num_new,
                )
            )(self.k_pages, self.v_pages, self.ks_pages, self.vs_pages,
              wk, wv, wks, wvs)
        else:
            wk, wv = tail  # [L, B, Hkv, K, D] bf16 (keys already rotated)
            new_k, new_v, new_ks, new_vs = jax.vmap(
                lambda lk, lv, lks, lvs, tkl, tvl: self._scatter_q(
                    lk, lv, lks, lvs,
                    jnp.moveaxis(tkl, 1, 2), jnp.moveaxis(tvl, 1, 2),
                    q_pos, num_new,
                )
            )(self.k_pages, self.v_pages, self.ks_pages, self.vs_pages, wk, wv)
        return self.replace(
            k_pages=new_k, v_pages=new_v, ks_pages=new_ks, vs_pages=new_vs,
            lengths=self.lengths + tail_len,
        )


# -- learned sparse attention: an index plane beside K and V -------------------
#
# A model that selects its keys (``ModelConfig.sparse``,
# ``ops/sparse_attention.py``) caches ONE index key a token a layer beside K
# and V: a third kind of per-token state in the same pool, under the same page
# table. The two classes below are their parents' own code with that plane
# more: it is listed in ``PLANE_FIELDS``, so it travels wherever planes travel
# (copy-on-write, the prefix store's spill and reload, export and ingest for
# the disagg codec and for preemption with resume), it is written by the same
# write paths (prefill scatter, decode step, tail flush), and the allocator's
# bytes a page grow by it. ``attend`` receives the model's ``index`` inputs,
# writes the index key, scores the row's live index keys, selects, and runs
# the parent's attention under the selection's mask.
#
# The index key's width is a property of the CLASS (``INDEX_DIM``), not an
# argument of ``create``: whoever builds "a cache like this one" from its
# pool's shape alone (``type(cache).create(layers, batch, pages, page size,
# slots, heads, width, dtype)``: the benchmark's probe, a resumed engine)
# gets the plane too. :func:`indexed_cache_class` makes the class once a
# width.


def _row_index_keys(table, plane):
    """A row's index keys in position order, by its page table: one layer's
    ``plane [P, 1, PS, D]`` to ``[B, T*PS, D]``. An unmapped slot reads the
    null page; validity comes from the lengths."""
    b, t = table.shape
    keys = jnp.take(plane, table, axis=0, mode="clip")
    return keys.reshape(b, t * keys.shape[3], keys.shape[4])


class _IndexPlane:
    """What the two indexed classes share: the plane's place in the class's
    field lists, its write path and its ingest. ``ik_pages [L, P, 1, PS,
    INDEX_DIM]`` holds the index keys in the MODEL's dtype beside an int8 K
    and V too: a selection is a hard choice, and an int8 index key moves
    which keys a query attends to (at 46 positions and topk 8 it moved the
    logits by 0.3 where int8 K and V move them by 0.005; tests/bench holds
    an int8 pool to 0.05), for 60 B a token a layer more than an int8 key
    and its scale would take, of 1184."""

    INDEX_DIM = None
    #: plane name -> pool field of what is stored beside K and V
    INDEX_PLANES = {"ik": "ik_pages"}

    @classmethod
    def create(cls, num_layers, batch, num_pages, page_size,
               max_pages_per_session, num_kv_heads, head_dim,
               dtype=jnp.bfloat16, use_kernel=False, use_ragged=False):
        """The parent pool's cache (``POOL.create``, its arguments) with a
        zeroed index plane over the same pages: the plane's width is the
        class's. A class without the kernels' paths (``KERNELS``) drops
        the flags."""
        base = cls.POOL.create(
            num_layers, batch, num_pages, page_size, max_pages_per_session,
            num_kv_heads, head_dim, dtype,
            use_kernel=use_kernel and cls.KERNELS,
            use_ragged=use_ragged and cls.KERNELS,
        )
        return cls(
            **{f.name: getattr(base, f.name) for f in dataclasses.fields(base)},
            ik_pages=jnp.zeros(
                (cls.index_rows(num_layers), num_pages, 1, page_size,
                 cls.INDEX_DIM), dtype,
            ),
        )

    @classmethod
    def index_rows(cls, num_layers: int) -> int:
        """Rows of the index plane of a cache of ``num_layers`` layers: one a
        layer, where every layer scores a selection."""
        return num_layers

    def _scatter_index(self, lik, index_k, q_pos, num_new):
        """Index keys ``[B, S, D]`` into one layer's plane, at the positions
        K and V go to (``_slot_pages``: pads and strangers' pages divert to
        the null page)."""
        page, offset = self._slot_pages(q_pos, num_new)
        return lik.at[page.reshape(-1), 0, offset.reshape(-1)].set(
            index_k.reshape(-1, index_k.shape[-1]).astype(lik.dtype),
            mode="drop",
        )

    def _select(self, index, nik, q_pos, num_new):
        """The selection ``[B, S, T*PS]`` of a dispatch's queries over the
        row's index keys, this dispatch's included."""
        return selection_mask(
            index, _row_index_keys(self.page_table, nik), q_pos,
            self.lengths + num_new,
        )

    def _tail_select(self, index, pool_keys, tail_keys, base_len, tail_len,
                     num_new):
        """A decode step's selection ``[B, N + K]`` over a row's pool
        positions (``pool_keys [B, N, D]``, by table slot) and, behind them,
        its tail slots (``tail_keys [B, 1, K, D]``, this step's included)."""
        b, n = pool_keys.shape[:2]
        steps = jnp.arange(tail_keys.shape[2], dtype=jnp.int32)[None, :]
        slots = jnp.arange(n, dtype=jnp.int32)[None, :]
        return selection_mask(
            index, jnp.concatenate([pool_keys, tail_keys[:, 0]], 1),
            (base_len + tail_len)[:, None], None,
            key_pos=jnp.concatenate(
                [jnp.broadcast_to(slots, (b, n)), base_len[:, None] + steps], 1
            ),
            key_valid=jnp.concatenate([
                slots < base_len[:, None],
                steps < (tail_len + num_new)[:, None],
            ], 1),
        )[:, 0]

    def ingest_index_row(self, planes, n_valid, first_slot=0):
        """Install shipped index planes (``INDEX_PLANES`` names; ``[L, 1, S,
        1, D]``) as ``ingest_row`` installs K and V."""
        return self._ingest_planes(
            {self.INDEX_PLANES[name]: a for name, a in planes.items()},
            n_valid, first_slot,
        )


class IndexedPagedKVCache(_IndexPlane, PagedKVCache):
    """:class:`PagedKVCache` with the index plane. The exact-arithmetic form
    (float32 tests, the int8 class's oracle): attention is the gather path
    under the selection's mask, a token a dispatch; the kernels and the
    write-behind tail are the int8 class's."""

    ik_pages: jax.Array = None

    POOL, KERNELS = PagedKVCache, False
    LAYER_FIELDS = ("k_pages", "v_pages", "ik_pages")
    SHARED_FIELDS = LAYER_FIELDS
    PLANE_FIELDS = {"k": "k_pages", "v": "v_pages", "ik": "ik_pages"}

    @property
    def layer_stacks(self):
        return (self.k_pages, self.v_pages, self.ik_pages)

    def with_layer_stacks(self, k, v, ik) -> "IndexedPagedKVCache":
        return self.replace(k_pages=k, v_pages=v, ik_pages=ik)

    def attend(self, layer_state, q, k_new, v_new, rope, q_pos, num_new,
               sliding_window, attention_fn, scale=None, index=None):
        *kv_state, lik = layer_state
        nik = self._scatter_index(lik, index.k, q_pos, num_new)
        sel = self._select(index, nik, q_pos, num_new)
        q_rot, k_all, v_all, mask, new = self.update_and_gather(
            tuple(kv_state), q, k_new, v_new, rope, q_pos, num_new,
            sliding_window,
        )
        with jax.named_scope("sparse_attention"):
            out = attention_fn(q_rot, k_all, v_all, mask & sel, scale=scale)
        return out, (*new, nik)

    has_tail = False

    def tail_init(self, k_steps: int):
        raise NotImplementedError(
            "the value-dtype indexed cache decodes a token a dispatch"
        )


class IndexedQuantizedPagedKVCache(_IndexPlane, QuantizedPagedKVCache):
    """:class:`QuantizedPagedKVCache` with the index plane. It inherits the
    tail protocol: a decode step writes its index key into an index tail
    ``[L, B, 1, K, INDEX_DIM]`` beside the K/V tail, scores the pool's and
    the tail's index keys together, selects, and runs the parent's fused
    in-place sweep under the selection; ``tail_flush`` scatters the index
    tail where the K/V tail goes. The fused window reads the pool in place
    at every table width (``INPLACE_CTX`` 0): the gathered form has no
    mask."""

    ik_pages: jax.Array = None

    POOL, KERNELS = QuantizedPagedKVCache, True
    INPLACE_CTX = 0
    LAYER_FIELDS = (
        "k_pages", "v_pages", "ks_pages", "vs_pages", "ik_pages",
    )
    SHARED_FIELDS = LAYER_FIELDS
    PLANE_FIELDS = {
        "k": "k_pages", "v": "v_pages", "ks": "ks_pages", "vs": "vs_pages",
        "ik": "ik_pages",
    }

    @property
    def layer_stacks(self):
        return (*super().layer_stacks, self.ik_pages)

    def with_layer_stacks(self, k, v, ks, vs, ik):
        return self.replace(
            k_pages=k, v_pages=v, ks_pages=ks, vs_pages=vs, ik_pages=ik
        )

    def attend(self, layer_state, q, k_new, v_new, rope, q_pos, num_new,
               sliding_window, attention_fn, scale=None, index=None):
        *kv_state, lik = layer_state
        nik = self._scatter_index(lik, index.k, q_pos, num_new)
        sel = self._select(index, nik, q_pos, num_new)
        if self.use_ragged and q.shape[1] > 1:
            from ..ops.ragged_attention import (
                quantized_ragged_paged_attention,
            )

            new = self._scatter_q(
                *kv_state, apply_rope(k_new, rope.cos, rope.sin), v_new,
                q_pos, num_new,
            )
            b, s, _ = sel.shape
            with jax.named_scope("sparse_attention"):
                out = quantized_ragged_paged_attention(
                    apply_rope(q, rope.cos, rope.sin),
                    new[0], new[2], new[1], new[3], self.page_table,
                    self.lengths + num_new, num_new,
                    scale=scale, sliding_window=sliding_window,
                    # [B, S, T*PS] -> a (page, q-block) tile a block
                    select=sel.reshape(b, s, -1, self.page_size).transpose(
                        0, 2, 1, 3
                    ).astype(jnp.int8),
                    name=KERNEL_PREFILL,
                )
            return out, (*new, nik)
        # Without the ragged kernel (the CPU's default plan; a decode step
        # of an engine without the tail): the gather path under the mask.
        q_rot, k_all, v_all, mask, new = self.update_and_gather(
            tuple(kv_state), q, k_new, v_new, rope, q_pos, num_new,
            sliding_window,
        )
        with jax.named_scope("sparse_attention"):
            out = attention_fn(q_rot, k_all, v_all, mask & sel, scale=scale)
        return out, (*new, nik)

    # -- write-behind tail ----------------------------------------------------

    def tail_big_stacks(self):
        big = super().tail_big_stacks()
        if self._fused_inplace:
            return (*big, self.ik_pages)
        l, b = self.ik_pages.shape[0], self.page_table.shape[0]
        ik = jnp.take(self.ik_pages, self.page_table, axis=1)
        return (*big, ik.reshape(l, b, -1, ik.shape[-1]))

    def tail_init(self, k_steps: int):
        l, b = self.ik_pages.shape[0], self.page_table.shape[0]
        return (
            *super().tail_init(k_steps),
            jnp.zeros((l, b, 1, k_steps, self.INDEX_DIM), self.ik_pages.dtype),
        )

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None, index=None):
        whole = self._kernel_tail_ok and q.shape[1] == 1
        *kv_tail, tik = tail_state
        ik_new = index.k[:, None].astype(tik.dtype)        # [B, 1, 1, D]
        if whole:
            # whole [L, ...] planes and tails, the layer's index last
            *kv_big, gik, lidx = big_state
            kv_big = (*kv_big, lidx)
            tik = jax.lax.dynamic_update_slice(
                tik, ik_new[None], (lidx, 0, 0, step_idx, 0)
            )
            pool_keys = _row_index_keys(
                self.page_table,
                jax.lax.dynamic_index_in_dim(gik, lidx, keepdims=False),
            )
            tail_keys = jax.lax.dynamic_index_in_dim(tik, lidx, keepdims=False)
        else:
            # this layer's gathered [B, N, D] keys and [B, 1, K, D] tail
            *kv_big, pool_keys = big_state
            tik = jax.lax.dynamic_update_slice_in_dim(tik, ik_new, step_idx, 2)
            tail_keys = tik
        b, n = pool_keys.shape[:2]
        sel = self._tail_select(
            index, pool_keys, tail_keys, base_len, tail_len, num_new
        )
        select = (sel[:, :n], sel[:, n:])
        if whole:
            select = (
                select[0].reshape(b, -1, 1, self.page_size),
                select[1][:, None, :],
            )
        with jax.named_scope("sparse_attention"):
            out, new_kv = super().tail_attend(
                tuple(kv_big), tuple(kv_tail), q, k_new, v_new, rope,
                base_len, tail_len, step_idx, num_new, sliding_window, scale,
                select=select,
            )
        return out, (*new_kv, tik)

    def tail_flush(self, tail, tail_len):
        *kv_tail, tik = tail                      # tik [L, B, 1, K, D]
        kk = tik.shape[3]
        if self._kernel_tail_ok and kk <= self.page_size:
            # The K/V tail's own page read-modify-write, for the reason it
            # has one: the XLA scatter below wants the plane transposed,
            # two whole-plane relayout copies in the decode executable
            # (1.7 GB of temporaries at the cell's pool: a described-v5e
            # compile, PR 32).
            from ..ops.paged_attention import paged_tail_flush

            (nik,) = paged_tail_flush(
                self.ik_pages, None, None, None, tik, None, None, None,
                self.page_table, self.lengths, tail_len,
                name=KERNEL_INDEX_FLUSH,
            )
        else:
            q_pos = self.lengths[:, None] + jnp.arange(
                kk, dtype=jnp.int32
            )[None, :]
            nik = jax.vmap(
                lambda lik, t: self._scatter_index(
                    lik, t[:, 0], q_pos, tail_len
                )
            )(self.ik_pages, tik)
        return super().tail_flush(tuple(kv_tail), tail_len).replace(
            ik_pages=nik
        )


@functools.lru_cache(maxsize=None)
def indexed_cache_class(quantized: bool, index_dim: int):
    """THE indexed cache class of a stored form and an index key's width,
    made once (a class is a pytree node type: two engines of one width must
    hold the same one)."""
    base = IndexedQuantizedPagedKVCache if quantized else IndexedPagedKVCache
    return type(
        f"{base.__name__}{index_dim}", (base,), {"INDEX_DIM": int(index_dim)}
    )


# -- window and full layers in one stack: a pool a kind --------------------------
#
# A model whose layers are not all of one attention kind
# (``ModelConfig.mixed_attention``) keeps two kinds of per-token state. A full
# layer needs every position of a row for as long as the row lives; a window
# layer needs the last ``window`` positions and nothing before them. Under ONE
# page table a window layer would keep pages it can never read, so the two
# classes below hold the kinds apart: the full layers keep the parent's pool
# ``k_pages [full layers, P, heads, PS, D]`` and table, and the window layers
# a pool ``wk_pages [window layers, Pw, ...]`` with a table ``w_page_table``
# of their own, of the SAME logical width (slot ``i`` is positions ``i * PS
# .. (i + 1) * PS`` in both). The kernels then run on (window pool, window
# table, the window) unchanged: ``ops/paged_attention.py:_live_pages`` and
# ``ops/ragged_attention.py:_tile_live`` never fetch a slot that lies wholly
# before the window, so the engine releases such a slot's page and gives it
# to another row, and a row's window pages are bounded by the window and what
# one dispatch writes, whatever its context.
#
# A released slot keeps its STALE page id in the table. That is safe, and
# relied on, in two ways. The id stays a valid page of the window pool (a
# page is only ever handed to another row, never removed), so the paths that
# gather a row's whole table in XLA (``update_and_gather``, the gathered
# ``tail_big_stacks`` under ``INPLACE_CTX``) read finite values of another
# row there; and every position of a released slot is at or before ``query -
# window`` for every query the row can still make (the engine's release
# rule), so those values lie under the window's mask (``causal_mask`` /
# ``segment_valids`` with ``sliding_window``) and weigh nothing. Writes never
# reach a stale slot: they go to positions at or past the row's length.
#
# The model reaches a pool through :meth:`pool_view`: a plain instance of the
# parent class over that pool's planes and table (the window pool's under
# kernel names of its own), so attention, the scatter paths and the whole
# tail protocol are the parent's code for both pools, and
# ``models/llama.py`` puts the planes back with :meth:`with_pool_view`.
#
# Which layers are of which kind is the CLASS's (``LAYER_KINDS``), as the
# index key's width is the indexed classes': whoever builds "a cache like
# this one" from ``k_pages``' shape alone (the benchmark's probe) gets both
# pools. :func:`two_pool_cache_class` makes the class once a (stored form,
# layer kinds, window).

_WINDOW_KERNELS = {
    "decode": "window_paged_fused_attention",
    "prefill": "window_ragged_paged_attention",
    "flush": "window_tail_flush",
    "write": "window_piece_write",
}


class _WindowPagedKVCache(PagedKVCache):
    """The window layers' pool as the model sees it: the parent's code
    under the window pool's kernel names."""

    KERNEL_NAMES = _WINDOW_KERNELS


class _WindowQuantizedPagedKVCache(QuantizedPagedKVCache):
    KERNEL_NAMES = _WINDOW_KERNELS


class _TwoPools:
    """What the two two-pool classes share. ``WINDOW_PLANES`` maps a parent
    plane's field to the window pool's."""

    LAYER_KINDS = None      # "window" | "full", a layer of the stack
    WINDOW = None           # the window layers' window, in positions

    @classmethod
    def num_layers_of(cls, kind: str) -> int:
        return sum(1 for k in cls.LAYER_KINDS if k == kind)

    @classmethod
    def create(cls, num_layers, batch, num_pages, page_size,
               max_pages_per_session, num_kv_heads, head_dim,
               dtype=jnp.bfloat16, use_kernel=False, use_ragged=False,
               window_pages=None):
        """``num_layers`` counts ``k_pages``' layers, the FULL ones (what a
        caller reads off ``k_pages.shape``); the window pool's layers are
        the class's. ``window_pages``: the window pool's pages (the engine
        sizes it by the window; default as many as the full pool, which is
        what a one-row probe wants: no page is ever reused there)."""
        if num_layers != cls.num_layers_of("full"):
            raise ValueError(
                f"{cls.__name__} holds {cls.num_layers_of('full')} full "
                f"layers in k_pages (and {cls.num_layers_of('window')} "
                f"window layers beside them), not {num_layers}"
            )
        full = cls.POOL.create(
            num_layers, batch, num_pages, page_size, max_pages_per_session,
            num_kv_heads, head_dim, dtype,
            use_kernel=use_kernel, use_ragged=use_ragged,
        )
        window = cls.POOL.create(
            cls.num_layers_of("window"), batch, window_pages or num_pages,
            page_size, max_pages_per_session, num_kv_heads, head_dim, dtype,
        )
        return cls(
            **{f.name: getattr(full, f.name) for f in dataclasses.fields(full)},
            **{w: getattr(window, f) for f, w in cls.WINDOW_PLANES.items()},
            w_page_table=window.page_table,
        )

    # -- the pools, as the model sees them ------------------------------------

    def pool_view(self, kind: str):
        """The cache of one attention kind's layers: an instance of the
        parent class over that pool's planes and table, sharing this
        cache's lengths."""
        if kind == "full":
            return self.POOL(**{
                f.name: getattr(self, f.name)
                for f in dataclasses.fields(self.POOL)
            })
        return self.WINDOW_POOL(
            **{f: getattr(self, w) for f, w in self.WINDOW_PLANES.items()},
            page_table=self.w_page_table, lengths=self.lengths,
            page_size=self.page_size, use_kernel=self.use_kernel,
            use_ragged=self.use_ragged,
        )

    def with_pool_view(self, kind: str, view, lengths: bool = False):
        """This cache with ``view``'s planes put back (and, after a tail
        flush, its advanced lengths: the same from either pool)."""
        planes = (
            {f: getattr(view, f) for f in self.WINDOW_PLANES}
            if kind == "full"
            else {w: getattr(view, f) for f, w in self.WINDOW_PLANES.items()}
        )
        if lengths:
            planes["lengths"] = view.lengths
        return self.replace(**planes)

    # -- rows ----------------------------------------------------------------

    def reset_rows(self, row_mask):
        return super().reset_rows(row_mask).replace(
            w_page_table=jnp.where(row_mask[:, None], 0, self.w_page_table)
        )

    def select_row(self, row):
        return super().select_row(row).replace(
            w_page_table=jax.lax.dynamic_slice_in_dim(
                self.w_page_table, row, 1, axis=0
            )
        )

    def merge_row(self, sub, row):
        return super().merge_row(sub, row).replace(
            w_page_table=jax.lax.dynamic_update_slice_in_dim(
                self.w_page_table, sub.w_page_table, row, axis=0
            )
        )

    def select_rows(self, rows):
        return super().select_rows(rows).replace(
            w_page_table=jnp.take(self.w_page_table, rows, axis=0, mode="clip")
        )

    def merge_rows(self, sub, rows):
        return super().merge_rows(sub, rows).replace(
            w_page_table=self.w_page_table.at[rows].set(
                sub.w_page_table, mode="drop"
            )
        )

    # -- tables --------------------------------------------------------------

    def assign_pages(self, row, pages, start_slot=0):
        """The SAME page ids into both tables: what a one-row cache wants
        (the benchmark's probe; the engine's warm-up of the table write),
        where both pools hold as many pages and none is reused. The engine
        installs a serving row's pages a pool at a time
        (``assign_pages_batch``, ``assign_window_pages_batch``)."""
        pages = jnp.asarray(pages, jnp.int32)
        at = (pages[None, :], jnp.int32(row), jnp.int32(start_slot))
        return self.replace(
            page_table=_table_write(self.page_table, *at),
            w_page_table=_table_write(self.w_page_table, *at),
        )

    def assign_window_pages_batch(self, rows, slots, pages, pad_to=0):
        """:meth:`assign_pages_batch` into the window table."""
        view = self.pool_view("window").assign_pages_batch(
            rows, slots, pages, pad_to
        )
        return self.replace(w_page_table=view.page_table)

    # -- what a two-pool cache does not do --------------------------------------

    def _one_table_only(self, what: str):
        raise NotImplementedError(
            f"{what} is not implemented for a cache of window and full "
            f"layers: a window layer keeps only a row's last pages, so a "
            f"row's KV cannot be shipped, shared or reloaded as one run of "
            f"pages"
        )

    def _ingest_planes(self, planes, n_valid, first_slot=0):
        self._one_table_only("ingesting a row's KV")

    def copy_page(self, dst, src):
        self._one_table_only("a copy-on-write page split")

    def read_page(self, page):
        self._one_table_only("reading a page for the spill store")

    def write_page(self, page, tiles):
        self._one_table_only("reloading a spilled page")


class TwoPoolPagedKVCache(_TwoPools, PagedKVCache):
    """:class:`PagedKVCache` for a stack of window and full layers: the
    exact-arithmetic form (float32 tests, the int8 class's oracle)."""

    wk_pages: jax.Array = None
    wv_pages: jax.Array = None
    w_page_table: jax.Array = None

    POOL, WINDOW_POOL = PagedKVCache, _WindowPagedKVCache
    WINDOW_PLANES = {"k_pages": "wk_pages", "v_pages": "wv_pages"}
    BATCH_AXES = {"page_table": 0, "lengths": 0, "w_page_table": 0}
    TABLE_FIELDS = ("page_table", "w_page_table")
    LAYER_FIELDS = ("k_pages", "v_pages", "wk_pages", "wv_pages")
    SHARED_FIELDS = LAYER_FIELDS
    PLANE_FIELDS = {
        "k": "k_pages", "v": "v_pages", "wk": "wk_pages", "wv": "wv_pages",
    }


class TwoPoolQuantizedPagedKVCache(_TwoPools, QuantizedPagedKVCache):
    """:class:`QuantizedPagedKVCache` for a stack of window and full layers.
    Both pools have the parent's tail protocol, each through its view: the
    fused 16-step scan writes a K/V tail a pool and flushes each where its
    table says."""

    wk_pages: jax.Array = None
    wv_pages: jax.Array = None
    wks_pages: jax.Array = None
    wvs_pages: jax.Array = None
    w_page_table: jax.Array = None

    POOL, WINDOW_POOL = QuantizedPagedKVCache, _WindowQuantizedPagedKVCache
    WINDOW_PLANES = {
        "k_pages": "wk_pages", "v_pages": "wv_pages",
        "ks_pages": "wks_pages", "vs_pages": "wvs_pages",
    }
    BATCH_AXES = {"page_table": 0, "lengths": 0, "w_page_table": 0}
    TABLE_FIELDS = ("page_table", "w_page_table")
    LAYER_FIELDS = (
        "k_pages", "v_pages", "ks_pages", "vs_pages",
        "wk_pages", "wv_pages", "wks_pages", "wvs_pages",
    )
    SHARED_FIELDS = LAYER_FIELDS
    PLANE_FIELDS = {
        "k": "k_pages", "v": "v_pages", "ks": "ks_pages", "vs": "vs_pages",
        "wk": "wk_pages", "wv": "wv_pages",
        "wks": "wks_pages", "wvs": "wvs_pages",
    }


@functools.lru_cache(maxsize=None)
def two_pool_cache_class(quantized: bool, layer_kinds: tuple, window: int):
    """THE two-pool cache class of a stored form, a stack's attention kinds
    ("window" | "full", a layer) and its window, made once (a class is a
    pytree node type: two engines of one stack must hold the same one)."""
    base = TwoPoolQuantizedPagedKVCache if quantized else TwoPoolPagedKVCache
    full = sum(1 for k in layer_kinds if k == "full")
    return type(
        f"{base.__name__}{full}of{len(layer_kinds)}w{window}", (base,),
        {"LAYER_KINDS": tuple(layer_kinds), "WINDOW": int(window)},
    )


def window_pages_bound(window: int, page_size: int, tokens: int) -> int:
    """The most window pages a row holds around one dispatch that writes
    ``tokens`` positions: the pages the window before the first of them
    reaches into, those the dispatch writes, and one for the straddle."""
    return -(-(window - 1 + tokens) // page_size) + 1
