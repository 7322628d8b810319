"""Latent (low-rank) paged KV cache — MLA-style compression.

Instead of per-head K/V (``2 * Hkv * D`` values/token/layer) the pool
stores ONE fused latent per token: ``[c ; k_rope]`` where ``c`` is the
shared ``rank``-dim KV latent and ``k_rope`` the ``rope_head_dim``-dim
decoupled rotary key (``lat_dim = rank + rope_head_dim`` values/token).
At D=128, Hkv=8, rank=64, rope=16 that is a 32x raw reduction (bf16
baseline -> f32 latent still 12.8x), which shrinks together everything
priced in KV bytes/token: resident HBM, the disagg wire, migration
checkpoints, and the host spill arena.

The trick that makes one stored latent serve every query head with NO
per-token decompression is the absorbed-MLA formulation
(``models/llama.py:_latent_attention``): the key up-projection is
folded into the query (``q_lat[h] = q_nope[h] @ w_uk[h]``) and the value
up-projection is applied AFTER attention, so the attention itself runs
over the stored form — ``K = V = [c ; k_rope]`` with a single KV head.
Every existing paged kernel is generic over ``(Hkv, head_dim)``, so the
"fused decompression" is literally the kernels' existing page-table walk
reading the latent pool in place (``ops/ragged_attention.py:
latent_ragged_paged_attention`` for prefill; for decode
``ops/paged_attention.py: quantized_latent_paged_fused_attention`` over the
int8 pool, the fused 16-step window with a write-behind tail, and
``latent_paged_attention`` / ``quantized_latent_paged_attention``, the
one-token step of the float32 pool and of an int8 pool without its kernel).

Three consequences shape this module:

* Rope is applied by the MODEL (to the ``k_rope`` slice only, before the
  latent is handed to the cache) — the latent itself is position-free.
  So unlike every other cache, ``attend``/``update_and_gather`` must NOT
  re-apply rope; ``k_new`` arrives in stored form.
* The pool is the serialization format. Stored planes are ``c`` (f32
  ``[lat_dim]`` per token) or ``c``+``cs`` (int8 + per-token f32 scale),
  flowing unchanged through export/ingest/spill/page-ship — the same
  page/refcount/CoW machinery as the parent, via ``PLANE_FIELDS``.

* The write-behind tail is the int8 pool's alone
  (``QuantizedLatentPagedKVCache.tail_*``): ONE stored plane where the
  per-head pool has two, rope-free, in the kernel. The float32 pool keeps
  the one-token path; it runs in no benchmark cell.

``v_pages`` survives as a 1-element placeholder (flax dataclass fields
cannot be removed in a subclass); no code path reads it — every pool
consumer walks ``PLANE_FIELDS``/``LAYER_FIELDS``, which name only the
latent planes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..ops.attention import causal_mask
from .paged import PagedKVCache, _IndexPlane, _row_index_keys

__all__ = [
    "LatentPagedKVCache",
    "QuantizedLatentPagedKVCache",
    "IndexedLatentPagedKVCache",
    "IndexedQuantizedLatentPagedKVCache",
    "indexed_latent_cache_class",
]


class LatentPagedKVCache(PagedKVCache):
    """Paged pool storing one f32 ``[lat_dim]`` latent per token.

    ``k_pages``: ``[L, num_pages, 1, page_size, lat_dim]`` f32 — the
    fused ``[c ; k_rope]`` stored form (f32: the latent is the ONLY copy
    of the KV information; rounding it to bf16 at rank ~64 measurably
    moves logits, and the byte win over per-head K/V is already >10x).
    """

    LAYER_FIELDS = ("k_pages",)
    SHARED_FIELDS = ("k_pages",)
    PLANE_FIELDS = {"c": "k_pages"}

    @staticmethod
    def create(
        num_layers: int,
        batch: int,
        num_pages: int,
        page_size: int,
        max_pages_per_session: int,
        num_kv_heads: int,
        lat_dim: int,
        dtype=jnp.float32,  # interface parity; the stored form is f32
        use_kernel: bool = False,
        use_ragged: bool = False,
    ) -> "LatentPagedKVCache":
        if num_kv_heads != 1:
            raise ValueError(
                f"latent cache stores ONE shared latent head, got "
                f"num_kv_heads={num_kv_heads}"
            )
        shape = (num_layers, num_pages, 1, page_size, lat_dim)
        return LatentPagedKVCache(
            k_pages=jnp.zeros(shape, jnp.float32),
            v_pages=jnp.zeros((num_layers, 1, 1, 1, 1), jnp.float32),
            page_table=jnp.zeros((batch, max_pages_per_session), jnp.int32),
            lengths=jnp.zeros((batch,), jnp.int32),
            page_size=page_size,
            use_kernel=use_kernel,
            use_ragged=use_ragged,
        )

    @property
    def lat_dim(self) -> int:
        return self.k_pages.shape[-1]

    @property
    def layer_stacks(self):
        return (self.k_pages,)

    def with_layer_stacks(self, new_c) -> "LatentPagedKVCache":
        return self.replace(k_pages=new_c)

    # -- pool writes / reads ------------------------------------------------
    def _scatter_latent(self, layer_state, c_new, q_pos, num_new):
        """Scatter incoming fused latents ``[B, S, 1, lat_dim]`` into the
        page pool (the parent's :meth:`_scatter` write pattern, one
        plane)."""
        (layer_c,) = layer_state
        b, s, _, d = c_new.shape
        phys_page, offset_bs = self._slot_pages(q_pos, num_new)
        if s == 1:
            page = phys_page[:, 0]
            offset = offset_bs[:, 0]

            def body(r, buf):
                cv = c_new[r, 0][:, None, :].astype(buf.dtype)  # [1, 1, D]
                return jax.lax.dynamic_update_slice(
                    buf, cv[None], (page[r], 0, offset[r], 0)
                )

            return (jax.lax.fori_loop(0, b, body, layer_c),)
        new_c = layer_c.at[
            phys_page.reshape(-1), :, offset_bs.reshape(-1)
        ].set(c_new.reshape(b * s, 1, d).astype(layer_c.dtype), mode="drop")
        return (new_c,)

    def _contiguous_view(self, layer_state, batch, dt):
        """Gather each row's pages into ``[B, max_len, 1, lat_dim]``."""
        (new_c,) = layer_state
        return jnp.take(new_c, self.page_table, axis=0).transpose(
            0, 1, 3, 2, 4
        ).reshape(batch, self.max_len, 1, self.lat_dim).astype(dt)

    # -- attention ----------------------------------------------------------
    def attend(self, layer_state, q, k_new, v_new, rope, q_pos, num_new,
               sliding_window, attention_fn, scale=None):
        """``q`` is the absorbed query ``[B, S, Hq, lat_dim]`` and
        ``k_new`` (== ``v_new``) the fused latent — both already carry
        rope on their ``k_rope`` slice, so no path here rotates
        anything. Kernel paths read the latent pool in place."""
        new_state = self._scatter_latent(layer_state, k_new, q_pos, num_new)
        if self.use_ragged and q.shape[1] > 1:
            from ..ops.ragged_attention import latent_ragged_paged_attention

            out = latent_ragged_paged_attention(
                q, new_state[0], self.page_table, self.lengths + num_new,
                num_new, scale=scale, sliding_window=sliding_window,
            )
            return out, new_state
        if self.use_kernel and q.shape[1] == 1:
            from ..ops.paged_attention import latent_paged_attention

            out = latent_paged_attention(
                q, new_state[0], self.page_table, self.lengths + num_new,
                scale=scale, sliding_window=sliding_window,
            )
            return out, new_state
        c_all = self._contiguous_view(new_state, q.shape[0], q.dtype)
        mask = self._latent_mask(q.shape[0], q_pos, num_new, sliding_window)
        return attention_fn(q, c_all, c_all, mask, scale=scale), new_state

    def _latent_mask(self, b, q_pos, num_new, sliding_window):
        kv_pos = jnp.broadcast_to(
            jnp.arange(self.max_len, dtype=jnp.int32)[None, :],
            (b, self.max_len),
        )
        kv_valid = kv_pos < (self.lengths + num_new)[:, None]
        return causal_mask(q_pos, kv_pos, kv_valid, sliding_window)

    def update_and_gather(self, layer_state, q, k_new, v_new, rope, q_pos,
                          num_new, sliding_window: Optional[int] = None):
        """Gather fallback view (NO rope — see :meth:`attend`)."""
        new_state = self._scatter_latent(layer_state, k_new, q_pos, num_new)
        c_all = self._contiguous_view(new_state, q.shape[0], q.dtype)
        mask = self._latent_mask(q.shape[0], q_pos, num_new, sliding_window)
        return q, c_all, c_all, mask, new_state

    # -- serialization ------------------------------------------------------
    def ingest_row(self, ks, vs, n_valid, first_slot=0):
        raise TypeError(
            "latent cache has no k/v planes; use ingest_latent_row"
        )

    def ingest_latent_row(self, planes, n_valid, first_slot=0):
        """Install STORED-form latent planes (``{"c": [L, 1, S, 1,
        lat_dim]}``, plus ``"cs"`` scales on the int8 pool) bit-exact —
        the latent counterpart of ``ingest_planes_row``; shares the
        parent's page-chunk scatter via ``PLANE_FIELDS``."""
        if set(planes) != set(self.PLANE_FIELDS):
            raise ValueError(
                f"latent ingest planes {sorted(planes)} != "
                f"{sorted(self.PLANE_FIELDS)}"
            )
        return self._ingest_planes(
            {self.PLANE_FIELDS[name]: a for name, a in planes.items()},
            n_valid,
            first_slot,
        )

    # -- write-behind tail: the float32 pool has none (it runs in no
    # benchmark cell; the engine's tail gate passes only a cache whose
    # ``has_tail`` says so, and the parent's tail would re-apply rope to the
    # pre-rotated stored form). Fail loudly if reached.
    has_tail = False

    def tail_init(self, k_steps: int):
        raise NotImplementedError(
            "float32 latent cache has no write-behind tail"
        )


class QuantizedLatentPagedKVCache(LatentPagedKVCache):
    """Latent pool in int8 with per-token f32 scales.

    ``k_pages``: int8 ``[L, P, 1, PS, lat_dim]``; ``cs_pages``: f32
    ``[L, P, 1, PS]`` (one absmax scale per token per layer — the fused
    latent is a single "head"). ~4x the f32 form's density at ~0.4%
    scale overhead; the gather path dequantizes its contiguous view, the
    kernel path dequantizes on the scores exactly like the per-head int8
    pool."""

    # Dataclass inheritance: fields after the parent's defaulted ones need
    # defaults; create() always supplies real arrays.
    cs_pages: jax.Array = None

    LAYER_FIELDS = ("k_pages", "cs_pages")
    SHARED_FIELDS = ("k_pages", "cs_pages")
    PLANE_FIELDS = {"c": "k_pages", "cs": "cs_pages"}

    @staticmethod
    def create(
        num_layers: int,
        batch: int,
        num_pages: int,
        page_size: int,
        max_pages_per_session: int,
        num_kv_heads: int,
        lat_dim: int,
        dtype=jnp.float32,  # interface parity; values are int8
        use_kernel: bool = False,
        use_ragged: bool = False,
    ) -> "QuantizedLatentPagedKVCache":
        if num_kv_heads != 1:
            raise ValueError(
                f"latent cache stores ONE shared latent head, got "
                f"num_kv_heads={num_kv_heads}"
            )
        shape = (num_layers, num_pages, 1, page_size, lat_dim)
        return QuantizedLatentPagedKVCache(
            k_pages=jnp.zeros(shape, jnp.int8),
            v_pages=jnp.zeros((num_layers, 1, 1, 1, 1), jnp.float32),
            cs_pages=jnp.zeros(shape[:-1], jnp.float32),
            page_table=jnp.zeros((batch, max_pages_per_session), jnp.int32),
            lengths=jnp.zeros((batch,), jnp.int32),
            page_size=page_size,
            use_kernel=use_kernel,
            use_ragged=use_ragged,
        )

    @property
    def layer_stacks(self):
        return (self.k_pages, self.cs_pages)

    def with_layer_stacks(self, new_c, new_cs) -> "QuantizedLatentPagedKVCache":
        return self.replace(k_pages=new_c, cs_pages=new_cs)

    def merge_row(self, sub, row) -> "QuantizedLatentPagedKVCache":
        return super().merge_row(sub, row).replace(cs_pages=sub.cs_pages)

    def _scatter_latent(self, layer_state, c_new, q_pos, num_new):
        from .dense import _quantize_kv

        layer_c, layer_cs = layer_state
        b, s, _, d = c_new.shape
        c_q, c_s = _quantize_kv(c_new)  # int8 [B,S,1,D] / f32 [B,S,1]
        phys_page, offset_bs = self._slot_pages(q_pos, num_new)
        if s == 1:
            page = phys_page[:, 0]
            offset = offset_bs[:, 0]

            def body(r, bufs):
                bc, bcs = bufs
                cv = c_q[r, 0][:, None, :]
                sv = c_s[r, 0][:, None]
                return (
                    jax.lax.dynamic_update_slice(
                        bc, cv[None], (page[r], 0, offset[r], 0)
                    ),
                    jax.lax.dynamic_update_slice(
                        bcs, sv[None], (page[r], 0, offset[r])
                    ),
                )

            return jax.lax.fori_loop(0, b, body, (layer_c, layer_cs))
        return self._scatter_planes(
            layer_c, layer_cs, c_q, c_s, phys_page, offset_bs
        )

    @staticmethod
    def _scatter_planes(layer_c, layer_cs, c_q, c_s, phys_page, offset_bs):
        """PRE-QUANTIZED latents ``[B, S, 1, D]`` int8 and scales
        ``[B, S, 1]`` into one layer's pool at ``(page, offset)`` ``[B, S]``
        (:meth:`_slot_pages`' diverted to the null page where not valid)."""
        b, s, _, d = c_q.shape
        flat_page = phys_page.reshape(-1)
        flat_off = offset_bs.reshape(-1)
        return (
            layer_c.at[flat_page, :, flat_off].set(
                c_q.reshape(b * s, 1, d), mode="drop"
            ),
            layer_cs.at[flat_page, :, flat_off].set(
                c_s.reshape(b * s, 1), mode="drop"
            ),
        )

    def _contiguous_view(self, layer_state, batch, dt):
        new_c, new_cs = layer_state
        g = jnp.take(new_c, self.page_table, axis=0).astype(dt)
        sc = jnp.take(new_cs, self.page_table, axis=0).astype(dt)
        return (g * sc[..., None]).transpose(0, 1, 3, 2, 4).reshape(
            batch, self.max_len, 1, self.lat_dim
        )

    def attend(self, layer_state, q, k_new, v_new, rope, q_pos, num_new,
               sliding_window, attention_fn, scale=None):
        new_state = self._scatter_latent(layer_state, k_new, q_pos, num_new)
        if self.use_ragged and q.shape[1] > 1:
            from ..ops.ragged_attention import (
                quantized_latent_ragged_paged_attention,
            )

            out = quantized_latent_ragged_paged_attention(
                q, new_state[0], new_state[1], self.page_table,
                self.lengths + num_new, num_new,
                scale=scale, sliding_window=sliding_window,
            )
            return out, new_state
        if self.use_kernel and q.shape[1] == 1:
            from ..ops.paged_attention import quantized_latent_paged_attention

            out = quantized_latent_paged_attention(
                q, new_state[0], new_state[1], self.page_table,
                self.lengths + num_new,
                scale=scale, sliding_window=sliding_window,
            )
            return out, new_state
        c_all = self._contiguous_view(new_state, q.shape[0], q.dtype)
        mask = self._latent_mask(q.shape[0], q_pos, num_new, sliding_window)
        return attention_fn(q, c_all, c_all, mask, scale=scale), new_state

    # -- write-behind tail ----------------------------------------------------
    #
    # The protocol of ``QuantizedPagedKVCache`` past ``INPLACE_CTX``, with
    # ONE stored plane: through a fused K-step window the pool is a
    # read-only operand, whole (``[L, P, 1, PS, lat_dim]`` in HBM, the layer
    # resolved inside the kernel: no slice, no copy), each row's live pages
    # are swept in place and fetched once (the page is K and V), the step's
    # latent is quantized in the kernel into an io-aliased tail, and the
    # tail is merged into the pool once a window. There is no gathered form
    # under a context threshold and no XLA form: without the kernel the
    # cache has no tail and the engine decodes a token a dispatch.

    @property
    def has_tail(self) -> bool:
        return self.use_kernel

    tail_reads_whole_big = has_tail
    tail_in_kernel = has_tail

    def tail_big_stacks(self):
        return (self.k_pages, self.cs_pages)

    def tail_init(self, k_steps: int):
        if not self.has_tail:
            raise NotImplementedError(
                "the int8 latent cache's tail is in-kernel (use_kernel)"
            )
        l, _, _, _, d = self.k_pages.shape
        b = self.page_table.shape[0]
        return (
            jnp.zeros((l, b, 1, k_steps, d), jnp.int8),
            jnp.zeros((l, b, 1, k_steps), jnp.float32),
        )

    def tail_walk(self, k_steps: int, base_len, num_new):
        """What every :meth:`tail_attend` of a window of ``k_steps`` walks
        (``ops/paged_attention.py:latent_sweep_walk``; ``None`` where the
        pool is swept by copies): a function of the table, of ``base_len``
        and of which rows decode, ``num_new`` positive at the window's first
        step. The model builds it once a window and hands it to every
        layer's call of every step."""
        from ..ops.paged_attention import latent_sweep_walk

        return latent_sweep_walk(
            self.k_pages, k_steps, self.page_table, base_len, num_new
        )

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None, walk=None):
        """NO rope here either (see :meth:`attend`): ``q`` and ``k_new``
        arrive rotated, ``k_new`` in stored form."""
        from ..ops.paged_attention import (
            quantized_latent_paged_fused_attention,
        )

        if sliding_window is not None:
            raise ValueError("latent attention has no sliding window")
        pool_c, pool_cs, lidx = big_state  # whole [L, ...] + layer index
        tail_c, tail_cs = tail_state
        out, tail_c, tail_cs = quantized_latent_paged_fused_attention(
            q, k_new, pool_c, pool_cs, tail_c, tail_cs,
            layer_idx=lidx, step_idx=step_idx,
            page_table=self.page_table, base_len=base_len,
            tail_valid_len=tail_len + num_new,
            q_positions=base_len + tail_len, scale=scale, walk=walk,
        )
        return out, (tail_c, tail_cs)

    def tail_flush(self, tail, tail_len):
        """The window's tail into the pool, pre-quantized: what the pool
        holds afterwards is bit for bit what :meth:`_scatter_latent` writes
        a token at a time."""
        tail_c, tail_cs = tail  # [L, B, 1, K, D] int8 / [L, B, 1, K] f32
        kk = tail_c.shape[3]
        if kk <= self.page_size:
            # The blocked page RMW of the per-head pool (its reasons:
            # ops/paged_attention.py:paged_tail_flush), one plane.
            from ..ops.paged_attention import paged_tail_flush

            new_c, new_cs = paged_tail_flush(
                self.k_pages, self.cs_pages, None, None,
                tail_c, tail_cs, None, None,
                self.page_table, self.lengths, tail_len,
            )
        else:
            q_pos = (
                self.lengths[:, None]
                + jnp.arange(kk, dtype=jnp.int32)[None, :]
            )
            page, off = self._slot_pages(q_pos, tail_len)
            # a layer's tail [B, 1, K, D] / [B, 1, K] -> [B, K, 1, D] / [B, K, 1]
            new_c, new_cs = jax.vmap(
                lambda lc, lcs, tc, tcs: self._scatter_planes(
                    lc, lcs, jnp.swapaxes(tc, 1, 2), jnp.swapaxes(tcs, 1, 2),
                    page, off,
                )
            )(self.k_pages, self.cs_pages, tail_c, tail_cs)
        return self.replace(
            k_pages=new_c, cs_pages=new_cs,
            lengths=self.lengths + tail_len,
        )


# -- a learned selection INSIDE latent attention ---------------------------------
#
# A block with a latent AND a lightning indexer (``ModelConfig.sparse`` beside
# ``ModelConfig.latent``) selects, for each query, ``topk`` of the stored
# latents, and only some of its layers score: a "score" layer has an indexer,
# writes its index key and chooses; a "reuse" layer has none and attends to
# what the nearest scoring layer before it chose for the same query
# (``ModelConfig.index_layers``). Two things follow for the cache.
#
# The index plane ``ik_pages [scoring layers, P, 1, PS, INDEX_DIM]`` has rows
# for the scoring layers ONLY (3 of 9 in the benchmark's cut, 21 of 78 as
# published), in the model's dtype for ``cache/paged.py:_IndexPlane``'s reason.
# WHICH layers score is the class's (``SCORING``), as the index key's width
# is: whoever builds "a cache like this one" from ``k_pages``' shape alone
# (the benchmark's probe) gets the plane with the right rows.
# :func:`indexed_latent_cache_class` makes the class once a (stored form,
# width, scoring layers).
#
# The selection outlives the layer that made it. It rides the cache's own
# per-layer state, which is what the model's scans carry: a segment of the
# stack (``ModelConfig.segments``: runs of layers alike in MLP, attention AND
# part in the selection) takes its view of the cache (:meth:`index_view`),
# whose ``layer_stacks`` hold, beside the latent planes, the index plane (a
# scoring segment's) and ONE row of selection ``sel`` that every layer of the
# view reads or writes (:meth:`stack_rows` says which row of each stack a
# layer owns). A scoring layer writes it, the reusing layers behind read it,
# and :meth:`advance`, the end of a forward pass, drops it. In the fused
# decode scan the same state is the tail's: the index tail beside the latent
# tail, and the step's selection over pool positions and tail slots.


class _LatentIndex(_IndexPlane):
    """What the two indexed latent classes share. ``seg`` (static) is the
    view's part in the selection and the offset from a layer's index to its
    row of the index plane; None is the cache as the engine holds it, where
    every layer is taken to score (a stack without ``index_layers``)."""

    SCORING = None

    @classmethod
    def index_rows(cls, num_layers: int) -> int:
        """Rows for the class's scoring layers (``create`` is the parent
        mixin's: the pool's cache and a zeroed plane, whose ``dtype`` is the
        index keys': the latent's stored form is the pool's own)."""
        scoring = cls.SCORING or (True,) * num_layers
        if len(scoring) != num_layers:
            raise ValueError(
                f"{cls.__name__} is a cache of {len(scoring)} layers, got "
                f"num_layers={num_layers}"
            )
        return sum(scoring)

    # -- the segment's view, and what the model's scans carry ---------------
    def index_view(self, kind: str, delta: int, seq_len: Optional[int] = None):
        """This cache for a segment whose layers all ``kind`` ("score" |
        "reuse") and whose layer ``i`` owns row ``i + delta`` of the index
        plane. ``seq_len``: the dispatch's queries a row, which sizes the
        selection in flight where none is (None: the fused decode scan,
        whose tail carries it)."""
        sel = self.sel
        if sel is None and seq_len is not None:
            shape, dtype = self._sel_form(seq_len)
            sel = jnp.zeros((1, *shape), dtype)
        return self.replace(seg=(kind, int(delta)), sel=sel)

    def _sel_form(self, s: int):
        """Shape and dtype of a dispatch's selection as the attention takes
        it: the ragged kernel's ``[B, T, S, PS]`` int8 (a (page, q-block)
        tile a block), else the mask ``[B, S, T*PS]`` of the gather path."""
        b, t = self.page_table.shape
        if self.use_ragged and s > 1:
            return (b, t, s, self.page_size), jnp.int8
        return (b, s, t * self.page_size), jnp.bool_

    @property
    def _kind(self):
        return None if self.seg is None else self.seg[0]

    @property
    def layer_stacks(self):
        base = self.POOL.layer_stacks.fget(self)
        if self._kind is None:
            return (*base, self.ik_pages)
        if self._kind == "score":
            return (*base, self.ik_pages, self.sel)
        return (*base, self.sel)

    def stack_rows(self, idx):
        """The row of each of :attr:`layer_stacks` that layer ``idx`` owns."""
        lat = (idx,) * len(self.POOL.LAYER_FIELDS)
        if self._kind is None:
            return (*lat, idx)
        if self._kind == "score":
            return (*lat, idx + self.seg[1], 0)
        return (*lat, 0)

    def with_layer_stacks(self, *new):
        n = len(self.POOL.LAYER_FIELDS)
        more = dict(zip(
            {None: ("ik_pages",), "score": ("ik_pages", "sel"),
             "reuse": ("sel",)}[self._kind],
            new[n:],
        ))
        return self.POOL.with_layer_stacks(self, *new[:n]).replace(
            seg=None, **more
        )

    def advance(self, num_new):
        return self.POOL.advance(self, num_new).replace(seg=None, sel=None)

    def _reuses(self) -> None:
        if self._kind != "reuse":
            raise ValueError(
                "a layer without an indexer attends to the selection of a "
                "scoring layer before it: the model hands such a layer the "
                "cache's index_view('reuse', ...)"
            )

    # -- attention ------------------------------------------------------------
    def attend(self, layer_state, q, k_new, v_new, rope, q_pos, num_new,
               sliding_window, attention_fn, scale=None, index=None):
        """The parent's attention under a selection: the layer's own where
        it has an indexer (``index``: its index key is written, the row's
        index keys scored, ``topk`` chosen, and the choice left in the layer
        state for the layers behind), else the one the state carries."""
        n = len(self.POOL.LAYER_FIELDS)
        b, s = q.shape[:2]
        new_lat = self._scatter_latent(
            tuple(layer_state[:n]), k_new, q_pos, num_new
        )
        rest = tuple(layer_state[n:])
        if index is not None:
            nik = self._scatter_index(rest[0], index.k, q_pos, num_new)
            mask = self._select(index, nik, q_pos, num_new)
            if self._sel_form(s)[1] == jnp.int8:
                # [B, S, T*PS] -> a (page, q-block) tile a block
                mask = mask.reshape(b, s, -1, self.page_size).transpose(
                    0, 2, 1, 3
                ).astype(jnp.int8)
            sel, scope = mask, "sparse_attention"
            rest = (nik,) if self._kind is None else (nik, sel)
        else:
            self._reuses()
            sel, scope = rest[-1], "index_reuse/sparse_attention"
        with jax.named_scope(scope):
            if sel.dtype == jnp.int8:
                from ..ops.ragged_attention import (
                    quantized_latent_ragged_paged_attention,
                )

                out = quantized_latent_ragged_paged_attention(
                    q, new_lat[0], new_lat[1], self.page_table,
                    self.lengths + num_new, num_new, scale=scale, select=sel,
                )
            else:
                # the exact form, the CPU's plan, a one-token step of an
                # engine without the tail: the gather path under the mask
                c_all = self._contiguous_view(new_lat, b, q.dtype)
                seen = self._latent_mask(b, q_pos, num_new, sliding_window)
                out = attention_fn(q, c_all, c_all, seen & sel, scale=scale)
        return out, (*new_lat, *rest)


class IndexedLatentPagedKVCache(_LatentIndex, LatentPagedKVCache):
    """:class:`LatentPagedKVCache` under a learned selection. The
    exact-arithmetic form (float32 tests, the int8 class's oracle):
    attention is the gather path under the selection's mask, a token a
    dispatch; the kernels and the write-behind tail are the int8 class's."""

    ik_pages: jax.Array = None
    sel: Optional[jax.Array] = None
    seg: Optional[Tuple[str, int]] = struct.field(
        pytree_node=False, default=None
    )

    POOL, KERNELS = LatentPagedKVCache, False
    SHARED_FIELDS = ("k_pages", "ik_pages")
    PLANE_FIELDS = {"c": "k_pages", "ik": "ik_pages"}


class IndexedQuantizedLatentPagedKVCache(
    _LatentIndex, QuantizedLatentPagedKVCache
):
    """:class:`QuantizedLatentPagedKVCache` under a learned selection. A
    prefill chunk runs the ragged kernel under the selection's mask
    (``sparse_latent_ragged_paged_attention``). The write-behind tail gains
    an index tail ``[scoring layers, B, 1, K, INDEX_DIM]`` and the step's
    selection ``(pool [B, T, 1, PS], tail [B, 1, K])``: a scoring layer
    writes its index key, scores the pool's and the tail's index keys
    together, selects and leaves the selection in the tail state; every
    layer runs the fused one-plane sweep under it
    (``sparse_latent_paged_fused_attention``), and ``tail_flush`` merges the
    index tail where the latent tail goes (``latent_index_tail_flush``)."""

    ik_pages: jax.Array = None
    sel: Optional[jax.Array] = None
    seg: Optional[Tuple[str, int]] = struct.field(
        pytree_node=False, default=None
    )

    POOL, KERNELS = QuantizedLatentPagedKVCache, True
    SHARED_FIELDS = ("k_pages", "cs_pages", "ik_pages")
    PLANE_FIELDS = {"c": "k_pages", "cs": "cs_pages", "ik": "ik_pages"}

    def tail_big_stacks(self):
        return (self.k_pages, self.cs_pages, self.ik_pages)

    def tail_init(self, k_steps: int):
        b, t = self.page_table.shape
        return (
            *super().tail_init(k_steps),
            jnp.zeros(
                (self.ik_pages.shape[0], b, 1, k_steps, self.INDEX_DIM),
                self.ik_pages.dtype,
            ),
            jnp.zeros((b, t, 1, self.page_size), jnp.float32),
            jnp.zeros((b, 1, k_steps), jnp.float32),
        )

    def tail_walk(self, k_steps: int, base_len, num_new):
        """None: the sweep under a selection walks no list yet
        (``ops/paged_attention.py``: ``walked``)."""
        return None

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None, index=None):
        from ..ops.paged_attention import (
            quantized_latent_paged_fused_attention,
        )

        if sliding_window is not None:
            raise ValueError("latent attention has no sliding window")
        pool_c, pool_cs, gik, lidx = big_state    # whole planes + the layer
        tail_c, tail_cs, tik, sel_pool, sel_tail = tail_state
        scope = "index_reuse/sparse_attention"
        if index is not None:
            scope = "sparse_attention"
            row = lidx + (0 if self.seg is None else self.seg[1])
            tik = jax.lax.dynamic_update_slice(
                tik, index.k[None, :, None].astype(tik.dtype),
                (row, 0, 0, step_idx, 0),
            )
            pool_keys = _row_index_keys(
                self.page_table,
                jax.lax.dynamic_index_in_dim(gik, row, keepdims=False),
            )
            tail_keys = jax.lax.dynamic_index_in_dim(
                tik, row, keepdims=False
            )                                             # [B, 1, K, D]
            b, n = pool_keys.shape[:2]
            sel = self._tail_select(
                index, pool_keys, tail_keys, base_len, tail_len, num_new
            ).astype(jnp.float32)
            sel_pool = sel[:, :n].reshape(b, -1, 1, self.page_size)
            sel_tail = sel[:, None, n:]
        else:
            self._reuses()
        with jax.named_scope(scope):
            out, tail_c, tail_cs = quantized_latent_paged_fused_attention(
                q, k_new, pool_c, pool_cs, tail_c, tail_cs,
                layer_idx=lidx, step_idx=step_idx,
                page_table=self.page_table, base_len=base_len,
                tail_valid_len=tail_len + num_new,
                q_positions=base_len + tail_len, scale=scale,
                select=(sel_pool, sel_tail),
            )
        return out, (tail_c, tail_cs, tik, sel_pool, sel_tail)

    def tail_flush(self, tail, tail_len):
        tail_c, tail_cs, tik, _, _ = tail     # tik [scoring, B, 1, K, D]
        kk = tik.shape[3]
        if kk <= self.page_size:
            from ..ops.paged_attention import (
                KERNEL_LATENT_INDEX_FLUSH, paged_tail_flush,
            )

            (nik,) = paged_tail_flush(
                self.ik_pages, None, None, None, tik, None, None, None,
                self.page_table, self.lengths, tail_len,
                name=KERNEL_LATENT_INDEX_FLUSH,
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
        return self.POOL.tail_flush(
            self, (tail_c, tail_cs), tail_len
        ).replace(ik_pages=nik)


@functools.lru_cache(maxsize=None)
def indexed_latent_cache_class(quantized: bool, index_dim: int,
                               scoring: Tuple[bool, ...]):
    """THE indexed latent cache class of a stored form, an index key's width
    and the layers that score (a bool a layer), made once (a class is a
    pytree node type: two engines of one stack must hold the same one)."""
    base = (
        IndexedQuantizedLatentPagedKVCache if quantized
        else IndexedLatentPagedKVCache
    )
    scoring = tuple(bool(x) for x in scoring)
    return type(
        f"{base.__name__}{index_dim}x{sum(scoring)}of{len(scoring)}",
        (base,), {"INDEX_DIM": int(index_dim), "SCORING": scoring},
    )
