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

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import causal_mask
from .paged import PagedKVCache

__all__ = ["LatentPagedKVCache", "QuantizedLatentPagedKVCache"]


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

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None):
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
            q_positions=base_len + tail_len, scale=scale,
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
