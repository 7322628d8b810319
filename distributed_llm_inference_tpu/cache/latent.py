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
latent_ragged_paged_attention`` and ``ops/paged_attention.py:
latent_paged_attention`` are the named entry points the AttentionPlan
selects).

Two consequences shape this module:

* Rope is applied by the MODEL (to the ``k_rope`` slice only, before the
  latent is handed to the cache) — the latent itself is position-free.
  So unlike every other cache, ``attend``/``update_and_gather`` must NOT
  re-apply rope; ``k_new`` arrives in stored form.
* The pool is the serialization format. Stored planes are ``c`` (f32
  ``[lat_dim]`` per token) or ``c``+``cs`` (int8 + per-token f32 scale),
  flowing unchanged through export/ingest/spill/page-ship — the same
  page/refcount/CoW machinery as the parent, via ``PLANE_FIELDS``.

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

    # -- write-behind tail: never used (the engine's tail gate excludes
    # latent caches — the parent's tail re-applies rope, which would
    # corrupt the pre-rotated stored form). Fail loudly if reached.
    def tail_init(self, k_steps: int):
        raise NotImplementedError("latent cache has no write-behind tail")


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
