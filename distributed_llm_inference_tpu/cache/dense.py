"""Dense (contiguous, preallocated) KV cache — bf16 and int8-quantized.

The simplest of the cache policies (dense / paged / sink). Unlike the
reference's ``torch.cat`` growth pattern
(``/root/reference/distributed_llm_inference/models/llama/cache.py:108-109``),
the buffer is preallocated at ``max_seq_len`` and written with per-row
``dynamic_update_slice`` — XLA requires static shapes, and a fixed buffer also
means decode steps always hit the same compiled executable (the role CUDA-graph
capture plays in the reference, ``utils/cuda.py:6``).

Batch rows are independent sessions with their own write offsets
(``lengths``), which is what makes continuous batching possible: the
``generation_id``-keyed dict-of-tensors in the reference
(``models/llama/cache.py:14-19``) becomes integer slot indexing into the batch
dimension.

:class:`QuantizedDenseKVCache` stores K/V as int8 with per-(token, head)
fp32 scales — decode attention reads the whole active KV working set every
step, so halving KV bytes directly buys decode bandwidth (KV traffic
dominates weights at large batch). Dequantization is a broadcast multiply
fused by XLA into the attention operand read; scales ride the layer-state
tuple alongside the value planes (see ``cache/base.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..ops.attention import causal_mask
from ..ops.rotary import RopeAngles, apply_rope
from .base import GatherAttendMixin, flash_prefill_fn


def _tail_flush_rows(big, tail, lengths, tail_len, axis):
    """Merge a write-behind tail into the big buffer at per-row offsets.

    ``big``/``tail``: ``[L, B, …]`` with the time axis (length ``T`` / ``K``)
    at per-row axis ``axis`` (coordinates of the ``[L, …]`` row view; the
    full-array axis is ``axis + 1``, batch being axis 1). A vectorized
    gather+select, chunked over GROUPS of layers: the whole-stack form holds
    two full-cache-sized temps live (shrinks the largest servable batch by
    ~25% in the 7B-in-16GB fit), while per-layer chunking (or per-row
    slice/merge/write-back) pays heavy per-iteration overhead / crashes the
    compiler. ~8-layer slabs keep temps <1/4 of the cache with near-zero
    iteration cost.
    """
    kk = tail.shape[axis + 1]
    b = big.shape[1]
    t = big.shape[axis + 1]
    nd = big.ndim
    src = jnp.arange(t, dtype=jnp.int32)[None, :] - lengths[:, None]  # [B, T]
    sel = (src >= 0) & (src < tail_len[:, None])
    shp = [1] * nd
    shp[1] = b
    shp[axis + 1] = t
    idx = jnp.clip(src, 0, kk - 1).reshape(shp)
    selb = sel.reshape(shp)

    def merge(args):
        big_c, tail_c = args  # [chunk, B, …]
        return jnp.where(
            selb, jnp.take_along_axis(tail_c, idx, axis=axis + 1), big_c
        )

    num_layers = big.shape[0]
    chunk = next((c for c in (8, 4, 2) if num_layers % c == 0), 1)
    if chunk == 1 or num_layers <= chunk:
        return merge((big, tail))
    groups = num_layers // chunk
    gshape = lambda a: (groups, chunk) + a.shape[1:]
    out = jax.lax.map(
        lambda args: merge(args),
        (big.reshape(gshape(big)), tail.reshape(gshape(tail))),
    )
    return out.reshape(big.shape)


def segment_valids(base_len, tail_len, num_new, t, kk, sliding_window):
    """Validity masks ``([B, T], [B, K])`` for the (big, tail) segments of
    the fused decode — shared by the bf16/int8 dense ``tail_attend`` and the
    gathered paged tail so the window/validity rules cannot diverge."""
    q_pos = base_len + tail_len
    big_pos = jnp.arange(t, dtype=jnp.int32)[None, :]
    big_valid = big_pos < base_len[:, None]
    tail_pos = (
        base_len[:, None] + jnp.arange(kk, dtype=jnp.int32)[None, :]
    )
    tail_valid = (
        jnp.arange(kk, dtype=jnp.int32)[None, :]
        < (tail_len + num_new)[:, None]
    )
    if sliding_window is not None:
        big_valid &= big_pos > (q_pos[:, None] - sliding_window)
        tail_valid &= tail_pos > (q_pos[:, None] - sliding_window)
    return big_valid, tail_valid


class _DenseRowsMixin(GatherAttendMixin):
    """Shared row bookkeeping for contiguous per-row caches: absolute
    positions from ``lengths``, bucket-safe writes, causal masking, and
    generic (BATCH_AXES-driven) row slicing."""

    def q_positions(self, seq_len: int) -> jnp.ndarray:
        """Absolute positions of the incoming tokens: ``[B, S]``."""
        return self.lengths[:, None] + jnp.arange(seq_len, dtype=jnp.int32)[None, :]

    def rope_positions(self, seq_len: int, num_new: jnp.ndarray) -> jnp.ndarray:
        """Positions at which incoming queries are rotated (= absolute here;
        the sink cache overrides this with window-relative positions)."""
        return self.q_positions(seq_len)

    def fits(self, num_new) -> jnp.ndarray:
        """Per-row: can ``num_new`` more tokens be appended without overflow?

        The scheduler MUST check this before admitting tokens: past capacity,
        writes are dropped (see ``_write``) and the overflowing tokens
        silently never enter the cache (engine contract).
        """
        return self.lengths + num_new <= self.max_len

    def advance(self, num_new: jnp.ndarray):
        return self.replace(lengths=self.lengths + num_new)

    def reset_rows(self, row_mask: jnp.ndarray):
        """Zero the lengths of rows where ``row_mask`` is True (slot reuse for
        a new session — the analog of a fresh ``generation_id``, reference
        ``models/llama/cache.py:78-84``). Stale k/v need no clearing: validity
        derives from ``lengths``."""
        return self.replace(lengths=jnp.where(row_mask, 0, self.lengths))

    def _fields(self):
        return [
            f.name for f in dataclasses.fields(self)
            if f.metadata.get("pytree_node", True)
        ]

    def select_row(self, row):
        """Batch-1 view of one session row (jit-safe, ``row`` may be traced).
        Used by the engine to prefill a newly admitted session without
        touching (or recomputing over) the other rows."""
        return self.replace(**{
            name: jax.lax.dynamic_slice_in_dim(
                getattr(self, name), row, 1, axis=self.BATCH_AXES[name]
            )
            for name in self._fields()
        })

    def merge_row(self, sub, row):
        return self.replace(**{
            name: jax.lax.dynamic_update_slice_in_dim(
                getattr(self, name), getattr(sub, name), row,
                axis=self.BATCH_AXES[name],
            )
            for name in self._fields()
        })

    def select_rows(self, rows):
        """Compact ``len(rows)``-row view (jit-safe, ``rows`` traced int32
        ``[NR]``). Padding entries use an OUT-OF-RANGE row index: the
        gather clamps them (content irrelevant — their ``num_new = 0``
        prefill never writes) and :meth:`merge_rows` drops their
        write-back. (Padding by DUPLICATING a real row corrupts it: a
        duplicate-index scatter with differing values is undefined-order,
        and the stale pad copy can win over the real row's fresh KV.) The
        batched-admission prefill runs ONE bucketed dispatch over k
        freshly admitted sessions instead of k sequential single-row
        prefills (each a full weight sweep + a host round trip)."""
        def take(name):
            ax = self.BATCH_AXES[name]
            return jnp.take(getattr(self, name), rows, axis=ax, mode="clip")

        return self.replace(**{name: take(name) for name in self._fields()})

    def merge_rows(self, sub, rows):
        """Scatter a :meth:`select_rows` sub-cache back; out-of-range
        (padding) rows drop."""
        def put(name):
            ax = self.BATCH_AXES[name]
            idx = (slice(None),) * ax + (rows,)
            return getattr(self, name).at[idx].set(
                getattr(sub, name), mode="drop"
            )

        return self.replace(**{name: put(name) for name in self._fields()})

    def _write(self, layer_buf, new_vals, num_new):
        """Merge incoming ``[B, S, ...]`` rows into ``[B, T, ...]`` at each
        row's write offset (``lengths``)."""
        b, s = new_vals.shape[:2]
        t = layer_buf.shape[1]
        # the buffer's dtype (a scratch in a page pool's dtype under a model
        # that computes in another rounds as the pool's own write does)
        new_vals = new_vals.astype(layer_buf.dtype)
        if s == 1:
            # Decode hot path: single-token contiguous write. Always in
            # bounds — the scheduler's capacity check guarantees
            # ``lengths + 1 <= max_len`` for active rows — and it partitions
            # cleanly under SPMD (a scatter here ABORTS in GSPMD inside the
            # shard_map pipeline; and the per-row traced offsets make this
            # vmap lower to a serial while over rows on TPU, ~26ms/step at
            # batch 80 7B shapes — the write-behind decode path in
            # ``llama.multi_decode_apply`` exists to keep this off the hot
            # loop).
            # Inactive rows (num_new == 0) must write NOTHING: their offset
            # may sit at a full buffer's end, where the DUS clamp would
            # overwrite the row's last real token (an idle co-batched
            # session would silently corrupt). Re-writing the old value
            # keeps the write unconditional but harmless.
            def write_row(buf, val, start, n):
                start_idx = (start,) + (0,) * (buf.ndim - 1)
                old = jax.lax.dynamic_slice(buf, start_idx, val.shape)
                return jax.lax.dynamic_update_slice(
                    buf, jnp.where(n > 0, val, old), start_idx
                )

            return jax.vmap(write_row)(
                layer_buf, new_vals, self.lengths, num_new
            )
        # Prefill: the chunk is padded to a bucket that may extend past
        # the buffer end (bucket > remaining capacity), where a contiguous
        # dynamic_update_slice would either fail to compile (update wider
        # than operand) or clamp the start offset and silently overwrite
        # earlier tokens. Rebuild the buffer as a gather + select instead
        # (SPMD-friendly, unlike a scatter): buffer position p takes
        # incoming row ``p - lengths`` when that lies in [0, num_new).
        src = (
            jnp.arange(t, dtype=jnp.int32)[None, :] - self.lengths[:, None]
        )  # [B, T]: index into the incoming chunk
        take = (src >= 0) & (src < num_new[:, None])
        extra = new_vals.ndim - 2
        idx = jnp.clip(src, 0, s - 1).reshape(b, t, *([1] * extra))
        sel = take.reshape(b, t, *([1] * extra))
        return jnp.where(
            sel, jnp.take_along_axis(new_vals, idx, axis=1), layer_buf
        )

    def grow_to(self, new_len: int):
        """Zero-pad every layer-stacked buffer's time axis (2) to
        ``new_len`` — the growth-ladder step shared by the engine and the
        block backend."""
        pad = new_len - self.max_len
        if pad <= 0:
            return self

        def grow(a):
            widths = [(0, 0)] * a.ndim
            widths[2] = (0, pad)
            return jnp.pad(a, widths)

        return self.with_layer_stacks(*(grow(a) for a in self.layer_stacks))

    def _mask(self, q, q_pos, num_new, sliding_window):
        t = self.max_len
        kv_pos = jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None, :], (q.shape[0], t)
        )
        kv_valid = kv_pos < (self.lengths + num_new)[:, None]
        return causal_mask(q_pos, kv_pos, kv_valid, sliding_window)

    def _segment_valids(self, base_len, tail_len, num_new, t, kk,
                        sliding_window):
        return segment_valids(base_len, tail_len, num_new, t, kk,
                              sliding_window)


class DenseKVCache(_DenseRowsMixin, struct.PyTreeNode):
    """``k``/``v``: ``[L, B, T, Hkv, D]`` (keys stored rotated); ``lengths``: ``[B]``."""

    k: jax.Array
    v: jax.Array
    lengths: jax.Array

    # Declarative layout for generic consumers (pipeline row slicing, pp
    # sharding specs): field → batch axis; fields with a leading layer axis.
    BATCH_AXES = {"k": 1, "v": 1, "lengths": 0}
    LAYER_FIELDS = ("k", "v")

    @staticmethod
    def create(
        num_layers: int,
        batch: int,
        max_seq_len: int,
        num_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
    ) -> "DenseKVCache":
        shape = (num_layers, batch, max_seq_len, num_kv_heads, head_dim)
        return DenseKVCache(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            lengths=jnp.zeros((batch,), jnp.int32),
        )

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def layer_stacks(self):
        """Per-layer stacks (leading dim = layers) for the model's scan."""
        return (self.k, self.v)

    def with_layer_stacks(self, new_k, new_v) -> "DenseKVCache":
        return self.replace(k=new_k, v=new_v)

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
        """Rotate q/k, write k/v into this layer's buffer, build the mask.

        ``layer_state``: ``(layer_k, layer_v)``, each ``[B, T, Hkv, D]`` (one
        layer's slice, as delivered by ``lax.scan`` over the leading layer
        axis). ``rope`` holds cos/sin precomputed once per block for
        ``q_pos``. Returns ``(q_rot, k_all, v_all, mask, new_layer_state)``.
        """
        layer_k, layer_v = layer_state
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        new_k = self._write(layer_k, k_rot, num_new)
        new_v = self._write(layer_v, v_new, num_new)
        mask = self._mask(q, q_pos, num_new, sliding_window)
        return q_rot, new_k, new_v, mask, (new_k, new_v)

    def ingest_row(self, ks, vs, n_valid):
        """Install ring-prefill KV — ``[L, B, S, Hkv, D]``, keys already
        rotated (``parallel/ring.py:ring_prefill`` output; ``B`` matches this
        cache's batch, 1 for the engine's per-admission sub-cache) — as the
        rows' prefix; ``lengths`` ← ``n_valid`` (scalar or ``[B]``). ``S``
        beyond ``max_len`` is cropped (ring buckets round up past the buffer;
        callers guarantee ``n_valid <= max_len``)."""
        t = self.max_len
        s = ks.shape[2]
        if s >= t:
            k_new, v_new = ks[:, :, :t], vs[:, :, :t]
        else:
            pad = [(0, 0), (0, 0), (0, t - s), (0, 0), (0, 0)]
            k_new, v_new = jnp.pad(ks, pad), jnp.pad(vs, pad)
        lengths = jnp.broadcast_to(
            jnp.asarray(n_valid, jnp.int32), self.lengths.shape
        )
        return self.replace(
            k=k_new.astype(self.k.dtype),
            v=v_new.astype(self.v.dtype),
            lengths=lengths,
        )

    # -- write-behind tail (fused multi-step decode) --------------------------

    def tail_init(self, k_steps: int):
        l, b, t, h, d = self.k.shape
        z = jnp.zeros((l, b, k_steps, h, d), self.k.dtype)
        return (z, z)

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None):
        """Two-segment attention: the big buffer stays read-only; the new
        token's k/v lands in the tail at scalar slot ``step_idx`` (one
        vectorized write — see ``multi_decode_apply``)."""
        from ..ops.attention import gqa_attention_segments

        big_k, big_v = big_state
        tk, tv = tail_state
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        tk = jax.lax.dynamic_update_slice_in_dim(tk, k_rot, step_idx, axis=1)
        tv = jax.lax.dynamic_update_slice_in_dim(tv, v_new, step_idx, axis=1)

        big_valid, tail_valid = self._segment_valids(
            base_len, tail_len, num_new, big_k.shape[1], tk.shape[1],
            sliding_window,
        )
        out = gqa_attention_segments(
            q_rot,
            [(big_k, big_v, big_valid), (tk, tv, tail_valid)],
            scale,
        )
        return out, (tk, tv)

    def tail_flush(self, tail, tail_len):
        """Merge the tail into the big buffers (per-row K-token windows,
        amortized over the K fused steps) and advance lengths."""
        wk, wv = tail  # [L, B, K, Hkv, D]
        return self.replace(
            k=_tail_flush_rows(self.k, wk, self.lengths, tail_len, axis=1),
            v=_tail_flush_rows(self.v, wv, self.lengths, tail_len, axis=1),
            lengths=self.lengths + tail_len,
        )


def _quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(token, head) symmetric int8: ``x`` ``[B, S, H, D]`` →
    ``(q int8 [B, S, H, D], scale f32 [B, S, H])``."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


class QuantizedDenseKVCache(_DenseRowsMixin, struct.PyTreeNode):
    """Dense cache with int8 K/V + per-(token, head) fp32 scales.

    ``k``/``v``: int8 ``[L, B, Hkv, T, D]``; ``ks``/``vs``: f32
    ``[L, B, Hkv, T]`` (≈3% byte overhead at D=128). The layout is
    HEAD-major (time axis 3, unlike the bf16 cache's ``[L, B, T, Hkv, D]``):
    the attention contractions then consume the int8 buffers directly with no
    transpose, which is what lets XLA keep the int8→bf16 convert inside the
    dot instead of materializing a bf16 copy of K and V every decode step.
    The reference's cache is unquantized fp16 torch tensors
    (``models/llama/cache.py``); int8 KV is the TPU-native bandwidth play for
    the decode path, analogous to its bitsandbytes int8 *weights*
    (``utils/model.py:93-123``) applied to the cache instead.
    """

    k: jax.Array
    v: jax.Array
    ks: jax.Array
    vs: jax.Array
    lengths: jax.Array
    # Decode via the Pallas kernel (ops/quant_attention.py): int8 K/V stream
    # through VMEM once instead of XLA materializing bf16 copies each step.
    use_kernel: bool = struct.field(pytree_node=False, default=False)

    BATCH_AXES = {"k": 1, "v": 1, "ks": 1, "vs": 1, "lengths": 0}
    LAYER_FIELDS = ("k", "v", "ks", "vs")

    @staticmethod
    def create(
        num_layers: int,
        batch: int,
        max_seq_len: int,
        num_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,  # accepted for interface parity; values are int8
        use_kernel: bool = False,
    ) -> "QuantizedDenseKVCache":
        shape = (num_layers, batch, num_kv_heads, max_seq_len, head_dim)
        sshape = shape[:-1]
        return QuantizedDenseKVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            ks=jnp.zeros(sshape, jnp.float32),
            vs=jnp.zeros(sshape, jnp.float32),
            lengths=jnp.zeros((batch,), jnp.int32),
            use_kernel=use_kernel,
        )

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    @property
    def _kernel_tail_ok(self) -> bool:
        """Kernel-mode fused tail requires a 32-aligned time axis: the
        io-aliased whole-stack operands cannot be padded (engine buffers
        are always 32-aligned via the window ladder; direct API users with
        odd buffers keep the XLA segments path end to end)."""
        return self.use_kernel and self.max_len % 32 == 0

    @property
    def tail_reads_whole_big(self) -> bool:
        """Fused decode passes the big K/V stacks UNSLICED (plus a layer
        index) so the Pallas kernel reads the cache in place — slicing a
        layer out of the stack to feed a custom call copies it through HBM
        every (layer, step), which measured ~3x decode cost at batch 112."""
        return self._kernel_tail_ok

    @property
    def layer_stacks(self):
        return (self.k, self.v, self.ks, self.vs)

    def with_layer_stacks(self, k, v, ks, vs) -> "QuantizedDenseKVCache":
        return self.replace(k=k, v=v, ks=ks, vs=vs)

    def _write(self, layer_buf, new_vals, num_new):
        """Head-major write: incoming ``[B, S, Hkv(, D)]`` rows merged into
        ``[B, Hkv, T(, D)]`` at each row's offset (cf. the time-major mixin
        version, whose regimes this mirrors)."""
        b, s = new_vals.shape[:2]
        t = layer_buf.shape[2]
        nv = jnp.moveaxis(new_vals, 1, 2)  # [B, Hkv, S(, D)]
        if s == 1:
            # Per-row DUS (see the time-major mixin's notes: scatter aborts
            # under GSPMD; inactive rows re-write the old value so a clamped
            # offset cannot corrupt; the fused multi-step decode keeps this
            # write off the hot path).
            def write_row(buf, val, start, n):
                start_idx = (0, start) + (0,) * (buf.ndim - 2)
                old = jax.lax.dynamic_slice(buf, start_idx, val.shape)
                return jax.lax.dynamic_update_slice(
                    buf, jnp.where(n > 0, val, old), start_idx
                )

            return jax.vmap(write_row)(layer_buf, nv, self.lengths, num_new)
        src = (
            jnp.arange(t, dtype=jnp.int32)[None, :] - self.lengths[:, None]
        )  # [B, T]
        take = (src >= 0) & (src < num_new[:, None])
        extra = nv.ndim - 3  # 1 for k/v (trailing D), 0 for scale planes
        idx = jnp.clip(src, 0, s - 1).reshape(b, 1, t, *([1] * extra))
        sel = take.reshape(b, 1, t, *([1] * extra))
        return jnp.where(
            sel, jnp.take_along_axis(nv, idx, axis=2), layer_buf
        )

    def grow_to(self, new_len: int):
        """Zero-pad the time axis — axis 3 for values AND scale planes in
        the head-major layout."""
        pad = new_len - self.max_len
        if pad <= 0:
            return self

        def grow(a):
            widths = [(0, 0)] * a.ndim
            widths[3] = (0, pad)
            return jnp.pad(a, widths)

        return self.with_layer_stacks(*(grow(a) for a in self.layer_stacks))

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
        """Quantized fast path: int8 K/V feed the attention matmuls directly,
        per-(token, head) scales applied to the scores (see
        :func:`ops.attention.gqa_attention_quantized` — the dequant-multiply
        formulation materializes bf16 K/V copies each step). A non-default
        ``attention_fn`` (Pallas kernels expect bf16 K/V) falls back to the
        dequantizing gather path.

        LONG prefills (S >= ``FLASH_PREFILL_MIN_S``, tiles permitting) also
        take the gather path — through the flash kernel: the int8-score
        formulation materializes [B, Hq, S, T] scores in HBM, which turns
        from noise at S=512 (int8 path 93 ms vs flash 119 for an 8B-shape
        prefill) into the dominant cost at S=2048 (743 vs 593 ms) — flash's
        online softmax never materializes them."""
        from ..ops.attention import gqa_attention, gqa_attention_quantized

        if attention_fn is not gqa_attention:
            return super().attend(
                layer_state, q, k_new, v_new, rope, q_pos, num_new,
                sliding_window, attention_fn, scale,
            )
        # head-major layout: T is axis 2 of the per-layer k plane.
        flash = flash_prefill_fn(
            q.shape[1], layer_state[0].shape[2], attention_fn
        )
        if flash is not None:
            return super().attend(
                layer_state, q, k_new, v_new, rope, q_pos, num_new,
                sliding_window, flash, scale,
            )
        layer_k, layer_v, layer_ks, layer_vs = layer_state
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        k_q, k_s = _quantize_kv(k_rot)
        v_q, v_s = _quantize_kv(v_new)
        new_k = self._write(layer_k, k_q, num_new)
        new_v = self._write(layer_v, v_q, num_new)
        new_ks = self._write(layer_ks, k_s, num_new)
        new_vs = self._write(layer_vs, v_s, num_new)
        if self.use_kernel and q.shape[1] == 1:
            from ..ops.quant_attention import quantized_decode_attention

            out = quantized_decode_attention(
                q_rot, new_k, new_ks, new_v, new_vs,
                self.lengths + num_new, scale, sliding_window,
            )
        else:
            mask = self._mask(q, q_pos, num_new, sliding_window)
            out = gqa_attention_quantized(
                q_rot, new_k, new_ks, new_v, new_vs, mask, scale
            )
        return out, (new_k, new_v, new_ks, new_vs)

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
        """As :meth:`DenseKVCache.update_and_gather`, but values are stored
        int8 and returned DEQUANTIZED and transposed back to time-major
        ``[B, T, Hkv, D]`` (the fallback path for non-default attention fns;
        the default path is :meth:`attend` above)."""
        layer_k, layer_v, layer_ks, layer_vs = layer_state
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)

        k_q, k_s = _quantize_kv(k_rot)
        v_q, v_s = _quantize_kv(v_new)
        new_k = self._write(layer_k, k_q, num_new)
        new_v = self._write(layer_v, v_q, num_new)
        new_ks = self._write(layer_ks, k_s, num_new)
        new_vs = self._write(layer_vs, v_s, num_new)

        dt = q.dtype
        k_all = (new_k.astype(dt) * new_ks[..., None].astype(dt)).transpose(
            0, 2, 1, 3
        )
        v_all = (new_v.astype(dt) * new_vs[..., None].astype(dt)).transpose(
            0, 2, 1, 3
        )
        mask = self._mask(q, q_pos, num_new, sliding_window)
        return q_rot, k_all, v_all, mask, (new_k, new_v, new_ks, new_vs)

    def ingest_row(self, ks, vs, n_valid):
        """Ring-prefill ingest (cf. :meth:`DenseKVCache.ingest_row`):
        quantize the ``[L, B, S, Hkv, D]`` ring KV per (token, head) and lay
        it out head-major."""
        k_q, k_s = _quantize_kv(ks)  # [L, 1, S, H, D] / [L, 1, S, H]
        v_q, v_s = _quantize_kv(vs)
        return self.ingest_planes_row(k_q, v_q, k_s, v_s, n_valid)

    def ingest_planes_row(self, k_q, v_q, k_s, v_s, n_valid):
        """Install ALREADY-quantized time-major planes (int8 values
        ``[L, B, S, Hkv, D]`` + f32 scales ``[L, B, S, Hkv]``) without
        requantizing: disaggregated decode imports the prefill pool's
        STORED planes bit-exact — quantizing a dequantized copy would
        not round-trip."""
        k_q = jnp.moveaxis(jnp.asarray(k_q), 2, 3)  # [L, 1, H, S, D]
        v_q = jnp.moveaxis(jnp.asarray(v_q), 2, 3)
        k_s = jnp.swapaxes(jnp.asarray(k_s), 2, 3)  # [L, 1, H, S]
        v_s = jnp.swapaxes(jnp.asarray(v_s), 2, 3)
        t = self.max_len
        s = k_q.shape[3]

        def fit(a):
            if s >= t:
                return jax.lax.slice_in_dim(a, 0, t, axis=3)
            widths = [(0, 0)] * a.ndim
            widths[3] = (0, t - s)
            return jnp.pad(a, widths)

        return self.replace(
            k=fit(k_q), v=fit(v_q),
            ks=fit(k_s.astype(jnp.float32)), vs=fit(v_s.astype(jnp.float32)),
            lengths=jnp.broadcast_to(
                jnp.asarray(n_valid, jnp.int32), self.lengths.shape
            ),
        )

    # -- write-behind tail (fused multi-step decode) --------------------------

    @property
    def tail_in_kernel(self) -> bool:
        """Kernel mode handles the tail INSIDE the Pallas kernel: the whole
        tail stacks pass through as io-aliased operands (no per-layer
        slicing in the scan), the step's K/V quantize in-kernel, and the
        tail is the final online-softmax tile."""
        return self._kernel_tail_ok

    def tail_init(self, k_steps: int):
        l, b, h, t, d = self.k.shape
        zs = jnp.zeros((l, b, h, k_steps), jnp.float32)
        if self._kernel_tail_ok:
            # Distinct buffers: the fused kernel aliases each tail operand
            # to an output; a shared k/v zeros array cannot be donated twice.
            return (
                jnp.zeros((l, b, h, k_steps, d), jnp.int8),
                jnp.zeros((l, b, h, k_steps, d), jnp.int8),
                zs,
                jnp.zeros((l, b, h, k_steps), jnp.float32),
            )
        zq = jnp.zeros((l, b, h, k_steps, d), jnp.int8)
        return (zq, zq, zs, zs)

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None):
        """Two-segment int8 attention; the big head-major buffer is
        read-only, the new token is quantized into the tail at scalar slot
        ``step_idx``."""
        from ..ops.attention import gqa_attention_quantized_segments

        big_k, big_v, big_ks, big_vs = big_state[:4]
        tk, tv, tks, tvs = tail_state
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        if self._kernel_tail_ok and q.shape[1] == 1:
            # Everything in ONE Pallas call: the step's K/V quantize
            # in-kernel and land in the io-aliased whole-stack tail, and
            # the tail joins the big sweep as the final online-softmax
            # tile. XLA never touches the int8 planes (the XLA-side tail —
            # quantize, 4 update-slices, einsums, merge — measured ~8
            # ms/step at batch 112 under the custom call's layout
            # constraints).
            from ..ops.quant_attention import (
                quantized_fused_decode_attention,
            )

            out, ntk, ntks, ntv, ntvs = quantized_fused_decode_attention(
                q_rot, k_rot, v_new,
                big_k, big_ks, big_v, big_vs,
                tk, tks, tv, tvs,
                layer_idx=big_state[4], step_idx=step_idx,
                base_len=base_len, tail_valid_len=tail_len + num_new,
                q_positions=base_len + tail_len,
                scale=scale, sliding_window=sliding_window,
            )
            return out, (ntk, ntv, ntks, ntvs)
        k_q, k_s = _quantize_kv(k_rot)   # [B, 1, Hkv, D] / [B, 1, Hkv]
        v_q, v_s = _quantize_kv(v_new)
        tk = jax.lax.dynamic_update_slice_in_dim(
            tk, jnp.moveaxis(k_q, 1, 2), step_idx, axis=2
        )
        tv = jax.lax.dynamic_update_slice_in_dim(
            tv, jnp.moveaxis(v_q, 1, 2), step_idx, axis=2
        )
        tks = jax.lax.dynamic_update_slice_in_dim(
            tks, jnp.moveaxis(k_s, 1, 2), step_idx, axis=2
        )
        tvs = jax.lax.dynamic_update_slice_in_dim(
            tvs, jnp.moveaxis(v_s, 1, 2), step_idx, axis=2
        )

        big_valid, tail_valid = self._segment_valids(
            base_len, tail_len, num_new, big_k.shape[2], tk.shape[2],
            sliding_window,
        )
        out = gqa_attention_quantized_segments(
            q_rot,
            [
                (big_k, big_ks, big_v, big_vs, big_valid),
                (tk, tks, tv, tvs, tail_valid),
            ],
            scale,
        )
        return out, (tk, tv, tks, tvs)

    def tail_flush(self, tail, tail_len):
        """Per-row K-token window merge (head-major: time axis 2 of the
        ``[L, Hkv, T(, D)]`` row view)."""
        wk, wv, wks, wvs = tail  # [L, B, Hkv, K, D] / [L, B, Hkv, K]
        if self._kernel_tail_ok:
            # Blocked RMW merge: the XLA where/take rewrite of the whole
            # big buffers costs ~58 ms per fused call at batch 112. (Tiny
            # non-32-multiple buffers keep the XLA path.)
            from ..ops.quant_attention import fused_tail_flush

            nk, nks, nv, nvs = fused_tail_flush(
                self.k, self.ks, self.v, self.vs, wk, wks, wv, wvs,
                self.lengths, tail_len,
            )
            return self.replace(
                k=nk, v=nv, ks=nks, vs=nvs,
                lengths=self.lengths + tail_len,
            )
        merge = lambda big, tl: _tail_flush_rows(
            big, tl, self.lengths, tail_len, axis=2
        )
        return self.replace(
            k=merge(self.k, wk), v=merge(self.v, wv),
            ks=merge(self.ks, wks), vs=merge(self.vs, wvs),
            lengths=self.lengths + tail_len,
        )
