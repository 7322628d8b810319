"""Pallas decode-attention kernel over the int8-quantized dense KV cache.

Why a kernel: the XLA path must feed the attention matmuls bf16 operands, so
the int8 cache is dequantized first — and depending on layout/formulation XLA
can materialize a full bf16 copy of K and V through HBM every step (measured
~13 GB extra per step at batch 80, Llama-7B shapes — more than the entire
ideal step traffic). Here the int8 buffers stream through VMEM exactly once:
scores are computed on the int8 values and the per-(token, head) scales are
applied to the scores (``q·(k·s_t) = s_t·(q·k)``); the v scales fold into the
probs before PV.

Structure follows ``paged_attention.py`` (grid over (batch, time-tiles),
online-softmax scratch carried across the inner axis, VPU multiply-reduce for
MHA / batched ``dot_general`` for GQA); the operand here is the contiguous
HEAD-major ``[B, Hkv, T, D]`` dense buffer instead of a page pool — the same
head-major tile shape the paged pool uses — with time-tiles past the row's
live length clamped to tile 0 so short rows in a long batch fetch one hot
tile instead of the padded span.

This is the decode half of the int8-KV serving mode (the reference's only
deployment optimization is int8 *weights*,
``/root/reference/distributed_llm_inference/utils/model.py:93-123``; int8 KV
is its TPU-native counterpart for the bandwidth-bound decode path). Runs in
interpret mode off-TPU so the CPU test mesh exercises it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _NEG_INF

__all__ = [
    "quantized_decode_attention",
    "quantized_fused_decode_attention",
    "fused_tail_flush",
    "sink_fused_decode_attention",
    "sink_tail_flush",
]


def _qdense_kernel(
    len_ref,    # SMEM [B] int32 (scalar prefetch)
    qpos_ref,   # SMEM [B] int32 (query positions, for the sliding window)
    q_ref,      # [1, Hkv, G, D]
    k_ref,      # [1, Hkv, BT, D] int8
    ks_ref,     # [1, Hkv, BT] f32
    v_ref,      # [1, Hkv, BT, D] int8
    vs_ref,     # [1, Hkv, BT] f32
    out_ref,    # [1, Hkv, G, D]
    acc_ref,    # VMEM [Hkv*G, D] f32
    m_ref,      # VMEM [Hkv*G, 128] f32
    l_ref,      # VMEM [Hkv*G, 128] f32
    *,
    scale: float,
    block_t: int,
    num_blocks: int,
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
    pos = j * block_t + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1)
    valid = pos < kv_len  # decode: causality ≡ slot validity
    if sliding_window is not None:
        valid &= pos > qpos_ref[b] - sliding_window

    q = q_ref[0]                       # [Hkv, G, D]
    k = k_ref[0]                       # [Hkv, BT, D] int8
    ks = ks_ref[0]                     # [Hkv, BT] f32

    if g == 1:
        # MHA: VPU multiply-reduce (1-row MXU matmuls waste the array).
        qv = q[:, 0, :][:, None, :].astype(jnp.float32)      # [Hkv, 1, D]
        s = jnp.sum(k.astype(jnp.float32) * qv, axis=-1)     # [Hkv, BT]
        s = s * ks
    else:
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                    # [Hkv, G, BT]
        s = s * ks[:, None, :]
        s = s.reshape(hkv * g, block_t)
    s = s * scale
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)            # [Hkv*G, BT]

    l_ref[:] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    v = v_ref[0]                       # [Hkv, BT, D] int8
    vs = vs_ref[0]                     # [Hkv, BT] f32
    if g == 1:
        pw = p.reshape(hkv, block_t) * vs                    # [Hkv, BT]
        pv = jnp.sum(pw[:, :, None] * v.astype(jnp.float32), axis=1)
        acc_ref[:] = acc_ref[:] * alpha + pv                 # [Hkv, D]
    else:
        pw = p.reshape(hkv, g, block_t) * vs[:, None, :]
        pv = jax.lax.dot_general(
            pw, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(hkv * g, -1)

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-20)
        out_ref[0] = out.reshape(hkv, g, -1).astype(out_ref.dtype)


def quantized_decode_attention(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    ks: jnp.ndarray,
    v_q: jnp.ndarray,
    vs: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_t: int = 128,
    interpret: Optional[bool] = None,
    q_positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Decode attention straight over the int8 head-major dense cache.

    ``q``: ``[B, 1, Hq, D]`` (already rotated); ``k_q``/``v_q``: int8
    ``[B, Hkv, T, D]`` (keys stored rotated); ``ks``/``vs``: f32
    ``[B, Hkv, T]`` per-(token, head) scales; ``kv_lengths``: ``[B]`` live kv
    count per row *including* tokens written this step. Returns
    ``[B, 1, Hq, D]`` in q's dtype.

    ``q_positions`` (``[B]``, default ``kv_lengths - 1``): the absolute
    position of each row's query, which anchors the sliding window.
    """
    b, s, hq, d = q.shape
    if s != 1:
        raise ValueError(f"decode-only kernel (S=1), got S={s}")
    hkv, t = k_q.shape[1], k_q.shape[2]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if q_positions is None:
        q_positions = kv_lengths - 1
    bt = min(block_t, t)
    num_blocks = -(-t // bt)
    if t % bt:
        pad = num_blocks * bt - t
        k_q = jnp.pad(k_q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_q = jnp.pad(v_q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad)))

    qr = q.reshape(b, hkv, g, d)

    def _tile_index(bi, ji, lens, qpos):
        # Tiles past the row's live span clamp to tile 0 (one hot fetch).
        live = ji * bt < lens[bi]
        return (bi, 0, jnp.where(live, ji, 0), 0)

    def _tile_index3(bi, ji, lens, qpos):
        live = ji * bt < lens[bi]
        return (bi, 0, jnp.where(live, ji, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, num_blocks),
        in_specs=[
            pl.BlockSpec(
                (1, hkv, g, d), lambda bi, ji, lens, qpos: (bi, 0, 0, 0)
            ),
            pl.BlockSpec((1, hkv, bt, d), _tile_index),
            pl.BlockSpec((1, hkv, bt), _tile_index3),
            pl.BlockSpec((1, hkv, bt, d), _tile_index),
            pl.BlockSpec((1, hkv, bt), _tile_index3),
        ],
        out_specs=pl.BlockSpec(
            (1, hkv, g, d), lambda bi, ji, lens, qpos: (bi, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv * g, d), jnp.float32),
            pltpu.VMEM((hkv * g, 128), jnp.float32),
            pltpu.VMEM((hkv * g, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _qdense_kernel,
        scale=scale,
        block_t=bt,
        num_blocks=num_blocks,
        sliding_window=sliding_window,
        hkv=hkv,
        g=g,
    )
    out = pl.pallas_call(
        kernel,
        name="quantized_decode_attention",
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(kv_lengths.astype(jnp.int32), q_positions.astype(jnp.int32),
      qr, k_q, ks, v_q, vs)
    return out.reshape(b, 1, hq, d)


def quantized_fused_decode_attention(
    q: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    big_k: jnp.ndarray,
    big_ks: jnp.ndarray,
    big_v: jnp.ndarray,
    big_vs: jnp.ndarray,
    tail_k: jnp.ndarray,
    tail_ks: jnp.ndarray,
    tail_v: jnp.ndarray,
    tail_vs: jnp.ndarray,
    layer_idx: jnp.ndarray,
    step_idx: jnp.ndarray,
    base_len: jnp.ndarray,
    tail_valid_len: jnp.ndarray,
    q_positions: jnp.ndarray,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    # 256 swallows short-context buffers in ONE time block (the 2-block
    # split at T=160 measured ~8% slower: the second, mostly-clamped tile
    # still pays a full grid step); longer buffers tile at 256 and keep the
    # short-row clamp optimization.
    block_t: int = 256,
    block_b: int = 8,
    interpret: Optional[bool] = None,
):
    """ONE kernel for a whole fused-decode attention step: quantizes the
    step's fresh K/V, writes them into the write-behind tail IN PLACE
    (io-aliased whole-stack tail operands), and runs the joint softmax over
    the read-only big segment plus the updated tail — the tail is simply the
    final online-softmax tile.

    Why: with the tail handled in XLA around a big-segment-only kernel, the
    quantize + four dynamic-update-slices + tail einsums + stats merge cost
    ~8 ms/step at batch 112 (Llama-7B shapes) — more than the big segment's
    entire byte cost — because the custom call's layout constraints de-fuse
    and re-layout every tail op. In-kernel, the tail round-trips VMEM once
    per (layer, step) (~0.5 MB/row-block) and XLA never touches the int8
    planes at all.

    Shapes: ``q`` ``[B, 1, Hq, D]`` (rotated); ``k_new``/``v_new``
    ``[B, 1, Hkv, D]`` (k rotated); big stacks ``[L, B, Hkv, T, D]`` (+
    ``[L, B, Hkv, T]`` scales); tail stacks ``[L, B, Hkv, KT, D]`` (+
    scales). Scalars: ``layer_idx``/``step_idx`` traced ints; ``base_len``
    ``[B]`` live big-segment length; ``tail_valid_len`` ``[B]`` =
    ``tail_len + num_new`` (valid tail slots AFTER this write — a finished
    row keeps its shorter span, so its slot-``step_idx`` garbage write is
    never read); ``q_positions`` ``[B]`` = ``base_len + tail_len`` anchors
    the sliding window.

    Returns ``(out [B, 1, Hq, D], tail_k', tail_ks', tail_v', tail_vs')``
    with the tail outputs aliased to the inputs (callers must treat the
    inputs as consumed).
    """
    b, s, hq, d = q.shape
    if s != 1:
        raise ValueError(f"decode-only kernel (S=1), got S={s}")
    num_l, _, hkv, t, _ = big_k.shape
    kt = tail_k.shape[3]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if t % 32 and not interpret:
        # The io-aliased whole-stack operands cannot pad on TPU, so the
        # time axis must sit on an int8 sublane boundary; callers
        # (tail_attend) gate on max_len % 32 == 0 and keep the XLA
        # segments path for odd buffers. 32-aligned t keeps the r3 tiling
        # UNCHANGED — min(block_t, t) blocks with a partial (32-aligned)
        # last tile, which Mosaic handles and which the perf record is
        # built on. (An r4 attempt to force bt to a divisor of t regressed
        # 1k-ctx decode 4.6x — bt=96 tiles — and broke kernels whose
        # forced bt fell below the 128-lane scale-plane block at other
        # buffer lengths.)
        raise ValueError(
            f"big-buffer length {t} must be a multiple of 32 on TPU"
        )
    bt = min(block_t, t)
    num_blocks = -(-t // bt)
    # The io-aliased tail stacks cannot be batch-padded, so the row block
    # must DIVIDE the batch: largest divisor <= block_b (worst case 1).
    nb = next(n for n in range(min(block_b, b), 0, -1) if b % n == 0)
    num_row_blocks = b // nb

    qr = q.reshape(b, hkv, g, d)
    knr = jnp.moveaxis(k_new, 1, 2)  # [B, Hkv, 1, D]
    vnr = jnp.moveaxis(v_new, 1, 2)
    lref = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    sref = jnp.asarray(step_idx, jnp.int32).reshape(1)

    def _row_live(bi, ji, lens):
        live = ji * bt < lens[bi * nb]
        for r in range(1, nb):
            live |= ji * bt < lens[bi * nb + r]
        return live

    def _big_index(bi, ji, lidx, step, lens, vlen, qpos):
        return (lidx[0], bi, 0,
                jnp.where(_row_live(bi, ji, lens), ji, 0), 0)

    def _big_index3(bi, ji, lidx, step, lens, vlen, qpos):
        return (lidx[0], bi, 0, jnp.where(_row_live(bi, ji, lens), ji, 0))

    def _tail_index(bi, ji, lidx, step, lens, vlen, qpos):
        return (lidx[0], bi, 0, 0, 0)

    def _tail_index3(bi, ji, lidx, step, lens, vlen, qpos):
        return (lidx[0], bi, 0, 0)

    def _row_index(bi, ji, lidx, step, lens, vlen, qpos):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(num_row_blocks, num_blocks),
        in_specs=[
            pl.BlockSpec((nb, hkv, g, d), _row_index),
            pl.BlockSpec((nb, hkv, 1, d), _row_index),
            pl.BlockSpec((nb, hkv, 1, d), _row_index),
            pl.BlockSpec((1, nb, hkv, bt, d), _big_index),
            pl.BlockSpec((1, nb, hkv, bt), _big_index3),
            pl.BlockSpec((1, nb, hkv, bt, d), _big_index),
            pl.BlockSpec((1, nb, hkv, bt), _big_index3),
            pl.BlockSpec((1, nb, hkv, kt, d), _tail_index),
            pl.BlockSpec((1, nb, hkv, kt), _tail_index3),
            pl.BlockSpec((1, nb, hkv, kt, d), _tail_index),
            pl.BlockSpec((1, nb, hkv, kt), _tail_index3),
        ],
        out_specs=(
            pl.BlockSpec((nb, hkv, g, d), _row_index),
            pl.BlockSpec((1, nb, hkv, kt, d), _tail_index),
            pl.BlockSpec((1, nb, hkv, kt), _tail_index3),
            pl.BlockSpec((1, nb, hkv, kt, d), _tail_index),
            pl.BlockSpec((1, nb, hkv, kt), _tail_index3),
        ),
        scratch_shapes=[
            pltpu.VMEM((nb, hkv * g, d), jnp.float32),
            pltpu.VMEM((nb, hkv * g, 128), jnp.float32),
            pltpu.VMEM((nb, hkv * g, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _qfused_kernel,
        scale=scale,
        block_t=bt,
        num_blocks=num_blocks,
        sliding_window=sliding_window,
        hkv=hkv,
        g=g,
        nb=nb,
        kt=kt,
    )
    out, tk, tks, tv, tvs = pl.pallas_call(
        kernel,
        name="quantized_fused_decode_attention",
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct(tail_k.shape, tail_k.dtype),
            jax.ShapeDtypeStruct(tail_ks.shape, tail_ks.dtype),
            jax.ShapeDtypeStruct(tail_v.shape, tail_v.dtype),
            jax.ShapeDtypeStruct(tail_vs.shape, tail_vs.dtype),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        # Tail stacks update in place; indices count every flattened input
        # including the 5 scalar-prefetch operands.
        input_output_aliases={12: 1, 13: 2, 14: 3, 15: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(lref, sref, base_len.astype(jnp.int32),
      tail_valid_len.astype(jnp.int32), q_positions.astype(jnp.int32),
      qr, knr, vnr, big_k, big_ks, big_v, big_vs,
      tail_k, tail_ks, tail_v, tail_vs)
    return out.reshape(b, 1, hq, d), tk, tks, tv, tvs


def _qfused_kernel(
    lidx_ref,   # SMEM [1] int32 (layer; consumed by index maps)
    step_ref,   # SMEM [1] int32 (tail write slot)
    len_ref,    # SMEM [B] int32 (big live length)
    vlen_ref,   # SMEM [B] int32 (valid tail slots incl. this write)
    qpos_ref,   # SMEM [B] int32 (query positions)
    q_ref,      # [NB, Hkv, G, D]
    kn_ref,     # [NB, Hkv, 1, D] (rotated, unquantized)
    vn_ref,     # [NB, Hkv, 1, D]
    k_ref,      # [1, NB, Hkv, BT, D] int8
    ks_ref,     # [1, NB, Hkv, BT] f32
    v_ref,      # [1, NB, Hkv, BT, D] int8
    vs_ref,     # [1, NB, Hkv, BT] f32
    tk_ref,     # [1, NB, Hkv, KT, D] int8 (in)
    tks_ref,    # [1, NB, Hkv, KT] f32 (in)
    tv_ref,     # [1, NB, Hkv, KT, D] int8 (in)
    tvs_ref,    # [1, NB, Hkv, KT] f32 (in)
    out_ref,    # [NB, Hkv, G, D]
    tk_out,     # aliased tail outputs
    tks_out,
    tv_out,
    tvs_out,
    acc_ref,    # VMEM [NB, Hkv*G, D] f32
    m_ref,      # VMEM [NB, Hkv*G, 128] f32
    l_ref,      # VMEM [NB, Hkv*G, 128] f32
    *,
    scale: float,
    block_t: int,
    num_blocks: int,
    sliding_window: Optional[int],
    hkv: int,
    g: int,
    nb: int,
    kt: int,
):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[:]                               # [NB, Hkv, G, D]

    def _accumulate(s, valid):
        """One online-softmax tile: scores ``s`` [NB, Hkv*G, W] masked by
        ``valid`` [NB, 1, W]; returns probs for the PV accumulation."""
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        return p, alpha

    def _big_tile():
        pos = j * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_t), 1
        )
        row_valids = []
        for r in range(nb):
            vr = pos < len_ref[bi * nb + r]
            if sliding_window is not None:
                vr &= pos > qpos_ref[bi * nb + r] - sliding_window
            row_valids.append(vr)
        valid = jnp.stack(row_valids)          # [NB, 1, BT]

        k = k_ref[0]                           # [NB, Hkv, BT, D] int8
        ks = ks_ref[0]
        s = jax.lax.dot_general(
            q.astype(jnp.bfloat16).reshape(nb * hkv, g, -1),
            k.astype(jnp.bfloat16).reshape(nb * hkv, block_t, -1),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(nb, hkv, g, block_t)
        s = (s * ks[:, :, None, :] * scale).reshape(nb, hkv * g, block_t)
        p, alpha = _accumulate(s, valid)

        v = v_ref[0]
        vs = vs_ref[0]
        pw = p.reshape(nb, hkv, g, block_t) * vs[:, :, None, :]
        pv = jax.lax.dot_general(
            pw.astype(jnp.bfloat16).reshape(nb * hkv, g, block_t),
            v.astype(jnp.bfloat16).reshape(nb * hkv, block_t, -1),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(nb, hkv * g, -1)

    _big_tile()

    @pl.when(j == num_blocks - 1)
    def _tail_tile():
        step = step_ref[0]
        # Quantize this step's K/V (must match cache._quantize_kv: symmetric
        # per-(token, head) absmax int8 with a 1e-8 floor and RNE rounding).
        kn = kn_ref[:].astype(jnp.float32)     # [NB, Hkv, 1, D]
        vn = vn_ref[:].astype(jnp.float32)
        ksc = jnp.maximum(jnp.max(jnp.abs(kn), axis=-1), 1e-8) / 127.0
        vsc = jnp.maximum(jnp.max(jnp.abs(vn), axis=-1), 1e-8) / 127.0
        kq = jnp.clip(jnp.round(kn / ksc[..., None]), -127, 127).astype(
            jnp.int8
        )
        vq = jnp.clip(jnp.round(vn / vsc[..., None]), -127, 127).astype(
            jnp.int8
        )

        # Two iotas, not ``hit4[..., 0]``: Mosaic (jax 0.9.0) refuses the
        # squeeze of a mask's lane dim ("Invalid vector register cast").
        hit4 = jax.lax.broadcasted_iota(jnp.int32, (1, 1, kt, 1), 2) == step
        hit3 = jax.lax.broadcasted_iota(jnp.int32, (1, 1, kt), 2) == step
        tk = jnp.where(hit4, kq, tk_ref[0])    # [NB, Hkv, KT, D]
        tv = jnp.where(hit4, vq, tv_ref[0])
        tks = jnp.where(hit3, ksc, tks_ref[0])  # [NB, Hkv, KT]
        tvs = jnp.where(hit3, vsc, tvs_ref[0])
        tk_out[0] = tk
        tv_out[0] = tv
        tks_out[0] = tks
        tvs_out[0] = tvs

        pos1 = jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1)
        row_valids = []
        for r in range(nb):
            row = bi * nb + r
            vr = pos1 < vlen_ref[row]
            if sliding_window is not None:
                tail_pos = len_ref[row] + pos1
                vr &= tail_pos > qpos_ref[row] - sliding_window
            row_valids.append(vr)
        valid = jnp.stack(row_valids)          # [NB, 1, KT]

        s = jax.lax.dot_general(
            q.astype(jnp.bfloat16).reshape(nb * hkv, g, -1),
            tk.astype(jnp.bfloat16).reshape(nb * hkv, kt, -1),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(nb, hkv, g, kt)
        s = (s * tks[:, :, None, :] * scale).reshape(nb, hkv * g, kt)
        p, alpha = _accumulate(s, valid)

        pw = p.reshape(nb, hkv, g, kt) * tvs[:, :, None, :]
        pv = jax.lax.dot_general(
            pw.astype(jnp.bfloat16).reshape(nb * hkv, g, kt),
            tv.astype(jnp.bfloat16).reshape(nb * hkv, kt, -1),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(nb, hkv * g, -1)

        l = l_ref[:, :, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-20)
        out_ref[:] = out.reshape(nb, hkv, g, -1).astype(out_ref.dtype)

def fused_tail_flush(
    big_k: jnp.ndarray,
    big_ks: jnp.ndarray,
    big_v: jnp.ndarray,
    big_vs: jnp.ndarray,
    tail_k: jnp.ndarray,
    tail_ks: jnp.ndarray,
    tail_v: jnp.ndarray,
    tail_vs: jnp.ndarray,
    base_len: jnp.ndarray,
    tail_len: jnp.ndarray,
    interpret: Optional[bool] = None,
):
    """Merge the write-behind tail into the big head-major buffers by
    read-modify-writing only the 32-token-aligned blocks each row's window
    touches.

    The XLA formulation (where/take_along_axis over the whole time axis)
    re-reads AND re-writes every byte of the big buffers to place KT tokens
    per row — measured ~58 ms per fused-16-step call at batch 112
    (3.7 ms/step, a quarter of the attention itself); per-row
    ``dynamic_update_slice`` lowers to a serial loop, ``lax.scatter``
    aborts under GSPMD, and raw DMAs at per-row offsets fail Mosaic's
    tile-divisibility rule. Here each (layer, row) round-trips two
    32-token value blocks (and two 128-slot scale blocks) through VMEM,
    composing the tail in with POSITION-based masks: a row whose window
    fits one block has both grid steps clamp to the same block index and
    compose identical content, so the duplicate write is idempotent.

    ``tail_len`` may be any value in ``[0, KT]`` per row (masks cover
    partial and empty tails, and edge rows whose window would run past the
    buffer write only their live slots). Returns the four updated big
    buffers (inputs are consumed — aliased).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    num_l, b, hkv, t, d = big_k.shape
    kt = tail_k.shape[3]
    BV = 32    # value-plane block width (int8 sublane tile multiple)
    BS = 128   # scale-plane block width (f32 lane tile)
    nbv = t // BV
    nbs = -(-t // BS)
    # A KT-token window starting anywhere touches at most ceil(KT/BV)+1
    # value blocks (and fewer scale blocks — their visits clamp and the
    # position-based compose is idempotent, so extra visits are no-ops).
    nj = -(-kt // BV) + 1

    def _vidx(li, bi, ji, lens, tl):
        blk = jnp.minimum(lens[bi] // BV + ji, nbv - 1)
        return (li, bi, 0, blk, 0)

    def _sidx(li, bi, ji, lens, tl):
        blk = jnp.minimum(lens[bi] // BS + ji, nbs - 1)
        return (li, bi, 0, blk)

    def _tidx(li, bi, ji, lens, tl):
        return (li, bi, 0, 0, 0)

    def _tidx3(li, bi, ji, lens, tl):
        return (li, bi, 0, 0)

    def kernel(lens_ref, tl_ref,
               tk, tks, tv, tvs,
               bk_in, bks_in, bv_in, bvs_in,
               bk_out, bks_out, bv_out, bvs_out):
        bi = pl.program_id(1)
        ji = pl.program_id(2)
        start = lens_ref[bi]
        tl = tl_ref[bi]

        def compose_values(big_ref, tail_ref, out_ref):
            blk = jnp.minimum(start // BV + ji, nbv - 1)
            pos = blk * BV + jax.lax.broadcasted_iota(
                jnp.int32, (1, BV, 1), 1
            )
            cur = big_ref[0, 0]                        # [Hkv, BV, D]
            tail = tail_ref[0, 0]                      # [Hkv, KT, D]
            for i in range(kt):
                hit = (pos == start + i) & (i < tl)
                cur = jnp.where(hit, tail[:, i : i + 1], cur)
            out_ref[0, 0] = cur

        def compose_scales(big_ref, tail_ref, out_ref):
            blk = jnp.minimum(start // BS + ji, nbs - 1)
            pos = blk * BS + jax.lax.broadcasted_iota(
                jnp.int32, (1, BS), 1
            )
            cur = big_ref[0, 0]                        # [Hkv, BS]
            tail = tail_ref[0, 0]                      # [Hkv, KT]
            for i in range(kt):
                hit = (pos == start + i) & (i < tl)
                cur = jnp.where(hit, tail[:, i : i + 1], cur)
            out_ref[0, 0] = cur

        compose_values(bk_in, tk, bk_out)
        compose_values(bv_in, tv, bv_out)
        compose_scales(bks_in, tks, bks_out)
        compose_scales(bvs_in, tvs, bvs_out)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_l, b, nj),
        in_specs=[
            pl.BlockSpec((1, 1, hkv, kt, d), _tidx),
            pl.BlockSpec((1, 1, hkv, kt), _tidx3),
            pl.BlockSpec((1, 1, hkv, kt, d), _tidx),
            pl.BlockSpec((1, 1, hkv, kt), _tidx3),
            pl.BlockSpec((1, 1, hkv, BV, d), _vidx),
            pl.BlockSpec((1, 1, hkv, BS), _sidx),
            pl.BlockSpec((1, 1, hkv, BV, d), _vidx),
            pl.BlockSpec((1, 1, hkv, BS), _sidx),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, hkv, BV, d), _vidx),
            pl.BlockSpec((1, 1, hkv, BS), _sidx),
            pl.BlockSpec((1, 1, hkv, BV, d), _vidx),
            pl.BlockSpec((1, 1, hkv, BS), _sidx),
        ),
        scratch_shapes=[],
    )
    return pl.pallas_call(
        kernel,
        name="fused_tail_flush",
        out_shape=(
            jax.ShapeDtypeStruct(big_k.shape, big_k.dtype),
            jax.ShapeDtypeStruct(big_ks.shape, big_ks.dtype),
            jax.ShapeDtypeStruct(big_v.shape, big_v.dtype),
            jax.ShapeDtypeStruct(big_vs.shape, big_vs.dtype),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        # Inputs counting scalars: lens 0, tl 1, tails 2-5, bigs 6-9.
        input_output_aliases={6: 0, 7: 1, 8: 2, 9: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(base_len.astype(jnp.int32), tail_len.astype(jnp.int32),
      tail_k, tail_ks, tail_v, tail_vs,
      big_k, big_ks, big_v, big_vs)


def sink_fused_decode_attention(
    q: jnp.ndarray,
    q_sink: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    big_k: jnp.ndarray,
    big_ks: jnp.ndarray,
    big_v: jnp.ndarray,
    big_vs: jnp.ndarray,
    sink_k: jnp.ndarray,
    sink_ks: jnp.ndarray,
    sink_v: jnp.ndarray,
    sink_vs: jnp.ndarray,
    tail_k: jnp.ndarray,
    tail_ks: jnp.ndarray,
    tail_v: jnp.ndarray,
    tail_vs: jnp.ndarray,
    layer_idx: jnp.ndarray,
    step_idx: jnp.ndarray,
    ring_len: jnp.ndarray,
    ring_ptr: jnp.ndarray,
    evict_len: jnp.ndarray,
    sink_len: jnp.ndarray,
    tail_valid_len: jnp.ndarray,
    ring_slots: int,
    scale: Optional[float] = None,
    block_t: int = 256,
    block_b: int = 8,
    interpret: Optional[bool] = None,
):
    """The fused decode step over the QUANTIZED SINK cache: one kernel per
    (layer, step) sweeping three joint-softmax segments — the int8 ring of
    recent tokens, the int8 attention sinks, and the write-behind tail the
    step's fresh K/V is quantized into in place.

    Position design (see ``cache/sink.py:QuantizedSinkKVCache``): RoPE
    scores depend only on position DIFFERENCES, so ring keys are stored
    rotated at their ABSOLUTE stream positions (write-once — the per-step
    whole-window re-rotation of the bf16 ring, the reference's
    ``cache.py:111-133`` re-rotation chain, disappears) and ``q`` is rotated
    at the absolute query position. Only the handful of sink tokens need the
    StreamingLLM compressed positions: they are stored rotated at their
    fixed slots ``0..s-1`` and attended with ``q_sink``, the same query
    rotated at its window-relative position.

    Ring validity: live slots are the prefix ``[0, ring_len)``; of those,
    the ``evict_len`` slots starting at ``ring_ptr`` (mod ``ring_slots``)
    hold tokens the in-flight tail has already evicted (exact per-step
    StreamingLLM window semantics, ahead of the physical overwrite at
    flush). ``evict_len`` = this step's tail length; callers guarantee
    the tail never exceeds the ring span (engine guard).

    Shapes: ``q``/``q_sink`` ``[B, 1, Hq, D]``; ``k_new``/``v_new``
    ``[B, 1, Hkv, D]`` (k abs-rotated); big stacks ``[L, B, Hkv, TR, D]``
    (+ scales, TR = padded ring span); sink stacks ``[L, B, Hkv, SP, D]``
    (+ scales); tail stacks ``[L, B, Hkv, KT, D]`` (+ scales, io-aliased).
    Returns ``(out, tail_k', tail_ks', tail_v', tail_vs')``.
    """
    b, s, hq, d = q.shape
    if s != 1:
        raise ValueError(f"decode-only kernel (S=1), got S={s}")
    num_l, _, hkv, t, _ = big_k.shape
    kt = tail_k.shape[3]
    sp = sink_k.shape[3]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Largest 32-multiple divisor of TR (caches pad TR to a 32 multiple) so
    # tiles never straddle the buffer end; fall back to 32 (always a
    # divisor) rather than a whole-axis tile.
    bt = 32
    for cand in range(min(block_t, t), 31, -32):
        if t % cand == 0:
            bt = cand
            break
    num_blocks = t // bt
    nb = next(n for n in range(min(block_b, b), 0, -1) if b % n == 0)
    num_row_blocks = b // nb

    qr = q.reshape(b, hkv, g, d)
    qsr = q_sink.reshape(b, hkv, g, d)
    knr = jnp.moveaxis(k_new, 1, 2)  # [B, Hkv, 1, D]
    vnr = jnp.moveaxis(v_new, 1, 2)
    lref = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    sref = jnp.asarray(step_idx, jnp.int32).reshape(1)

    def _row_live(bi, ji, lens):
        live = ji * bt < lens[bi * nb]
        for r in range(1, nb):
            live |= ji * bt < lens[bi * nb + r]
        return live

    def _big_index(bi, ji, lidx, step, lens, ptr, ev, slen, vlen):
        return (lidx[0], bi, 0,
                jnp.where(_row_live(bi, ji, lens), ji, 0), 0)

    def _big_index3(bi, ji, lidx, step, lens, ptr, ev, slen, vlen):
        return (lidx[0], bi, 0, jnp.where(_row_live(bi, ji, lens), ji, 0))

    def _lay_index(bi, ji, lidx, step, lens, ptr, ev, slen, vlen):
        return (lidx[0], bi, 0, 0, 0)

    def _lay_index3(bi, ji, lidx, step, lens, ptr, ev, slen, vlen):
        return (lidx[0], bi, 0, 0)

    def _row_index(bi, ji, lidx, step, lens, ptr, ev, slen, vlen):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(num_row_blocks, num_blocks),
        in_specs=[
            pl.BlockSpec((nb, hkv, g, d), _row_index),
            pl.BlockSpec((nb, hkv, g, d), _row_index),
            pl.BlockSpec((nb, hkv, 1, d), _row_index),
            pl.BlockSpec((nb, hkv, 1, d), _row_index),
            pl.BlockSpec((1, nb, hkv, bt, d), _big_index),
            pl.BlockSpec((1, nb, hkv, bt), _big_index3),
            pl.BlockSpec((1, nb, hkv, bt, d), _big_index),
            pl.BlockSpec((1, nb, hkv, bt), _big_index3),
            pl.BlockSpec((1, nb, hkv, sp, d), _lay_index),
            pl.BlockSpec((1, nb, hkv, sp), _lay_index3),
            pl.BlockSpec((1, nb, hkv, sp, d), _lay_index),
            pl.BlockSpec((1, nb, hkv, sp), _lay_index3),
            pl.BlockSpec((1, nb, hkv, kt, d), _lay_index),
            pl.BlockSpec((1, nb, hkv, kt), _lay_index3),
            pl.BlockSpec((1, nb, hkv, kt, d), _lay_index),
            pl.BlockSpec((1, nb, hkv, kt), _lay_index3),
        ],
        out_specs=(
            pl.BlockSpec((nb, hkv, g, d), _row_index),
            pl.BlockSpec((1, nb, hkv, kt, d), _lay_index),
            pl.BlockSpec((1, nb, hkv, kt), _lay_index3),
            pl.BlockSpec((1, nb, hkv, kt, d), _lay_index),
            pl.BlockSpec((1, nb, hkv, kt), _lay_index3),
        ),
        scratch_shapes=[
            pltpu.VMEM((nb, hkv * g, d), jnp.float32),
            pltpu.VMEM((nb, hkv * g, 128), jnp.float32),
            pltpu.VMEM((nb, hkv * g, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _qsink_kernel,
        scale=scale,
        block_t=bt,
        num_blocks=num_blocks,
        ring_slots=ring_slots,
        hkv=hkv,
        g=g,
        nb=nb,
        sp=sp,
        kt=kt,
    )
    out, tk, tks, tv, tvs = pl.pallas_call(
        kernel,
        name="sink_fused_decode_attention",
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct(tail_k.shape, tail_k.dtype),
            jax.ShapeDtypeStruct(tail_ks.shape, tail_ks.dtype),
            jax.ShapeDtypeStruct(tail_v.shape, tail_v.dtype),
            jax.ShapeDtypeStruct(tail_vs.shape, tail_vs.dtype),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        # Tail stacks update in place; indices count every flattened input
        # including the 7 scalar-prefetch operands.
        input_output_aliases={19: 1, 20: 2, 21: 3, 22: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(lref, sref, ring_len.astype(jnp.int32), ring_ptr.astype(jnp.int32),
      evict_len.astype(jnp.int32), sink_len.astype(jnp.int32),
      tail_valid_len.astype(jnp.int32),
      qr, qsr, knr, vnr,
      big_k, big_ks, big_v, big_vs,
      sink_k, sink_ks, sink_v, sink_vs,
      tail_k, tail_ks, tail_v, tail_vs)
    return out.reshape(b, 1, hq, d), tk, tks, tv, tvs


def _qsink_kernel(
    lidx_ref,   # SMEM [1] int32 (layer; consumed by index maps)
    step_ref,   # SMEM [1] int32 (tail write slot)
    rlen_ref,   # SMEM [B] int32 (live ring prefix length)
    rptr_ref,   # SMEM [B] int32 (ring write pointer = oldest live slot)
    ev_ref,     # SMEM [B] int32 (slots evicted by the in-flight tail)
    slen_ref,   # SMEM [B] int32 (valid sink slots)
    vlen_ref,   # SMEM [B] int32 (valid tail slots incl. this write)
    q_ref,      # [NB, Hkv, G, D] (abs-rotated)
    qs_ref,     # [NB, Hkv, G, D] (window-relative-rotated, for sinks)
    kn_ref,     # [NB, Hkv, 1, D]
    vn_ref,     # [NB, Hkv, 1, D]
    k_ref,      # [1, NB, Hkv, BT, D] int8 (ring)
    ks_ref,     # [1, NB, Hkv, BT] f32
    v_ref,      # [1, NB, Hkv, BT, D] int8
    vs_ref,     # [1, NB, Hkv, BT] f32
    sk_ref,     # [1, NB, Hkv, SP, D] int8 (sinks; read-only)
    sks_ref,    # [1, NB, Hkv, SP] f32
    sv_ref,     # [1, NB, Hkv, SP, D] int8
    svs_ref,    # [1, NB, Hkv, SP] f32
    tk_ref,     # [1, NB, Hkv, KT, D] int8 (in)
    tks_ref,    # [1, NB, Hkv, KT] f32 (in)
    tv_ref,     # [1, NB, Hkv, KT, D] int8 (in)
    tvs_ref,    # [1, NB, Hkv, KT] f32 (in)
    out_ref,    # [NB, Hkv, G, D]
    tk_out,     # aliased tail outputs
    tks_out,
    tv_out,
    tvs_out,
    acc_ref,    # VMEM [NB, Hkv*G, D] f32
    m_ref,      # VMEM [NB, Hkv*G, 128] f32
    l_ref,      # VMEM [NB, Hkv*G, 128] f32
    *,
    scale: float,
    block_t: int,
    num_blocks: int,
    ring_slots: int,
    hkv: int,
    g: int,
    nb: int,
    sp: int,
    kt: int,
):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[:]                               # [NB, Hkv, G, D]

    def _accumulate(s, valid):
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        return p, alpha

    def _tile(qq, kk, kks, vv, vvs, valid, width):
        """One online-softmax tile over ``width`` int8 slots."""
        s = jax.lax.dot_general(
            qq.astype(jnp.bfloat16).reshape(nb * hkv, g, -1),
            kk.astype(jnp.bfloat16).reshape(nb * hkv, width, -1),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(nb, hkv, g, width)
        s = (s * kks[:, :, None, :] * scale).reshape(nb, hkv * g, width)
        p, alpha = _accumulate(s, valid)
        pw = p.reshape(nb, hkv, g, width) * vvs[:, :, None, :]
        pv = jax.lax.dot_general(
            pw.astype(jnp.bfloat16).reshape(nb * hkv, g, width),
            vv.astype(jnp.bfloat16).reshape(nb * hkv, width, -1),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(nb, hkv * g, -1)

    def _ring_tile():
        slot = j * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_t), 1
        )
        row_valids = []
        for r in range(nb):
            row = bi * nb + r
            live = slot < rlen_ref[row]
            # Slots in [ring_ptr, ring_ptr + evict_len) mod R hold tokens
            # the in-flight tail has evicted (exact per-step window).
            w = rptr_ref[row]
            dd = slot - w + jnp.where(slot < w, ring_slots, 0)
            row_valids.append(live & (dd >= ev_ref[row]))
        valid = jnp.stack(row_valids)          # [NB, 1, BT]
        _tile(q, k_ref[0], ks_ref[0], v_ref[0], vs_ref[0], valid, block_t)

    _ring_tile()

    @pl.when(j == num_blocks - 1)
    def _final_tiles():
        # -- sink tile (window-relative query) --------------------------------
        slot1 = jax.lax.broadcasted_iota(jnp.int32, (1, sp), 1)
        sink_valid = jnp.stack(
            [slot1 < slen_ref[bi * nb + r] for r in range(nb)]
        )
        _tile(qs_ref[:], sk_ref[0], sks_ref[0], sv_ref[0], svs_ref[0],
              sink_valid, sp)

        # -- tail tile (quantize-in-kernel write + attend) --------------------
        step = step_ref[0]
        kn = kn_ref[:].astype(jnp.float32)     # [NB, Hkv, 1, D]
        vn = vn_ref[:].astype(jnp.float32)
        ksc = jnp.maximum(jnp.max(jnp.abs(kn), axis=-1), 1e-8) / 127.0
        vsc = jnp.maximum(jnp.max(jnp.abs(vn), axis=-1), 1e-8) / 127.0
        kq = jnp.clip(jnp.round(kn / ksc[..., None]), -127, 127).astype(
            jnp.int8
        )
        vq = jnp.clip(jnp.round(vn / vsc[..., None]), -127, 127).astype(
            jnp.int8
        )
        # As in _qfused_kernel: no squeeze of the mask's lane dim.
        hit4 = jax.lax.broadcasted_iota(jnp.int32, (1, 1, kt, 1), 2) == step
        hit3 = jax.lax.broadcasted_iota(jnp.int32, (1, 1, kt), 2) == step
        tk = jnp.where(hit4, kq, tk_ref[0])    # [NB, Hkv, KT, D]
        tv = jnp.where(hit4, vq, tv_ref[0])
        tks = jnp.where(hit3, ksc, tks_ref[0])  # [NB, Hkv, KT]
        tvs = jnp.where(hit3, vsc, tvs_ref[0])
        tk_out[0] = tk
        tv_out[0] = tv
        tks_out[0] = tks
        tvs_out[0] = tvs

        pos1 = jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1)
        tail_valid = jnp.stack(
            [pos1 < vlen_ref[bi * nb + r] for r in range(nb)]
        )
        _tile(q, tk, tks, tv, tvs, tail_valid, kt)

        l = l_ref[:, :, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-20)
        out_ref[:] = out.reshape(nb, hkv, g, -1).astype(out_ref.dtype)


def sink_tail_flush(
    big_k: jnp.ndarray,
    big_ks: jnp.ndarray,
    big_v: jnp.ndarray,
    big_vs: jnp.ndarray,
    tail_k: jnp.ndarray,
    tail_ks: jnp.ndarray,
    tail_v: jnp.ndarray,
    tail_vs: jnp.ndarray,
    ring_ptr: jnp.ndarray,
    skip: jnp.ndarray,
    tail_len: jnp.ndarray,
    ring_slots: int,
    interpret: Optional[bool] = None,
):
    """:func:`fused_tail_flush` for the sink RING: merge the write-behind
    tail into the int8 ring planes at per-row slots that WRAP mod
    ``ring_slots``. Tail token ``i`` (for ``skip <= i < tail_len``) lands at
    ring slot ``(ring_ptr + i - skip) % ring_slots``; the first ``skip``
    tokens are sink-bound (stream positions below the sink span) and are
    merged into the small sink planes by the caller in XLA.

    Blocked RMW like the dense flush, with a third block visit pinned to
    block 0 so a wrapped window's head is always covered (a consecutive
    mod-``nbv`` sweep can miss it when the ring spans >2 blocks).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    num_l, b, hkv, t, d = big_k.shape
    kt = tail_k.shape[3]
    BV = 32
    BS = 128
    nbv = t // BV
    nbs = -(-t // BS)
    nj = 3  # {ptr block, next mod, block 0} — covers straddle AND wrap

    def _vidx(li, bi, ji, ptr, sk, tl):
        blk = jnp.where(
            ji == nj - 1, 0, (ptr[bi] // BV + ji) % nbv
        )
        return (li, bi, 0, blk, 0)

    def _sidx(li, bi, ji, ptr, sk, tl):
        blk = jnp.where(
            ji == nj - 1, 0, (ptr[bi] // BS + ji) % nbs
        )
        return (li, bi, 0, blk)

    def _tidx(li, bi, ji, ptr, sk, tl):
        return (li, bi, 0, 0, 0)

    def _tidx3(li, bi, ji, ptr, sk, tl):
        return (li, bi, 0, 0)

    def kernel(ptr_ref, skip_ref, tl_ref,
               tk, tks, tv, tvs,
               bk_in, bks_in, bv_in, bvs_in,
               bk_out, bks_out, bv_out, bvs_out):
        bi = pl.program_id(1)
        ji = pl.program_id(2)
        ptr = ptr_ref[bi]
        sk_n = skip_ref[bi]
        tl = tl_ref[bi]

        def targets():
            """Ring slot of each tail index (mod ring_slots) + liveness."""
            out = []
            for i in range(kt):
                t0 = ptr + (i - sk_n)
                tgt = jax.lax.rem(
                    jnp.maximum(t0, 0), jnp.int32(ring_slots)
                )
                out.append((tgt, (i >= sk_n) & (i < tl)))
            return out

        tgts = targets()

        def compose_values(big_ref, tail_ref, out_ref, blk):
            pos = blk * BV + jax.lax.broadcasted_iota(
                jnp.int32, (1, BV, 1), 1
            )
            cur = big_ref[0, 0]                        # [Hkv, BV, D]
            tail = tail_ref[0, 0]                      # [Hkv, KT, D]
            for i in range(kt):
                tgt, live = tgts[i]
                hit = (pos == tgt) & live
                cur = jnp.where(hit, tail[:, i : i + 1], cur)
            out_ref[0, 0] = cur

        def compose_scales(big_ref, tail_ref, out_ref, blk):
            pos = blk * BS + jax.lax.broadcasted_iota(
                jnp.int32, (1, BS), 1
            )
            cur = big_ref[0, 0]                        # [Hkv, BS]
            tail = tail_ref[0, 0]                      # [Hkv, KT]
            for i in range(kt):
                tgt, live = tgts[i]
                hit = (pos == tgt) & live
                cur = jnp.where(hit, tail[:, i : i + 1], cur)
            out_ref[0, 0] = cur

        vblk = jnp.where(ji == nj - 1, 0, (ptr // BV + ji) % nbv)
        sblk = jnp.where(ji == nj - 1, 0, (ptr // BS + ji) % nbs)
        compose_values(bk_in, tk, bk_out, vblk)
        compose_values(bv_in, tv, bv_out, vblk)
        compose_scales(bks_in, tks, bks_out, sblk)
        compose_scales(bvs_in, tvs, bvs_out, sblk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_l, b, nj),
        in_specs=[
            pl.BlockSpec((1, 1, hkv, kt, d), _tidx),
            pl.BlockSpec((1, 1, hkv, kt), _tidx3),
            pl.BlockSpec((1, 1, hkv, kt, d), _tidx),
            pl.BlockSpec((1, 1, hkv, kt), _tidx3),
            pl.BlockSpec((1, 1, hkv, BV, d), _vidx),
            pl.BlockSpec((1, 1, hkv, BS), _sidx),
            pl.BlockSpec((1, 1, hkv, BV, d), _vidx),
            pl.BlockSpec((1, 1, hkv, BS), _sidx),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, hkv, BV, d), _vidx),
            pl.BlockSpec((1, 1, hkv, BS), _sidx),
            pl.BlockSpec((1, 1, hkv, BV, d), _vidx),
            pl.BlockSpec((1, 1, hkv, BS), _sidx),
        ),
        scratch_shapes=[],
    )
    return pl.pallas_call(
        kernel,
        name="sink_tail_flush",
        out_shape=(
            jax.ShapeDtypeStruct(big_k.shape, big_k.dtype),
            jax.ShapeDtypeStruct(big_ks.shape, big_ks.dtype),
            jax.ShapeDtypeStruct(big_v.shape, big_v.dtype),
            jax.ShapeDtypeStruct(big_vs.shape, big_vs.dtype),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        # Inputs counting scalars: ptr 0, skip 1, tl 2, tails 3-6, bigs 7-10.
        input_output_aliases={7: 0, 8: 1, 9: 2, 10: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(ring_ptr.astype(jnp.int32), skip.astype(jnp.int32),
      tail_len.astype(jnp.int32),
      tail_k, tail_ks, tail_v, tail_vs,
      big_k, big_ks, big_v, big_vs)
