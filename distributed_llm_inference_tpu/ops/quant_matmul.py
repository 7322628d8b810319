"""Pallas int4-weight matmul for bandwidth-bound decode.

Why a kernel: the XLA int4 path (``ops/quant.py:matmul`` on a
:class:`QuantizedTensor4` — arithmetic nibble unpack + einsum over
the packed pair axis) reads only the packed half-byte per value from HBM, but
the pair-axis contraction shape keeps the MXU from tiling it like a plain
matmul — measured r2: int4 weights LOST to int8 (2,682 vs 3,139 tok/s at
Llama-7B decode) despite half the weight bytes. Here the packed bytes stream
through VMEM once, nibbles are sign-extended in VMEM (int32 domain — Mosaic
has no int8 shifts), and two plain
``[BIN, BOUTP]`` MXU matmuls consume the halves with per-channel scales folded
in at the epilogue — HBM traffic is the int4 bytes.

Packing layout ("half-split", cf. the XLA path's adjacent-pair packing): byte
column ``j`` holds channel ``j`` in the low nibble and channel
``j + OUT_pad/2`` in the high nibble, so the two unpacked tiles are the
*contiguous* first/last halves of the output. The kernel keeps the halves as
two separate outputs with clean ``[B, BOUTP]`` blocks — a fused ``[B, 2, X]``
output forces a degenerate ``T(2,128)`` tiling (4x sublane waste on every
accumulate; measured 60x off the roofline in the first version of this
kernel) — and the caller concatenates once. Weights are padded to tile
multiples at quantization time (``pack_int4_split``), not per step.

The reference's deployment play was quantized serving via bitsandbytes
(``/root/reference/distributed_llm_inference/utils/model.py:93-123``,
CUDA-only); this is its TPU-native int4 half. Runs in interpret mode off-TPU
so CPU tests exercise the same code path.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pack_int4_split", "int4_matmul", "unpack_int4_split"]

# Tile sizes: BIN x BOUTP packed bytes per DMA (512 KB) — big enough that
# DMA issue overhead amortizes, small enough that the unpacked halves and two
# f32 accumulators stay a few MB of VMEM.
_BIN = 1024
_BOUTP = 512


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def pack_int4_split(
    q: jnp.ndarray, in_pad: Optional[int] = None, out_pad: Optional[int] = None
) -> jnp.ndarray:
    """Pack int4 values ``[..., in, out]`` (int8 container, range [-7, 7])
    into half-split bytes ``[..., in_pad, out_pad // 2]``.

    Channel ``j`` → low nibble of byte column ``j``; channel
    ``j + out_pad/2`` → high nibble. Padding rows/channels are zero.
    """
    *lead, in_dim, out = q.shape
    in_pad = in_pad or _pad_to(in_dim, _BIN)
    out_pad = out_pad or _pad_to(out, 2 * _BOUTP)
    widths = [(0, 0)] * len(lead) + [(0, in_pad - in_dim), (0, out_pad - out)]
    qp = jnp.pad(q, widths)
    lo = qp[..., : out_pad // 2]
    hi = qp[..., out_pad // 2 :]
    return jnp.bitwise_or(
        jnp.bitwise_and(lo, jnp.int8(0x0F)), jnp.left_shift(hi, jnp.int8(4))
    )


def unpack_int4_split(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4_split` (padded shape): ``[..., in_pad,
    out_pad]`` int8 values. XLA fallback path for many-row (prefill) calls."""
    lo = jnp.right_shift(
        jnp.left_shift(packed, jnp.int8(4)), jnp.int8(4)
    )
    hi = jnp.right_shift(packed, jnp.int8(4))
    return jnp.concatenate([lo, hi], axis=-1)


def _int4_kernel(
    x_ref, w_ref, slo_ref, shi_ref, olo_ref, ohi_ref, alo_ref, ahi_ref,
    *, n_in: int,
):
    """One (out-tile, in-tile) grid step.

    ``x_ref``: ``[B, BIN]``; ``w_ref``: packed int8 ``[BIN, BOUTP]``;
    ``slo_ref``/``shi_ref``: f32 ``[1, BOUTP]`` channel scales;
    ``olo_ref``/``ohi_ref``: ``[B, BOUTP]`` halves of the output;
    ``alo_ref``/``ahi_ref``: f32 VMEM accumulators ``[B, BOUTP]``.

    """
    ii = pl.program_id(1)

    @pl.when(ii == 0)
    def _init():
        alo_ref[:] = jnp.zeros_like(alo_ref)
        ahi_ref[:] = jnp.zeros_like(ahi_ref)

    # int32-domain unpack: Mosaic could not lower int8 left_shift when this
    # was written (not retried on jax 0.9.0); the sign-extending
    # int8→int32 convert makes
    # the arithmetic right shift recover hi directly.
    w32 = w_ref[...].astype(jnp.int32)
    x = x_ref[...]
    lo = jnp.right_shift(jnp.left_shift(w32, 28), 28).astype(x.dtype)
    hi = jnp.right_shift(w32, 4).astype(x.dtype)
    alo_ref[...] += jnp.dot(x, lo, preferred_element_type=jnp.float32)
    ahi_ref[...] += jnp.dot(x, hi, preferred_element_type=jnp.float32)

    @pl.when(ii == n_in - 1)
    def _finalize():
        olo_ref[...] = (alo_ref[...] * slo_ref[...]).astype(olo_ref.dtype)
        ohi_ref[...] = (ahi_ref[...] * shi_ref[...]).astype(ohi_ref.dtype)


def _kernel_tiles(in_pad: int, outp: int) -> Tuple[int, int]:
    bin_ = _BIN if in_pad % _BIN == 0 else np.gcd(in_pad, _BIN)
    boutp = _BOUTP if outp % _BOUTP == 0 else np.gcd(outp, _BOUTP)
    return int(bin_), int(boutp)


def int4_matmul(
    x: jnp.ndarray,
    packed: jnp.ndarray,
    scale_lo: jnp.ndarray,
    scale_hi: jnp.ndarray,
    out_dim: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``x @ w`` with half-split-packed int4 weights and per-channel scales.

    ``x``: ``[..., in]``; ``packed``: ``[in_pad, out_pad // 2]`` int8
    (:func:`pack_int4_split`); ``scale_lo``/``scale_hi``: f32
    ``[1, out_pad // 2]`` (pre-split at quantization time — per-call slicing
    of a combined array materializes copies every decode step); returns
    ``[..., out_dim]`` in x's dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    *lead, in_dim = x.shape
    in_pad, outp = packed.shape
    b = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(b, in_dim)
    # Row padding: bf16 VMEM tiles are (16, 128); f32 accumulators (8, 128).
    bp = _pad_to(max(b, 16), 16)
    if in_pad != in_dim or bp != b:
        x2 = jnp.pad(x2, ((0, bp - b), (0, in_pad - in_dim)))

    bin_, boutp = _kernel_tiles(in_pad, outp)
    n_in = in_pad // bin_
    n_out = outp // boutp

    s_lo = scale_lo.reshape(1, outp).astype(jnp.float32)
    s_hi = scale_hi.reshape(1, outp).astype(jnp.float32)

    out_lo, out_hi = pl.pallas_call(
        functools.partial(_int4_kernel, n_in=n_in),
        name="int4_matmul",
        grid=(n_out, n_in),
        in_specs=[
            pl.BlockSpec((bp, bin_), lambda oi, ii: (0, ii)),
            pl.BlockSpec((bin_, boutp), lambda oi, ii: (ii, oi)),
            pl.BlockSpec((1, boutp), lambda oi, ii: (0, oi)),
            pl.BlockSpec((1, boutp), lambda oi, ii: (0, oi)),
        ],
        out_specs=[
            pl.BlockSpec((bp, boutp), lambda oi, ii: (0, oi)),
            pl.BlockSpec((bp, boutp), lambda oi, ii: (0, oi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, outp), x.dtype),
            jax.ShapeDtypeStruct((bp, outp), x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bp, boutp), jnp.float32),
            pltpu.VMEM((bp, boutp), jnp.float32),
        ],
        interpret=interpret,
    )(x2, packed, s_lo, s_hi)
    y = jnp.concatenate([out_lo, out_hi], axis=-1)[:b, :out_dim]
    return y.reshape(*lead, out_dim)


def _int4_stacked_kernel(
    lidx_ref, x_ref, w_ref, slo_ref, shi_ref, olo_ref, ohi_ref,
    alo_ref, ahi_ref, *, n_in: int,
):
    """As :func:`_int4_kernel`, but the weight/scale operands carry a
    leading layer axis the block index map already resolved (refs peel one
    unit dim)."""
    ii = pl.program_id(1)

    @pl.when(ii == 0)
    def _init():
        alo_ref[:] = jnp.zeros_like(alo_ref)
        ahi_ref[:] = jnp.zeros_like(ahi_ref)

    w32 = w_ref[0].astype(jnp.int32)
    x = x_ref[...]
    lo = jnp.right_shift(jnp.left_shift(w32, 28), 28).astype(x.dtype)
    hi = jnp.right_shift(w32, 4).astype(x.dtype)
    alo_ref[...] += jnp.dot(x, lo, preferred_element_type=jnp.float32)
    ahi_ref[...] += jnp.dot(x, hi, preferred_element_type=jnp.float32)

    @pl.when(ii == n_in - 1)
    def _finalize():
        olo_ref[...] = (alo_ref[...] * slo_ref[0]).astype(olo_ref.dtype)
        ohi_ref[...] = (ahi_ref[...] * shi_ref[0]).astype(ohi_ref.dtype)


def int4_matmul_stacked(
    x: jnp.ndarray,
    packed: jnp.ndarray,
    scale_lo: jnp.ndarray,
    scale_hi: jnp.ndarray,
    layer_idx: jnp.ndarray,
    out_dim: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """:func:`int4_matmul` over the WHOLE layer-stacked weight with a traced
    layer index resolved in the block index map.

    Inside the decode's layer scan, slicing one layer's packed weight to
    feed the kernel materializes an HBM copy of it every (layer, step) —
    read + write + kernel re-read ≈ 3x the weight bytes, which is why int4
    decode measured SLOWER than int8 despite half the bytes. The stacked
    operand is zero-copy; the kernel DMAs exactly the tiles it contracts.

    ``packed``: int8 ``[L, in_pad, out_pad // 2]``; ``scale_lo/hi``: f32
    ``[L, 1, out_pad // 2]``; ``layer_idx``: traced int32 scalar.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    *lead, in_dim = x.shape
    num_l, in_pad, outp = packed.shape
    b = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(b, in_dim)
    bp = _pad_to(max(b, 16), 16)
    if in_pad != in_dim or bp != b:
        x2 = jnp.pad(x2, ((0, bp - b), (0, in_pad - in_dim)))

    bin_, boutp = _kernel_tiles(in_pad, outp)
    n_in = in_pad // bin_
    n_out = outp // boutp

    s_lo = scale_lo.reshape(num_l, 1, outp).astype(jnp.float32)
    s_hi = scale_hi.reshape(num_l, 1, outp).astype(jnp.float32)
    lref = jnp.asarray(layer_idx, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_out, n_in),
        in_specs=[
            pl.BlockSpec((bp, bin_), lambda oi, ii, lidx: (0, ii)),
            pl.BlockSpec(
                (1, bin_, boutp), lambda oi, ii, lidx: (lidx[0], ii, oi)
            ),
            pl.BlockSpec(
                (1, 1, boutp), lambda oi, ii, lidx: (lidx[0], 0, oi)
            ),
            pl.BlockSpec(
                (1, 1, boutp), lambda oi, ii, lidx: (lidx[0], 0, oi)
            ),
        ],
        out_specs=(
            pl.BlockSpec((bp, boutp), lambda oi, ii, lidx: (0, oi)),
            pl.BlockSpec((bp, boutp), lambda oi, ii, lidx: (0, oi)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bp, boutp), jnp.float32),
            pltpu.VMEM((bp, boutp), jnp.float32),
        ],
    )
    out_lo, out_hi = pl.pallas_call(
        functools.partial(_int4_stacked_kernel, n_in=n_in),
        name="int4_matmul_stacked",
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((bp, outp), x.dtype),
            jax.ShapeDtypeStruct((bp, outp), x.dtype),
        ),
        interpret=interpret,
    )(lref, x2, packed, s_lo, s_hi)
    y = jnp.concatenate([out_lo, out_hi], axis=-1)[:b, :out_dim]
    return y.reshape(*lead, out_dim)
