"""Weight-only int8 / int4 quantization for bandwidth-bound decode.

TPU-native replacement for the reference's bitsandbytes ``Linear8bitLt`` swap
(``/root/reference/distributed_llm_inference/utils/model.py:93-123``, CUDA-only
guard at ``:117-118``). Instead of a module-tree surgery, quantization is a
pytree transform: each projection matrix becomes a :class:`QuantizedTensor`
(int8 values + per-output-channel fp scales) or :class:`QuantizedTensor4`
(int4 values + per-(input-group, output-channel) scales), and the matmul
helper dequantizes in-kernel.

Why weight-only symmetric int8: decode is HBM-bandwidth-bound (the whole
weight set is read once per token), so halving weight bytes ≈ doubles decode
throughput and frees HBM for larger batches; XLA fuses the
``int8→bf16 convert × scale`` into the matmul's operand read, so there is no
extra memory pass. A true int8×int8 MXU path (dynamic per-token activation
scales, AQT-style) is the prefill compute optimization — weight-only keeps
activations in bf16 and loses no MXU throughput at decode shapes.

int4 halves weight bytes again (XLA packs two ``s4`` values per byte on TPU)
at the cost of per-group scales: a per-output-channel scale alone is too
coarse at 4 bits, so the input dimension is split into groups of
``group_size`` (AWQ/GPTQ-style) and each (group, out-channel) pair gets its
own scale; the matmul computes per-group partial sums and scales them before
reduction, keeping the int4→bf16 convert fused into the operand read.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from flax import struct

__all__ = [
    "QuantizedTensor",
    "QuantizedTensorOutlier",
    "QuantizedTensor4",
    "QuantizedTensor4Split",
    "QuantizedTensor4SplitView",
    "quantize_int8",
    "quantize_int8_outlier",
    "quantize_int4",
    "quantize_int4_split",
    "matmul",
    "quantize_params",
    "QUANTIZED_WEIGHTS",
    "INT4_WEIGHTS",
]

# Layer-stack weights worth quantizing (the large matmuls). Norm gains and
# biases stay in bf16 — they are O(hidden) and scale-sensitive.
QUANTIZED_WEIGHTS = (
    "wq", "wk", "wv", "wo", "wg", "wu", "wd",  # dense attention + MLP
    "wq_a", "wq_b",                            # compressed queries (latent)
    "we_g", "we_u", "we_d",                    # MoE experts
    "ws_g", "ws_u", "ws_d",                    # shared experts
    # latent attention's own: ``wkv_a`` (its output IS the stored latent)
    # and the einsum operands ``wk_b`` / ``wv_b`` ``[rank, Hq, d]`` stay in
    # the model's dtype: 4.2 M of a Moonlight layer's 585 M parameters
    # a learned selection's indexer (``wq_i``, ``wk_i``, ``w_i``) stays in the
    # model's dtype too: its scores rank keys, a hard choice that rounding
    # moves, for 2.3 M of a Keye layer's 625 M parameters
    "lm_head",
)

# Weights eligible for group-wise int4 (plain ``x @ w`` projections).
INT4_WEIGHTS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "lm_head")


class QuantizedTensor(struct.PyTreeNode):
    """``q``: int8 values, original shape ``[..., in, out]``; ``scale``: fp
    per-output-channel scales, shape ``[..., out]`` (leading dims = layer
    stack / experts)."""

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.scale.dtype


class QuantizedTensorOutlier(struct.PyTreeNode):
    """Mixed-precision int8: LLM.int8()-style outlier decomposition.

    bitsandbytes keeps outlier features in fp16 next to the int8 body
    (``Linear8bitLt(threshold=5.0)``, the reference's serving-node swap at
    ``/root/reference/distributed_llm_inference/utils/model.py:102-108``) —
    the handful of activation channels with huge magnitudes otherwise
    dominate the per-channel scale and crush the resolution of everything
    else. TPU-native form: a FIXED number of input channels (static shape —
    a data-dependent threshold would make the weight layout dynamic under
    ``jit``) are carried at full precision and ZEROED in the int8 body;
    the matmul adds ``x[..., idx] @ outlier_w`` back, a [rows, K] x
    [K, out] side matmul whose cost is noise for K ≈ 32 next to the int8
    sweep. Channel choice: calibration activation scales when provided,
    weight-column energy otherwise (quantize_int8_outlier).

    ``q``/``scale``: as :class:`QuantizedTensor` (outlier rows zeroed);
    ``outlier_idx``: int32 ``[..., K]`` input-channel indices;
    ``outlier_w``: fp ``[..., K, out]`` original rows.
    """

    q: jax.Array
    scale: jax.Array
    outlier_idx: jax.Array
    outlier_w: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.outlier_w.dtype


def quantize_int8_outlier(
    w: jax.Array,
    num_outliers: int = 32,
    act_scales: Optional[jax.Array] = None,
    scale_dtype=jnp.bfloat16,
) -> QuantizedTensorOutlier:
    """Outlier-decomposed symmetric int8 of ``[..., in, out]``.

    ``act_scales`` (``[..., in]`` per-input-channel activation absmax from a
    calibration pass) selects the channels the way LLM.int8() does — by the
    ACTIVATIONS that flow through them; without calibration the fallback
    proxy is weight-row energy (the rows whose magnitude dominates the
    column absmax and therefore the quantization step)."""
    *lead, in_dim, out = w.shape
    k = min(num_outliers, in_dim)
    wf = w.astype(jnp.float32)
    score = (
        act_scales.astype(jnp.float32)
        if act_scales is not None
        else jnp.max(jnp.abs(wf), axis=-1)
    )  # [..., in]
    # A shared per-channel calibration vector ([in]) broadcasts across a
    # stacked projection's lead (layer) axes.
    score = jnp.broadcast_to(jnp.asarray(score), (*lead, in_dim))
    _, idx = jax.lax.top_k(score, k)  # [..., k]
    outlier_w = jnp.take_along_axis(wf, idx[..., None], axis=-2)
    mask = jnp.any(
        jnp.arange(in_dim) == idx[..., :, None], axis=-2
    )  # [..., in]
    body = jnp.where(mask[..., None], 0.0, wf)
    amax = jnp.max(jnp.abs(body), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(body / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensorOutlier(
        q=q,
        scale=scale.squeeze(-2).astype(scale_dtype),
        outlier_idx=idx.astype(jnp.int32),
        outlier_w=outlier_w.astype(scale_dtype),
    )


def _unpack_nibbles(q: jax.Array):
    """``(low, high)`` int4-valued int8 halves of a nibble-packed byte via
    arithmetic shift-and-sign-extend. The ONLY sanctioned unpack:
    ``lax.bitcast_convert_type`` to int4 reads the nibbles differently on
    XLA:TPU than on CPU (cos ≈ -0.3 vs the fp reference on a real v5e —
    caught by tools/quant_accuracy.py in r4)."""
    lo = jnp.right_shift(jnp.left_shift(q, jnp.int8(4)), jnp.int8(4))
    hi = jnp.right_shift(q, jnp.int8(4))
    return lo, hi


class QuantizedTensor4(struct.PyTreeNode):
    """int4 weight with per-(input-group, output-channel) scales.

    ``q``: **nibble-packed int8** ``[..., G, group_size, out // 2]`` — two
    adjacent output channels per byte (even channel in the low nibble). The
    int8 container keeps the pytree leaf a universally supported dtype
    (whether ``s4`` leaves cross the jit boundary on a directly attached
    chip has not been tried); :func:`matmul` unpacks the nibbles ARITHMETICALLY
    (shift + sign-extend, fused into the operand read) — HBM traffic is the
    packed half byte per value. ``lax.bitcast_convert_type`` to ``int4``
    must NOT be used here: XLA:TPU interprets the nibbles differently from
    CPU (measured cos ≈ -0.3 against the fp reference on a real v5e, exact
    on CPU — caught by ``tools/quant_accuracy.py`` in r4). ``scale``: fp
    ``[..., G, out]``. ``shape`` reports the logical ``[..., in, out]``.
    """

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        *lead, g, gs, out_packed = self.q.shape
        return (*lead, g * gs, out_packed * 2)

    @property
    def dtype(self):
        return self.scale.dtype

    def unpack(self) -> jax.Array:
        """In-graph int4-valued int8 view ``[..., G, gs, out]`` (low nibble
        = even channel), via arithmetic shift-and-sign-extend — portable
        across CPU and TPU (the int4 bitcast is not; see class docstring)."""
        *lead, g, gs, out_packed = self.q.shape
        lo, hi = _unpack_nibbles(self.q)
        return jnp.stack([lo, hi], axis=-1).reshape(
            *lead, g, gs, out_packed * 2
        )


class QuantizedTensor4Split(struct.PyTreeNode):
    """int4 weight in the Pallas decode-matmul layout (half-split packing).

    ``q``: int8 ``[..., in_pad, out_pad // 2]`` — byte column ``j`` holds
    channel ``j`` (low nibble) and channel ``j + out_pad/2`` (high nibble);
    padded to the kernel's tile multiples at quantization time (see
    ``ops/quant_matmul.py``). ``scale_lo``/``scale_hi``: f32
    ``[..., 1, out_pad // 2]`` per-output-channel scales for the two halves —
    stored PRE-SPLIT so the kernel call slices nothing per step (a
    ``[2, outp]`` array would need per-call row slices that XLA materializes,
    and a (1, x) block of a 2-row array is not a legal Mosaic tile). Coarser
    than :class:`QuantizedTensor4`'s grouped scales (per-channel only) but
    decode reads stream straight through the MXU kernel — this is the
    throughput configuration; grouped pair-packing is the accuracy
    configuration.
    """

    q: jax.Array
    scale_lo: jax.Array
    scale_hi: jax.Array
    in_dim: int = struct.field(pytree_node=False, default=0)
    out_dim: int = struct.field(pytree_node=False, default=0)

    @property
    def shape(self):
        return (*self.q.shape[:-2], self.in_dim, self.out_dim)

    @property
    def dtype(self):
        return self.scale_lo.dtype

    def full_scale(self) -> jax.Array:
        """``[..., out_pad]`` concatenated per-channel scales (fallback /
        oracle paths)."""
        return jnp.concatenate(
            [self.scale_lo, self.scale_hi], axis=-1
        ).reshape(*self.q.shape[:-2], -1)


class QuantizedTensor4SplitView(struct.PyTreeNode):
    """One layer's int4 weight, VIEWED out of the layer-stacked tensor with
    a traced ``layer`` index instead of being sliced.

    Why this exists: inside ``lax.scan`` over layers, slicing a
    :class:`QuantizedTensor4Split` leaf out of the ``[L, ...]`` stack to
    feed the Pallas matmul materializes a full HBM copy of that layer's
    packed weight every (layer, step) — XLA cannot fuse a dynamic-slice
    into a custom call operand. That copy traffic (read + write + re-read ≈
    3x the weight bytes) is exactly why the int4 deployment measured SLOWER
    than int8 despite reading half the bytes. The view keeps the whole
    stack as the kernel operand and folds ``layer`` into the block index
    map (same pattern as the whole-stack KV kernels, quant_attention.py).
    """

    q: jax.Array         # [L, in_pad, out_pad // 2] int8
    scale_lo: jax.Array  # [L, 1, out_pad // 2] f32
    scale_hi: jax.Array  # [L, 1, out_pad // 2] f32
    layer: jax.Array     # scalar int32 (traced)
    in_dim: int = struct.field(pytree_node=False, default=0)
    out_dim: int = struct.field(pytree_node=False, default=0)

    @property
    def shape(self):
        return (self.in_dim, self.out_dim)

    @property
    def dtype(self):
        return self.scale_lo.dtype


def quantize_int4_split(w: jax.Array) -> QuantizedTensor4Split:
    """Symmetric per-output-channel int4 in the half-split Pallas layout.

    Scales are always f32: the kernel accumulates in f32 and multiplies the
    scales in at the epilogue, so there is no bf16 round trip to save, and
    per-channel scale bytes are noise next to the packed weights.
    """
    from .quant_matmul import pack_int4_split

    *lead, in_dim, out = w.shape
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -7, 7).astype(
        jnp.int8
    )
    packed = pack_int4_split(q)
    out_pad = packed.shape[-1] * 2
    sc = jnp.pad(
        scale.squeeze(-2).astype(jnp.float32),
        [(0, 0)] * len(lead) + [(0, out_pad - out)],
    )
    half = out_pad // 2
    return QuantizedTensor4Split(
        q=packed,
        scale_lo=sc[..., None, :half],
        scale_hi=sc[..., None, half:],
        in_dim=in_dim,
        out_dim=out,
    )


def quantize_int8(w: jax.Array, scale_dtype=jnp.bfloat16) -> QuantizedTensor:
    """Symmetric per-output-channel int8 quantization of ``[..., in, out]``."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(
        jnp.int8
    )
    return QuantizedTensor(q=q, scale=scale.squeeze(-2).astype(scale_dtype))


def quantize_int4(
    w: jax.Array, group_size: Optional[int] = 128, scale_dtype=jnp.bfloat16
) -> QuantizedTensor4:
    """Symmetric group-wise int4 quantization of ``[..., in, out]``.

    ``in`` must be divisible by ``group_size`` (true for every transformer
    projection at real model shapes; pad otherwise before calling).
    ``group_size=None`` uses one group (per-output-channel scales only):
    fastest decode (a single ungrouped matmul) but coarser quantization —
    prefer grouped scales for accuracy-sensitive serving.
    """
    *lead, in_dim, out = w.shape
    if group_size is None:
        group_size = in_dim
    if in_dim % group_size:
        raise ValueError(f"in dim {in_dim} not divisible by group {group_size}")
    if out % 2:
        raise ValueError(f"out dim {out} must be even (nibble packing)")
    g = in_dim // group_size
    wf = w.astype(jnp.float32).reshape(*lead, g, group_size, out)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)  # [..., G, 1, out]
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(wf / scale), -7, 7).astype(jnp.int8)
    # Pack adjacent output channels: even → low nibble, odd → high nibble
    # (matches the little-endian pair order of bitcast int8 → int4[..., 2]).
    lo = jnp.bitwise_and(q[..., 0::2], jnp.int8(0x0F))
    hi = jnp.left_shift(q[..., 1::2], jnp.int8(4))
    return QuantizedTensor4(
        q=jnp.bitwise_or(lo, hi), scale=scale.squeeze(-2).astype(scale_dtype)
    )


# Prefill calls (>= this many sequence positions) against int8 weights run
# int8 x int8 on the MXU with dynamic per-token activation scales (AQT
# style) instead of dequantizing the weight into a bf16 matmul: the int8
# systolic path has 2x the bf16 peak on v5e, and at prefill row counts the
# per-token abs-max/round VPU work amortizes. The threshold is not
# measured on the chip and no cell is on the other side (ROADMAP D5).
# Decode (S == 1) and short verifies keep the weight-only path: they are
# HBM-bound, and W8A8 would change their numerics for no throughput.
ACT_QUANT_PREFILL = True
ACT_QUANT_MIN_SEQ = 128


def w8a8_matmul(x: jax.Array, w: QuantizedTensor) -> jax.Array:
    """int8 x int8 MXU matmul with dynamic symmetric per-token activation
    scales: ``y = (q_x @ q_w) * x_scale * w_scale``. The int32 accumulator
    is exact and the scales are applied in f32 BEFORE the cast to the
    activation dtype (casting the ~1e5-magnitude accumulator to bf16 first
    would round away ~2^-9 relative); the only additional quantization
    error vs weight-only int8 is the activations' own rounding — per-token
    scales keep the combined matmul error ~1% relative
    (tests/test_quant.py::test_w8a8_matmul_close_to_fp)."""
    amax = jnp.max(jnp.abs(x).astype(jnp.float32), axis=-1, keepdims=True)
    xs = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / xs), -127, 127
    ).astype(jnp.int8)
    y = jax.lax.dot_general(
        q, w.q, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (
        y.astype(jnp.float32) * xs * w.scale.astype(jnp.float32)
    ).astype(x.dtype)


def matmul(x: jax.Array, w) -> jax.Array:
    """``x @ w`` that transparently handles quantized weights.

    For a :class:`QuantizedTensor`, computes ``(x @ q) * scale`` with the
    int8→bf16 convert fused into the matmul operand read by XLA — except
    prefill-shaped calls on TPU, which take :func:`w8a8_matmul`'s int8 MXU
    path (see ``ACT_QUANT_PREFILL``). For a :class:`QuantizedTensor4`,
    per-group partial sums are scaled before the group reduction.
    """
    if (
        ACT_QUANT_PREFILL
        and isinstance(w, QuantizedTensor)
        and w.q.ndim == 2
        and x.ndim >= 3
        and x.shape[-2] >= ACT_QUANT_MIN_SEQ
        and jax.default_backend() == "tpu"
    ):
        return w8a8_matmul(x, w)
    if isinstance(w, QuantizedTensorOutlier):
        y = (x @ w.q.astype(x.dtype)) * w.scale.astype(x.dtype)
        xo = jnp.take(x, w.outlier_idx, axis=-1)
        return y + xo @ w.outlier_w.astype(x.dtype)
    if isinstance(w, QuantizedTensor):
        y = x @ w.q.astype(x.dtype)
        return y * w.scale.astype(x.dtype)
    if isinstance(w, QuantizedTensor4SplitView):
        import numpy as np

        from .quant_matmul import int4_matmul_stacked, unpack_int4_split

        rows = int(np.prod(x.shape[:-1]))
        # Decode (S == 1) takes the stacked kernel at ANY batch — the
        # row-count heuristic alone would route large-batch decode (e.g.
        # b384 GQA serving) to the slice path and reintroduce the
        # per-(layer, step) weight copy this view exists to remove. The
        # row threshold only gates genuine many-row prefill, where the
        # XLA unpack amortizes and MXU shapes are already efficient.
        decode = x.ndim >= 3 and x.shape[-2] == 1
        if decode or rows <= 256:
            return int4_matmul_stacked(
                x, w.q, w.scale_lo, w.scale_hi, w.layer, w.out_dim
            )
        # Many-row (prefill) calls: slice the layer (amortized over rows)
        # and run the plain XLA dequant matmul.
        wq = jax.lax.dynamic_index_in_dim(w.q, w.layer, 0, keepdims=False)
        slo = jax.lax.dynamic_index_in_dim(
            w.scale_lo, w.layer, 0, keepdims=False
        )
        shi = jax.lax.dynamic_index_in_dim(
            w.scale_hi, w.layer, 0, keepdims=False
        )
        w4 = unpack_int4_split(wq)[: x.shape[-1]]
        y = x @ w4.astype(x.dtype)
        sc = jnp.concatenate([slo, shi], axis=-1).reshape(-1)
        return (y * sc.astype(x.dtype))[..., : w.out_dim]
    if isinstance(w, QuantizedTensor4Split):
        import numpy as np

        from .quant_matmul import int4_matmul, unpack_int4_split

        if w.q.ndim != 2:
            raise ValueError(
                "QuantizedTensor4Split matmul expects a per-layer 2D packed "
                f"weight (scan-sliced), got shape {w.q.shape}"
            )
        rows = int(np.prod(x.shape[:-1]))
        if rows <= 256:
            return int4_matmul(x, w.q, w.scale_lo, w.scale_hi, w.out_dim)
        # Many-row (prefill) calls: plain XLA dequant matmul — the unpack is
        # amortized over the rows and the MXU shape is already efficient.
        w4 = unpack_int4_split(w.q)[: x.shape[-1]]
        y = x @ w4.astype(x.dtype)
        return (y * w.full_scale().astype(x.dtype))[..., : w.out_dim]
    if isinstance(w, QuantizedTensor4):
        g, gs, outp = w.q.shape[-3:]
        # Unpack nibbles ARITHMETICALLY (shift-and-sign-extend), not via
        # bitcast_convert_type(int4): the int4 bitcast produces a DIFFERENT
        # nibble interpretation on XLA:TPU than on CPU — measured cos ≈ -0.3
        # against the fp reference at every width on a real v5e while CPU was
        # exact (caught by the r4 accuracy harness; the split/Pallas layout
        # was unaffected, so perf phases never saw it). Two half-matmuls with
        # the int8->bf16 convert fused into the operand read replace it.
        lo, hi = _unpack_nibbles(w.q)
        xg = x.reshape(*x.shape[:-1], g, gs).astype(jnp.float32)
        # f32 operands: full-precision group accumulation (this is the
        # ACCURACY configuration), and XLA:CPU's dot thunk rejects
        # bf16 x bf16 -> f32.
        part = jnp.stack(
            [
                jnp.einsum(
                    "...gi,gio->...go", xg, h.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                )
                for h in (lo, hi)
            ],
            axis=-1,
        )  # [..., G, outp, 2]
        sc = w.scale.reshape(*w.scale.shape[:-1], outp, 2).astype(jnp.float32)
        y = jnp.sum(part * sc, axis=-3)  # reduce groups
        return y.reshape(*y.shape[:-2], outp * 2).astype(x.dtype)
    return x @ w


def einsum(spec: str, x: jax.Array, w) -> jax.Array:
    """``jnp.einsum`` that transparently handles quantized weights.

    Requires the weight's non-contracted subscripts to appear LAST in the
    output (true for the MoE einsums here), so the ``[..., out]`` scale
    broadcasts against the result's trailing dims.
    """
    if isinstance(w, QuantizedTensor):
        y = jnp.einsum(spec, x, w.q.astype(x.dtype))
        return y * w.scale.astype(x.dtype)
    return jnp.einsum(spec, x, w)


def quantize_params(
    params: Dict[str, Any],
    names=QUANTIZED_WEIGHTS,
    scale_dtype=jnp.bfloat16,
    bits: int = 8,
    group_size: int = 128,
    int4_layout: str = "grouped",
    group_multiple: int = 1,
    outlier_channels: int = 0,
    act_scales: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Quantize the named weights in a param pytree (full-model or block-only);
    everything else passes through unchanged.

    ``bits=4`` uses int4 for the dense projections (:data:`INT4_WEIGHTS`);
    MoE expert stacks stay int8 (the ``einsum`` helper's scale broadcast
    doesn't cover grouped contraction). ``int4_layout``: "grouped" =
    pair-packed group-wise scales (accuracy configuration, XLA path; group
    size degrades to ``gcd(group_size, in_dim)`` so small test shapes
    divide); "split" = half-split per-channel layout consumed by the Pallas
    decode matmul (throughput configuration, ``ops/quant_matmul.py``).
    ``group_multiple``: force the group COUNT divisible by this — tp-sharded
    serving puts the contracted-axis sharding on the group axis (whole groups
    per device, ``parallel/tp.py``), so engines pass their tp degree.
    ``outlier_channels > 0`` (bits=8) switches the dense projections to the
    LLM.int8()-style outlier decomposition (:func:`quantize_int8_outlier`,
    the reference's ``threshold=5.0`` capability) with that many fp
    channels; ``act_scales`` optionally maps weight name → per-input-channel
    calibration activation absmax. MoE expert stacks stay plain int8 (the
    grouped-expert einsum has no outlier side-path).
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if int4_layout not in ("grouped", "split"):
        raise ValueError(f"unknown int4_layout {int4_layout!r}")

    def quantize_one(name, w):
        if bits == 4 and name in INT4_WEIGHTS and w.shape[-1] % 2 == 0:
            if int4_layout == "split":
                return quantize_int4_split(w)
            gs = math.gcd(group_size, w.shape[-2])
            while gs > 1 and (w.shape[-2] // gs) % group_multiple:
                gs //= 2
            return quantize_int4(w, gs, scale_dtype)
        if outlier_channels > 0 and name in INT4_WEIGHTS:
            return quantize_int8_outlier(
                w, outlier_channels,
                (act_scales or {}).get(name), scale_dtype,
            )
        return quantize_int8(w, scale_dtype)

    # A leaf may still be a HOST array (a loaded checkpoint's layer stacks,
    # utils/checkpoint.py), and a layer STACK is quantized one layer at a
    # time: only that layer's unquantized copy and f32 temporaries are on
    # the device at once. Placed whole, a 7B bf16 tree does not fit a 16 GB
    # chip beside its int8 copy; and even one whole [32, 4096, 14336] stack
    # costs 7.5 GB of f32 temporaries. The quantizers reduce over the
    # trailing two axes only and run eagerly, exactly as they always have,
    # so the bytes are those of the whole-leaf form (under jit XLA turns
    # ``x / 7.0`` into a multiply and moves int4 values by a nibble).
    # Outlier calibration vectors may carry the layer axis themselves, so
    # that variant keeps the whole-leaf form.
    def quantize_leaf(name, w):
        if w.ndim < 3 or outlier_channels > 0:
            return quantize_one(name, jnp.asarray(w))
        layers = [quantize_one(name, jnp.asarray(layer)) for layer in w]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

    out: Dict[str, Any] = {}
    for k, v in params.items():
        if k.startswith("layers"):  # a segment's stack (ModelConfig.segments)
            out[k] = {
                n: quantize_leaf(n, w) if n in names else w
                for n, w in v.items()
            }
        elif k in names:
            out[k] = quantize_leaf(k, v)
        else:
            out[k] = v
    return out
