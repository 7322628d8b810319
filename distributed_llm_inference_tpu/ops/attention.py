"""Reference (XLA-fused) GQA attention and mask construction.

Replaces the reference's eager attention at
``/root/reference/distributed_llm_inference/models/llama/modules.py:87-97``:
QK^T/sqrt(d), additive causal mask, fp32 softmax, PV. Two TPU-first changes:

* No ``repeat_kv`` materialization (reference ``modules.py:87-88``): queries are
  reshaped to ``[B, S, Hkv, G, D]`` and contracted against KV heads directly, so
  the GQA expansion never touches HBM.
* Masks are boolean and fused into the softmax via ``where`` rather than a
  precomputed additive min-dtype tensor (reference ``models/llama/model.py:103-135``)
  — XLA folds the select into the fused softmax.

The Pallas flash/paged kernels in ``flash_attention.py`` / ``paged_attention.py``
are drop-in replacements for the hot paths; this module is the always-correct
fallback and the oracle for their tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def causal_mask(
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    kv_valid: Optional[jnp.ndarray] = None,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Boolean attend-mask ``[..., S, T]`` from per-token positions.

    ``q_positions``: ``[..., S]`` absolute positions of the queries.
    ``kv_positions``: ``[..., T]`` absolute positions of the cached keys.
    ``kv_valid``: optional ``[..., T]`` validity of each cache slot (ring
    buffers / padding).
    ``sliding_window``: Mistral-style window — key visible iff
    ``q_pos - w < k_pos <= q_pos``.
    """
    q = q_positions[..., :, None]
    k = kv_positions[..., None, :]
    mask = k <= q
    if sliding_window is not None:
        mask &= k > (q - sliding_window)
    if kv_valid is not None:
        mask &= kv_valid[..., None, :]
    return mask


def gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Grouped-query attention.

    ``q``: ``[B, S, Hq, D]``; ``k``/``v``: ``[B, T, Hkv, D]`` with
    ``Hq = G * Hkv``. ``mask``: boolean ``[B, S, T]`` or ``[B, 1, S, T]``
    (True = attend). Returns ``[B, S, Hq, D]`` in q's dtype; softmax in fp32
    (parity with reference ``modules.py:96``).
    """
    b, s, hq, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5

    qg = q.reshape(b, s, hkv, g, d)
    # [B, Hkv, G, S, T]
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32)
    scores = scores * scale

    if mask is not None:
        if mask.ndim == 3:
            m = mask[:, None, None, :, :]
        elif mask.ndim == 4:  # [B, 1, S, T]
            m = mask[:, :, None, :, :]
        else:
            raise ValueError(f"mask ndim {mask.ndim}")
        scores = jnp.where(m, scores, _NEG_INF)

    # Guard fully-masked rows (e.g. padded slots): softmax of all -inf → 0.
    weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    if mask is not None:
        weights = jnp.where(m, weights, 0.0)
    denom = jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights / jnp.maximum(denom, 1e-20)

    out = jnp.einsum(
        "bkgst,btkd->bskgd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, hq, d).astype(q.dtype)


def gqa_attention_quantized(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    ks: jnp.ndarray,
    v_q: jnp.ndarray,
    vs: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """GQA attention over an int8-quantized KV cache WITHOUT dequantizing it.

    ``k_q``/``v_q``: int8 ``[B, Hkv, T, D]`` (HEAD-major); ``ks``/``vs``:
    fp32 ``[B, Hkv, T]`` per-(token, head) scales. Two things keep the big
    int8 buffers on the minimal-traffic path:

    * the scales commute past the contractions — ``q·(k·s_t) = s_t·(q·k)``
      and ``p·(v·s_t) = (p·s_t)·v`` — so they are applied to the
      SCORES/probs (``[B, Hkv, G, S, T]``, small). The elementwise
      dequant-multiply formulation makes XLA materialize bf16 copies of K
      and V every step (write + re-read ≈ 3x the KV traffic; measured ~45%
      of the whole decode step at batch 80, Llama-7B shapes);
    * the head-major layout matches the contraction's batch(B, Hkv) ×
      contract(D or T) structure, so the int8→bf16 convert needs no
      relayout and stays fused in the dot's operand read.
    """
    b, s, hq, d = q.shape
    hkv, t = k_q.shape[1], k_q.shape[2]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5

    qg = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum(
        "bskgd,bktd->bkgst", qg, k_q.astype(q.dtype),
        preferred_element_type=jnp.float32,
    )
    # [B, Hkv, T] → [B, Hkv, 1, 1, T] broadcast over (G, S).
    k_scales = ks[:, :, None, None, :]
    scores = scores * (k_scales * scale)

    if mask is not None:
        if mask.ndim == 3:
            m = mask[:, None, None, :, :]
        elif mask.ndim == 4:  # [B, 1, S, T]
            m = mask[:, :, None, :, :]
        else:
            raise ValueError(f"mask ndim {mask.ndim}")
        scores = jnp.where(m, scores, _NEG_INF)

    weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    if mask is not None:
        weights = jnp.where(m, weights, 0.0)
    denom = jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights / jnp.maximum(denom, 1e-20)

    v_scales = vs[:, :, None, None, :]
    wv = (weights * v_scales).astype(q.dtype)
    out = jnp.einsum(
        "bkgst,bktd->bskgd", wv, v_q.astype(q.dtype),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, hq, d).astype(q.dtype)


def gqa_attention_segments(
    q: jnp.ndarray,
    segments: Sequence[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]],
    scale: Optional[float] = None,
    head_major: bool = False,
) -> jnp.ndarray:
    """GQA attention over MULTIPLE KV segments under one joint softmax.

    Exact (not an approximation): softmax is linear in its pieces once a
    global max is shared, so splitting the keys into segments changes only
    the association order. Used by the fused multi-step decode
    (``models/llama.py:multi_decode_apply``): segment 0 is the big read-only
    cache, segment 1 the small write-behind tail.

    ``q``: ``[B, S, Hq, D]``; each segment ``(k, v, valid)`` with
    ``k``/``v`` ``[B, Ti, Hkv, D]`` (time-major; ``[B, Hkv, Ti, D]`` with
    ``head_major``, the paged pool's gathered spans) and ``valid`` ``[B,
    Ti]`` (True = attend). Returns ``[B, S, Hq, D]``.
    """
    b, s, hq, d = q.shape
    kv = "bktd" if head_major else "btkd"
    hkv = segments[0][0].shape[1 if head_major else 2]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    qg = q.reshape(b, s, hkv, g, d)

    scored = []
    for k, v, valid in segments:
        sc = jnp.einsum(
            f"bskgd,{kv}->bkgst", qg, k,
            preferred_element_type=jnp.float32,
        ) * scale
        m = valid[:, None, None, None, :]
        scored.append((jnp.where(m, sc, _NEG_INF), m))

    gmax = functools.reduce(
        jnp.maximum,
        [jnp.max(sc, axis=-1, keepdims=True) for sc, _ in scored],
    )
    # head-major: the product as the dot gives it, permuted afterwards (the
    # CPU backend has no bf16 dot with a permuted result)
    pv = f"bkgst,{kv}->" + ("bkgsd" if head_major else "bskgd")
    denom = 0.0
    out = 0.0
    for (sc, m), (k, v, valid) in zip(scored, segments):
        w = jnp.where(m, jnp.exp(sc - gmax), 0.0)
        denom = denom + jnp.sum(w, axis=-1, keepdims=True)
        out = out + jnp.einsum(
            pv, w.astype(v.dtype), v, preferred_element_type=jnp.float32,
        )
    denom = jnp.maximum(denom, 1e-20)
    if head_major:
        out = (out / denom).transpose(0, 3, 1, 2, 4)
    else:
        out = out / denom.transpose(0, 3, 1, 2, 4)
    return out.reshape(b, s, hq, d).astype(q.dtype)


def gqa_attention_quantized_multi_q_segments(
    segments: Sequence[Tuple[jnp.ndarray, ...]],
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Joint softmax over int8 head-major segments, each with its OWN query
    and full mask.

    The general form behind :func:`gqa_attention_quantized_segments`, needed
    by the quantized sink cache: its sink segment is attended with a
    window-relative-rotated query while the ring/tail segments use the
    absolute-rotated one (RoPE scores depend only on position differences —
    ``cache/sink.py``). Each segment is ``(q [B, S, Hq, D], k_q [B, Hkv,
    Ti, D] int8, ks [B, Hkv, Ti] f32, v_q, vs, mask)`` with ``mask`` either
    ``[B, S, Ti]`` or a broadcastable ``[B, 1, Ti]``.
    """
    q0 = segments[0][0]
    b, s, hq, d = q0.shape
    hkv = segments[0][1].shape[1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5

    scored = []
    for q, k_q, ks, v_q, vs, mask in segments:
        qg = q.reshape(b, s, hkv, g, d)
        sc = jnp.einsum(
            "bskgd,bktd->bkgst", qg, k_q.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        sc = sc * (ks[:, :, None, None, :] * scale)
        m = mask[:, None, None, :, :]  # [B, 1, 1, S, T]
        scored.append((jnp.where(m, sc, _NEG_INF), m))

    gmax = functools.reduce(
        jnp.maximum,
        [jnp.max(sc, axis=-1, keepdims=True) for sc, _ in scored],
    )
    denom = 0.0
    out = 0.0
    for (sc, m), (q, k_q, ks, v_q, vs, mask) in zip(scored, segments):
        w = jnp.where(m, jnp.exp(sc - gmax), 0.0)
        denom = denom + jnp.sum(w, axis=-1, keepdims=True)
        wv = (w * vs[:, :, None, None, :]).astype(q0.dtype)
        out = out + jnp.einsum(
            "bkgst,bktd->bskgd", wv, v_q.astype(q0.dtype),
            preferred_element_type=jnp.float32,
        )
    denom = jnp.maximum(denom, 1e-20).transpose(0, 3, 1, 2, 4)
    return (out / denom).reshape(b, s, hq, d).astype(q0.dtype)


def gqa_attention_quantized_segments(
    q: jnp.ndarray,
    segments: Sequence[Tuple[jnp.ndarray, ...]],
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """As :func:`gqa_attention_segments` for int8 head-major segments.

    Each segment is ``(k_q, ks, v_q, vs, valid)`` with ``k_q``/``v_q`` int8
    ``[B, Hkv, Ti, D]``, ``ks``/``vs`` f32 ``[B, Hkv, Ti]``, ``valid``
    ``[B, Ti]``. Scales apply to scores/probs (see
    :func:`gqa_attention_quantized`), so the int8 buffers feed the matmuls
    directly. Delegates to the general shared-query-free form.
    """
    return gqa_attention_quantized_multi_q_segments(
        [
            (q, k_q, ks, v_q, vs, valid[:, None, :])
            for k_q, ks, v_q, vs, valid in segments
        ],
        scale,
    )


def merge_softmax_segments(
    q: jnp.ndarray,
    out_a: jnp.ndarray,
    m_a: jnp.ndarray,
    l_a: jnp.ndarray,
    k_tail: jnp.ndarray,
    v_tail: jnp.ndarray,
    tail_valid: jnp.ndarray,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Joint softmax of a PRE-COMPUTED attention segment with a small tail.

    ``out_a`` (``[B, 1, Hq, D]``, already normalized) with online-softmax
    stats ``m_a``/``l_a`` (``[B, Hkv, G]``) comes from a kernel that swept
    its own keys (the paged pool); the tail segment (``k_tail``/``v_tail``
    ``[B, K, Hkv, D]`` time-major, ``tail_valid`` ``[B, K]``) holds the
    fused decode steps' fresh tokens. Flash-attention-style merge: exact,
    not an approximation.
    """
    b, s, hq, d = q.shape
    hkv = k_tail.shape[2]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    qg = q.reshape(b, s, hkv, g, d)

    sc = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k_tail, preferred_element_type=jnp.float32
    ) * scale                                            # [B, Hkv, G, 1, K]
    mask = tail_valid[:, None, None, None, :]
    sc = jnp.where(mask, sc, _NEG_INF)
    m_t = jnp.max(sc, axis=-1)                           # [B, Hkv, G, 1]
    w = jnp.where(mask, jnp.exp(sc - m_t[..., None]), 0.0)
    l_t = jnp.sum(w, axis=-1)                            # [B, Hkv, G, 1]
    pv_t = jnp.einsum(
        "bkgst,btkd->bskgd", w.astype(v_tail.dtype), v_tail,
        preferred_element_type=jnp.float32,
    )                                                    # [B, 1, Hkv, G, D]
    out_t = pv_t / jnp.maximum(l_t, 1e-20).reshape(b, 1, hkv, g, 1)

    m_t = m_t[..., 0]
    l_t = l_t[..., 0]
    m = jnp.maximum(m_a, m_t)                            # [B, Hkv, G]
    w_a = l_a * jnp.exp(m_a - m)
    w_t = l_t * jnp.exp(m_t - m)
    denom = jnp.maximum(w_a + w_t, 1e-20)
    fa = (w_a / denom)[:, None, :, :, None]
    ft = (w_t / denom)[:, None, :, :, None]
    out = (
        out_a.reshape(b, s, hkv, g, d).astype(jnp.float32) * fa
        + out_t * ft
    )
    return out.reshape(b, s, hq, d).astype(q.dtype)
