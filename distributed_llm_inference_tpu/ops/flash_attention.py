"""Pallas flash-attention kernel (prefill hot path).

The Pallas realization of the attention the reference computes eagerly —
QK^T, additive mask, fp32 softmax, PV with a materialized ``[B, H, S, T]``
score tensor (``/root/reference/distributed_llm_inference/models/llama/
modules.py:87-97``). Flash tiling never materializes scores in HBM: the grid
walks (batch, kv-head, q-block, kv-block) with the online-softmax running
max/denominator and the output accumulator living in VMEM scratch, carried
across the kv-block grid dimension (TPU grids iterate the last axis
innermost, so scratch persists across the kv sweep for one q-block).

GQA is folded into the matmul rows: the ``G = Hq/Hkv`` query heads sharing a
kv head are flattened into the q-block's row dimension, so every MXU call
contracts ``[BQ*G, D] x [D, BK]`` — the ``repeat_kv`` HBM expansion of the
reference (``modules.py:87-88``) never exists.

Same signature as :func:`ops.attention.gqa_attention` (the XLA fallback and
test oracle): boolean mask carries causality, cache validity, sliding window,
and sink structure, so every cache policy works unchanged. Runs in interpret
mode off-TPU, making the kernel testable on the CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _NEG_INF, gqa_attention

__all__ = ["flash_attention"]


def _flash_kernel(
    q_ref,      # [1, 1, BQ, G, D]
    k_ref,      # [1, 1, BK, D]
    v_ref,      # [1, 1, BK, D]
    mask_ref,   # [1, BQ, BK] bool
    out_ref,    # [1, 1, BQ, G, D]
    acc_ref,    # VMEM [BQ*G, D] f32
    m_ref,      # VMEM [BQ*G, 128] f32 (stats broadcast across lanes)
    l_ref,      # VMEM [BQ*G, 128] f32
    *,
    scale: float,
    num_k_blocks: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    _, _, bq, g, d = q_ref.shape
    bk = k_ref.shape[2]
    rows = bq * g

    q = q_ref[0, 0].reshape(rows, d)
    k = k_ref[0, 0]
    v = v_ref[0, 0]

    # [BQ*G, BK] scores on the MXU, fp32.
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    mask = jnp.repeat(mask_ref[0], g, axis=0)  # [BQ, BK] -> [BQ*G, BK]
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:, :1]  # [rows, 1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)

    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        # Fully-masked rows (query padding) have l == 0 -> emit zeros.
        l = l_ref[:, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-20)
        out_ref[0, 0] = out.reshape(bq, g, d).astype(out_ref.dtype)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Drop-in for :func:`gqa_attention` on shapes the tiling accepts;
    delegates to the XLA path otherwise (decode steps, ragged tiles).

    ``q``: ``[B, S, Hq, D]``; ``k``/``v``: ``[B, T, Hkv, D]``;
    ``mask``: bool ``[B, S, T]`` (True = attend).
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5

    bq = min(block_q, s)
    bk = min(block_k, t)
    # Tiling preconditions; anything else takes the always-correct XLA path
    # (notably S == 1 decode, whose attention is bandwidth-trivial).
    if s % bq or t % bk or s < 8 or mask is None or mask.ndim != 3:
        return gqa_attention(q, k, v, mask, scale)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # [B, Hkv, S, G, D]: kv-head-major so one grid cell's q rows are the G
    # query heads of one kv head.
    qr = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4)
    kr = k.transpose(0, 2, 1, 3)  # [B, Hkv, T, D]
    vr = v.transpose(0, 2, 1, 3)

    grid = (b, hkv, s // bq, t // bk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, num_k_blocks=t // bk
    )
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        out_shape=jax.ShapeDtypeStruct((b, hkv, s, g, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, g, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, bq, bk), lambda bi, hi, qi, ki: (bi, qi, ki)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, g, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bq * g, d), jnp.float32),
            pltpu.VMEM((bq * g, 128), jnp.float32),
            pltpu.VMEM((bq * g, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, mask)
    # [B, Hkv, S, G, D] -> [B, S, Hq, D]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, hq, d)
