"""Rotary position embeddings (RoPE).

The reference computes cos/sin once per block via an HF ``LlamaRotaryEmbedding``
module (``/root/reference/distributed_llm_inference/models/llama/model.py:23,55``
— note the bug there: it passes ``position_ids`` as the dtype-carrying ``x``
argument, SURVEY §2.9.4) and replays a CUDA-graphed ``apply_rotary_pos_emb``
for the decode path (``modules.py:28-34,73-76``). Here RoPE is a pair of pure
functions; XLA fuses them into the surrounding attention computation, so no
graph capture is needed.

Conventions match HF ``transformers`` (non-interleaved halves, ``rotate_half``).
Includes Llama-3 "llama3" frequency scaling and DeepSeek-V2/V3's "yarn".
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

from ..config import RopeScaling


class RopeAngles(NamedTuple):
    """Precomputed rotary state for one forward step.

    ``cos``/``sin`` are the tables for the *query* positions (``[B, S, D]``),
    computed once per block and shared by every layer (the reference computes
    them once per block too, ``models/llama/model.py:55``). ``inv_freq`` rides
    along for cache policies that must re-derive per-slot key angles (the sink
    cache's effective-position rotation).
    """

    inv_freq: jnp.ndarray
    cos: jnp.ndarray
    sin: jnp.ndarray


def rope_inv_freq(
    head_dim: int,
    theta: float,
    scaling: Optional[RopeScaling] = None,
) -> jnp.ndarray:
    """Per-frequency inverse wavelengths ``[head_dim // 2]`` (fp32)."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta**exponent)
    if scaling is None or scaling.rope_type == "default":
        return inv_freq
    if scaling.rope_type == "linear":
        return inv_freq / scaling.factor
    if scaling.rope_type == "llama3":
        orig = scaling.original_max_position_embeddings
        low_wavelen = orig / scaling.low_freq_factor
        high_wavelen = orig / scaling.high_freq_factor
        wavelen = 2.0 * math.pi / inv_freq
        scaled = inv_freq / scaling.factor
        smooth = (orig / wavelen - scaling.low_freq_factor) / (
            scaling.high_freq_factor - scaling.low_freq_factor
        )
        smoothed = (1.0 - smooth) * scaled + smooth * inv_freq
        out = jnp.where(wavelen > low_wavelen, scaled, inv_freq)
        is_medium = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
        return jnp.where(is_medium, smoothed, out)
    if scaling.rope_type == "yarn":
        # DeepSeek-V2/V3's ``DeepseekV3YarnRotaryEmbedding``: frequencies that
        # turn more than ``beta_fast`` times over the original context stay,
        # those that turn less than ``beta_slow`` times are divided by
        # ``factor``, and a linear ramp over the dims between blends the two.
        # (What YaRN does to the softmax scale is
        # ``RopeScaling.softmax_factor``, applied where the scale is made.)
        def correction_dim(rotations):
            return head_dim * math.log(
                scaling.original_max_position_embeddings
                / (rotations * 2.0 * math.pi)
            ) / (2.0 * math.log(theta))

        low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
        high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
        if low == high:
            high += 0.001  # the published guard against a zero-width ramp
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low),
            0.0, 1.0,
        )
        return inv_freq / scaling.factor * ramp + inv_freq * (1.0 - ramp)
    raise ValueError(f"unsupported rope_type: {scaling.rope_type}")


def rope_cos_sin(
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer ``positions`` ``[...]`` → ``[..., head_dim]``.

    The tables duplicate the half-dim frequencies across both halves, matching
    HF's ``emb = cat(freqs, freqs)`` layout.
    """
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
) -> jnp.ndarray:
    """Rotate ``x[..., seq, heads, head_dim]`` by ``cos/sin[..., seq, head_dim]``.

    Computed in fp32 and cast back — rotary precision matters for long-context
    position fidelity.
    """
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return (xf * c + rotate_half(xf) * s).astype(dtype)
