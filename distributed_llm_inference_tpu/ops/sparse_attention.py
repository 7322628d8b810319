"""Learned sparse attention: the indexer's scores, the exact top-k selection
and attention over the selected keys, read from the page pool in place.

The mechanism is DeepSeek-V3.2's lightning indexer and top-k selection as a
``KeyeVL2`` block applies it over GQA (``ModelConfig.sparse``). A layer
projects, beside ``q``, ``k`` and ``v``, ``index_heads`` index queries ``qI``
and ONE index key ``kI`` of ``index_dim`` a token and a weight ``w`` a head
(``models/llama.py``); the index key is cached beside K and V in a plane of
its own (``cache/paged.py``: the indexed cache classes). For a query at
position ``t`` and a key at ``s <= t``

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])

and the query attends to ``S_t``, the ``topk`` positions of largest ``I``
(ties to the lower position; every ``s <= t`` while ``t < topk``), the same
set for all attention heads.

Three steps, each under its own scope in a device trace:

* ``index_scores`` (:func:`index_scores`): the scores of a block of queries
  against a row's index keys, gathered by the page table. Plain XLA: a
  64-deep contraction a head, a relu and the weighted sum over heads.
* ``index_select`` (:func:`select_topk`): the EXACT selection as a mask, with
  no sort: the ``k``-th largest score is found by 32 steps of bisection over
  the scores' bit patterns (a count of the elements at or above a candidate
  a step), then every score above it is taken and, of those equal to it, the
  first ones by position. Cost is fixed by the shape, and the result is the
  mask the kernels want.
* ``sparse_attention``: the dense paged kernels under one more mask. A
  decode step runs ``ops/paged_attention.py``'s fused in-place sweep
  (traced as ``sparse_paged_fused_attention``) with the row's selection over
  its pool positions and its tail slots; a prefill chunk runs the ragged
  kernel (``sparse_ragged_paged_attention``) with a mask a (query, key)
  pair, laid out a page a block. Both read EVERY live page and mask the
  unselected positions: with contexts a few times ``topk`` nearly every page
  of 64 holds a selected position, so fetching pages reads about
  context / topk times the selected rows' bytes and saves the row-by-row
  DMAs. The kernels' roofline readers count the selected rows alone, so
  this form reads low there, honestly (PERF.md).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "IndexInputs",
    "index_scores",
    "select_topk",
    "selection_mask",
    "KERNEL_DECODE",
    "KERNEL_PREFILL",
    "KERNEL_INDEX_FLUSH",
]

#: what a device trace calls the two kernels under a selection
KERNEL_DECODE = "sparse_paged_fused_attention"
KERNEL_PREFILL = "sparse_ragged_paged_attention"
#: the index tail's merge into its plane (``paged_tail_flush`` over one
#: value plane, ``cache/paged.py``)
KERNEL_INDEX_FLUSH = "index_tail_flush"

# Bytes one block of queries' per-head scores ``[B, block, heads, N]`` (f32)
# may take before the relu and the weighted sum reduce them to ``[B, block,
# N]``: a 4096-wide chunk over a 10752-position table is 2.8 GB whole.
_SCORE_BLOCK_BYTES = 128 * 2**20


class IndexInputs(NamedTuple):
    """What the model hands a cache beside q, k, v when it selects keys:
    ``q`` ``[B, S, Hi, Di]`` index queries (rotated), ``k`` ``[B, S, Di]``
    index keys (normed, rotated: the stored form), ``w`` ``[B, S, Hi]`` head
    weights (scaled) and the static ``topk``."""

    q: jnp.ndarray
    k: jnp.ndarray
    w: jnp.ndarray
    topk: int


def index_scores(qi, w, keys):
    """``I[b, s, n]`` float32 of queries ``qi [B, S, Hi, Di]`` with weights
    ``w [B, S, Hi]`` against index keys ``keys [B, N, Di]``."""
    with jax.named_scope("index_scores"):
        dots = jnp.einsum(
            "bshd,bnd->bshn", qi, keys.astype(qi.dtype),
            preferred_element_type=jnp.float32,
        )
        scores = jnp.einsum(
            "bshn,bsh->bsn", jax.nn.relu(dots), w.astype(jnp.float32)
        )
        # -0.0 (every head's relu zero under a negative weight) and 0.0 are
        # one score: the order below is over bit patterns.
        return jnp.where(scores == 0, 0.0, scores)


def select_topk(scores, valid, k: int):
    """Mask ``[..., N]`` of the ``k`` largest ``scores`` among ``valid``
    (all of them where fewer than ``k`` are valid), ties to the lower index.
    Exact: see the module's text."""
    with jax.named_scope("index_select"):
        bits = jax.lax.bitcast_convert_type(
            scores.astype(jnp.float32), jnp.uint32
        )
        # unsigned order of ``u`` is the floats' order; no score maps to 0
        # (that would be a NaN's pattern), which is what invalid ones get
        u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
        u = jnp.where(valid, u, jnp.uint32(0))

        def narrow(i, t):
            cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
            enough = jnp.sum(
                u >= cand[..., None], axis=-1, dtype=jnp.int32
            ) >= k
            return jnp.where(enough, cand, t)

        kth = jax.lax.fori_loop(
            0, 32, narrow, jnp.zeros(u.shape[:-1], jnp.uint32)
        )[..., None]
        above = u > kth
        equal = (u == kth) & valid
        room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        first = jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= room
        return (above | (equal & first)) & valid


def _query_block(b: int, s: int, heads: int, n: int) -> int:
    block = 1
    while 2 * block <= s and b * 2 * block * heads * n * 4 <= _SCORE_BLOCK_BYTES:
        block *= 2
    return block


def selection_mask(index: IndexInputs, keys, q_pos, kv_len,
                   key_pos=None, key_valid=None):
    """The selection of every query as a mask ``[B, S, N]`` (bool) over
    ``N`` candidate keys ``keys [B, N, Di]``. A key is a candidate of a query at ``q_pos [B, S]`` if it is live
    and not after it: by default key ``n`` sits at position ``n`` and is
    live under ``kv_len [B]``; ``key_pos`` / ``key_valid`` ``[B, N]`` say
    otherwise (a decode step's tail slots behind its pool positions).
    Queries are scored a block at a time (:data:`_SCORE_BLOCK_BYTES`)."""
    b, s = q_pos.shape
    n = keys.shape[1]
    if key_pos is None:
        key_pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))
    if key_valid is None:
        key_valid = key_pos < kv_len[:, None]

    def block_mask(qi, w, pos):
        valid = key_valid[:, None, :] & (key_pos[:, None, :] <= pos[:, :, None])
        return select_topk(
            index_scores(qi, w, keys), valid, index.topk
        )

    block = _query_block(b, s, index.q.shape[2], n)
    if block >= s:
        return block_mask(index.q, index.w, q_pos)
    pad = -s % block
    blocks = (s + pad) // block

    def split(x):  # [B, S, ...] -> [blocks, B, block, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(
            x.reshape(b, blocks, block, *x.shape[2:]), 1, 0
        )

    masks = jax.lax.map(
        lambda xs: block_mask(*xs),
        (split(index.q), split(index.w), split(q_pos)),
    )                                            # [blocks, B, block, N]
    return jnp.moveaxis(masks, 0, 1).reshape(b, s + pad, n)[:, :s]
