"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880) around a
sublayer, over a residual stream ``n = hyper.mult`` rows wide.

The stream of a token is ``x [n, C]``. Around each sublayer ``F`` (attention
or the MLP, each with its own pre-norm) three maps are computed from the
stream itself, in float32 whatever the activations' dtype::

    x~      = RMSNorm(vec(x))                  over all nC values, no gain
    H~_pre  = a_pre  (x~ phi_pre)  + b_pre     [n]
    H~_post = a_post (x~ phi_post) + b_post    [n]
    H~_res  = a_res  mat(x~ phi_res) + b_res   [n, n]
    H_pre   = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
    H_res   = Sinkhorn(exp(clip(H~_res)))      ``sinkhorn_iters`` times
                                               columns then rows, ``eps`` in
                                               each divisor
    h       = sum_i H_pre[i] x[i]              what F reads     (pre_mix)
    x'[i]   = sum_j H_res[i, j] x[j] + H_post[i] F(h)           (post_mix)

A sublayer's parameters are three float32 leaves under its prefix (``hc_attn``
/ ``hc_mlp``): ``_phi [2n + n^2, nC]`` (the three projections stacked, ONE
matmul ``x~ phi^T``: rows ``pre | post | res`` with ``res`` row-major; stored
with the long axis last so that no tile of it is padding), ``_alpha [3]`` and
``_bias [2n + n^2]`` in the same order.

Plain ``jnp`` under ``jax.named_scope("mhc_pre")`` / ``("mhc_post")``: the
mixes are bandwidth-bound passes over the stream that XLA fuses. The maps
are carried as their ENTRIES (``n`` and ``n x n`` arrays a token-shaped
``[...]`` each) and the small sums over them, Sinkhorn's among them, are
adds of entries, not reductions over an axis of ``n`` nor matmuls with a
contraction of ``n``: an axis of 4 in the minor dimensions splits the 20
rounds into some eighty little fusions a mix on the chip, where the entries'
form is elementwise over ``[...]`` throughout.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..config import HyperConnectionConfig

F32 = jnp.float32


def leaf_shapes(hc: HyperConnectionConfig, hidden: int) -> dict:
    """Shapes of one sublayer's leaves (all float32)."""
    n = hc.mult
    width = 2 * n + n * n
    return {"phi": (width, n * hidden), "alpha": (3,), "bias": (width,)}


def _add(terms):
    out = terms[0]
    for t in terms[1:]:
        out = out + t
    return out


#: Sinkhorn rounds a trip of its loop. All twenty unrolled cost an XLA CPU
#: compile of 95 s a program at the tests' size (its fusion duplicates each
#: round's sums into the next round's sixteen entries) where trips of four
#: cost 11 s, and save 1.9 us of a 23.6 us mix at 32 rows on the chip and
#: nothing at 2048 tokens (tools/profile_mhc_mix.py, PR 47)
ROUNDS_A_TRIP = 4


def sinkhorn_entries(m, iters: int, eps: float):
    """``iters`` rounds over a positive ``n x n`` map given as its entries
    ``m[i][j] [...]`` (a list of lists of like arrays): every column divided
    by its sum (over the rows ``i``) plus ``eps``, then every row by its sum
    (over the columns ``j``) plus ``eps``. Entry by entry, so that a round
    is elementwise over ``[...]``."""
    n = len(m)

    def rounds(count, m):
        for _ in range(count):
            cols = [_add([m[i][j] for i in range(n)]) + eps for j in range(n)]
            m = [[m[i][j] / cols[j] for j in range(n)] for i in range(n)]
            rows = [_add(m[i]) + eps for i in range(n)]
            m = [[m[i][j] / rows[i] for j in range(n)] for i in range(n)]
        return m

    trips, rest = divmod(iters, ROUNDS_A_TRIP)
    if trips > 1:
        m = jax.lax.fori_loop(
            0, trips, lambda _, m: rounds(ROUNDS_A_TRIP, m), m
        )
    else:
        rest = iters
    return rounds(rest, m)


def _map_entries(hc: HyperConnectionConfig, p, prefix: str, x, norm_eps: float):
    """The three maps of the stream ``x [..., n, C]`` as their entries, each
    a float32 ``[...]``: ``h_pre[i]``, ``h_post[i]``, ``h_res[i][j]``."""
    n = hc.mult
    lead = x.shape[:-2]
    flat = x.reshape(*lead, n * x.shape[-1]).astype(F32)
    # RMSNorm has no gain here, so its scalar commutes with the projection:
    # (x r) phi = (x phi) r, and the stream is read once
    r = jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1) + norm_eps)
    proj = jnp.einsum(
        "...k,mk->...m", flat, p[f"{prefix}_phi"],
        precision=jax.lax.Precision.HIGHEST,
    )
    alpha, bias = p[f"{prefix}_alpha"], p[f"{prefix}_bias"]

    def logit(k, a):
        return proj[..., k] * r * alpha[a] + bias[k]

    h_pre = [jax.nn.sigmoid(logit(i, 0)) for i in range(n)]
    h_post = [2.0 * jax.nn.sigmoid(logit(n + i, 1)) for i in range(n)]
    lo, hi = hc.res_clamp
    m = [
        [jnp.exp(jnp.clip(logit(2 * n + i * n + j, 2), lo, hi)) for j in range(n)]
        for i in range(n)
    ]
    return h_pre, h_post, sinkhorn_entries(m, hc.sinkhorn_iters, hc.eps)


def maps(
    hc: HyperConnectionConfig, p, prefix: str, x: jnp.ndarray, norm_eps: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` in float32
    of the stream ``x [..., n, C]``."""
    h_pre, h_post, h_res = _map_entries(hc, p, prefix, x, norm_eps)
    return (
        jnp.stack(h_pre, -1), jnp.stack(h_post, -1),
        jnp.stack([jnp.stack(row, -1) for row in h_res], -2),
    )


def pre_mix(hc: HyperConnectionConfig, p, prefix: str, x, norm_eps: float):
    """What the sublayer reads, ``h [..., C]`` in ``x``'s dtype, and the
    two maps' entries :func:`post_mix` takes."""
    with jax.named_scope("mhc_pre"):
        h_pre, h_post, h_res = _map_entries(hc, p, prefix, x, norm_eps)
        h = _add([
            h_pre[i][..., None] * x[..., i, :].astype(F32)
            for i in range(hc.mult)
        ])
        return h.astype(x.dtype), (h_post, h_res)


def post_mix(x, y, mix):
    """The stream behind the sublayer: its rows mixed by ``H_res`` plus the
    sublayer's output ``y [..., C]`` weighed a row by ``H_post``."""
    h_post, h_res = mix
    n = len(h_post)
    with jax.named_scope("mhc_post"):
        yf = y.astype(F32)
        rows = [x[..., j, :].astype(F32) for j in range(n)]
        out = [
            _add(
                [h_post[i][..., None] * yf]
                + [h_res[i][j][..., None] * rows[j] for j in range(n)]
            )
            for i in range(n)
        ]
        return jnp.stack(out, -2).astype(x.dtype)
