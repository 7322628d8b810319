"""Power retention (Manifest AI, arXiv:2507.04239): attention whose weights
are ``(q . k) ** 2`` decayed by a learned gate, which has an exact recurrent
form over a state of fixed size.

For query head ``h`` of key-value head ``m`` and ``j <= t``::

    a_tj = (q_t . k_j) ** 2 * exp(G_t - G_j)        G_t = sum_{i <= t} g_i
    o_t  = sum_j a_tj v_j / (sum_j a_tj + eps)

with ``g_i <= 0`` the log-gate of position ``i`` (one a key-value head). With
a feature map ``phi`` such that ``phi(x) . phi(y) = (x . y) ** 2`` the sums
over ANY prefix ``j < F`` are a state ``S = sum_j exp(G_ref - G_j) phi(k_j)
v_j^T`` and its sum of keys ``z = sum_j exp(G_ref - G_j) phi(k_j)``, referenced
to the gate sum ``G_ref`` of some position at or before every later query::

    o_t = (e^{G_t - G_ref} phi(q_t)^T S + sum_{j >= F} a_tj v_j)
        / (e^{G_t - G_ref} phi(q_t)^T z + sum_{j >= F} a_tj + eps)

Where the split ``F`` lies is tiling and no part of the mathematics; the
cache (``cache/retention.py``) folds at page boundaries.

**The feature map.** ``phi(x)`` is ``d / 2 + 1`` rotations of ``x`` against
itself, side by side: ``phi_s(x)[i] = c_s x[i] x[(i + s) mod d]`` for ``s = 0
.. d / 2``, with ``c_0 = 1`` (the squares), ``c_s = sqrt 2`` for ``0 < s < d
/ 2`` (each unordered pair once) and ``c_{d/2} = 1`` (each pair ``{i, i + d /
2}`` twice). ``D = (d / 2 + 1) d``: 8320 for 128, of which 8256 = 128 * 129 /
2 are distinct and 64 repeat, 0.8% over the triangle; 144 for 16 (136
distinct). Every rotation is ``d`` lanes wide, so a kernel makes ``phi`` of a
query or a key in registers by lane rotations and never reads it from memory,
and the state's ``[D, d]`` is ``d / 2 + 1`` square tiles.

Three computations, each a Pallas kernel on the chip (its ``name=`` is the
event's name in a device trace) beside the XLA form that the CPU and the
tests compare it with; the rehearsal runs the kernels interpreted:

* :func:`power_retention_decode` / ``_xla`` (memory-bound): one query a live
  row, ``phi(q)^T S`` and ``phi(q)^T z`` for the ``G`` query heads of a
  key-value head from ONE read of the head's state, plus the unfolded
  positions pair by pair, one normalisation.
* :func:`power_retention_prefill` / :func:`retention_chunk` (compute-bound):
  a chunk's queries, sub-chunk by sub-chunk: against the running state, the
  quadratic part inside the sub-chunk under the causal, decayed mask, and the
  fold of the sub-chunk's folded positions into the state.
* :func:`power_retention_fold` / :func:`retention_fold`: positions into the
  state, in place, for the rows that fold (the decode window's once-a-page
  fold; the chunk forms' fold step is the same mathematics).

Float32 accumulation everywhere; the state is float32. On the chip a
float32 matmul runs in bfloat16 passes: the kernels take the state and ``phi``
in two bfloat16 halves each (three products; ``Precision.HIGH`` in the XLA
forms).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: the XLA forms' precision for contractions with the float32 state: three
#: bfloat16 passes (both operands in two halves)
STATE_PRECISION = jax.lax.Precision.HIGH
#: positions a step of :func:`retention_chunk` takes (a multiple of every
#: page size in use; the cache rounds it up to one)
SUB_CHUNK = 256


def num_shifts(head_dim: int) -> int:
    return head_dim // 2 + 1


def feature_dim(head_dim: int) -> int:
    return num_shifts(head_dim) * head_dim


def shift_weights(head_dim: int) -> Tuple[float, ...]:
    """``c_s`` of the rotations ``s = 0 .. d / 2``."""
    half = head_dim // 2
    return (1.0,) + (math.sqrt(2.0),) * (half - 1) + (1.0,)


def shift_feature(x, s: int, c: float):
    """``phi_s(x)``: rotation ``s`` of the feature map, ``[..., d]``."""
    return x * jnp.roll(x, -s, axis=-1) * c if s else x * x


def power_features(x) -> jnp.ndarray:
    """``phi(x)``: ``[..., d]`` to ``[..., D]`` float32 with ``phi(x) .
    phi(y) = (x . y) ** 2``."""
    x = x.astype(F32)
    d = x.shape[-1]
    return jnp.concatenate(
        [shift_feature(x, s, c) for s, c in enumerate(shift_weights(d))],
        axis=-1,
    )


def _interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


# ---------------------------------------------------------------------------
# decode: one query a row
# ---------------------------------------------------------------------------


def power_retention_decode_xla(q, state, zsum, dec, k_pairs, v_pairs, w_pairs,
                               eps: float):
    """One query a row against its state and its unfolded positions.

    ``q [B, Hkv, G, d]`` (the ``G`` query heads of each key-value head);
    ``state [B, Hkv, D, d]``, ``zsum [B, Hkv, D]`` float32; ``dec [B, Hkv]``
    = ``exp(G_t - G_ref)``; ``k_pairs``, ``v_pairs [B, Hkv, n, d]`` and
    ``w_pairs [B, Hkv, n]`` = ``exp(G_t - G_j)`` of the positions attended
    pair by pair, 0 where a place holds none. Returns ``[B, Hkv, G, d]``
    float32."""
    with jax.named_scope("retention_state"):
        phi = power_features(q)
        num = jnp.einsum(
            "bhgf,bhfd->bhgd", phi, state, precision=STATE_PRECISION,
            preferred_element_type=F32,
        ) * dec[..., None, None]
        den = jnp.einsum(
            "bhgf,bhf->bhg", phi, zsum, precision=STATE_PRECISION,
            preferred_element_type=F32,
        ) * dec[..., None]
    with jax.named_scope("retention_tail"):
        s = jnp.einsum(
            "bhgd,bhnd->bhgn", q, k_pairs, preferred_element_type=F32
        )
        a = s * s * w_pairs[:, :, None, :]
        num = num + jnp.einsum(
            "bhgn,bhnd->bhgd", a.astype(v_pairs.dtype), v_pairs,
            preferred_element_type=F32,
        )
        den = den + jnp.sum(a, axis=-1)
    return num / (den + eps)[..., None]


def live_rows(num_new):
    """The rows a decode window walks: ``(count, rows [B])``, the rows whose
    ``num_new`` is positive first and in order, the last of them repeated
    behind (a repeated block is not fetched again); a window with none
    walks row 0."""
    b = num_new.shape[0]
    live = num_new > 0
    order = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(jnp.int32)
    count = jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)
    rows = order[jnp.minimum(jnp.arange(b), count - 1)]
    return count, rows


def power_retention_decode(q, state, zsum, dec, k_pairs, v_pairs, w_pairs,
                           eps: float, layer=None, walk=None,
                           interpret: Optional[bool] = None):
    """:func:`power_retention_decode_xla` as a kernel that walks the live
    rows only. ``state [L, B, Hkv, D, d]`` and ``zsum [L, B, Hkv, D]`` are the
    WHOLE stacks with ``layer`` (int32 ``[1]``) naming the layer (a slice
    feeding a kernel would copy the layer's state through memory every
    step), or one layer's with ``layer`` None. ``walk``: :func:`live_rows`
    of the window (every row where None). A (row, key-value head) step reads
    the head's ``[D, d]`` state once, makes ``phi(q)`` of its ``G`` queries a
    rotation at a time in registers, and takes each rotation's ``[d, d]``
    tile through the MXU in two bfloat16 halves against ``phi`` in two halves
    stacked in one operand; the pairs' part and the division follow in the
    same step. Rows outside the walk return zeros."""
    b, hkv, g, d = q.shape
    sh = num_shifts(d)
    if layer is None:
        state, zsum = state[None], zsum[None]
        layer = jnp.zeros((1,), jnp.int32)
    num_l = state.shape[0]
    n = k_pairs.shape[2]
    gp = -(-g // 8) * 8
    count, rows = walk if walk is not None else (
        jnp.int32(b), jnp.arange(b, dtype=jnp.int32)
    )
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    state6 = state.reshape(num_l, b, hkv, sh, d, d)
    zsum5 = zsum.reshape(num_l, b, hkv, sh, d)
    lanes = jnp.broadcast_to(dec.astype(F32)[:, :, None, None], (b, hkv, 1, 128))
    w4 = w_pairs.astype(F32)[:, :, None, :]

    def at_row(i, h, lref, rref):
        return (rref[i], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, gp, d), at_row),
            pl.BlockSpec(
                (1, 1, 1, sh, d, d),
                lambda i, h, lref, rref: (lref[0], rref[i], h, 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, 1, sh, d),
                lambda i, h, lref, rref: (lref[0], rref[i], h, 0, 0),
            ),
            pl.BlockSpec((1, 1, 1, 128), at_row),
            pl.BlockSpec((1, 1, n, d), at_row),
            pl.BlockSpec((1, 1, n, d), at_row),
            pl.BlockSpec((1, 1, 1, n), at_row),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, d), at_row),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, eps=eps, gp=gp),
        name="power_retention_decode",
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), F32),
        grid_spec=grid_spec,
        interpret=_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(layer.astype(jnp.int32), rows.astype(jnp.int32), qp, state6, zsum5,
      lanes, k_pairs, v_pairs, w4)
    walked = jnp.zeros((b,), bool).at[rows].set(True)
    return jnp.where(walked[:, None, None, None], out[:, :, :g], 0.0)


def _rotation(x, s, d: int):
    """``phi_s`` of ``x [rows, d]`` inside a kernel: a lane rotation (``s``
    a Python int or a loop's index)."""
    c = jnp.where((s == 0) | (s == d // 2), 1.0, math.sqrt(2.0))
    return x * pltpu.roll(x, (d - s) % d, 1) * c.astype(F32)


def _halves(x):
    """A float32 array as two bfloat16 halves whose sum is it to 16 bits."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(F32)).astype(jnp.bfloat16)


def _decode_kernel(lref, rref, q_ref, s_ref, z_ref, dec_ref, k_ref, v_ref,
                   w_ref, o_ref, *, eps, gp):
    q = q_ref[0, 0].astype(F32)                       # [gp, d]
    d = q.shape[-1]
    num = jnp.zeros((gp, d), F32)
    den = jnp.zeros((gp, 1), F32)
    for s in range(num_shifts(d)):
        f = _rotation(q, s, d)
        f_hi, f_lo = _halves(f)
        both = jnp.concatenate([f_hi, f_lo], axis=0)  # [2 gp, d]
        s_hi, s_lo = _halves(s_ref[0, 0, 0, s])       # [d, d]
        part = (
            jnp.dot(both, s_hi, preferred_element_type=F32)
            + jnp.dot(both, s_lo, preferred_element_type=F32)
        )
        num = num + part[:gp] + part[gp:]
        den = den + jnp.sum(f * z_ref[0, 0, 0, s][None, :], axis=-1,
                            keepdims=True)
    dec = dec_ref[0, 0, 0, 0]
    k, v = k_ref[0, 0], v_ref[0, 0]                   # [n, d]
    sc = jax.lax.dot_general(
        q_ref[0, 0], k, (((1,), (1,)), ((), ())), preferred_element_type=F32
    )                                                 # [gp, n]
    a = sc * sc * w_ref[0, 0]
    num = num * dec + jnp.dot(a.astype(v.dtype), v, preferred_element_type=F32)
    den = den * dec + jnp.sum(a, axis=-1, keepdims=True)
    o_ref[0, 0] = num / (den + eps)


# ---------------------------------------------------------------------------
# fold: positions into the state
# ---------------------------------------------------------------------------


def _fold_weights(g_ref, gsum, fold):
    """What a fold of ``fold [B, n]`` of the positions ``gsum [..., B, n,
    Hkv]`` into a state referenced to ``g_ref [..., B, Hkv]`` multiplies by:
    ``(new_ref, keep, w)``: the new reference (the least gate sum folded,
    the last position's: gate sums never grow; the old one where none
    folds), the state's decay to it, and each position's (0 where it does
    not fold)."""
    mask = fold[..., None]
    new_ref = jnp.minimum(
        jnp.min(jnp.where(mask, gsum, g_ref[..., None, :]), axis=-2), g_ref
    )
    w = jnp.where(
        mask, jnp.exp(jnp.minimum(new_ref[..., None, :] - gsum, 0.0)), 0.0
    )
    return new_ref, jnp.exp(new_ref - g_ref), w


def retention_fold(state, zsum, g_ref, k, v, gsum, fold):
    """Fold positions into a state (XLA; every rotation a ``[d, n] x [n,
    d]`` product). ``state [B, Hkv, D, d]``, ``zsum [B, Hkv, D]``, ``g_ref
    [B, Hkv]`` (the gate sum the state is referenced to); ``k``, ``v [B, n,
    Hkv, d]``, ``gsum [B, n, Hkv]`` the positions' gate sums, ``fold [B, n]``
    which of them fold. The new reference is the least gate sum among them
    (the last position's: gate sums never grow), or the old one where none
    folds. Returns ``(state, zsum, g_ref)``."""
    with jax.named_scope("retention_fold"):
        d = k.shape[-1]
        new_ref, keep, w = _fold_weights(g_ref, gsum, fold)   # w [B, n, Hkv]
        k32 = k.astype(F32)
        wv = v.astype(F32) * w[..., None]
        s5 = state.reshape(*state.shape[:2], -1, d, d)
        z4 = zsum.reshape(*zsum.shape[:2], -1, d)
        new_s, new_z = [], []
        for s, c in enumerate(shift_weights(d)):
            fk = shift_feature(k32, s, c)                     # [B, n, Hkv, d]
            new_s.append(
                s5[:, :, s] * keep[..., None, None] + jnp.einsum(
                    "bnhi,bnhd->bhid", fk, wv, precision=STATE_PRECISION,
                    preferred_element_type=F32,
                )
            )
            new_z.append(
                z4[:, :, s] * keep[..., None]
                + jnp.sum(fk * w[..., None], axis=1)
            )
        return (
            jnp.stack(new_s, axis=2).reshape(state.shape),
            jnp.stack(new_z, axis=2).reshape(zsum.shape),
            new_ref,
        )


def power_retention_fold(state, zsum, g_ref, k, v, gsum, fold, walk=None,
                         interpret: Optional[bool] = None):
    """:func:`retention_fold` of EVERY layer as one kernel, in place, over
    the rows that fold and no others: ``state [L, B, Hkv, D, d]``, ``zsum
    [L, B, Hkv, D]`` and ``g_ref [L, B, Hkv]`` are the whole planes (aliased
    to the results: a row that folds nothing is not read), ``k``, ``v [L, B,
    n, Hkv, d]``, ``gsum [L, B, n, Hkv]``, ``fold [B, n]``. ``walk``:
    :func:`live_rows` of the rows with a position to fold. A (row, layer,
    key-value head) step makes ``phi`` of the ``n`` keys a rotation at a time
    and adds each rotation's ``[d, n] x [n, d]`` product to its tile of the
    state, both operands in two bfloat16 halves. The decode window's fold:
    a window of 16 steps fills a page of 64 in one row of four, so three
    quarters of the pool are left alone."""
    num_l, b, hkv, width, d = state.shape
    n, sh = k.shape[2], num_shifts(d)
    new_ref, keep, w = _fold_weights(g_ref, gsum, fold)       # w [L, B, n, Hkv]
    count, rows = walk if walk is not None else live_rows(
        jnp.any(fold, axis=1).astype(jnp.int32)
    )
    heads = lambda x: jnp.moveaxis(x, 2, 3)                   # [L, B, Hkv, n, ..]
    wv = heads(v.astype(F32) * w[..., None])
    w8 = jnp.broadcast_to(heads(w)[:, :, :, None, :], (num_l, b, hkv, 8, n))
    keep_l = jnp.broadcast_to(keep[..., None, None], (num_l, b, hkv, 1, 128))

    def at(i, l, h, rref):
        return (l, rref[i], h, 0, 0)

    def at6(i, l, h, rref):
        return (l, rref[i], h, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count, num_l, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, 1, sh, d, d), at6),
            pl.BlockSpec((1, 1, 1, sh, d), at),
            pl.BlockSpec((1, 1, 1, n, d), at),
            pl.BlockSpec((1, 1, 1, n, d), at),
            pl.BlockSpec((1, 1, 1, 8, n), at),
            pl.BlockSpec((1, 1, 1, 1, 128), at),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, 1, sh, d, d), at6),
            pl.BlockSpec((1, 1, 1, sh, d), at),
        ),
    )
    new_state, new_zsum = pl.pallas_call(
        _fold_kernel,
        name="power_retention_fold",
        out_shape=(
            jax.ShapeDtypeStruct((num_l, b, hkv, sh, d, d), F32),
            jax.ShapeDtypeStruct((num_l, b, hkv, sh, d), F32),
        ),
        grid_spec=grid_spec,
        interpret=_interpret(interpret),
        # the state and the summed keys update in place (an alias's index
        # counts the scalar-prefetch operand too)
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(rows.astype(jnp.int32), state.reshape(num_l, b, hkv, sh, d, d),
      zsum.reshape(num_l, b, hkv, sh, d), heads(k), wv, w8, keep_l)
    return new_state.reshape(state.shape), new_zsum.reshape(zsum.shape), new_ref


def _fold_kernel(rref, s_ref, z_ref, k_ref, wv_ref, w_ref, keep_ref,
                 so_ref, zo_ref):
    k = k_ref[0, 0, 0].astype(F32)                    # [n, d]
    d = k.shape[-1]
    wv_hi, wv_lo = _halves(wv_ref[0, 0, 0])           # [n, d]
    w_hi, w_lo = _halves(w_ref[0, 0, 0])              # [8, n], rows alike
    keep = keep_ref[0, 0, 0, 0, 0]
    tn = (((0,), (0,)), ((), ()))                     # contract the positions

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=F32)

    sums = []
    for s in range(num_shifts(d)):
        f_hi, f_lo = _halves(_rotation(k, s, d))
        add = (
            jax.lax.dot_general(f_hi, wv_hi, tn, preferred_element_type=F32)
            + jax.lax.dot_general(f_hi, wv_lo, tn, preferred_element_type=F32)
            + jax.lax.dot_general(f_lo, wv_hi, tn, preferred_element_type=F32)
        )                                             # [d, d]
        so_ref[0, 0, 0, s] = s_ref[0, 0, 0, s] * keep + add
        sums.append((dot(w_hi, f_hi) + dot(w_hi, f_lo) + dot(w_lo, f_hi))[:1])
    zo_ref[0, 0, 0] = z_ref[0, 0, 0] * keep + jnp.concatenate(sums, axis=0)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def retention_chunk(q, k, v, gsum, valid_q, valid_k, fold, state, zsum,
                    g_ref, eps: float, sub: int = SUB_CHUNK):
    """A dispatch's queries, ``sub`` positions a step (XLA): linear across
    steps, quadratic inside one.

    Everything is in the row's positions since its last fold, in order:
    ``q [B, E, Hkv, G, d]``, ``k``, ``v [B, E, Hkv, d]``, ``gsum [B, E, Hkv]``
    float32, ``valid_q``, ``valid_k``, ``fold [B, E]`` (which places hold a
    query, a key, a key this dispatch folds), ``E`` a multiple of ``sub``.
    The caller sees to it that a step's unfolded keys have no query in a
    later step (folds end at a page boundary, ``sub`` is whole pages, and
    what lies past the last boundary is the dispatch's end). A step whose
    places hold nothing is skipped. Returns ``(out [B, E, Hkv, G, d]
    float32, state, zsum, g_ref)``."""
    b, e, hkv, g, d = q.shape
    steps = e // sub
    weights = shift_weights(d)

    def cut(x):
        return jnp.moveaxis(x.reshape(b, steps, sub, *x.shape[2:]), 1, 0)

    order = jnp.arange(sub)
    causal = order[None, :] <= order[:, None]                 # [t, j]

    def step(carry, xs):
        def live(carry):
            st, zs, ref = carry
            qc, kc, vc, gc, vq, vk, fc = xs
            with jax.named_scope("retention_state"):
                s5 = st.reshape(b, hkv, -1, d, d)
                z4 = zs.reshape(b, hkv, -1, d)
                q32 = qc.astype(F32)
                num = jnp.zeros((b, sub, hkv, g, d), F32)
                den = jnp.zeros((b, sub, hkv, g), F32)
                for s, c in enumerate(weights):
                    f = shift_feature(q32, s, c)
                    num = num + jnp.einsum(
                        "bchgi,bhid->bchgd", f, s5[:, :, s],
                        precision=STATE_PRECISION, preferred_element_type=F32,
                    )
                    den = den + jnp.einsum(
                        "bchgi,bhi->bchg", f, z4[:, :, s],
                        precision=STATE_PRECISION, preferred_element_type=F32,
                    )
                dec = jnp.exp(jnp.minimum(gc - ref[:, None, :], 0.0))
                num = num * dec[..., None, None]
                den = den * dec[..., None]
            with jax.named_scope("retention_tail"):
                sc = jnp.einsum(
                    "bchgd,bjhd->bhgcj", qc, kc, preferred_element_type=F32
                )
                gh = jnp.moveaxis(gc, 1, 2)                   # [B, Hkv, sub]
                decay = jnp.exp(jnp.minimum(
                    gh[:, :, :, None] - gh[:, :, None, :], 0.0
                ))                                            # [B, Hkv, t, j]
                seen = causal[None] & vk[:, None, :]          # [B, t, j]
                wgt = jnp.where(seen[:, None], decay, 0.0)
                a = sc * sc * wgt[:, :, None]
                num = num + jnp.einsum(
                    "bhgcj,bjhd->bchgd", a.astype(vc.dtype), vc,
                    preferred_element_type=F32,
                )
                den = den + jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)
            out = num / (den + eps)[..., None]
            out = jnp.where(vq[:, :, None, None, None], out, 0.0)
            return retention_fold(st, zs, ref, kc, vc, gc, fc), out

        def dead(carry):
            return carry, jnp.zeros((b, sub, hkv, g, d), F32)

        return jax.lax.cond(jnp.any(xs[5]), live, dead, carry)

    (state, zsum, g_ref), outs = jax.lax.scan(
        step, (state, zsum, g_ref),
        tuple(cut(x) for x in (q, k, v, gsum, valid_q, valid_k, fold)),
    )
    return (
        jnp.moveaxis(outs, 0, 1).reshape(b, e, hkv, g, d), state, zsum, g_ref
    )


def power_retention_prefill(q, k, v, gsum, valid_q, valid_k, fold, state,
                            zsum, g_ref, eps: float, sub: int = SUB_CHUNK,
                            interpret: Optional[bool] = None):
    """:func:`retention_chunk` as a kernel: a (row, key-value head) walks its
    sub-chunks in order with the head's running state in VMEM (the output
    block, aliased to the input's), so no rotation's features and no
    accumulator ever reach memory. A step: ``phi`` of its ``sub x G`` queries
    a rotation at a time against that rotation's tile of the state (both in
    two bfloat16 halves), the squared scores inside the sub-chunk under the
    causal, decayed mask, one division, then the fold of its folded keys
    into the state. A sub-chunk whose places hold nothing (a prompt's pad)
    is skipped. What is the same for every rotation is made outside, once:
    the decays of queries and of folded keys against the reference a step
    starts and ends with."""
    b, e, hkv, g, d = q.shape
    steps, sh = e // sub, num_shifts(d)
    at_step = lambda x: x.reshape(b, steps, sub, *x.shape[2:])
    # the reference a step ends with: the least gate sum folded so far
    least = jnp.min(jnp.where(
        at_step(fold)[..., None], at_step(gsum), jnp.inf
    ), axis=2)                                                # [B, steps, Hkv]
    after = jnp.minimum(
        jax.lax.cummin(least, axis=1), g_ref[:, None, :]
    )
    before = jnp.concatenate([g_ref[:, None, :], after[:, :-1]], axis=1)
    spread = lambda x: jnp.repeat(x, sub, axis=1)             # [B, E, Hkv]
    dec = jnp.exp(jnp.minimum(gsum - spread(before), 0.0))
    wf = jnp.where(
        fold[..., None], jnp.exp(jnp.minimum(spread(after) - gsum, 0.0)), 0.0
    )
    heads = lambda x: jnp.moveaxis(x, 1, 2)                   # [B, Hkv, E..]
    zeros = jnp.zeros_like(gsum)
    rows = heads(jnp.stack([
        gsum, dec, jnp.broadcast_to(valid_q[..., None], gsum.shape).astype(F32),
        wf, zeros, zeros, zeros, zeros,
    ], axis=-1))                                              # [B, Hkv, E, 8]
    cols = jnp.moveaxis(jnp.stack([
        gsum, jnp.broadcast_to(valid_k[..., None], gsum.shape).astype(F32),
        wf, zeros, zeros, zeros, zeros, zeros,
    ], axis=-1), (1, 2, 3), (3, 1, 2))                        # [B, Hkv, 8, E]
    keep = jnp.broadcast_to(
        jnp.moveaxis(jnp.exp(after - before), 1, 2)[..., None, None],
        (b, hkv, steps, 8, 128),
    )
    live = jnp.any(at_step(valid_k), axis=2).astype(jnp.int32).reshape(-1)

    def tile(bi, h, i, live):
        return (bi, h, i, 0)

    def whole(bi, h, i, live):
        return (bi, h, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, steps),
        in_specs=[
            pl.BlockSpec((1, 1, g, sub, d), lambda bi, h, i, live: (bi, h, 0, i, 0)),
            pl.BlockSpec((1, 1, sub, d), tile),
            pl.BlockSpec((1, 1, sub, d), tile),
            pl.BlockSpec((1, 1, sub, 8), tile),
            pl.BlockSpec((1, 1, 8, sub), lambda bi, h, i, live: (bi, h, 0, i)),
            pl.BlockSpec((1, 1, 1, 8, 128), lambda bi, h, i, live: (bi, h, i, 0, 0)),
            pl.BlockSpec((1, 1, sh, d, d), whole),
            pl.BlockSpec((1, 1, sh, 1, d), whole),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, g, sub, d), lambda bi, h, i, live: (bi, h, 0, i, 0)),
            pl.BlockSpec((1, 1, sh, d, d), whole),
            pl.BlockSpec((1, 1, sh, 1, d), whole),
        ),
    )
    out, new_state, new_zsum = pl.pallas_call(
        functools.partial(
            _prefill_kernel, g=g, sub=sub, d=d, steps=steps, eps=eps,
        ),
        name="power_retention_prefill",
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, e, d), F32),
            jax.ShapeDtypeStruct((b, hkv, sh, d, d), F32),
            jax.ShapeDtypeStruct((b, hkv, sh, 1, d), F32),
        ),
        grid_spec=grid_spec,
        interpret=_interpret(interpret),
        # the state and the summed keys update in place (an alias's index
        # counts the scalar-prefetch operand too)
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(live, jnp.moveaxis(q, (2, 3), (1, 2)), heads(k), heads(v), rows, cols,
      keep, state.reshape(b, hkv, sh, d, d), zsum.reshape(b, hkv, sh, 1, d))
    return (
        jnp.moveaxis(out, (1, 2), (2, 3)), new_state.reshape(state.shape),
        new_zsum.reshape(zsum.shape), after[:, -1],
    )


def _prefill_kernel(live_ref, q_ref, k_ref, v_ref, row_ref, col_ref, keep_ref,
                    s_ref, z_ref, o_ref, so_ref, zo_ref, *, g, sub, d, steps,
                    eps):
    i = pl.program_id(2)
    at = pl.program_id(0) * steps + i
    half = d // 2

    @pl.when(i == 0)
    def _():
        so_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]

    @pl.when(live_ref[at] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=F32)

    @pl.when(live_ref[at] != 0)
    def _():
        q = q_ref[0, 0].reshape(g * sub, d)
        q32 = q.astype(F32)
        k, v = k_ref[0, 0], v_ref[0, 0]                       # [sub, d]
        rows, cols = row_ref[0, 0], col_ref[0, 0]             # [sub, 8], [8, sub]
        keep = keep_ref[0, 0, 0, 0, 0]

        def query(s, carry):
            num, fz = carry
            f = _rotation(q32, s, d)
            f_hi, f_lo = _halves(f)
            s_hi, s_lo = _halves(so_ref[0, 0, s])
            num = num + dot(f_hi, s_hi) + dot(f_hi, s_lo) + dot(f_lo, s_hi)
            return num, fz + f * zo_ref[0, 0, s]

        zero = jnp.zeros((g * sub, d), F32)
        num, fz = jax.lax.fori_loop(0, half + 1, query, (zero, zero))
        den = jnp.sum(fz, axis=1, keepdims=True)              # [g sub, 1]
        # inside the sub-chunk: key j under query t, decayed
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
        )                                                     # [g sub, sub]
        order = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
        key = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
        seen = (key <= order) & (cols[1:2] > 0.0)
        decay = jnp.where(
            seen, jnp.exp(jnp.minimum(rows[:, 0:1] - cols[0:1], 0.0)), 0.0
        )                                                     # [t, j]
        dec = rows[:, 1:2]
        outs = []
        for gi in range(g):
            part = slice(gi * sub, (gi + 1) * sub)
            a = scores[part] * scores[part] * decay
            top = num[part] * dec + dot(a.astype(v.dtype), v)
            below = den[part] * dec + jnp.sum(a, axis=1, keepdims=True)
            outs.append(jnp.where(rows[:, 2:3] > 0.0, top / (below + eps), 0.0))
        o_ref[0, 0] = jnp.stack(outs, axis=0)
        # the fold of this sub-chunk's folded keys
        k32 = k.astype(F32)
        wv_hi, wv_lo = _halves(v.astype(F32) * rows[:, 3:4])
        c_hi, c_lo = _halves(cols)
        tn = (((0,), (0,)), ((), ()))

        def fold(s, carry):
            f_hi, f_lo = _halves(_rotation(k32, s, d))
            add = (
                jax.lax.dot_general(f_hi, wv_hi, tn, preferred_element_type=F32)
                + jax.lax.dot_general(f_hi, wv_lo, tn, preferred_element_type=F32)
                + jax.lax.dot_general(f_lo, wv_hi, tn, preferred_element_type=F32)
            )
            so_ref[0, 0, s] = so_ref[0, 0, s] * keep + add
            zo_ref[0, 0, s] = zo_ref[0, 0, s] * keep + (
                dot(c_hi, f_hi) + dot(c_hi, f_lo) + dot(c_lo, f_hi)
            )[2:3]
            return carry

        jax.lax.fori_loop(0, half + 1, fold, 0)
