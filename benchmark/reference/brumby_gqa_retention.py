"""Plain reference: the ``brumby`` decoder (Brumby-14B-Base), the Qwen3 block
with softmax attention replaced by power retention (Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239, and the model
card), by the QUADRATIC form: one ``[T, T]`` weight matrix a head, no state,
no cache, no recurrence.

The layer (``n`` the RMS-normed input of the sublayer):

* ``q = W_q n`` (``Hq`` heads of ``d``), ``k = W_k n``, ``v = W_v n`` (``Hkv``
  heads); ``q``, ``k`` <- per-head RMSNorm with a learned gain (Qwen3's
  ``q_norm`` / ``k_norm``), then RoPE (halves rotated, ``rope_theta``);
  ``g_t = logsigmoid(W_g n + b_g)``, one log-gate a key-value head, float32;
  ``G_t = sum_{i <= t} g_i``.
* for query head ``h`` of key-value head ``m = h // (Hq / Hkv)`` and ``j <=
  t``: ``a_tj = (q_t . k_j) ** 2 * exp(G_t - G_j)``, ``o_t = sum_j a_tj v_j /
  (sum_j a_tj + eps)``; the sublayer's output is ``W_o`` of the heads side by
  side. A softmax scale would multiply numerator and denominator alike: none
  is applied.
* the rest is the Qwen3 block: pre-norm residuals, ``W_down(silu(W_gate n) *
  W_up n)``, final RMSNorm, untied head.

What the published ``config.json`` does not state is ``assumed`` in the
configuration file: the degree (2), the gate's form, ``eps`` =
``rms_norm_eps``. The same mathematics as a recurrence over a state of fixed
size is what the program serves; where it splits the sum over ``j`` into a
folded part and pairs is its tiling.

Straightforward float32 ``jax.numpy``; it imports nothing from the program.
The caller sets ``jax.default_matmul_precision("highest")`` around it. The
weight matrix is made ``BLOCK`` queries at a time so that a 3088-token probe
of 40 heads fits beside the weights. ``params`` as ``dense_gqa``'s, with
``q_norm``, ``k_norm`` ``[L, d]``, ``w_gate [L, H, Hkv]`` and ``b_gate [L,
Hkv]`` in every layer. Departure from the published code: none in the
mathematics; matrices are laid out ``[in, out]``.

``state_dtype`` (a control of the configuration's ``correct``, never the
reference itself): the SAME mathematics with the sum over ``j`` split at page
boundaries into a state and pairs, the state rounded to that dtype at every
fold; ``folded=False`` (the other control) drops the folded part and keeps
the pairs of the open page alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_gqa import F32, mlp, rms_norm, rope, weight

BLOCK = 256


def projections(cfg, lp, x):
    """``q [S, Hq, d]``, ``k``, ``v [S, Hkv, d]`` and the gate sums ``G [S,
    Hkv]`` of one layer's normed input ``x [S, H]``."""
    s = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // hq
    eps, pos = cfg["rms_norm_eps"], jnp.arange(s)
    q = rms_norm((x @ weight(lp["wq"])).reshape(s, hq, d), lp["q_norm"], eps)
    k = rms_norm((x @ weight(lp["wk"])).reshape(s, hkv, d), lp["k_norm"], eps)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    v = (x @ weight(lp["wv"])).reshape(s, hkv, d)
    gate = jax.nn.log_sigmoid(
        x @ lp["w_gate"].astype(F32) + lp["b_gate"].astype(F32)
    )
    return q, k, v, jnp.cumsum(gate, axis=0)


def retention(cfg, lp, x):
    """The retention sublayer by the quadratic form, ``BLOCK`` queries at a
    time."""
    s = x.shape[0]
    q, k, v, gsum = projections(cfg, lp, x)
    hq, hkv, d = q.shape[1], k.shape[1], q.shape[2]
    eps = cfg["rms_norm_eps"]
    pad = -s % BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, BLOCK, hkv, hq // hkv, d
    )
    gb = jnp.pad(gsum, ((0, pad), (0, 0))).reshape(-1, BLOCK, hkv)
    tb = jnp.arange(s + pad).reshape(-1, BLOCK)

    def block(xs):
        qq, gg, tt = xs
        scores = jnp.einsum("thgd,jhd->hgtj", qq, k)
        seen = jnp.arange(s)[None, :] <= tt[:, None]              # [t, j]
        decay = jnp.exp(jnp.minimum(gg.T[:, :, None] - gsum.T[:, None, :], 0.0))
        a = scores * scores * jnp.where(seen[None], decay, 0.0)[:, None]
        num = jnp.einsum("hgtj,jhd->thgd", a, v)
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 2, 0)             # [t, h, g]
        return num / (den + eps)[..., None]

    out = jax.lax.map(block, (qb, gb, tb)).reshape(-1, hq * d)[:s]
    return out @ weight(lp["wo"])


def forward(cfg, params, tokens, mixer=retention):
    """Logits ``[S, V]`` of every position of one sequence ``tokens [S]``."""
    x = params["embed"].astype(F32)[tokens]
    eps = cfg["rms_norm_eps"]

    def layer(x, lp):
        x = x + mixer(cfg, lp, rms_norm(x, lp["attn_norm"], eps))
        x = x + mlp(cfg, lp, rms_norm(x, lp["mlp_norm"], eps))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], eps) @ weight(params["lm_head"])


# ---------------------------------------------------------------------------
# the controls of ``correct`` (never the reference)
# ---------------------------------------------------------------------------


def _rounded(a, state_dtype):
    # (reduce_precision: a cast there and back is one that XLA may drop, and
    # on the chip does)
    if state_dtype is None:
        return a
    kind = jnp.finfo(state_dtype)
    return jax.lax.reduce_precision(a, kind.nexp, kind.nmant)


def _fold_page(carry, kk, vv, gg, state_dtype):
    """One page ``kk``, ``vv [P, h, d]``, ``gg [P, h]`` into the state
    ``st [h, d, d, dv]`` (the outer product of a key with itself against its
    value: ``(q . k) ** 2 = q^T kk q``), its sum of keys ``zs [h, d, d]`` and
    the gate sum ``ref [h]`` both are referenced to: the page's last."""
    st, zs, ref = carry
    new_ref = gg[-1]
    w = jnp.exp(jnp.minimum(new_ref[None] - gg, 0.0))              # [j, h]
    keep = jnp.exp(jnp.minimum(new_ref - ref, 0.0))
    st = _rounded(st * keep[:, None, None, None] + jnp.einsum(
        "jha,jhb,jhd->habd", kk, kk, vv * w[..., None]
    ), state_dtype)
    zs = _rounded(zs * keep[:, None, None] + jnp.einsum(
        "jha,jhb->hab", kk, kk * w[..., None]
    ), state_dtype)
    return st, zs, new_ref


def _empty_state(hkv, d):
    return (
        jnp.zeros((hkv, d, d, d), F32), jnp.zeros((hkv, d, d), F32),
        jnp.zeros((hkv,), F32),
    )


def folded_state(cfg, lp, x, page: int, state_dtype=None):
    """``(st, zs, ref)`` of one layer's normed input ``x [S, H]`` after its
    ``S // page`` full pages, a page at a time: what a served state is held
    to, entry by entry, where the logits cannot tell (a test's)."""
    _, k, v, gsum = projections(cfg, lp, x)
    full = x.shape[0] // page

    def pages(a):
        return a[: full * page].reshape(full, page, *a.shape[1:])

    carry, _ = jax.lax.scan(
        lambda c, xs: (_fold_page(c, *xs, state_dtype), None),
        _empty_state(k.shape[1], k.shape[2]), (pages(k), pages(v), pages(gsum)),
    )
    return carry


def paged_retention(page: int, state_dtype=None, folded: bool = True):
    """The retention sublayer with the sum over ``j`` split at the last
    multiple of ``page`` at or before ``t``: positions before it through the
    state of :func:`_fold_page`, built a page at a time and rounded to
    ``state_dtype`` after every page where one is given; the rest pair by
    pair. ``folded=False`` drops the state's part. With neither it is
    :func:`retention` to rounding."""

    def mixer(cfg, lp, x):
        s = x.shape[0]
        q, k, v, gsum = projections(cfg, lp, x)
        hq, hkv, d = q.shape[1], k.shape[1], q.shape[2]
        eps = cfg["rms_norm_eps"]
        pad = -s % page
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, page, hkv, hq // hkv, d
        )
        kb = jnp.pad(k, ((0, pad), (0, 0), (0, 0))).reshape(-1, page, hkv, d)
        vb = jnp.pad(v, ((0, pad), (0, 0), (0, 0))).reshape(-1, page, hkv, d)
        gb = jnp.pad(gsum, ((0, pad), (0, 0)), mode="edge").reshape(
            -1, page, hkv
        )
        causal = jnp.arange(page)[None, :] <= jnp.arange(page)[:, None]

        def one(carry, xs):
            st, zs, ref = carry
            qq, kk, vv, gg = xs
            dec = jnp.exp(jnp.minimum(gg - ref[None], 0.0))        # [t, h]
            num = jnp.einsum("thga,thgb,habd->thgd", qq, qq, st) * dec[..., None, None]
            den = jnp.einsum("thga,thgb,hab->thg", qq, qq, zs) * dec[..., None]
            if not folded:
                num, den = 0.0 * num, 0.0 * den
            sc = jnp.einsum("thgd,jhd->hgtj", qq, kk)
            decay = jnp.exp(jnp.minimum(gg.T[:, :, None] - gg.T[:, None, :], 0.0))
            a = sc * sc * jnp.where(causal[None], decay, 0.0)[:, None]
            num = num + jnp.einsum("hgtj,jhd->thgd", a, vv)
            den = den + jnp.moveaxis(jnp.sum(a, axis=-1), 2, 0)
            return (
                _fold_page(carry, kk, vv, gg, state_dtype),
                num / (den + eps)[..., None],
            )

        _, out = jax.lax.scan(one, _empty_state(hkv, d), (qb, kb, vb, gb))
        return out.reshape(-1, hq * d)[:s] @ weight(lp["wo"])

    return mixer
