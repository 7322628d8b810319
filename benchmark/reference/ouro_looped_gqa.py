"""Plain reference: the ``ouro`` decoder (Ouro-2.6B, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): a stack of ``L``
dense layers that every token crosses ``total_ut_steps`` times, all laps
sharing the layers' weights, with an exit gate behind each lap.

With ``x`` the residual, ``t`` the lap and ``l`` the layer::

    layer l, lap t:
      a = Attn_l(RMS(x; g1_l))       # q, k, v, o without bias; RoPE on q, k;
                                     # causal softmax / sqrt(d) over the K, V
                                     # that lap t of layer l made for the
                                     # positions up to this one
      x = x + RMS(a; g2_l)           # "sandwich": the sublayer's OUTPUT is
                                     # normed before it is added
      h = RMS(x; g3_l)
      m = W_down_l(silu(W_gate_l h) * (W_up_l h))
      x = x + RMS(m; g4_l)
    behind the L layers of lap t:
      x = RMS(x; g_final)            # the one final norm: its output ENTERS
                                     # lap t + 1
      h_t = x;  lam_t = sigmoid(w_exit . h_t + b_exit)
    exit, a position (T laps):
      p_t = lam_t * prod_{s<t}(1 - lam_s) for t < T - 1;  p_{T-1} the rest
      t*  = the first t with p_0 + ... + p_t >= early_exit_threshold, else T-1
      logits = W_head h_{t*}

A full causal forward of one sequence with no cache: lap ``t`` attends within
lap ``t`` by construction, which is what a cache of ``T x L`` layers serves.
What the published ``config.json`` does not state is ``assumed`` in the
configuration file: the output norms, that the final norm feeds the next lap,
the gate's form. Every lap runs whatever the gate says, as the published
inference code does.

Straightforward float32 ``jax.numpy``; it imports nothing from the program.
The caller sets ``jax.default_matmul_precision("highest")`` around it.
``params`` as ``dense_gqa``'s, with ``attn_out_norm`` / ``mlp_out_norm`` ``[L,
H]`` in every layer and ``exit_w [H]``, ``exit_b []`` beside the head.
Attention makes its ``[heads, BLOCK, S]`` scores a block of queries at a time.
Departure from the published code: none in the mathematics; matrices are laid
out ``[in, out]``.

Two keyword switches are CONTROLS of the configuration's ``correct`` and of
the tests, never the reference itself: ``laps`` runs fewer laps than the
config states (a dropped lap), ``share_lap_kv`` makes every lap attend to lap
0's K and V (a cache of ``L`` layers where the model needs ``T x L``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_gqa import F32, mlp, rms_norm, rope, weight

BLOCK = 512


def keys_values(cfg, lp, x):
    """``q [S, Hq, d]`` and ``k``, ``v [S, Hkv, d]`` of one layer's normed
    input ``x [S, H]``, ``q`` and ``k`` rotated."""
    s = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // hq
    pos = jnp.arange(s)
    q = rope((x @ weight(lp["wq"])).reshape(s, hq, d), pos, cfg["rope_theta"])
    k = rope((x @ weight(lp["wk"])).reshape(s, hkv, d), pos, cfg["rope_theta"])
    v = (x @ weight(lp["wv"])).reshape(s, hkv, d)
    return q, k, v


def attend(q, k, v):
    """Causal softmax attention of ``q [S, Hq, d]`` over ``k``, ``v [S, Hkv,
    d]`` (query head ``i`` reads key-value head ``i // (Hq / Hkv)``), a
    block of queries at a time: ``[S, Hq * d]``."""
    s, hq, d = q.shape
    group = hq // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    pos = jnp.arange(s)
    out = []
    for lo in range(0, s, BLOCK):
        qb = q[lo: lo + BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(d))
        seen = pos[None, :] <= pos[lo: lo + BLOCK, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out).reshape(s, hq * d)


def run(cfg, params, tokens, laps=None, share_lap_kv=False, drop=()):
    """Everything the laps make of one sequence ``tokens [S]``: ``(logits [S,
    V], keys [T, L, S, Hkv, d] (rotated, as a cache would hold them), exit
    laps [S], gates [T, S])``. ``drop``: terms a TEST leaves out to show that
    it would notice (``"attn_out_norm"``, ``"mlp_out_norm"``,
    ``"lap_norm"``: the final norm between laps)."""
    eps = cfg["rms_norm_eps"]
    total = int(cfg.get("total_ut_steps", 1) if laps is None else laps)
    threshold = float(cfg.get("early_exit_threshold", 1.0))
    x = params["embed"].astype(F32)[tokens]
    s = x.shape[0]

    def out_norm(name, y, lp):
        return y if name in drop else rms_norm(y, lp[name], eps)

    def layer(x, xs):
        lp, shared = xs
        q, k, v = keys_values(cfg, lp, rms_norm(x, lp["attn_norm"], eps))
        ka, va = (k, v) if shared is None else shared
        a = attend(q, ka, va) @ weight(lp["wo"])
        x = x + out_norm("attn_out_norm", a, lp)
        m = mlp(cfg, lp, rms_norm(x, lp["mlp_norm"], eps))
        return x + out_norm("mlp_out_norm", m, lp), (k, v)

    inside = jnp.ones((s,), F32)
    summed = jnp.zeros((s,), F32)
    at = jnp.full((s,), -1, jnp.int32)
    chosen = jnp.zeros_like(x)
    keys, gates, first = [], [], None
    for t in range(total):
        shared = first if share_lap_kv and t else None
        x, (k, v) = jax.lax.scan(layer, x, (params["layers"], shared))
        if t == 0:
            first = (k, v)
        keys.append(k)
        last = t == total - 1
        if not ("lap_norm" in drop and not last):
            x = rms_norm(x, params["final_norm"], eps)
        lam = jax.nn.sigmoid(
            x @ params["exit_w"].astype(F32) + params["exit_b"].astype(F32)
        )
        gates.append(lam)
        summed = summed + (inside if last else lam * inside)
        inside = inside * (1.0 - lam)
        take = (at < 0) & ((summed >= threshold) | last)
        chosen = jnp.where(take[:, None], x, chosen)
        at = jnp.where(take, t, at)
    logits = chosen @ weight(params["lm_head"])
    return logits, jnp.stack(keys), at, jnp.stack(gates)


def forward(cfg, params, tokens, laps=None, share_lap_kv=False):
    """Logits ``[S, V]`` of every position of one sequence ``tokens [S]``."""
    return run(cfg, params, tokens, laps, share_lap_kv)[0]
