"""Plain reference: a decoder-only transformer with grouped-query attention,
rotary positions, an optional sliding window, RMSNorm and a SwiGLU MLP, as
the Mistral-7B paper (arXiv:2310.06825) and the published ``modeling_mistral``
describe it.

Straightforward float32 ``jax.numpy``: no cache, no kernel, no batching, one
sequence. It imports nothing from the program (``models/``, ``ops/``,
``cache/``). The caller sets ``jax.default_matmul_precision("highest")``
around it; on a TPU a float32 matmul otherwise runs in bf16 passes.

``params``: ``embed [V, H]``, ``final_norm [H]``, ``lm_head [H, V]`` and
``layers``, a dict of arrays stacked over depth: ``attn_norm``, ``mlp_norm``
``[L, H]``; ``wq [L, H, Hq*D]``, ``wk``/``wv [L, H, Hkv*D]``, ``wo
[L, Hq*D, H]``; ``wg``/``wu [L, H, F]``, ``wd [L, F, H]``. A matrix may be
given in stored int8 form as ``{"q": int8 [..., in, out], "scale": [..., out]}``
and is dequantized where it is used, one layer at a time, so that a 7B model
never exists in float32. Departure from the published code: none in the
mathematics; weights are laid out ``[in, out]`` (``x @ w``), the transpose of
``nn.Linear``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def weight(leaf):
    if isinstance(leaf, dict):
        return leaf["q"].astype(F32) * leaf["scale"].astype(F32)[..., None, :]
    return leaf.astype(F32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain.astype(F32)


def rope(x, positions, theta):
    """``x [S, heads, D]``; halves rotated as in ``rotate_half``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rotated * sin


def attention(cfg, lp, x):
    s = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // hq
    pos = jnp.arange(s)
    q = rope((x @ weight(lp["wq"])).reshape(s, hq, d), pos, cfg["rope_theta"])
    k = rope((x @ weight(lp["wk"])).reshape(s, hkv, d), pos, cfg["rope_theta"])
    v = (x @ weight(lp["wv"])).reshape(s, hkv, d)
    # query head i reads key/value head i // (Hq / Hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    seen = pos[None, :] <= pos[:, None]
    if cfg.get("sliding_window"):
        seen &= pos[:, None] - pos[None, :] < cfg["sliding_window"]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, hq * d) @ weight(lp["wo"])


def mlp(cfg, lp, x):
    return (jax.nn.silu(x @ weight(lp["wg"])) * (x @ weight(lp["wu"]))) @ weight(lp["wd"])


def forward(cfg, params, tokens, mlp_fn=mlp):
    """Logits ``[S, V]`` of every position of one sequence ``tokens [S]``."""
    x = params["embed"].astype(F32)[tokens]

    def layer(x, lp):
        x = x + attention(cfg, lp, rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"]))
        x = x + mlp_fn(cfg, lp, rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"]))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return x @ weight(params["lm_head"])
