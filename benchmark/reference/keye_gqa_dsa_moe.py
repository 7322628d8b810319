"""Plain reference: the ``KeyeVL2`` language model's decoder layer as
Keye-VL-2.0-30B-A3B's ``config.json`` states it: grouped-query attention
under a learned top-k key selection (``sa_config``: DeepSeek-V3.2's lightning
indexer and its selection, arXiv:2512.02556 section 2.1 and the published
inference code's ``Indexer``), and softmax-routed experts with no shared
expert and no dense layer. One layer, for the hidden states ``h [T, H]``:

    x   = RMSNorm(h)
    q   = x Wq -> [T, Hq, D];  k = x Wk -> [T, Hkv, D];  v = x Wv
    q,k = RMSNorm over each head's D (its own gain), then RoPE(theta, all D)
    qI  = RoPE(x WqI -> [T, Hi, Di]);  kI = RoPE(LayerNorm(x WkI -> [T, Di]))
    w   = (x Ww -> [T, Hi]) * Hi^-1/2 * Di^-1/2
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          for s <= t
    S_t = the topk positions s <= t of largest I[t, s]  (all while t < topk)
    o[t, hd] = sum_{s in S_t} softmax_{S_t}(q[t, hd] . k[s, g(hd)] / sqrt(D))
               v[s, g(hd)];   h' = h + concat(o) Wo
    y   = RMSNorm(h');  p = softmax(y Wr);  E_t = top-k experts of p
    c_e = p_e / sum_{E_t} p   (norm_topk_prob)
    h'' = h' + sum_{e in E_t} c_e Wdown_e(silu(Wgate_e y) * (Wup_e y))

Float32 ``jax.numpy``, one sequence, no cache, no kernel, no batching; the
caller sets ``jax.default_matmul_precision("highest")``. It imports nothing
of the program. Queries are walked ``BLOCK`` positions at a time (scores of a
block against every key: 3088 positions fit beside a resident engine) and
experts one at a time, dequantised where used, as ``moonlight_mla_moe`` does.
The selection is ``jax.lax.top_k`` (ties to the lower index) over the
positions at or before the query.

``params`` is the served tree: ``embed``, ``final_norm``, ``lm_head`` and
``layers``, depth-stacked: ``attn_norm``, ``mlp_norm [L, H]``; ``wq [L, H,
Hq*D]``, ``wk``/``wv [L, H, Hkv*D]``, ``wo [L, Hq*D, H]``, ``q_norm``/
``k_norm [L, D]``; the indexer's ``wq_i [L, H, Hi*Di]``, ``wk_i [L, H, Di]``,
``w_i [L, H, Hi]``, ``k_i_norm``/``k_i_norm_bias [L, Di]``; ``router [L, H,
E]``, ``we_g``/``we_u [L, E, H, F]``, ``we_d [L, E, F, H]``. A matrix may be
in stored int8 form (``dense_gqa.weight``).

Departures from the published description, each listed under ``assumed`` in
the configuration file: the per-head RMSNorm of q and k (the Qwen3 block this
family builds on has it; no config key names it); RoPE over all ``Di`` of
``qI`` and ``kI`` at the model's ``rope_theta``, halves rotated
(``rotate_half``); ``mrope_section`` with the three position ids equal, which
for text is plain RoPE; the LayerNorm's epsilon taken as ``rms_norm_eps``;
``q_chunk_size`` / ``kv_chunk_size`` read as a tiling with no effect on the
mathematics; the Hadamard rotation DeepSeek's code applies to ``qI`` and
``kI`` before its FP8 cast left out (orthogonal: it cancels in the dot
product); the query taken from ``x`` (this block has no compressed query).
``cfg["sa_config"]["topk"]`` at or above the sequence length is dense causal
attention: the control that shows the probe sees the selection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_gqa import F32, rms_norm, rope, weight

BLOCK = 512


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain.astype(F32) + bias.astype(F32)


def selected(cfg, lp, x, pos, lo, hi):
    """``[hi - lo, T]`` bool: which keys the queries ``lo .. hi`` attend to."""
    sa = cfg["sa_config"]
    hi_, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    t, theta, eps = x.shape[0], cfg["rope_theta"], cfg["rms_norm_eps"]
    xq, pq = x[lo:hi], pos[lo:hi]
    qi = rope((xq @ weight(lp["wq_i"])).reshape(-1, hi_, di), pq, theta)
    ki = layer_norm(x @ weight(lp["wk_i"]), lp["k_i_norm"], lp["k_i_norm_bias"], eps)
    ki = rope(ki[:, None, :], pos, theta)[:, 0]
    w = (xq @ weight(lp["w_i"])) * F32(hi_) ** -0.5 * F32(di) ** -0.5
    score = jnp.einsum("qj,qjs->qs", w, jax.nn.relu(jnp.einsum("qjd,sd->qjs", qi, ki)))
    score = jnp.where(score == 0, 0.0, score)            # -0.0 is 0.0
    seen = pos[None, :] <= pq[:, None]
    k = min(sa["topk"], t)
    _, top = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), k)
    chosen = jnp.zeros((hi - lo, t), bool).at[jnp.arange(hi - lo)[:, None], top].set(True)
    return chosen & seen


def attention(cfg, lp, x):
    t, hq, hkv = x.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, theta, eps = cfg["head_dim"], cfg["rope_theta"], cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    q = (x @ weight(lp["wq"])).reshape(t, hq, d)
    k = (x @ weight(lp["wk"])).reshape(t, hkv, d)
    v = (x @ weight(lp["wv"])).reshape(t, hkv, d)
    q = rope(rms_norm(q, lp["q_norm"], eps), pos, theta)
    k = rope(rms_norm(k, lp["k_norm"], eps), pos, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)      # query head i reads kv head i // G
    v = jnp.repeat(v, hq // hkv, axis=1)
    outs = []
    for lo in range(0, t, BLOCK):
        hi = min(lo + BLOCK, t)
        keep = selected(cfg, lp, x, pos, lo, hi)
        scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) / jnp.sqrt(F32(d))
        scores = jnp.where(keep[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs).reshape(t, hq * d) @ weight(lp["wo"])


def moe(cfg, lp, x):
    probs = jax.nn.softmax(x @ lp["router"].astype(F32), -1)
    top_w, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob"):
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)

    def one_expert(acc, expert):
        e, wg, wu, wd = expert
        share = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)      # [T]
        y = (jax.nn.silu(x @ weight(wg)) * (x @ weight(wu))) @ weight(wd)
        return acc + share[:, None] * y, None

    experts = (jnp.arange(cfg["num_experts"]), lp["we_g"], lp["we_u"], lp["we_d"])
    width = (lp["we_g"]["q"] if isinstance(lp["we_g"], dict) else lp["we_g"]).shape[-1]
    assert width == cfg["moe_intermediate_size"]
    return jax.lax.scan(one_expert, jnp.zeros_like(x), experts)[0]


def forward(cfg, params, tokens):
    """Logits ``[T, V]`` of every position of one sequence ``tokens [T]``."""
    assert cfg.get("decoder_sparse_step", 1) == 1 and not cfg.get("mlp_only_layers")
    x = params["embed"].astype(F32)[tokens]
    eps = cfg["rms_norm_eps"]
    stack = params["layers"]
    assert stack["attn_norm"].shape[0] == cfg["num_hidden_layers"]

    def layer(x, lp):
        x = x + attention(cfg, lp, rms_norm(x, lp["attn_norm"], eps))
        return x + moe(cfg, lp, rms_norm(x, lp["mlp_norm"], eps)), None

    x, _ = jax.lax.scan(layer, x, stack)
    return rms_norm(x, params["final_norm"], eps) @ weight(params["lm_head"])
