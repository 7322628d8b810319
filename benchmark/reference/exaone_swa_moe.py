"""Plain reference: the ``exaone_moe`` decoder layer as K-EXAONE-236B-A23B's
``config.json`` states it: grouped-query attention whose layers are of two
kinds by ``layer_types`` (``sliding_attention`` with ``sliding_window`` 128,
``full_attention``), a leading dense layer by ``mlp_layer_types``, and then
sigmoid-routed experts beside one shared expert. One layer, for the hidden
states ``h [T, H]`` at positions ``t``:

    x   = RMSNorm(h)
    q   = x Wq -> [T, Hq, D];  k = x Wk -> [T, Hkv, D];  v = x Wv   (no bias)
    q,k = RMSNorm over each head's D (its own gain)
    a sliding_attention layer: q,k = RoPE(theta, all D, halves rotated);
        query t sees keys j with  t - window < j <= t
    a full_attention layer:    no RoPE; query t sees every key j <= t
    o[t, hd] = sum_j softmax_j(q[t, hd] . k[j, g(hd)] / sqrt(D)) v[j, g(hd)]
    h'  = h + concat(o) Wo
    y   = RMSNorm(h')
    layer 0:  h'' = h' + Wd(silu(Wg y) * Wu y)                  (18432 wide)
    others:   s = sigmoid(y Wr) over all E experts, float32
              E_t = the k experts of largest s + b
              c_e = s_e / sum_{E_t} s * routed_scaling_factor
              h'' = h' + sum_{e in E_t, e held here} c_e E_e(y) + S(y)

and after the last layer a final RMSNorm and the untied head.

**The share.** ``cfg["expert_share"]`` = ``{"router_experts", "shares",
"index"}`` says that the expert stacks given hold share ``index`` of
``shares`` contiguous shares of the router's ``router_experts``: the router
scores all of them, a token picks its k among all of them and the weights
are normalised over all k, and the sum runs over those of its picks that are
held here. What the absent experts would add is left out, as on one chip of
the deployment before its exchange, and that partial result goes on to the
next layer. Without the key every expert is here. The vocabulary's slice is
a smaller vocabulary and needs nothing.

Float32 ``jax.numpy``, one sequence, no cache, no kernel, no batching; the
caller sets ``jax.default_matmul_precision("highest")``. It imports nothing
of the program. Queries are walked ``BLOCK`` positions at a time and the
held experts one at a time, dequantised where used, so that the published
widths fit beside a resident engine.

``params`` is the served tree: ``embed``, ``final_norm``, ``lm_head`` and
one depth-stacked dict a run of like layers under ``layers_<n>_<mlp>`` (or
``layers`` where all are alike), in layer order: ``attn_norm``, ``mlp_norm
[L, H]``; ``wq [L, H, Hq*D]``, ``wk``/``wv [L, H, Hkv*D]``, ``wo [L, Hq*D,
H]``, ``q_norm``/``k_norm [L, D]``; a dense run ``wg``/``wu [L, H, F]``,
``wd [L, F, H]``; an expert run ``router [L, H, E]``, ``router_bias [L, E]``,
``we_g``/``we_u [L, held, H, Fe]``, ``we_d [L, held, Fe, H]``, ``ws_g``/
``ws_u [L, H, Fe]``, ``ws_d [L, Fe, H]``. A matrix may be in stored int8
form (``dense_gqa.weight``). Which layers a run holds is read off
``layer_types`` / ``mlp_layer_types`` by counting, and checked.

Departures from the published description, each under ``assumed`` in the
configuration file: the norms stand BEFORE each sublayer (EXAONE 4.0
normalises the sublayer's output); the per-head q/k norms and RoPE in the
window layers only are EXAONE 4.0's hybrid rule; the selection bias ``b`` is
DeepSeek-V3's ``e_score_correction_bias``; ``n_group`` / ``topk_group`` 1
make the group step the identity. The multi-token-prediction layer
(``num_nextn_predict_layers``) is not computed: it drafts tokens, and the
logits of the main model do not pass through it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_gqa import F32, mlp, rms_norm, rope, weight

BLOCK = 512


def attention(cfg, lp, x, kind: str):
    t, hq, hkv = x.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    q = rms_norm((x @ weight(lp["wq"])).reshape(t, hq, d), lp["q_norm"], eps)
    k = rms_norm((x @ weight(lp["wk"])).reshape(t, hkv, d), lp["k_norm"], eps)
    v = (x @ weight(lp["wv"])).reshape(t, hkv, d)
    if kind == "sliding_attention":
        theta = cfg["rope_parameters"]["rope_theta"]
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)      # query head i reads kv head i // G
    v = jnp.repeat(v, hq // hkv, axis=1)
    outs = []
    for lo in range(0, t, BLOCK):
        qp = pos[lo:lo + BLOCK, None]
        seen = pos[None, :] <= qp
        if kind == "sliding_attention":
            seen &= pos[None, :] > qp - cfg["sliding_window"]
        scores = jnp.einsum("qhd,khd->hqk", q[lo:lo + BLOCK], k) / jnp.sqrt(F32(d))
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs).reshape(t, hq * d) @ weight(lp["wo"])


def routing(cfg, lp, x):
    """``(weights [T, k], experts [T, k])`` of every token, over ALL the
    router's experts."""
    assert cfg.get("n_group", 1) == 1 and cfg.get("topk_group", 1) == 1
    logits = x @ lp["router"].astype(F32)
    if cfg.get("scoring_func", "sigmoid") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, -1)
    _, top_i = jax.lax.top_k(
        scores + lp["router_bias"].astype(F32)[None, :],
        cfg["num_experts_per_tok"],
    )
    top_w = jnp.take_along_axis(scores, top_i, -1)
    if cfg.get("norm_topk_prob"):
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    return top_w * cfg.get("routed_scaling_factor", 1.0), top_i


def moe(cfg, lp, x):
    top_w, top_i = routing(cfg, lp, x)
    held = cfg["num_experts"]
    share = cfg.get("expert_share") or {
        "router_experts": held, "shares": 1, "index": 0,
    }
    assert lp["router"].shape[-1] == share["router_experts"] == held * share["shares"]

    def one_expert(acc, expert):
        e, wg, wu, wd = expert
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)          # [T]
        y = (jax.nn.silu(x @ weight(wg)) * (x @ weight(wu))) @ weight(wd)
        return acc + w[:, None] * y, None

    experts = (
        share["index"] * held + jnp.arange(held),
        lp["we_g"], lp["we_u"], lp["we_d"],
    )
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), experts)
    shared = {"wg": lp["ws_g"], "wu": lp["ws_u"], "wd": lp["ws_d"]}
    assert weight(lp["ws_g"]).shape[-1] == (
        cfg["num_shared_experts"] * cfg["moe_intermediate_size"]
    )
    return out + mlp(cfg, shared, x)


def forward(cfg, params, tokens):
    """Logits ``[T, V]`` of every position of one sequence ``tokens [T]``."""
    x = params["embed"].astype(F32)[tokens]
    eps = cfg["rms_norm_eps"]
    kinds, mlps = cfg["layer_types"], cfg["mlp_layer_types"]
    assert len(kinds) == len(mlps) == cfg["num_hidden_layers"]
    depth = 0
    runs = sorted(
        (k for k in params if k.startswith("layers")),
        key=lambda k: int(k.split("_")[1]) if "_" in k else 0,
    )
    for key in runs:
        stack = params[key]
        count = stack["attn_norm"].shape[0]
        kind, routed = kinds[depth], "router" in stack
        # a run's layers are alike, as the block's two lists state them
        assert set(kinds[depth:depth + count]) == {kind}, key
        assert set(mlps[depth:depth + count]) == {"sparse" if routed else "dense"}
        depth += count

        def layer(x, lp, kind=kind, mlp_fn=moe if routed else mlp):
            x = x + attention(cfg, lp, rms_norm(x, lp["attn_norm"], eps), kind)
            return x + mlp_fn(cfg, lp, rms_norm(x, lp["mlp_norm"], eps)), None

        x, _ = jax.lax.scan(layer, x, stack)
    assert depth == cfg["num_hidden_layers"], (depth, cfg["num_hidden_layers"])
    return rms_norm(x, params["final_norm"], eps) @ weight(params["lm_head"])
