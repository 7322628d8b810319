"""Plain reference: the DeepSeek-V3 block as Moonlight-16B-A3B publishes it
(``modeling_deepseek.py`` beside the checkpoint: ``DeepseekV3Attention``,
``MoEGate``, ``DeepseekV3MoE``, ``DeepseekV3MLP``; arXiv:2412.19437 section
2.1, arXiv:2502.16982), with ``q_lora_rank`` null and groups of one.

**Attention, UN-absorbed** (the program runs the absorbed form; this is the
other algebra). ``q = x W_q`` per head ``[q_nope ; q_pe]``;
``[c ; k_pe] = x W_kv_a`` (``kv_a_proj_with_mqa``); ``c`` is RMS-normed
(``kv_a_layernorm``) and decompressed by ``kv_b_proj`` to every head's
``k_nope`` and ``v``; ``k = [k_nope ; rope(k_pe)]`` with the ONE ``k_pe``
shared by the heads; ``q = [q_nope ; rope(q_pe)]``; scores scaled by
``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``; causal softmax; the heads'
``v_head_dim``-wide results through ``o_proj``.

**Routing** (``MoEGate``, ``topk_method`` ``noaux_tc``): scores
``s = sigmoid(x W_r)`` in float32 over all experts (``softmax`` where
``scoring_func`` says so); the ``num_experts_per_tok`` experts with the
largest ``s + b`` are chosen, ``b`` the per-expert
``e_score_correction_bias`` (with ``n_group = topk_group = 1`` the published
group step keeps every expert: the identity); their weights are ``s`` WITHOUT
``b``, divided by their sum plus 1e-20 where ``norm_topk_prob``, times
``routed_scaling_factor``. Output: the weighted sum of the chosen SwiGLU
experts plus, where ``n_shared_experts`` > 0, ONE SwiGLU MLP
``n_shared_experts * moe_intermediate_size`` wide that every token passes.
The first ``first_k_dense_replace`` layers have a dense SwiGLU MLP
``intermediate_size`` wide instead.

Float32 ``jax.numpy``, one sequence, no cache, no kernel; the caller sets
``jax.default_matmul_precision("highest")``. It imports nothing of the
program. ``params`` is the served tree: ``embed``, ``final_norm``,
``lm_head`` and one dict of depth-stacked leaves a run of like layers, under
keys that start with ``layers`` and sort in layer order (``layers`` alone, or
``layers_0_dense`` then ``layers_1_moe``): ``attn_norm``, ``mlp_norm``,
``kv_norm [L, rank]``, ``wq [L, H, Hq*(dn+dr)]``, ``wkv_a [L, H, rank+dr]``,
``wk_b [L, rank, Hq, dn]``, ``wv_b [L, rank, Hq, dv]`` (``kv_b_proj`` split
by its output columns), ``wo [L, Hq*dv, H]``; dense ``wg``/``wu``/``wd``;
routed ``router [L, H, E]``, ``router_bias [L, E]``, ``we_g``/``we_u
[L, E, H, F]``, ``we_d [L, E, F, H]``, shared ``ws_g``/``ws_u``/``ws_d``. A
matrix may be in stored int8 form (``dense_gqa.weight``).

Departures from the published code: weights are ``[in, out]``; the rotary
slices are rotated as halves (``rotate_half`` on ``[x_0..x_{d/2-1} ;
x_{d/2}..]``) where the checkpoint stores (even, odd) pairs side by side and
de-interleaves them first: the same rotation over permuted columns, and
seeded weights have no column order to keep (the configuration file lists
this under ``assumed``). Written one expert at a time, as ``mixtral_moe``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_gqa import F32, mlp, rms_norm, rope, weight


def attention(cfg, lp, x):
    s, hq = x.shape[0], cfg["num_attention_heads"]
    rank = cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    theta, pos = cfg["rope_theta"], jnp.arange(s)
    q = (x @ weight(lp["wq"])).reshape(s, hq, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, theta)], -1)
    ckv = x @ weight(lp["wkv_a"])
    c = rms_norm(ckv[:, :rank], lp["kv_norm"], cfg["rms_norm_eps"])
    k_pe = rope(ckv[:, None, rank:], pos, theta)                   # [S, 1, dr]
    k_nope = jnp.einsum("sr,rhd->shd", c, lp["wk_b"].astype(F32))
    v = jnp.einsum("sr,rhd->shd", c, lp["wv_b"].astype(F32))
    assert k_nope.shape[-1] == dn and v.shape[-1] == dv
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, hq, dr))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * F32(dn + dr) ** -0.5
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, hq * dv) @ weight(lp["wo"])


def routing(cfg, lp, x):
    """``(weights [S, k], experts [S, k])`` of every token."""
    logits = x @ lp["router"].astype(F32)
    if cfg.get("scoring_func", "softmax") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, -1)
    choice = scores
    if cfg.get("topk_method") == "noaux_tc":
        choice = scores + lp["router_bias"].astype(F32)[None, :]
    _, top_i = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores, top_i, -1)
    if cfg.get("norm_topk_prob"):
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    return top_w * cfg.get("routed_scaling_factor", 1.0), top_i


def moe(cfg, lp, x):
    top_w, top_i = routing(cfg, lp, x)

    def one_expert(acc, expert):
        e, wg, wu, wd = expert
        share = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)      # [S]
        y = (jax.nn.silu(x @ weight(wg)) * (x @ weight(wu))) @ weight(wd)
        return acc + share[:, None] * y, None

    experts = (
        jnp.arange(cfg["n_routed_experts"]), lp["we_g"], lp["we_u"], lp["we_d"]
    )
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), experts)
    if cfg.get("n_shared_experts"):
        shared = {"wg": lp["ws_g"], "wu": lp["ws_u"], "wd": lp["ws_d"]}
        assert weight(lp["ws_g"]).shape[-1] == (
            cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
        )
        out = out + mlp(cfg, shared, x)
    return out


def forward(cfg, params, tokens):
    """Logits ``[S, V]`` of every position of one sequence ``tokens [S]``."""
    x = params["embed"].astype(F32)[tokens]
    eps = cfg["rms_norm_eps"]
    dense_layers = depth = 0
    dense_first = (
        cfg.get("first_k_dense_replace", 0) if cfg.get("n_routed_experts")
        else cfg["num_hidden_layers"]
    )
    for key in sorted(k for k in params if k.startswith("layers")):
        stack = params[key]
        routed = "router" in stack
        mlp_fn = moe if routed else mlp
        count = stack["attn_norm"].shape[0]
        # leading dense layers, then routed ones, as the block states
        assert routed == (depth >= dense_first), key
        dense_layers += 0 if routed else count
        depth += count

        def layer(x, lp, mlp_fn=mlp_fn):
            x = x + attention(cfg, lp, rms_norm(x, lp["attn_norm"], eps))
            return x + mlp_fn(cfg, lp, rms_norm(x, lp["mlp_norm"], eps)), None

        x, _ = jax.lax.scan(layer, x, stack)
    assert depth == cfg["num_hidden_layers"], (depth, cfg["num_hidden_layers"])
    assert dense_layers == min(dense_first, depth)
    x = rms_norm(x, params["final_norm"], eps)
    return x @ weight(params["lm_head"])
