"""Plain reference: the ``glm_moe_dsa`` decoder layer as GLM-5.2's
``config.json`` states it: latent attention with compressed queries, a
lightning indexer and top-k selection INSIDE it that only the layers marked
``full`` in ``indexer_types`` have (the ``shared`` ones attend to the
selection of the nearest ``full`` layer before them), leading dense layers by
``mlp_layer_types`` and then sigmoid-routed experts beside one shared expert.
One layer, for the hidden states ``h [T, H]`` at positions ``t``, with
``x = RMSNorm(h)``:

    cq  = RMSNorm(x Wqa)                                (q_lora_rank wide)
    q   = cq Wqb -> [T, Hq, dn + dr];   q = [q_nope ; RoPE(q_rope)]
    [ckv ; k_rope] = x Wkva;   ckv = RMSNorm(ckv);   k_rope = RoPE(k_rope)
    k[s, i] = [Wuk_i ckv[s] ; k_rope[s]]   (ONE k_rope for all heads)
    v[s, i] = Wuv_i ckv[s]
    a ``full`` layer:
        qI[t, j] = (cq[t] Wiq)_j        (index_n_heads of index_head_dim)
        kI[s]    = LayerNorm(x[s] Wik)  (ONE key of index_head_dim a token)
        RoPE on the first qk_rope_head_dim dims of each qI[t, j] and kI[s]
        w[t, j]  = (x[t] Wiw)_j * index_n_heads^-1/2 * index_head_dim^-1/2
        I[t, s]  = sum_j w[t, j] * relu(qI[t, j] . kI[s])
        S_t = the index_topk positions s <= t of largest I[t, s] (all of
              them while t < index_topk; ties to the lower position)
    a ``shared`` layer: S_t is the nearest earlier ``full`` layer's
    o[t, i] = sum_{s in S_t} softmax_s(q[t, i] . k[s, i] / sqrt(dn + dr)) v[s, i]
    h'  = h + concat_i(o[t, i]) Wo
    y   = RMSNorm(h')
    a dense layer:  h'' = h' + Wd(silu(Wg y) * Wu y)
    an expert one:  s = sigmoid(y Wr) over all E experts, float32
                    E_t = the k experts of largest s + b
                    c_e = s_e / sum_{E_t} s * routed_scaling_factor
                    h'' = h' + sum_{e in E_t, e held here} c_e E_e(y) + S(y)

and after the last layer a final RMSNorm and the untied head. The attention
is the UN-absorbed algebra (every head's keys and values decompressed); the
program runs the absorbed one over the stored latents.

**RoPE in interleaved pairs** (``rope_interleave`` / ``indexer_rope_interleave``
true): dims ``(2i, 2i + 1)`` of a rotary slice turn together by
``pos * theta^(-2i/d)``. The served tree stores every rotary slice with its
pairs' first members in the first half and their second members in the
second (the program rotates halves; a column permutation made once where
weights are made or loaded), so :func:`pairs` puts a slice back in the
published order before :func:`rope_pairs` turns it. A dot product does not
see a permutation made to both sides.

**The share.** ``cfg["expert_share"]`` = ``{"router_experts", "shares",
"index"}`` says that the expert stacks given hold share ``index`` of
``shares`` contiguous shares of the router's ``router_experts``
(``n_routed_experts`` counts the experts held): the router scores all of
them, a token picks its k among all of them and the weights are normalised
over all k, and the sum runs over those of its picks that are held here; the
partial result goes on to the next layer. Without the key every expert is
here. The vocabulary's slice is a smaller vocabulary and needs nothing.

Float32 ``jax.numpy``, one sequence, no cache, no kernel, no batching; the
caller sets ``jax.default_matmul_precision("highest")``. It imports nothing
of the program. Queries are walked ``BLOCK`` positions at a time and the held
experts one at a time, dequantised where used, so that the published widths
fit beside a resident engine.

``params`` is the served tree: ``embed``, ``final_norm``, ``lm_head`` and one
depth-stacked dict a run of like layers under ``layers_<n>_<mlp>`` (or
``layers``), in layer order: ``attn_norm``, ``mlp_norm [L, H]``; ``wq_a [L,
H, qr]``, ``q_a_norm [L, qr]``, ``wq_b [L, qr, Hq*(dn+dr)]``, ``wkv_a [L, H,
rank+dr]``, ``kv_norm [L, rank]``, ``wk_b [L, rank, Hq, dn]``, ``wv_b [L,
rank, Hq, dv]``, ``wo [L, Hq*dv, H]``; a ``full`` run also ``wq_i [L, qr,
Hi*Di]``, ``wk_i [L, H, Di]``, ``w_i [L, H, Hi]``, ``k_i_norm`` /
``k_i_norm_bias [L, Di]``; a dense run ``wg``/``wu``/``wd``; an expert run
``router [L, H, E]``, ``router_bias [L, E]``, ``we_g``/``we_u [L, held, H,
Fe]``, ``we_d [L, held, Fe, H]``, ``ws_g``/``ws_u``/``ws_d``. A matrix may be
in stored int8 form (``dense_gqa.weight``). Which layers a run holds is read
off ``indexer_types`` / ``mlp_layer_types`` by counting, and checked: a run's
layers are alike in both, and a run has an indexer's leaves exactly where the
list says ``full``.

Departures from the published description, each under ``assumed`` in the
configuration file: which ``qk_rope_head_dim`` of the indexer's dims are
rotated (the first); the LayerNorm's epsilon (``rms_norm_eps``); the Hadamard
rotation DeepSeek's code applies to ``qI`` and ``kI`` before its FP8 cast
left out (orthogonal: it cancels in the dot product); ``n_group`` /
``topk_group`` 1 make the group step the identity. The next-token-prediction
layer (``num_nextn_predict_layers``) is not computed: it drafts tokens, and
the logits of the main model do not pass through it.

Two switches make the controls that show what the probe sees of the
mechanism: ``cfg["index_topk"]`` at or above the sequence length is dense
causal attention, and ``forward(..., share=...)`` breaks the sharing:
``"first"`` (every ``shared`` layer reuses the FIRST ``full`` layer's
selection) or ``"none"`` (a ``shared`` layer attends to every key).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_gqa import F32, mlp, rms_norm, weight

BLOCK = 256


def pairs(x):
    """A stored rotary slice ``[..., d]`` (first members of the pairs, then
    second members) in the published order (pairs side by side)."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], -1).reshape(x.shape)


def rope_pairs(x, positions, theta):
    """``x [S, heads, d]`` with dims ``(2i, 2i + 1)`` rotated together."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angles = positions.astype(F32)[:, None, None] * inv_freq[None, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    first, second = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [first * cos - second * sin, second * cos + first * sin], -1
    ).reshape(x.shape)


def theta_of(cfg):
    return cfg["rope_parameters"]["rope_theta"]


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain.astype(F32) + bias.astype(F32)


def compressed_query(cfg, lp, x):
    return rms_norm(x @ weight(lp["wq_a"]), lp["q_a_norm"], cfg["rms_norm_eps"])


def index_queries(cfg, lp, x, cq):
    """``qI [T, Hi, Di]`` before RoPE: from the compressed query."""
    return (cq @ weight(lp["wq_i"])).reshape(
        -1, cfg["index_n_heads"], cfg["index_head_dim"]
    )


def rotated_head(x, pos, cfg):
    """RoPE on the first ``qk_rope_head_dim`` dims of ``x [T, heads, Di]``."""
    dr = cfg["qk_rope_head_dim"]
    return jnp.concatenate(
        [rope_pairs(pairs(x[..., :dr]), pos, theta_of(cfg)), x[..., dr:]], -1
    )


def selected(cfg, lp, x, cq):
    """``[T, T]`` bool: which keys each query of a ``full`` layer attends
    to."""
    hi_, di = cfg["index_n_heads"], cfg["index_head_dim"]
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    qi = rotated_head(index_queries(cfg, lp, x, cq), pos, cfg)
    ki = layer_norm(x @ weight(lp["wk_i"]), lp["k_i_norm"], lp["k_i_norm_bias"], eps)
    ki = rotated_head(ki[:, None, :], pos, cfg)[:, 0]
    w = (x @ weight(lp["w_i"])) * F32(hi_) ** -0.5 * F32(di) ** -0.5
    k = min(cfg["index_topk"], t)
    rows = []
    for lo in range(0, t, BLOCK):
        hi = min(lo + BLOCK, t)
        score = jnp.einsum(
            "qj,qjs->qs", w[lo:hi],
            jax.nn.relu(jnp.einsum("qjd,sd->qjs", qi[lo:hi], ki)),
        )
        score = jnp.where(score == 0, 0.0, score)            # -0.0 is 0.0
        seen = pos[None, :] <= pos[lo:hi, None]
        _, top = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), k)
        chosen = jnp.zeros((hi - lo, t), bool).at[
            jnp.arange(hi - lo)[:, None], top
        ].set(True)
        rows.append(chosen & seen)
    return jnp.concatenate(rows)


def attention(cfg, lp, x, cq, keep):
    """The layer's attention over the keys ``keep [T, T]`` lets each query
    see."""
    t, hq = x.shape[0], cfg["num_attention_heads"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    pos = jnp.arange(t)
    q = (cq @ weight(lp["wq_b"])).reshape(t, hq, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope_pairs(pairs(q[..., dn:]), pos, theta_of(cfg))], -1
    )
    ckv = x @ weight(lp["wkv_a"])
    c = rms_norm(ckv[:, :rank], lp["kv_norm"], eps)
    k_pe = rope_pairs(pairs(ckv[:, None, rank:]), pos, theta_of(cfg))  # [T, 1, dr]
    k_nope = jnp.einsum("sr,rhd->shd", c, lp["wk_b"].astype(F32))
    v = jnp.einsum("sr,rhd->shd", c, lp["wv_b"].astype(F32))
    assert k_nope.shape[-1] == dn and v.shape[-1] == dv
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, hq, dr))], -1)
    outs = []
    for lo in range(0, t, BLOCK):
        hi = min(lo + BLOCK, t)
        scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) * F32(dn + dr) ** -0.5
        scores = jnp.where(keep[lo:hi][None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs).reshape(t, hq * dv) @ weight(lp["wo"])


def routing(cfg, lp, x):
    """``(weights [T, k], experts [T, k])`` of every token, over ALL the
    router's experts."""
    assert cfg.get("n_group", 1) == 1 and cfg.get("topk_group", 1) == 1
    assert cfg["scoring_func"] == "sigmoid" and cfg["topk_method"] == "noaux_tc"
    scores = jax.nn.sigmoid(x @ lp["router"].astype(F32))
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
    held = cfg["n_routed_experts"]
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
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    )
    return out + mlp(cfg, shared, x)


def forward(cfg, params, tokens, share: str = "nearest"):
    """Logits ``[T, V]`` of every position of one sequence ``tokens [T]``.
    ``share``: "nearest" is the model; "first" the control whose ``shared``
    layers all reuse the first ``full`` layer's selection, "none" the one
    whose ``shared`` layers attend to every key."""
    assert share in ("nearest", "first", "none")
    x = params["embed"].astype(F32)[tokens]
    eps = cfg["rms_norm_eps"]
    kinds, mlps = cfg["indexer_types"], cfg["mlp_layer_types"]
    assert len(kinds) == len(mlps) == cfg["num_hidden_layers"] and kinds[0] == "full"
    t = tokens.shape[0]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    depth, keep, first = 0, None, None
    runs = sorted(
        (k for k in params if k.startswith("layers")),
        key=lambda k: int(k.split("_")[1]) if "_" in k else 0,
    )
    for key in runs:
        stack = params[key]
        count = stack["attn_norm"].shape[0]
        kind, routed = kinds[depth], "router" in stack
        # a run's layers are alike, as the block's two lists state them, and
        # only a ``full`` run has an indexer
        assert set(kinds[depth:depth + count]) == {kind}, key
        assert set(mlps[depth:depth + count]) == {"sparse" if routed else "dense"}
        assert ("wk_i" in stack) == (kind == "full"), key
        depth += count

        def layer(carry, lp, kind=kind, mlp_fn=moe if routed else mlp):
            x, keep = carry
            xn = rms_norm(x, lp["attn_norm"], eps)
            cq = compressed_query(cfg, lp, xn)
            if kind == "full":
                keep = selected(cfg, lp, xn, cq)
            seen = causal if kind == "shared" and share == "none" else keep
            x = x + attention(cfg, lp, xn, cq, seen)
            return (x + mlp_fn(cfg, lp, rms_norm(x, lp["mlp_norm"], eps)), keep), None

        if keep is None:
            keep = jnp.zeros((t, t), bool)
        (x, keep), _ = jax.lax.scan(layer, (x, keep), stack)
        if first is None:
            first = keep    # (of a first run of one ``full`` layer)
        if share == "first":
            keep = first
    assert depth == cfg["num_hidden_layers"], (depth, cfg["num_hidden_layers"])
    return rms_norm(x, params["final_norm"], eps) @ weight(params["lm_head"])
