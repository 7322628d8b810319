"""Plain reference: Xing4.0-29B-A4B's block (``model_type`` ``xing4_0``) —
DeepSeek-V3's latent attention with compressed queries under YaRN and its
sigmoid-routed experts beside a shared one behind leading dense layers
(``moonlight_mla_moe``: ``DeepseekV3Attention``, ``MoEGate``, ``DeepseekV3MoE``,
arXiv:2412.19437 section 2.1), inside a residual stream ``hc_mult`` rows wide
that manifold-constrained hyper-connections (mHC, arXiv:2512.24880) mix around
EVERY attention and MLP sublayer.

**The layer.** A token's stream is ``x [n, C]``, ``n = hc_mult``. Around each
sublayer ``F`` (attention after ``attn_norm``, the dense or routed MLP after
``mlp_norm``), with that sublayer's ``phi_pre, phi_post [nC, n]``, ``phi_res
[nC, n*n]``, biases ``b_pre, b_post [n]``, ``b_res [n, n]`` and scalars
``a_pre, a_post, a_res``::

    x~      = RMSNorm(vec(x))             over all nC values, eps rms_norm_eps,
                                          no gain            (assumed)
    H_pre   = sigmoid(a_pre (x~ phi_pre) + b_pre)
    H_post  = 2 sigmoid(a_post (x~ phi_post) + b_post)
    M0      = exp(clip(a_res mat(x~ phi_res) + b_res, clamp_min, clamp_max))
                                          the clamp on the logits (assumed)
    Mt      = rows(cols(M(t-1))), t = 1 .. hc_sinkhorn_iters
              cols: M / (its sum over rows + hc_eps); rows: M / (its sum over
              columns + hc_eps)           where hc_eps sits   (assumed)
    h       = sum_i H_pre[i] x[i]
    x'[i]   = sum_j H_res[i, j] x[j] + H_post[i] F(h)

The stack's entry replicates the embedding into the ``n`` rows and its exit
sums them before the final norm and the head (assumed: Hyper-Connections,
arXiv:2409.19606).

**Attention, UN-absorbed**, with compressed queries: ``cq = RMSNorm(x W_qa)``
(``q_a_layernorm``), ``q = cq W_qb`` per head ``[q_nope ; q_pe]``; ``[c ;
k_pe] = x W_kv_a``, ``c`` RMS-normed and decompressed to every head's
``k_nope`` and ``v``; the ONE ``k_pe`` shared by the heads. RoPE under
``rope_scaling`` ``yarn`` as ``DeepseekV3YarnRotaryEmbedding`` computes it:
the blend of ``1 / theta^(2i/d)`` and the same over ``factor`` by the linear
ramp between the correction dims of ``beta_fast`` and ``beta_slow``; cos and
sin times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``; the
softmax scale ``(dn + dr)^-1/2`` times ``mscale(factor, mscale_all_dim)^2``.

Float32 ``jax.numpy``, one sequence, no cache, no kernel; the caller sets
``jax.default_matmul_precision("highest")``. It imports nothing of the
program. ``params`` is the served tree as ``moonlight_mla_moe`` takes it, with
``wq_a [L, H, qr]``, ``q_a_norm [L, qr]``, ``wq_b [L, qr, Hq*(dn+dr)]`` in
place of ``wq``, and a sublayer's hyper-connection leaves under ``hc_attn_`` /
``hc_mlp_``: ``phi [L, 2n + n*n, nC]`` (one ROW an entry of a map, the
transpose of the equations' ``[nC, .]``: rows ``pre | post | res``, ``res``
row-major), ``alpha [L, 3]``, ``bias [L, 2n + n*n]``, all float32.

Departures from the published code are ``moonlight_mla_moe``'s (weights
``[in, out]``; rotary slices rotated as halves over de-interleaved columns);
the next-token-prediction layer (``num_nextn_predict_layers``) is not
computed: the main model's logits do not pass through it.

``forward(..., broken=...)`` makes the controls that show what a comparison
sees of the mechanism: ``"res_transposed"`` (``H_res`` applied transposed),
``"no_post"`` (``H_post`` taken as 1) and ``"one_row_norm"`` (Sinkhorn cut to
ONE row normalisation).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.dense_gqa import F32, mlp, rms_norm, weight
from benchmark.reference.moonlight_mla_moe import moe

BROKEN = (None, "res_transposed", "no_post", "one_row_norm")


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, theta, rs):
    """``DeepseekV3YarnRotaryEmbedding``'s frequencies ``[dim / 2]``."""
    def correction_dim(rotations):
        return dim * math.log(
            rs["original_max_position_embeddings"] / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    inter = extra / rs["factor"]
    keep = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0, 1)
    return inter * (1.0 - keep) + extra * keep


def yarn_rope(x, positions, cfg):
    """``x [S, heads, D]`` rotated as halves (``rotate_half``) by YaRN's
    angles, cos and sin scaled as the published embedding scales them."""
    rs = cfg["rope_scaling"]
    assert rs.get("type", rs.get("rope_type")) == "yarn", rs
    d = x.shape[-1]
    angles = positions.astype(F32)[:, None] * yarn_inv_freq(d, cfg["rope_theta"], rs)[None]
    m = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
        rs["factor"], rs["mscale_all_dim"]
    )
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :] * m
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :] * m
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rotated * sin


def attention(cfg, lp, x):
    s, hq = x.shape[0], cfg["num_attention_heads"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    pos = jnp.arange(s)
    cq = rms_norm(x @ weight(lp["wq_a"]), lp["q_a_norm"], eps)
    assert cq.shape[-1] == cfg["q_lora_rank"]
    q = (cq @ weight(lp["wq_b"])).reshape(s, hq, dn + dr)
    q = jnp.concatenate([q[..., :dn], yarn_rope(q[..., dn:], pos, cfg)], -1)
    ckv = x @ weight(lp["wkv_a"])
    c = rms_norm(ckv[:, :rank], lp["kv_norm"], eps)
    k_pe = yarn_rope(ckv[:, None, rank:], pos, cfg)                # [S, 1, dr]
    k_nope = jnp.einsum("sr,rhd->shd", c, lp["wk_b"].astype(F32))
    v = jnp.einsum("sr,rhd->shd", c, lp["wv_b"].astype(F32))
    assert k_nope.shape[-1] == dn and v.shape[-1] == dv
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, hq, dr))], -1)
    rs = cfg["rope_scaling"]
    scale = F32(dn + dr) ** -0.5
    if rs.get("mscale_all_dim"):
        scale = scale * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, hq * dv) @ weight(lp["wo"])


def hyper_maps(cfg, lp, prefix, x, broken=None):
    """``(H_pre [S, n], H_post [S, n], H_res [S, n, n])`` of the stream
    ``x [S, n, C]``."""
    n, s = cfg["hc_mult"], x.shape[0]
    phi = lp[f"{prefix}_phi"].astype(F32).T                  # [nC, 2n + n*n]
    alpha, bias = lp[f"{prefix}_alpha"].astype(F32), lp[f"{prefix}_bias"].astype(F32)
    phi_pre, phi_post, phi_res = phi[:, :n], phi[:, n:2 * n], phi[:, 2 * n:]
    b_pre, b_post, b_res = bias[:n], bias[n:2 * n], bias[2 * n:].reshape(n, n)
    flat = x.reshape(s, -1)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + cfg["rms_norm_eps"])
    h_pre = jax.nn.sigmoid(alpha[0] * (xt @ phi_pre) + b_pre)
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * (xt @ phi_post) + b_post)
    logits = alpha[2] * (xt @ phi_res).reshape(s, n, n) + b_res
    m = jnp.exp(jnp.clip(
        logits, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]
    ))
    eps = cfg["hc_eps"]
    if broken == "one_row_norm":
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
    else:
        for _ in range(cfg["hc_sinkhorn_iters"]):
            m = m / (jnp.sum(m, -2, keepdims=True) + eps)       # columns
            m = m / (jnp.sum(m, -1, keepdims=True) + eps)       # rows
    if broken == "res_transposed":
        m = jnp.swapaxes(m, -1, -2)
    if broken == "no_post":
        h_post = jnp.ones_like(h_post)
    return h_pre, h_post, m


def sublayer(cfg, lp, prefix, x, fn, broken=None):
    """``x [S, n, C]`` behind one sublayer ``fn: [S, C] -> [S, C]``."""
    h_pre, h_post, h_res = hyper_maps(cfg, lp, prefix, x, broken)
    y = fn(jnp.einsum("si,sic->sc", h_pre, x))
    return jnp.einsum("sij,sjc->sic", h_res, x) + h_post[:, :, None] * y[:, None, :]


def forward(cfg, params, tokens, broken=None):
    """Logits ``[S, V]`` of every position of one sequence ``tokens [S]``."""
    assert broken in BROKEN, broken
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]
    x = params["embed"].astype(F32)[tokens]
    x = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    dense_first, depth = cfg["first_k_dense_replace"], 0
    for key in sorted(k for k in params if k.startswith("layers")):
        stack = params[key]
        routed = "router" in stack
        count = stack["attn_norm"].shape[0]
        # leading dense layers, then routed ones, as the block states
        assert routed == (depth >= dense_first), key
        depth += count

        def layer(x, lp, mlp_fn=moe if routed else mlp):
            x = sublayer(cfg, lp, "hc_attn", x, lambda h: attention(
                cfg, lp, rms_norm(h, lp["attn_norm"], eps)
            ), broken)
            x = sublayer(cfg, lp, "hc_mlp", x, lambda h: mlp_fn(
                cfg, lp, rms_norm(h, lp["mlp_norm"], eps)
            ), broken)
            return x, None

        x, _ = jax.lax.scan(layer, x, stack)
    assert depth == cfg["num_hidden_layers"], (depth, cfg["num_hidden_layers"])
    x = rms_norm(jnp.sum(x, 1), params["final_norm"], eps)
    return x @ weight(params["lm_head"])
