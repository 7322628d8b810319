"""Operations and bytes a token of the ``xing4_0`` block (Xing4.0-29B-A4B)
needs, from the published keys alone: ``flops.py`` counts Llama-shaped keys,
``flops_mla_moe.py`` a latent with queries from the hidden state and bytes
only, and this family has compressed queries (``q_lora_rank``), a softmax over
the latent at the context's length, ``num_experts_per_tok`` + ``n_shared_experts``
experts a token behind ``first_k_dense_replace`` dense layers, AND a residual
stream ``hc_mult`` rows wide whose hyper-connection maps are computed and
applied twice a layer (``ops/hyper_connections.py``: the ``[nC, 2n + n^2]``
projection, ``hc_sinkhorn_iters`` Sinkhorn rounds, the pre-mix and the
post-mix). ``cfg`` is the configuration file's block, depth as run. Only what
the mathematics requires is counted: valid tokens, the experts a token's
result takes, nothing padded or recomputed. Kept with the benchmark so that no
PR that claims a gain can change the count.
"""

from __future__ import annotations

from benchmark.flops_mla_moe import latent_bytes_per_token  # noqa: F401  (the same pool)


def layers_of(cfg: dict) -> dict:
    layers = cfg["num_hidden_layers"]
    dense = min(cfg.get("first_k_dense_replace", 0), layers)
    return {"dense": dense, "sparse": layers - dense}


def layer_parameters(cfg: dict) -> dict:
    """One layer's matrices by kind (norm gains, the selection bias and the
    maps' biases left out): what is stored quantised and what stays in the
    model's dtype or in float32, apart."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, n = cfg["moe_intermediate_size"], cfg["hc_mult"]
    return {
        "attention": h * qr + qr * hq * (dn + dr) + hq * dv * h,
        "attention_plain": h * (rank + dr) + rank * hq * (dn + dv),
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "one_expert": 3 * h * f,
        "shared_expert": cfg["n_shared_experts"] * 3 * h * f,
        "router": h * cfg["n_routed_experts"],
        # ONE sublayer's projection of the maps (float32)
        "mhc_phi": n * h * (2 * n + n * n),
    }


def mhc_flops_per_mix(cfg: dict) -> float:
    """One token through one sublayer's hyper-connection: the RMS of its
    ``nC`` values, the ``[nC, 2n + n^2]`` projection, ``hc_sinkhorn_iters``
    rounds over the ``n x n`` map (a column and a row normalisation: ``n^2``
    divides and ``n (n - 1)`` adds each), the pre-mix (``n`` rows into one)
    and the post-mix (``n^2 + n`` multiply-adds a channel)."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    norm = 2.0 * n * c
    project = 2.0 * n * c * (2 * n + n * n)
    sinkhorn = cfg["hc_sinkhorn_iters"] * 2.0 * (n * n + n * (n - 1))
    pre = 2.0 * n * c
    post = 2.0 * (n * n + n) * c
    return norm + project + sinkhorn + pre + post


def mhc_bytes_per_mix(cfg: dict, value_bytes: float = 2.0) -> float:
    """Bytes of the stream one token moves through one sublayer's
    hyper-connection at the least: ``x`` read once for the maps and the
    pre-mix (``n C``), ``x`` and ``y`` read and ``x'`` written for the
    post-mix (``n C + C + n C``): ``(3n + 1) C`` values at the stream's
    width."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return (3 * n + 1) * c * value_bytes


def matmul_params_per_token(cfg: dict) -> float:
    """Weights one token is multiplied with, the head left out: attention's
    projections, the dense MLP or ``num_experts_per_tok`` routed experts, the
    shared one and the router."""
    p, n = layer_parameters(cfg), layers_of(cfg)
    routed = (
        cfg["num_experts_per_tok"] * p["one_expert"] + p["shared_expert"]
        + p["router"]
    )
    return (
        (n["dense"] + n["sparse"]) * (p["attention"] + p["attention_plain"])
        + n["dense"] * p["dense_mlp"] + n["sparse"] * routed
    )


def attention_flops(cfg: dict, keys_seen: float) -> float:
    """Scores and weighted values of queries that see ``keys_seen`` keys
    between them, in the UN-absorbed form (a head's queries and keys ``dn +
    dr`` wide, its values ``dv``): what the mathematics needs. The absorbed
    form the program runs multiplies ``rank + dr`` and ``rank`` wide rows
    and is counted as no more."""
    hq = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return 2.0 * cfg["num_hidden_layers"] * hq * (dn + dr + dv) * keys_seen


def mixes_per_token(cfg: dict) -> int:
    return 2 * cfg["num_hidden_layers"]


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` valid tokens, causal. The head runs on
    the last position only."""
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    seen = prompt_len * (prompt_len + 1) / 2
    return (
        prompt_len * (
            2.0 * matmul_params_per_token(cfg)
            + mixes_per_token(cfg) * mhc_flops_per_mix(cfg)
        )
        + attention_flops(cfg, seen) + head
    )


def decode_token_flops(cfg: dict, context: int) -> float:
    """One generated token that attends to ``context`` cached positions."""
    return (
        2.0 * matmul_params_per_token(cfg)
        + mixes_per_token(cfg) * mhc_flops_per_mix(cfg)
        + attention_flops(cfg, context)
        + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    )


def stored_weight_bytes(cfg: dict, weight_bytes: float, live_share: float,
                        plain_bytes: float = 2.0) -> float:
    """Bytes a decode step must read of the weights: every layer's attention
    projections and hyper-connection projections (float32), the dense layer's
    MLP, of each expert layer the shared expert, the router and the LIVE share
    of its routed experts (``live_share``: ``moe_decode_experts_live`` over
    ``moe_decode_experts_held`` of the window, what the live path of
    ``ops/moe.py`` is expected to read), and the head. ``weight_bytes`` a
    value for what is stored quantised, ``plain_bytes`` for what stays in the
    model's dtype. The embedding is a lookup."""
    p, n = layer_parameters(cfg), layers_of(cfg)
    layers = n["dense"] + n["sparse"]
    return (
        layers * (
            p["attention"] * weight_bytes + p["attention_plain"] * plain_bytes
            + 2 * p["mhc_phi"] * 4.0
        )
        + n["dense"] * p["dense_mlp"] * weight_bytes
        + n["sparse"] * (
            (
                live_share * cfg["n_routed_experts"] * p["one_expert"]
                + p["shared_expert"]
            ) * weight_bytes
            + p["router"] * plain_bytes
        )
        + cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes
    )
