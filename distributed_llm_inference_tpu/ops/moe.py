"""Mixture-of-experts MLP with expert-parallel sharding: routed experts,
optional shared experts, and the routing rule the config names.

The reference has no MoE layers — it only reuses hivemind's *moe.server*
machinery for serving scaffolding (SURVEY §2.3;
``/root/reference/distributed_llm_inference/server/backend.py:5``). MoE here is
a capability extension required for the Mixtral and DeepSeek-V2/V3 families.

Routing is ONE function (:func:`route`) whose rule the config chooses:
Mixtral's (softmax over ALL expert logits in fp32, top-k, renormalise) and
DeepSeek-V3's (sigmoid scores, selection by score plus a per-expert bias,
weights from the scores alone, normalised and scaled). Both compute
strategies sit behind it, all-static shapes:

* **dense-combine** (decode, S == 1) — every expert processes every token and
  a ``[B, S, E]`` combine matrix (zero off the top-k) weights the outputs.
  Decode is bound by READING every expert's weights regardless, so the
  overcompute is free, and with experts sharded over ``ep`` the combine
  contraction becomes a ``psum`` XLA inserts automatically.
* **sorted dispatch** (prefill) — (token, expert) pairs argsort to their
  experts; each expert computes only its capacity-bounded slice
  (``moe_mlp_dispatch``), cutting MLP FLOPs by E/(k·capacity_factor). The
  whole path is gathers (a scatter would serialize on TPU).

Shared experts (``p["ws_g"]``/``ws_u``/``ws_d``, present where the config
has them) are one SwiGLU MLP every token passes through, added to the routed
sum under the scope ``moe_shared``.

Two counts, not one. ``ModelConfig.num_experts`` is the ROUTER's width: every
token is scored over all of them and picks its ``k`` among all of them.
``ModelConfig.num_held_experts`` is how many expert matrices this program
holds (``we_*`` are ``[held, ...]``): all of them, or one of
``expert_shares`` contiguous shares (experts ``first_held_expert ..
first_held_expert + held``), as one chip of an expert-parallel deployment
holds. A layer with a share computes ITS experts' part of the routed sum
(the combine matrix keeps the held columns; a pick that lives elsewhere adds
nothing here) plus the shared expert, and that partial result goes on.
Nothing here stands in for the absent experts or for an exchange.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from . import quant

__all__ = ["moe_mlp", "route", "router_weights", "expert_rows_per_token"]


def route(cfg: ModelConfig, x: jnp.ndarray, router: jnp.ndarray, bias=None):
    """The routing rule, as the config states it. ``x``: ``[..., H]``;
    ``router``: ``[H, E]``; ``bias``: ``[E]`` or None. Returns ``(weights
    [..., k] fp32, experts [..., k] int32)``.

    Scores over ALL experts in fp32: softmax (Mixtral, DeepSeek-V2) or
    sigmoid (DeepSeek-V3 ``MoEGate``). Selection: the ``k`` largest of the
    scores, or of scores PLUS ``bias`` where the checkpoint carries one
    (``e_score_correction_bias``, ``topk_method`` "noaux_tc"; groups of one
    make the published group step the identity). The bias chooses and is
    never weighed: the weights are the selected SCORES, divided by their
    sum (``moe_norm_topk``), times ``moe_routed_scale``.
    """
    k = cfg.num_experts_per_tok
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top_w, top_i = jax.lax.top_k(scores, k)
    else:
        _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.moe_norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    if cfg.moe_routed_scale != 1.0:
        top_w = top_w * cfg.moe_routed_scale
    return top_w, top_i


def router_weights(
    cfg: ModelConfig, x: jnp.ndarray, router: jnp.ndarray, bias=None
) -> jnp.ndarray:
    """:func:`route` as the dense combine matrix.

    ``x``: ``[B, S, H]``; ``router``: ``[H, E]``, E the router's width.
    Returns ``[B, S, held]``: a token's routing weights at those of its
    selected experts that are held here, 0 elsewhere (``held`` = E unless
    the layer holds a share; a pick outside the share matches no column).
    """
    with jax.named_scope("moe_router"):
        top_p, top_i = route(cfg, x, router, bias)
        if cfg.expert_shares > 1:
            top_i = top_i - cfg.first_held_expert
        one_hot = jax.nn.one_hot(
            top_i, cfg.num_held_experts, dtype=jnp.float32
        )
        return jnp.einsum("bsk,bske->bse", top_p, one_hot)


def expert_rows_per_token(cfg: ModelConfig, seq_len: int):
    """``(needed, computed)``: expert MLPs one token's result needs in one
    expert layer (its selected experts and the shared ones) and how many
    the program runs for it in a dispatch ``seq_len`` wide (every routed
    HELD expert under dense-combine; its capacity's share under sorted
    dispatch). The census behind ``moe_expert_rows_*``. Where the layer
    holds a share, ``needed`` is an EXPECTATION: of a token's ``k`` picks
    over the router's ``E``, ``k * held / E`` fall here on average (uniform
    routing); which do is data the host does not see."""
    shared = cfg.num_shared_experts
    k = cfg.num_experts_per_tok
    held = cfg.num_held_experts
    if cfg.expert_shares > 1:
        k = k * held / cfg.num_experts
    if cfg.moe_capacity_factor is not None and seq_len >= 16:
        return k + shared, k * cfg.moe_capacity_factor + shared
    return k + shared, held + shared


# Dense-combine runs a dispatch's tokens whole up to this many a row (every
# cell before PR 32 pads to 2048 or fewer: their programs are as they were),
# and in blocks of ``DENSE_COMBINE_BLOCK`` past it.
DENSE_COMBINE_TOKENS = 2048
DENSE_COMBINE_BLOCK = 1024


def _shared_experts(p, x: jnp.ndarray):
    """The shared experts: one SwiGLU MLP over every token."""
    with jax.named_scope("moe_shared"):
        return quant.matmul(
            jax.nn.silu(quant.matmul(x, p["ws_g"])) * quant.matmul(x, p["ws_u"]),
            p["ws_d"],
        )


def moe_mlp(
    cfg: ModelConfig,
    p,
    x: jnp.ndarray,
    valid=None,
) -> jnp.ndarray:
    """SwiGLU expert MLPs + weighted combine.

    ``p["router"]``: ``[H, E]``, E the router's width; ``p["we_g"]`` /
    ``p["we_u"]``: ``[held, H, F]``; ``p["we_d"]``: ``[held, F, H]`` (held =
    E, or this program's share of E: the module docstring; the expert axis
    shardable over ``ep``, F over ``tp``);
    ``p["router_bias"]`` ``[E]`` and the shared experts' ``p["ws_*"]`` where
    the model has them (:func:`route`, :func:`_shared_experts`).

    Dense-combine is the default everywhere: exact, shape-static, and every
    token's output independent of co-batched rows (decode and verify steps
    are bound by reading every expert's weights regardless, so the
    overcompute is free there). Setting ``ModelConfig.moe_capacity_factor``
    OPTS IN to sorted dispatch for prefill-scale steps (S >= 16):
    E/(k·factor)× less MLP compute at the cost of capacity drops — which
    also make results depend on prefill chunk boundaries, hence opt-in.
    ``valid`` (``[B, S]`` bool) marks real tokens; bucket-padding positions
    must not consume expert capacity in the dispatched path.
    """
    if cfg.moe_capacity_factor is not None and x.shape[1] >= 16:
        out = moe_mlp_dispatch(cfg, p, x, cfg.moe_capacity_factor, valid)
    elif x.shape[1] > DENSE_COMBINE_TOKENS and x.shape[1] % DENSE_COMBINE_BLOCK == 0:
        # A dispatch wider than any before PR 32 (a 4096-wide chunk over 128
        # experts: ``[b, s, E, H]`` alone is 2.1 GB in bf16) walks its
        # tokens a block at a time. A token's result does not depend on its
        # neighbours, so the numbers are the whole dispatch's.
        b, s, h = x.shape
        blocks = jnp.moveaxis(
            x.reshape(b, s // DENSE_COMBINE_BLOCK, DENSE_COMBINE_BLOCK, h), 1, 0
        )
        routed = {k: v for k, v in p.items() if not k.startswith("ws_")}
        out = jax.lax.map(lambda xb: moe_mlp(cfg, routed, xb), blocks)
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, h)
    else:
        combine = router_weights(
            cfg, x, p["router"], p.get("router_bias")
        ).astype(x.dtype)
        with jax.named_scope("moe_experts"):
            t = quant.einsum("bsh,ehf->bsef", x, p["we_g"])
            u = quant.einsum("bsh,ehf->bsef", x, p["we_u"])
            y = quant.einsum("bsef,efh->bseh", jax.nn.silu(t) * u, p["we_d"])
        with jax.named_scope("moe_combine"):
            out = jnp.einsum("bse,bseh->bsh", combine, y)
    if "ws_g" in p:
        out = out + _shared_experts(p, x)
    return out


def _expert_matmul(spec: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """Per-expert einsum that handles quantized expert stacks. The generic
    ``quant.einsum`` needs the weight's non-contracted axes LAST in the
    output; here the expert axis leads (``ecf``/``ech``), so the
    per-(expert, out-channel) scale ``[E, out]`` broadcasts at axis -1 with
    the capacity axis in between."""
    if isinstance(w, quant.QuantizedTensor):
        y = jnp.einsum(spec, x, w.q.astype(x.dtype))
        return y * w.scale[:, None, :].astype(x.dtype)
    return jnp.einsum(spec, x, w)


def moe_mlp_dispatch(
    cfg: ModelConfig,
    p,
    x: jnp.ndarray,
    capacity_factor: float = 2.0,
    valid=None,
    capacity=None,
) -> jnp.ndarray:
    """Sorted (capacity-based) expert dispatch — the prefill MoE path.

    Gather-only by construction (a scatter lowers to a serial row loop on
    TPU and trips GSPMD — see cache/dense.py): (token, expert) pairs are
    argsorted by expert, each expert's slots gather their tokens, the
    per-expert MLP runs on ``[E, C, H]``, and undoing the sort turns the
    combine into a dense ``[N, k]`` weighted sum. ``C = N·k/E ·
    capacity_factor`` (E the router's width: the pairs an expert expects)
    rounds to a static shape; pairs past an expert's
    capacity are dropped (their routing weight contributes nothing) — rare
    at factor 2 under Mixtral's near-uniform routing, and bounded: a dropped
    pair loses at most its renormalized probability share of one token.

    ``valid`` (``[B, S]`` bool): invalid (bucket-padding) tokens route to a
    sentinel expert id ``E`` — the stable sort parks them AFTER every real
    expert's group, so padding can never evict a real token from capacity.

    NOTE: under an ``ep``-sharded mesh the expert-indexed gathers here have
    not been perf-verified (GSPMD may all-gather the expert stacks); the
    dense-combine path is the ep-proven one. Dispatch is opt-in
    (``ModelConfig.moe_capacity_factor``) partly for this reason.
    """
    b, s, h = x.shape
    # ``e``: the experts HELD here; the router scores all of its width and
    # a pick that lives in another share goes to the sentinel below.
    e, k = cfg.num_held_experts, cfg.num_experts_per_tok
    n = b * s
    xf = x.reshape(n, h)

    with jax.named_scope("moe_router"):
        top_p, top_i = route(cfg, xf, p["router"], p.get("router_bias"))

    pair_e = top_i.reshape(-1)                                  # [N*k]
    if cfg.expert_shares > 1:
        here = (top_i >= cfg.first_held_expert) & (
            top_i < cfg.first_held_expert + e
        )
        pair_e = jnp.where(
            here.reshape(-1), pair_e - cfg.first_held_expert, e
        )
        top_p = top_p * here.astype(top_p.dtype)
    pair_t = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)      # [N*k]
    if valid is not None:
        vf = valid.reshape(-1)
        pair_e = jnp.where(jnp.repeat(vf, k), pair_e, e)
        top_p = top_p * vf[:, None].astype(top_p.dtype)

    order = jnp.argsort(pair_e, stable=True)
    sorted_e = pair_e[order]
    sorted_t = pair_t[order]
    # e+1 bounds so sentinel (padding) pairs sit past EVERY group_end.
    bounds = jnp.searchsorted(sorted_e, jnp.arange(e + 1), side="left")
    group_start, group_end = bounds[:e], bounds[1:]
    pos_in_group = jnp.arange(n * k, dtype=jnp.int32) - group_start[
        jnp.clip(sorted_e, 0, e - 1)
    ]

    c = capacity if capacity is not None else max(
        1, min(n, math.ceil((n * k) / cfg.num_experts * capacity_factor))
    )
    # Slot (expert, c) holds the token at sorted position start_e + c.
    slot_pos = group_start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    slot_valid = slot_pos < group_end[:, None]
    slot_tok = sorted_t[jnp.clip(slot_pos, 0, n * k - 1)]       # [E, C]

    gathered = xf[slot_tok] * slot_valid[..., None].astype(x.dtype)
    with jax.named_scope("moe_experts"):
        t = _expert_matmul("ech,ehf->ecf", gathered, p["we_g"])
        u = _expert_matmul("ech,ehf->ecf", gathered, p["we_u"])
        y = _expert_matmul("ecf,efh->ech", jax.nn.silu(t) * u, p["we_d"])

    # Back to pair order (pure gathers: undo the sort), then a dense [N, k]
    # weighted combine.
    with jax.named_scope("moe_combine"):
        kept = pos_in_group < c
        pair_out_sorted = y[
            sorted_e, jnp.clip(pos_in_group, 0, c - 1)
        ] * kept[:, None].astype(x.dtype)                       # [N*k, H]
        inv = jnp.argsort(order)
        pair_out = pair_out_sorted[inv].reshape(n, k, h)
        out = jnp.einsum(
            "nk,nkh->nh", top_p.astype(jnp.float32),
            pair_out.astype(jnp.float32),
        )
        return out.reshape(b, s, h).astype(x.dtype)
