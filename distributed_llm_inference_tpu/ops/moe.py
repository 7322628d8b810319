"""Mixture-of-experts MLP (Mixtral-style) with expert-parallel sharding.

The reference has no MoE layers — it only reuses hivemind's *moe.server*
machinery for serving scaffolding (SURVEY §2.3;
``/root/reference/distributed_llm_inference/server/backend.py:5``). MoE here is
a capability extension required for the Mixtral model family.

Routing follows Mixtral: softmax over ALL expert logits in fp32, top-k
selection, renormalize the selected probabilities.

Two compute strategies, both all-static shapes:

* **dense-combine** (decode, S == 1) — every expert processes every token and
  a ``[B, S, E]`` combine matrix (zero off the top-k) weights the outputs.
  Decode is bound by READING every expert's weights regardless, so the
  overcompute is free, and with experts sharded over ``ep`` the combine
  contraction becomes a ``psum`` XLA inserts automatically.
* **sorted dispatch** (prefill) — (token, expert) pairs argsort to their
  experts; each expert computes only its capacity-bounded slice
  (``moe_mlp_dispatch``), cutting MLP FLOPs by E/(k·capacity_factor). The
  whole path is gathers (a scatter would serialize on TPU).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from . import quant

__all__ = ["moe_mlp", "router_weights"]


def router_weights(
    cfg: ModelConfig, x: jnp.ndarray, router: jnp.ndarray
) -> jnp.ndarray:
    """Mixtral routing: fp32 softmax over all experts → top-k → renormalize.

    ``x``: ``[B, S, H]``; ``router``: ``[H, E]``. Returns the dense combine
    matrix ``[B, S, E]`` (sums to 1 over the selected experts, 0 elsewhere).
    """
    with jax.named_scope("moe_router"):
        logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        one_hot = jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
        return jnp.einsum("bsk,bske->bse", top_p, one_hot)


def moe_mlp(
    cfg: ModelConfig,
    p,
    x: jnp.ndarray,
    valid=None,
) -> jnp.ndarray:
    """SwiGLU expert MLPs + weighted combine.

    ``p["router"]``: ``[H, E]``; ``p["we_g"]``/``p["we_u"]``: ``[E, H, F]``;
    ``p["we_d"]``: ``[E, F, H]`` (E shardable over ``ep``, F over ``tp``).

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
        return moe_mlp_dispatch(cfg, p, x, cfg.moe_capacity_factor, valid)
    combine = router_weights(cfg, x, p["router"]).astype(x.dtype)
    with jax.named_scope("moe_experts"):
        t = quant.einsum("bsh,ehf->bsef", x, p["we_g"])
        u = quant.einsum("bsh,ehf->bsef", x, p["we_u"])
        y = quant.einsum("bsef,efh->bseh", jax.nn.silu(t) * u, p["we_d"])
    with jax.named_scope("moe_combine"):
        return jnp.einsum("bse,bseh->bsh", combine, y)


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
    capacity_factor`` rounds to a static shape; pairs past an expert's
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
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    n = b * s
    xf = x.reshape(n, h)

    with jax.named_scope("moe_router"):
        logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    pair_e = top_i.reshape(-1)                                  # [N*k]
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
        1, min(n, math.ceil((n * k) / e * capacity_factor))
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
