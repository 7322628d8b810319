"""Seeded weights of an ``xing4_0`` decoder (Xing4.0-29B-A4B) in the program's
parameter layout: one stacked dict a segment of the stack
(``ModelConfig.segments``: the leading dense layers, then the routed ones),
made on the device in ONE jitted call, in the form they are served in.

The DeepSeek-V3 part is ``moonlight_mla_moe``'s with compressed queries
(``glm_mla_dsa_moe.layer_shapes``, no indexer, every expert held):
stored int8 where the configuration serves int8 (``dense_gqa.matrix``: ``wq_a``
/ ``wq_b``, ``wo``, the dense ``wg``/``wu``/``wd``, the experts ``we_*``, the
shared expert ``ws_*``, ``lm_head``: what ``ops.quant.QUANTIZED_WEIGHTS``
names); ``wkv_a``, ``wk_b`` / ``wv_b``, the router (``ROUTER_GAIN`` times
wider, as Moonlight's) and the norms' gains in the model's dtype; the
selection bias float32 with ``BIAS_STD`` 0.01.

The hyper-connection leaves of each sublayer (``hc_attn_*`` / ``hc_mlp_*``:
``phi [2n + n*n, nC]``, ``alpha [3]``, ``bias [2n + n*n]``, float32) are
seeded AWAY from the maps' degenerate points, so that a comparison sees the
mechanism: ``phi`` normal with standard deviation ``(nC)^-1/2`` and ``alpha``
1, so that ``alpha (x~ phi)`` over the unit-RMS ``x~`` has a standard
deviation of about 1 (the maps move with the token: ``H_pre`` from 0.1 to
0.9); ``b_pre`` / ``b_post`` normal with standard deviation ``HC_BIAS_STD`` 1,
and ``b_res`` normal with ``HC_RES_BIAS_STD`` 2, NOT the identity's logits: a
trained block starts at the identity (``H_res = I``, under which a transposed
``H_res`` or a Sinkhorn cut short is invisible), a seeded one must not sit
there. At 2 ``H_res`` is uneven enough that its transpose is another matrix
(at the rehearsal's size a transposed ``H_res`` moves the judged logits by
0.011 to 0.023 where the served path reads 0.002; at 1 it moved them by 0.005),
and still a map that 20 rounds bring to row and column sums within 0.04 of 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights.dense_gqa import WEIGHT_STD, matrix
from benchmark.weights.glm_mla_dsa_moe import layer_shapes
from benchmark.weights.moonlight_mla_moe import BIAS_STD, ROUTER_GAIN

HC_BIAS_STD = 1.0
HC_RES_BIAS_STD = 2.0
HC_SUBLAYERS = ("hc_attn", "hc_mlp")


def hyper_leaves(cfg, key) -> dict:
    """One layer's hyper-connection leaves, float32 (module docstring)."""
    n, h = cfg.hyper.mult, cfg.hidden_size
    width = 2 * n + n * n
    out = {}
    for prefix, k in zip(HC_SUBLAYERS, jax.random.split(key, len(HC_SUBLAYERS))):
        k_phi, k_bias = jax.random.split(k)
        out[f"{prefix}_phi"] = (
            jax.random.normal(k_phi, (width, n * h), jnp.float32) * (n * h) ** -0.5
        )
        out[f"{prefix}_alpha"] = jnp.ones((3,), jnp.float32)
        std = jnp.where(jnp.arange(width) < 2 * n, HC_BIAS_STD, HC_RES_BIAS_STD)
        out[f"{prefix}_bias"] = std * jax.random.normal(
            k_bias, (width,), jnp.float32
        )
    return out


def tree_fn(cfg, dtype, stored_as):
    h, v, e = cfg.hidden_size, cfg.vocab_size, cfg.num_experts

    def one_layer(kind, key):
        # the latent block with compressed queries, every expert held: the
        # glm maker's shapes without an indexer
        stored, plain, _ = layer_shapes(cfg, kind, None)
        keys = iter(jax.random.split(key, len(stored) + len(plain) + 3))
        layer = {n: matrix(next(keys), s, dtype, stored_as) for n, s in stored.items()}
        layer.update({n: matrix(next(keys), s, dtype, None) for n, s in plain.items()})
        layer.update(hyper_leaves(cfg, next(keys)))
        layer["attn_norm"] = jnp.ones((h,), dtype)
        layer["mlp_norm"] = jnp.ones((h,), dtype)
        layer["kv_norm"] = jnp.ones((cfg.latent.rank,), dtype)
        layer["q_a_norm"] = jnp.ones((cfg.latent.q_lora_rank,), dtype)
        if kind == "moe":
            layer["router"] = (
                jax.random.normal(next(keys), (h, e), jnp.float32)
                * WEIGHT_STD * ROUTER_GAIN
            ).astype(dtype)
            layer["router_bias"] = BIAS_STD * jax.random.normal(
                next(keys), (e,), jnp.float32
            )
        return layer

    def tree(key):
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        seg_keys = jax.random.split(k_layers, len(cfg.segments))
        return {
            "embed": (
                jax.random.normal(k_embed, (v, h), jnp.float32) * WEIGHT_STD
            ).astype(dtype),
            **{
                seg.key: jax.lax.map(
                    lambda k, kind=seg.kind: one_layer(kind, k),
                    jax.random.split(sk, seg.count),
                )
                for seg, sk in zip(cfg.segments, seg_keys)
            },
            "final_norm": jnp.ones((h,), dtype),
            "lm_head": matrix(k_head, (h, v), dtype, stored_as),
        }

    return tree


def make(cfg, seed: int, dtype, stored, mesh=None):
    if mesh is not None:
        raise ValueError("a widened stream is single-device (engine/engine.py)")
    latent = getattr(cfg, "latent", None)
    if (
        getattr(cfg, "hyper", None) is None
        or latent is None
        or getattr(latent, "q_lora_rank", None) is None
        or cfg.num_experts == 0
        or not cfg.moe_select_bias
    ):
        raise ValueError(
            "this program's ModelConfig read no widened stream, compressed "
            "queries and experts beside a latent from the block: it does not "
            "implement the xing4_0 layer (before PR 47)"
        )
    return jax.jit(tree_fn(cfg, dtype, stored))(jax.random.PRNGKey(seed))
