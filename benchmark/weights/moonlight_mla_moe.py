"""Seeded weights of a DeepSeek-V3-style decoder (Moonlight-16B-A3B) in the
program's parameter layout: one stacked dict a segment of the stack
(``ModelConfig.segments``: the leading dense layers, then the routed ones),
made on the device in ONE jitted call, in the form they are served in.

Stored int8 where the configuration serves int8, by ``dense_gqa.matrix``
(uniform int8 values, scales per output channel; the expert stacks per
expert and output channel): ``wq``, ``wo``, the dense ``wg``/``wu``/``wd``,
the experts ``we_*``, the shared experts ``ws_*`` and ``lm_head``: what
``ops.quant.QUANTIZED_WEIGHTS`` names. ``wkv_a`` (its output is the stored
latent) and the einsum operands ``wk_b`` / ``wv_b`` stay in the model's
dtype, as the program's quantiser leaves them.

The router is drawn ``ROUTER_GAIN`` times wider than the other matrices, for
``mixtral_moe``'s reason (a trained router is decisive; at ``WEIGHT_STD`` a
seeded one's sixth and seventh scores of 64 sit closer than bf16 rounding):
its logits then have a spread of 2.3, sigmoid scores of 0.1 to 0.99. The
selection bias ``router_bias`` is normal with ``BIAS_STD`` 0.01, the size of
the gap between the sixth and the seventh score: small, not zero, so that a
path that ignores it, or weighs with it, chooses other experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights.dense_gqa import WEIGHT_STD, matrix

ROUTER_GAIN = 2.5
BIAS_STD = 0.01


def layer_shapes(cfg, kind: str) -> tuple:
    """The matrices of one layer of a segment ``kind``: those drawn in
    stored form, and those kept in the model's dtype."""
    lat = cfg.latent
    h, hq, d = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    dn, dr = lat.nope_head_dim or d, lat.rope_head_dim
    dv = lat.v_head_dim or d
    stored = {"wq": (h, hq * (dn + dr)), "wo": (hq * dv, h)}
    plain = {
        "wkv_a": (h, lat.rank + dr), "wk_b": (lat.rank, hq, dn),
        "wv_b": (lat.rank, hq, dv),
    }
    if kind == "dense":
        f = cfg.intermediate_size
        stored.update({"wg": (h, f), "wu": (h, f), "wd": (f, h)})
    else:
        e, f = cfg.num_experts, cfg.expert_intermediate_size
        stored.update({"we_g": (e, h, f), "we_u": (e, h, f), "we_d": (e, f, h)})
        if cfg.num_shared_experts:
            fs = cfg.num_shared_experts * f
            stored.update({"ws_g": (h, fs), "ws_u": (h, fs), "ws_d": (fs, h)})
    return stored, plain


def tree_fn(cfg, dtype, stored_as):
    h, v, e = cfg.hidden_size, cfg.vocab_size, cfg.num_experts

    def one_layer(kind, key):
        stored, plain = layer_shapes(cfg, kind)
        keys = iter(jax.random.split(key, len(stored) + len(plain) + 2))
        layer = {n: matrix(next(keys), s, dtype, stored_as) for n, s in stored.items()}
        layer.update({n: matrix(next(keys), s, dtype, None) for n, s in plain.items()})
        layer["attn_norm"] = jnp.ones((h,), dtype)
        layer["mlp_norm"] = jnp.ones((h,), dtype)
        layer["kv_norm"] = jnp.ones((cfg.latent.rank,), dtype)
        if kind == "moe":
            layer["router"] = (
                jax.random.normal(next(keys), (h, e), jnp.float32)
                * WEIGHT_STD * ROUTER_GAIN
            ).astype(dtype)
            if cfg.moe_select_bias:
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
        raise ValueError("the latent pool is single-device (engine/engine.py)")
    if not getattr(cfg, "segments", None) or cfg.num_experts == 0:
        raise ValueError(
            "this program's ModelConfig read no routed experts behind leading "
            "dense layers from the block: it does not implement the "
            "DeepSeek-V3 stack (before PR 26)"
        )
    return jax.jit(tree_fn(cfg, dtype, stored))(jax.random.PRNGKey(seed))
