"""Seeded weights of an ``exaone_moe`` decoder (K-EXAONE-236B-A23B) in the
program's parameter layout: one stacked dict a segment of the stack
(``ModelConfig.segments``: runs of layers alike in MLP and in attention),
made on the device in ONE jitted call, in the form they are served in. Only
what this chip holds is made: the HELD experts of each layer
(``ModelConfig.num_held_experts``; the router keeps its published width) and
the vocabulary's slice (``vocab_size`` rows of the embedding and columns of
the head).

Stored int8 where the configuration serves int8, by ``dense_gqa.matrix``
(uniform int8 values, scales per output channel; the expert stacks per
expert and output channel): ``wq``, ``wk``, ``wv``, ``wo``, the dense
``wg``/``wu``/``wd``, the experts ``we_*``, the shared expert ``ws_*`` and
``lm_head``: what ``ops.quant.QUANTIZED_WEIGHTS`` names. The router, its
selection bias and the norms' gains (ones) stay in the model's dtype
(the bias float32).

The router is drawn ``ROUTER_GAIN`` times wider than the other matrices, for
``mixtral_moe``'s reason (a trained router is decisive; at ``WEIGHT_STD`` a
seeded one's eighth and ninth scores of 128 sit closer than bf16 rounding):
over a normed hidden state of 6144 its logits then have a spread of 3.9,
sigmoid scores from 0.02 to 0.98. The selection bias is normal with
``BIAS_STD`` 0.01: small, not zero, so that a path that ignores it, or
weighs with it, chooses other experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights.dense_gqa import WEIGHT_STD, matrix

ROUTER_GAIN = 2.5
BIAS_STD = 0.01


def layer_shapes(cfg, kind: str) -> dict:
    """The matrices of one layer of a segment of MLP ``kind`` that are drawn
    in stored form."""
    h, hq, hkv, d = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    shapes = {
        "wq": (h, hq * d), "wk": (h, hkv * d), "wv": (h, hkv * d),
        "wo": (hq * d, h),
    }
    if kind == "dense":
        f = cfg.intermediate_size
        shapes.update({"wg": (h, f), "wu": (h, f), "wd": (f, h)})
    else:
        held, f = cfg.num_held_experts, cfg.expert_intermediate_size
        shapes.update({
            "we_g": (held, h, f), "we_u": (held, h, f), "we_d": (held, f, h),
        })
        fs = cfg.num_shared_experts * f
        shapes.update({"ws_g": (h, fs), "ws_u": (h, fs), "ws_d": (fs, h)})
    return shapes


def tree_fn(cfg, dtype, stored_as):
    h, v, e = cfg.hidden_size, cfg.vocab_size, cfg.num_experts

    def one_layer(kind, key):
        shapes = layer_shapes(cfg, kind)
        keys = iter(jax.random.split(key, len(shapes) + 2))
        layer = {n: matrix(next(keys), s, dtype, stored_as) for n, s in shapes.items()}
        layer["attn_norm"] = jnp.ones((h,), dtype)
        layer["mlp_norm"] = jnp.ones((h,), dtype)
        layer["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        layer["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
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
        raise ValueError(
            "a cache of window and full layers is single-device "
            "(engine/engine.py)"
        )
    if (
        not getattr(cfg, "mixed_attention", False)
        or not getattr(cfg, "qk_norm", False)
        or cfg.num_experts == 0
    ):
        raise ValueError(
            "this program's ModelConfig read no window and full layers in "
            "one stack, per-head norms and experts from the block: it does "
            "not implement the exaone_moe layer (before PR 35)"
        )
    return jax.jit(tree_fn(cfg, dtype, stored))(jax.random.PRNGKey(seed))
