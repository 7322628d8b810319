"""Seeded weights of a ``glm_moe_dsa`` decoder (GLM-5.2) in the program's
parameter layout: one stacked dict a segment of the stack
(``ModelConfig.segments``: runs of layers alike in MLP and in their part in
the shared selection), made on the device in ONE jitted call, in the form they
are served in. Only what this chip holds is made: the HELD experts of each
layer (``ModelConfig.num_held_experts``; the router keeps its published
width), the vocabulary's slice (``vocab_size`` rows of the embedding and
columns of the head), and an indexer in the layers that score
(``LayerSegment.index`` "score": ``indexer_types`` "full") and in no other.

Stored int8 where the configuration serves int8, by ``dense_gqa.matrix``
(uniform int8 values, scales per output channel; the expert stacks per expert
and output channel): the compressed-query projections ``wq_a`` / ``wq_b``,
``wo``, the dense ``wg``/``wu``/``wd``, the experts ``we_*``, the shared
expert ``ws_*`` and ``lm_head``: what ``ops.quant.QUANTIZED_WEIGHTS`` names.
``wkv_a`` (its output is the stored latent), the einsum operands ``wk_b`` /
``wv_b``, the indexer's ``wq_i`` / ``wk_i`` / ``w_i`` (their scores rank
keys), the router, its selection bias and the norms' gains stay in the
model's dtype (the bias float32), as the program's quantiser leaves them.

The rotary slices (the last ``qk_rope_head_dim`` columns of each head of
``wq_b`` and of ``wkv_a``, the first ``qk_rope_head_dim`` of each index head
and of the index key) are in the program's order: the first members of the
published (2i, 2i + 1) pairs, then the second members, which is what a loader
makes of a checkpoint once (``models/llama.py:convert_hf_layer``). Seeded
columns have no order to keep, so nothing is permuted here; the plain
reference puts the pairs back side by side before it turns them.

The router is drawn ``ROUTER_GAIN`` times wider than the other matrices, for
``mixtral_moe``'s reason (a trained router is decisive; at ``WEIGHT_STD`` a
seeded one's eighth and ninth scores of 256 sit closer than bf16 rounding);
the selection bias is normal with ``BIAS_STD`` 0.01: small, not zero, so that
a path that ignores it, or weighs with it, chooses other experts. The
indexer's matrices are drawn ``INDEX_GAIN`` times wider for the same reason:
its scores rank 2048 of several thousand keys, and at ``WEIGHT_STD`` they
would sit within bf16 rounding of each other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights.dense_gqa import WEIGHT_STD, matrix

ROUTER_GAIN = 2.5
BIAS_STD = 0.01
INDEX_GAIN = 2.5


def layer_shapes(cfg, kind: str, index) -> tuple:
    """The matrices of one layer of a segment of MLP ``kind`` and selection
    part ``index``: those drawn in stored form, those kept in the model's
    dtype, and the indexer's (model's dtype, wider)."""
    lat, sa = cfg.latent, cfg.sparse
    h, hq, d = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    dn, dr = lat.nope_head_dim or d, lat.rope_head_dim
    dv, qr = lat.v_head_dim or d, lat.q_lora_rank
    stored = {
        "wq_a": (h, qr), "wq_b": (qr, hq * (dn + dr)), "wo": (hq * dv, h),
    }
    plain = {
        "wkv_a": (h, lat.rank + dr), "wk_b": (lat.rank, hq, dn),
        "wv_b": (lat.rank, hq, dv),
    }
    indexer = {} if index == "reuse" or sa is None else {
        "wq_i": (qr, sa.index_heads * sa.index_dim),
        "wk_i": (h, sa.index_dim), "w_i": (h, sa.index_heads),
    }
    if kind == "dense":
        f = cfg.intermediate_size
        stored.update({"wg": (h, f), "wu": (h, f), "wd": (f, h)})
    else:
        held, f = cfg.num_held_experts, cfg.expert_intermediate_size
        stored.update({
            "we_g": (held, h, f), "we_u": (held, h, f), "we_d": (held, f, h),
        })
        fs = cfg.num_shared_experts * f
        stored.update({"ws_g": (h, fs), "ws_u": (h, fs), "ws_d": (fs, h)})
    return stored, plain, indexer


def tree_fn(cfg, dtype, stored_as):
    h, v, e = cfg.hidden_size, cfg.vocab_size, cfg.num_experts

    def one_layer(seg, key):
        stored, plain, indexer = layer_shapes(cfg, seg.kind, seg.index)
        keys = iter(jax.random.split(
            key, len(stored) + len(plain) + len(indexer) + 2
        ))
        layer = {n: matrix(next(keys), s, dtype, stored_as) for n, s in stored.items()}
        layer.update({n: matrix(next(keys), s, dtype, None) for n, s in plain.items()})
        layer.update({
            n: (
                jax.random.normal(next(keys), s, jnp.float32)
                * WEIGHT_STD * INDEX_GAIN
            ).astype(dtype)
            for n, s in indexer.items()
        })
        layer["attn_norm"] = jnp.ones((h,), dtype)
        layer["mlp_norm"] = jnp.ones((h,), dtype)
        layer["kv_norm"] = jnp.ones((cfg.latent.rank,), dtype)
        layer["q_a_norm"] = jnp.ones((cfg.latent.q_lora_rank,), dtype)
        if indexer:
            layer["k_i_norm"] = jnp.ones((cfg.sparse.index_dim,), dtype)
            layer["k_i_norm_bias"] = jnp.zeros((cfg.sparse.index_dim,), dtype)
        if seg.kind == "moe":
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
                    lambda k, seg=seg: one_layer(seg, k),
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
            "a learned selection over a latent pool is single-device "
            "(engine/engine.py)"
        )
    latent = getattr(cfg, "latent", None)
    if (
        latent is None
        or getattr(latent, "q_lora_rank", None) is None
        or getattr(cfg, "sparse", None) is None
        or cfg.num_experts == 0
    ):
        raise ValueError(
            "this program's ModelConfig read no compressed queries, learned "
            "selection and experts beside a latent from the block: it does "
            "not implement the glm_moe_dsa layer (before PR 44)"
        )
    return jax.jit(tree_fn(cfg, dtype, stored))(jax.random.PRNGKey(seed))
