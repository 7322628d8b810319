"""Seeded weights of a ``KeyeVL2`` decoder (Keye-VL-2.0-30B-A3B's language
model) in the program's parameter layout, made on the device in ONE jitted
call, in the form they are served in (``dense_gqa.build``).

Stored int8 where the configuration serves int8: ``wq``, ``wk``, ``wv``,
``wo``, the experts ``we_*`` and ``lm_head``: what
``ops.quant.QUANTIZED_WEIGHTS`` names. In the model's dtype: the router, the
norms' gains (ones; the index key's LayerNorm bias zeros) and the indexer's
three projections ``wq_i``, ``wk_i``, ``w_i``.

The router is drawn ``ROUTER_GAIN`` times wider than the other matrices, for
``mixtral_moe``'s reason (a trained router is decisive; a seeded one at
``WEIGHT_STD`` puts the eighth and ninth of 128 scores closer than bf16
rounding). The indexer's projections are drawn at ``WEIGHT_STD``: over a
normed hidden state of 2048 an index query's entries then have a spread of
0.9, the LayerNormed index key's 1, a head's dot product 7, and ``I[t, s]``
a spread of about 0.5 over ``s`` with half of a head's terms zero under the
relu: neither all zeros nor all alike, so the selection is a real choice of
2048.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import dense_gqa

ROUTER_GAIN = 5.0


def make(cfg, seed: int, dtype, stored, mesh=None):
    sa = getattr(cfg, "sparse", None)
    if sa is None or not getattr(cfg, "qk_norm", False) or cfg.num_experts == 0:
        raise ValueError(
            "this program's ModelConfig read no learned key selection "
            "(sa_config), per-head norms and experts from the block: it does "
            "not implement the KeyeVL2 layer (before PR 32)"
        )
    if mesh is not None:
        raise ValueError("the index plane is single-device (engine/engine.py)")
    h, e, f = cfg.hidden_size, cfg.num_experts, cfg.expert_intermediate_size
    shapes = {
        k: s for k, s in dense_gqa.layer_shapes(cfg).items()
        if k in ("wq", "wk", "wv", "wo")
    }
    shapes.update({"we_g": (e, h, f), "we_u": (e, h, f), "we_d": (e, f, h)})
    plain = {
        "router": ((h, e), ROUTER_GAIN),
        "wq_i": ((h, sa.index_heads * sa.index_dim), 1.0),
        "wk_i": ((h, sa.index_dim), 1.0),
        "w_i": ((h, sa.index_heads), 1.0),
    }

    def extra(key):
        keys = jax.random.split(key, len(plain))
        layer = {
            name: (
                jax.random.normal(k, shape, jnp.float32)
                * dense_gqa.WEIGHT_STD * gain
            ).astype(dtype)
            for (name, (shape, gain)), k in zip(plain.items(), keys)
        }
        layer["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        layer["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
        layer["k_i_norm"] = jnp.ones((sa.index_dim,), dtype)
        layer["k_i_norm_bias"] = jnp.zeros((sa.index_dim,), dtype)
        return layer

    return dense_gqa.build(cfg, seed, dtype, stored, shapes, extra)
