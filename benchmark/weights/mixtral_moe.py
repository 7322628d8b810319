"""Seeded weights of a Mixtral-style decoder: the attention matrices of
``dense_gqa``, a bf16 router ``[H, E]`` and the expert stacks ``we_g``/``we_u
[E, H, F]``, ``we_d [E, F, H]`` in the program's layout, stored int8 where
the configuration serves int8 (scales per expert and output channel).

The router is drawn ``ROUTER_GAIN`` times wider than the other matrices: at
``WEIGHT_STD`` its logits have a spread of 1.3 and the second and third
expert of a token often sit closer than bf16 rounding, so that the served
path and the float32 reference route many tokens differently and their
logits say nothing (my chip run, PR 22: 0.17 to 1.2 from token to token). A
trained router is decisive; at a spread of 6 a seeded one is too. Every
expert is still chosen equally often, and the work is the same."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import dense_gqa

ROUTER_GAIN = 5.0


def make(cfg, seed: int, dtype, stored, mesh=None):
    h, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    shapes = {
        k: s for k, s in dense_gqa.layer_shapes(cfg).items()
        if k in ("wq", "wk", "wv", "wo")
    }
    shapes.update({"we_g": (e, h, f), "we_u": (e, h, f), "we_d": (e, f, h)})

    def router(key):
        return {"router": (
            jax.random.normal(key, (h, e), jnp.float32)
            * dense_gqa.WEIGHT_STD * ROUTER_GAIN
        ).astype(dtype)}

    return dense_gqa.build(cfg, seed, dtype, stored, shapes, router, mesh)
