"""Seeded weights of a ``brumby`` decoder (Brumby-14B-Base: the Qwen3 block
with power-retention layers) in the program's parameter layout, made on the
device in ONE jitted call, in the form they are served in
(``dense_gqa.build``).

Stored int8 where the configuration serves int8: ``wq``, ``wk``, ``wv``,
``wo``, the MLP and ``lm_head``: what ``ops.quant.QUANTIZED_WEIGHTS`` names.
In the model's dtype: the norms' gains (ones) and the gate's projection
``w_gate [H, Hkv]``; in float32 its bias ``b_gate [Hkv]``.

**The gates.** A retention layer's gate a key-value head is ``sigmoid(w_gate
n + b_gate)``. A gate that is always 1 makes the state an undecayed sum and
one that is always 0 makes it nothing, and either hides the decay from the
comparison with the reference. ``b_gate`` is drawn so that ``1 - gate`` is
log-uniform between 1e-1 and 10 ** ``-GATE_DECADES`` at ``w_gate n = 0``:
gates from 0.9 (a memory of ten positions) to 0.9997 (three thousand), a head
each its own; ``w_gate`` at ``WEIGHT_STD`` moves a position's logit by about
1.4 around it (a normed hidden state of 5120), so the gates also move from
position to position.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import dense_gqa

GATE_DECADES = 3.5


def make(cfg, seed: int, dtype, stored, mesh=None):
    if getattr(cfg, "retention", None) is None or not getattr(
        cfg, "qk_norm", False
    ):
        raise ValueError(
            "this program's ModelConfig read no retention part and per-head "
            "norms from the block: it does not implement the brumby layer "
            "(before PR 52)"
        )
    if mesh is not None:
        raise ValueError("the state pool is single-device (engine/engine.py)")
    h, hkv = cfg.hidden_size, cfg.num_kv_heads

    def extra(key):
        k_w, k_b = jax.random.split(key)
        decades = jax.random.uniform(k_b, (hkv,), jnp.float32, 1.0, GATE_DECADES)
        one_minus = 10.0 ** -decades
        return {
            "q_norm": jnp.ones((cfg.head_dim,), dtype),
            "k_norm": jnp.ones((cfg.head_dim,), dtype),
            "w_gate": (
                jax.random.normal(k_w, (h, hkv), jnp.float32)
                * dense_gqa.WEIGHT_STD
            ).astype(dtype),
            # the logit of 1 - 10 ** -u
            "b_gate": jnp.log1p(-one_minus) + math.log(10.0) * decades,
        }

    return dense_gqa.build(
        cfg, seed, dtype, stored, dense_gqa.layer_shapes(cfg), extra
    )
