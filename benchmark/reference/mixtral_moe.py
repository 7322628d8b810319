"""Plain reference: Mixtral's sparse mixture of experts (arXiv:2401.04088,
``modeling_mixtral``) on the attention stack of ``dense_gqa``.

Per token: router logits over all experts, softmax in float32, the
``num_experts_per_tok`` largest kept and renormalised to sum to one, and the
output is their weighted sum of SwiGLU experts. Written one expert at a time
(every expert sees every token and is weighted by 0 where it was not
chosen): the same result as gathering, with no dispatch to get wrong, and
one expert's float32 weights in memory at a time.

``layers`` carries, instead of ``wg``/``wu``/``wd``: ``router [L, H, E]``,
``we_g``/``we_u [L, E, H, F]``, ``we_d [L, E, F, H]`` (stored int8 form
allowed as in ``dense_gqa``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import dense_gqa
from benchmark.reference.dense_gqa import F32, weight


def moe(cfg, lp, x):
    probs = jax.nn.softmax(x @ lp["router"].astype(F32), -1)       # [S, E]
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)

    def one_expert(acc, expert):
        e, wg, wu, wd = expert
        share = jnp.sum(jnp.where(top_i == e, top_p, 0.0), -1)      # [S]
        y = (jax.nn.silu(x @ weight(wg)) * (x @ weight(wu))) @ weight(wd)
        return acc + share[:, None] * y, None

    experts = (
        jnp.arange(cfg["num_local_experts"]), lp["we_g"], lp["we_u"], lp["we_d"]
    )
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), experts)
    return out


def forward(cfg, params, tokens):
    return dense_gqa.forward(cfg, params, tokens, mlp_fn=moe)
