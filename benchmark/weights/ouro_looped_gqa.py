"""Seeded weights of an ``ouro`` decoder (Ouro-2.6B: the dense block with
normed sublayer outputs, run ``total_ut_steps`` times, an exit gate behind
each lap) in the program's parameter layout, made on the device in ONE jitted
call, in the form they are served in (``dense_gqa.tree_fn``).

Stored int8 where the configuration serves int8: ``wq``, ``wk``, ``wv``,
``wo``, the MLP and ``lm_head`` (what ``ops.quant.QUANTIZED_WEIGHTS`` names);
the published dtype, bfloat16, otherwise. In the model's dtype: the four norm
gains a layer and the gate's projection ``exit_w [H]``; in float32 its bias
``exit_b``.

**The gains and the gate.** A gain of exactly 1 and a gate of exactly 0 would
hide a missing norm and a missing gate from the comparison with the
reference. The gains of the two INPUT norms of a layer and the final norm's
are drawn uniform in ``1 -+ GAIN_SPREAD``, those of its two OUTPUT norms in
``OUT_GAIN[name] x (1 -+ GAIN_SPREAD)``; ``exit_w`` at ``WEIGHT_STD`` moves a
position's gate logit by about 0.9 (a normed hidden state of 2048) around
``exit_b``, which is drawn normal at ``BIAS_STD``.

**Why the output gains centre under 1** (my chip runs, PR 56, 320
+ 16 tokens at the published widths). Every sublayer's output is normed to
its gain's RMS before it is added, and every lap starts from a stream of RMS
about 1: with output gains around 1 each of the 384 sublayer applications a
token adds as much as the stream holds, and seeded weights make of that a
stack in which rounding grows until it saturates: the served path (bf16
activations, int8 K/V) read 0.24-0.40 from the float32 reference over twelve
seeds, the reference over int8 weights 0.41-0.62, and neighbouring positions'
reference logits 0.20-0.28 from each other, so no limit lay between the
first two and the probe could not tell a position from its neighbour
(queries drawn 3 and 6 times wider made it worse: 0.35-0.62 and 1.1). Around
0.5 the same stack is in its linear range: 0.09-0.11, 0.19-0.23 and 0.33
and around 0.25, where they stand, 0.045-0.057, 0.10-0.11 and 0.42-0.47
(before the queries were widened; the configuration's ``correct.reason`` has
the readings of the weights as they are). A trained checkpoint's gains are
whatever training left; the seeded ones are chosen so that the comparison
measures the path's precision and not a chaotic stack's saturation.

**Why the queries are widened to a score of standard deviation 1.** Under
projections at ``WEIGHT_STD`` a score's standard deviation is ``WEIGHT_STD**2
x hidden_size``: 0.82 at the published 2048 and 0.026 at the rehearsal's 64,
where softmax attention is then uniform over the context, its output the same
vector at neighbouring positions, and the output norm raises that common
vector to its gain's RMS whatever its size: neighbouring positions' reference
logits lie 0.1-0.2 apart there and a comparison of logits cannot tell a
position from its neighbour (``tests/bench/test_benchmark_reference.py`` asks
0.5 of every configuration). ``wq`` is multiplied by ``SCORE_STD /
(WEIGHT_STD**2 x hidden_size)``, 1.22 at the published width and 39 at the
rehearsal's, where neighbouring positions then lie 0.78-0.89 apart (CPU,
three seeds). (Taking the attention sublayer's gain DOWN instead, 0.1 beside
the MLP's 0.5, read 0.8-1.06 there and made the published size chaotic
again: the served path 0.83-1.08, my chip runs, PR 56: the common vector is
also what holds the seeded stack still.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import dense_gqa

GAIN_SPREAD = 0.25
BIAS_STD = 0.5
NORMS = ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm")


#: the centres of the two OUTPUT norms' gains (the docstring says why)
OUT_GAIN = {"attn_out_norm": 0.25, "mlp_out_norm": 0.25}
#: the standard deviation of an attention score (``q . k / sqrt(d)``) that the
#: queries' projection is widened to, at any width (the docstring says why)
SCORE_STD = 1.0


def gains(key, shape, dtype, centre=1.0):
    return (centre * jax.random.uniform(
        key, shape, jnp.float32, 1.0 - GAIN_SPREAD, 1.0 + GAIN_SPREAD
    )).astype(dtype)


def make(cfg, seed: int, dtype, stored, mesh=None):
    if getattr(cfg, "loop", None) is None:
        raise ValueError(
            "this program's ModelConfig read no lap count from the block: "
            "it does not implement the ouro layer (before PR 56)"
        )
    h = cfg.hidden_size

    def extra(key):
        # over ``tree_fn``'s gains of exactly 1 too
        return {
            n: gains(k, (h,), dtype, OUT_GAIN.get(n, 1.0))
            for n, k in zip(NORMS, jax.random.split(key, len(NORMS)))
        }

    layers = dense_gqa.tree_fn(
        cfg, dtype, stored, dense_gqa.layer_shapes(cfg), extra
    )
    wider = SCORE_STD / (dense_gqa.WEIGHT_STD ** 2 * h)

    def widened(w):
        if hasattr(w, "scale"):     # stored int8: the scales carry it
            return w.replace(scale=(w.scale * wider).astype(w.scale.dtype))
        return (w.astype(jnp.float32) * wider).astype(w.dtype)

    def tree(key):
        k_tree, k_norm, k_w, k_b = jax.random.split(key, 4)
        base = layers(k_tree)
        base["layers"] = {**base["layers"], "wq": widened(base["layers"]["wq"])}
        return {
            **base,
            "final_norm": gains(k_norm, (h,), dtype),
            "exit_w": (
                jax.random.normal(k_w, (h,), jnp.float32) * dense_gqa.WEIGHT_STD
            ).astype(dtype),
            "exit_b": jax.random.normal(k_b, (), jnp.float32) * BIAS_STD,
        }

    key = jax.random.PRNGKey(seed)
    if mesh is None:
        return jax.jit(tree)(key)
    with jax.threefry_partitionable(True):
        return jax.jit(
            tree, out_shardings=dense_gqa.shardings_for(tree, mesh)
        )(key)
