"""Seeded weights of a dense GQA decoder in the program's parameter layout,
made on the device in ONE jitted call, in the type they are served in.

``stored == "int8"`` draws every projection directly in stored form: uniform
int8 values and per-output-channel scales set so that the dequantized
weights have standard deviation ``WEIGHT_STD`` — a 7B tree never exists in
bf16. Under a mesh each leaf is born with the sharding the engine will give
it (``parallel.param_pspecs``), so nothing is gathered on one chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from distributed_llm_inference_tpu.ops.quant import QuantizedTensor

WEIGHT_STD = 0.02


def matrix(key, shape, dtype, stored):
    if stored != "int8":
        return (jax.random.normal(key, shape, jnp.float32) * WEIGHT_STD).astype(dtype)
    kq, ks = jax.random.split(key)
    q = jnp.maximum(
        jax.lax.bitcast_convert_type(
            jax.random.bits(kq, shape, jnp.uint8), jnp.int8
        ),
        -127,
    )
    # uniform[-127, 127] has std 127 / sqrt(3)
    scale = (WEIGHT_STD * math.sqrt(3) / 127) * jax.random.uniform(
        ks, shape[:-2] + shape[-1:], jnp.float32, 0.5, 1.5
    )
    return QuantizedTensor(q=q, scale=scale.astype(dtype))


def layer_shapes(cfg) -> dict:
    h, f = cfg.hidden_size, cfg.intermediate_size
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": (h, hq * d), "wk": (h, hkv * d), "wv": (h, hkv * d),
        "wo": (hq * d, h), "wg": (h, f), "wu": (h, f), "wd": (f, h),
    }


def tree_fn(cfg, dtype, stored, shapes, extra_layer=None):
    """``key -> params``: the whole tree. ``shapes``: the layer's matrices;
    ``extra_layer(key)``: further per-layer leaves that are never stored
    quantized (a router)."""
    h, v = cfg.hidden_size, cfg.vocab_size

    def one_layer(key):
        keys = jax.random.split(key, len(shapes) + 1)
        layer = {
            n: matrix(k, s, dtype, stored)
            for (n, s), k in zip(shapes.items(), keys)
        }
        layer["attn_norm"] = jnp.ones((h,), dtype)
        layer["mlp_norm"] = jnp.ones((h,), dtype)
        if extra_layer is not None:
            layer.update(extra_layer(keys[-1]))
        return layer

    def tree(key):
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        return {
            "embed": (
                jax.random.normal(k_embed, (v, h), jnp.float32) * WEIGHT_STD
            ).astype(dtype),
            "layers": jax.lax.map(
                one_layer, jax.random.split(k_layers, cfg.num_layers)
            ),
            "final_norm": jnp.ones((h,), dtype),
            "lm_head": matrix(k_head, (h, v), dtype, stored),
        }

    return tree


def shardings_for(tree, mesh):
    """The sharding the engine will give each leaf (``param_pspecs``)."""
    from jax.sharding import NamedSharding

    from distributed_llm_inference_tpu.parallel import param_pspecs

    specs = param_pspecs(jax.eval_shape(tree, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def build(cfg, seed: int, dtype, stored, shapes, extra_layer=None, mesh=None):
    """The whole tree from ``seed``, in one jitted call."""
    tree = tree_fn(cfg, dtype, stored, shapes, extra_layer)
    key = jax.random.PRNGKey(seed)
    if mesh is None:
        return jax.jit(tree)(key)
    # each shard draws its own part; the unpartitioned generator would
    # make every leaf whole on every chip first
    with jax.threefry_partitionable(True):
        return jax.jit(tree, out_shardings=shardings_for(tree, mesh))(key)


def make(cfg, seed: int, dtype, stored, mesh=None):
    return build(cfg, seed, dtype, stored, layer_shapes(cfg), mesh=mesh)
