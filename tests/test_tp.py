"""Tensor/data-parallel sharding correctness on the 8-device virtual mesh.

SURVEY §4(b): multi-device tests on one host via XLA host-platform device
emulation — mesh sharding + collective correctness without a real pod. The
oracle is the identical computation run unsharded on one device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import MeshConfig, ModelConfig
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.parallel import (
    build_mesh,
    cache_pspecs,
    param_pspecs,
    shard_pytree,
    validate_tp,
)

CFG = ModelConfig(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=8,
    num_kv_heads=4,
    head_dim=8,
    max_position_embeddings=64,
)


def _forward(params, tokens, cache):
    n = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    logits, cache = llama.model_apply(CFG, params, tokens, cache, n)
    return logits, cache


def _make_inputs(batch=4, seq=16):
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, CFG.vocab_size)
    cache = DenseKVCache.create(
        CFG.num_layers, batch, 32, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    return params, tokens, cache


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(dp=1, pp=1, tp=4, sp=1),
    MeshConfig(dp=2, pp=1, tp=2, sp=1),
    MeshConfig(dp=2, pp=1, tp=4, sp=1),
])
def test_tp_dp_matches_single_device(mesh_cfg):
    params, tokens, cache = _make_inputs()
    ref_logits, ref_cache = jax.jit(_forward)(params, tokens, cache)

    validate_tp(CFG, mesh_cfg.tp)
    mesh = build_mesh(mesh_cfg)
    from jax.sharding import NamedSharding, PartitionSpec as P

    sp_params = shard_pytree(params, mesh, param_pspecs(params))
    sp_cache = shard_pytree(cache, mesh, cache_pspecs(cache))
    sp_tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))

    out_logits, out_cache = jax.jit(_forward)(sp_params, sp_tokens, sp_cache)

    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(out_cache.k), np.asarray(ref_cache.k), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_array_equal(
        np.asarray(out_cache.lengths), np.asarray(ref_cache.lengths)
    )


def test_tp_decode_after_prefill_matches():
    params, tokens, cache = _make_inputs()
    logits, cache1 = jax.jit(_forward)(params, tokens, cache)
    next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    ref_logits, _ = jax.jit(_forward)(params, next_tok, cache1)

    mesh = build_mesh(MeshConfig(dp=2, pp=1, tp=2, sp=1))
    from jax.sharding import NamedSharding, PartitionSpec as P

    params2, tokens2, cache2 = _make_inputs()
    sp_params = shard_pytree(params2, mesh, param_pspecs(params2))
    sp_cache = shard_pytree(cache2, mesh, cache_pspecs(cache2))
    tok_sharding = NamedSharding(mesh, P("dp", None))
    sp_tokens = jax.device_put(tokens2, tok_sharding)

    logits_s, sp_cache = jax.jit(_forward)(sp_params, sp_tokens, sp_cache)
    next_s = jnp.argmax(logits_s[:, -1:], axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(next_s), np.asarray(next_tok))
    out, _ = jax.jit(_forward)(sp_params, jax.device_put(next_s, tok_sharding), sp_cache)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("kind", ["paged", "sink"])
def test_tp_sharded_paged_and_sink_caches(kind):
    from distributed_llm_inference_tpu.cache.paged import PagedKVCache
    from distributed_llm_inference_tpu.cache.sink import SinkKVCache

    batch, seq = 4, 16
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, CFG.vocab_size)

    def mk():
        if kind == "paged":
            c = PagedKVCache.create(
                CFG.num_layers, batch, 16, 8, 4, CFG.num_kv_heads, CFG.head_dim,
                jnp.float32,
            )
            # Each row gets 3 pages (ids 1..12), enough for seq+decode.
            table = jnp.asarray(
                [[1 + 3 * r + i for i in range(3)] + [0] for r in range(batch)],
                jnp.int32,
            )
            return c.replace(page_table=table)
        return SinkKVCache.create(
            CFG.num_layers, batch, 32, 2, CFG.num_kv_heads, CFG.head_dim, jnp.float32
        )

    ref_logits, ref_cache = jax.jit(_forward)(params, tokens, mk())

    mesh = build_mesh(MeshConfig(dp=2, pp=1, tp=2, sp=1))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sp_params = shard_pytree(params, mesh, param_pspecs(params))
    sp_cache = shard_pytree(mk(), mesh, cache_pspecs(mk()))
    sp_tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    out_logits, out_cache = jax.jit(_forward)(sp_params, sp_tokens, sp_cache)

    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )
    ref_k = ref_cache.k_pages if kind == "paged" else ref_cache.k
    out_k = out_cache.k_pages if kind == "paged" else out_cache.k
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(ref_k), rtol=2e-5, atol=2e-5)


def test_validate_tp_rejects_bad_degrees():
    with pytest.raises(ValueError):
        validate_tp(CFG, 3)
    with pytest.raises(ValueError):
        validate_tp(CFG, 2, sp=3)


def test_build_mesh_layout_failure_is_an_error(monkeypatch):
    """A shape ``create_device_mesh`` cannot lay over the slice used to
    warn and serve from an enumeration-order mesh; on real chips that
    hides a degraded layout, so it raises."""
    from jax.experimental import mesh_utils

    def refuse(*a, **k):
        raise RuntimeError("cannot assign this mesh to the slice")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    with pytest.raises(RuntimeError, match="cannot assign"):
        build_mesh(MeshConfig(dp=2, tp=4))
