"""Mixtral MoE: HF oracle parity, routing semantics, ep/tp sharding.

The reference has no MoE model (SURVEY §2.3); this is the Mixtral family
extension. Oracle: ``transformers`` MixtralForCausalLM on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import MeshConfig, ModelConfig
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.ops.moe import moe_mlp, router_weights
from distributed_llm_inference_tpu.parallel import (
    build_mesh,
    cache_pspecs,
    param_pspecs,
    shard_pytree,
)
from distributed_llm_inference_tpu.parallel.tp import validate_tp

CFG = ModelConfig(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=8,
    max_position_embeddings=64,
    num_experts=4,
    num_experts_per_tok=2,
    family="mixtral",
)


def _hf_mixtral():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf_cfg = transformers.MixtralConfig(
        vocab_size=CFG.vocab_size,
        hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        num_hidden_layers=CFG.num_layers,
        num_attention_heads=CFG.num_heads,
        num_key_value_heads=CFG.num_kv_heads,
        num_local_experts=CFG.num_experts,
        num_experts_per_tok=CFG.num_experts_per_tok,
        max_position_embeddings=CFG.max_position_embeddings,
        rms_norm_eps=CFG.rms_norm_eps,
        rope_theta=CFG.rope_theta,
        attention_dropout=0.0,
    )
    torch.manual_seed(0)
    model = transformers.MixtralForCausalLM(hf_cfg).eval()
    return torch, model


def test_router_weights_match_mixtral_semantics():
    """fp32 softmax over all experts → top-k → renormalize (HF mixtral)."""
    r = np.random.RandomState(0)
    x = r.randn(2, 3, CFG.hidden_size).astype(np.float32)
    router = r.randn(CFG.hidden_size, CFG.num_experts).astype(np.float32)
    combine = np.asarray(router_weights(CFG, jnp.asarray(x), jnp.asarray(router)))

    logits = x @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    for b in range(2):
        for s in range(3):
            row = combine[b, s]
            sel = np.nonzero(row)[0]
            assert len(sel) == CFG.num_experts_per_tok
            top = np.sort(np.argsort(probs[b, s])[-CFG.num_experts_per_tok:])
            np.testing.assert_array_equal(np.sort(sel), top)
            np.testing.assert_allclose(row.sum(), 1.0, rtol=1e-6)
            expected = probs[b, s][sel] / probs[b, s][sel].sum()
            np.testing.assert_allclose(row[sel], expected, rtol=1e-5)


def test_mixtral_logits_match_hf():
    torch, model = _hf_mixtral()
    state = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    params = llama.convert_hf_state_dict(CFG, state, None, jnp.float32)

    tokens = np.array([[3, 17, 42, 7, 99, 5]], dtype=np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens.astype(np.int64))).logits.numpy()

    cache = DenseKVCache.create(
        CFG.num_layers, 1, 16, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    num_new = jnp.full((1,), tokens.shape[1], jnp.int32)
    logits, _ = jax.jit(
        lambda p, t, c: llama.model_apply(CFG, p, t, c, num_new)
    )(params, jnp.asarray(tokens), cache)
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=2e-4, atol=2e-4)


def test_mixtral_decode_matches_hf_greedy():
    torch, model = _hf_mixtral()
    state = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    params = llama.convert_hf_state_dict(CFG, state, None, jnp.float32)

    prompt = np.array([[3, 17, 42]], dtype=np.int64)
    with torch.no_grad():
        ref_ids = model.generate(
            torch.from_numpy(prompt), max_new_tokens=5, do_sample=False
        ).numpy()[0, prompt.shape[1]:]

    cache = DenseKVCache.create(
        CFG.num_layers, 1, 16, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    step = jax.jit(
        lambda p, t, c, n: llama.model_apply(CFG, p, t, c, n)
    )
    logits, cache = step(
        params, jnp.asarray(prompt.astype(np.int32)), cache,
        jnp.full((1,), 3, jnp.int32),
    )
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    out = [int(tok[0, 0])]
    for _ in range(4):
        logits, cache = step(params, tok, cache, jnp.ones((1,), jnp.int32))
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        out.append(int(tok[0, 0]))
    np.testing.assert_array_equal(np.asarray(out), ref_ids)


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(ep=4),
    MeshConfig(ep=2, tp=2),
    MeshConfig(dp=2, ep=2, tp=2),
])
def test_moe_sharded_matches_single_device(mesh_cfg):
    validate_tp(CFG, mesh_cfg.tp, ep=mesh_cfg.ep)
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    batch, seq = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, CFG.vocab_size)
    mk = lambda: DenseKVCache.create(
        CFG.num_layers, batch, 16, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    n = jnp.full((batch,), seq, jnp.int32)
    ref, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
        params, tokens, mk()
    )

    mesh = build_mesh(mesh_cfg)
    sp = shard_pytree(params, mesh, param_pspecs(params))
    sc = shard_pytree(mk(), mesh, cache_pspecs(mk()))
    with mesh:
        out, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
            sp, tokens, sc
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_validate_ep_rejects_bad_degrees():
    with pytest.raises(ValueError):
        validate_tp(CFG, 1, ep=3)
    dense = ModelConfig(num_experts=0)
    with pytest.raises(ValueError):
        validate_tp(dense, 1, ep=2)
