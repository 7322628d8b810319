"""Logits parity of Mistral / Qwen2 / Mixtral against ``transformers``.

Extends the Llama parity suite (``test_llama_parity.py``) across the other
model families the framework serves (CONFIGS.md's config 4 is Mistral): same
tiny-random-HF-model-as-oracle strategy, exercising each family's quirk —
sliding-window attention, q/k/v biases + tied embeddings, MoE routing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.models import llama, registry

torch = pytest.importorskip("torch")

COMMON = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=172,
    num_hidden_layers=3,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=256,
    rms_norm_eps=1e-5,
    rope_theta=10000.0,
)


def _build(kind):
    import transformers as tf

    torch.manual_seed(0)
    if kind == "mistral":
        cfg = tf.MistralConfig(**COMMON, sliding_window=6,
                               attn_implementation="eager")
        model = tf.MistralForCausalLM(cfg)
    elif kind == "qwen2":
        cfg = tf.Qwen2Config(**COMMON, tie_word_embeddings=True,
                             attn_implementation="eager")
        model = tf.Qwen2ForCausalLM(cfg)
    elif kind == "mixtral":
        cfg = tf.MixtralConfig(**COMMON, num_local_experts=4,
                               num_experts_per_tok=2,
                               attn_implementation="eager")
        model = tf.MixtralForCausalLM(cfg)
    else:
        raise ValueError(kind)
    model.eval()
    return model


def _convert(model):
    cfg = ModelConfig.from_hf_config(model.config)
    fam = registry.validate_config(cfg)
    state = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    if "lm_head.weight" not in state:
        state["lm_head.weight"] = state["model.embed_tokens.weight"]
    params = fam.convert_state_dict(cfg, state, dtype=jnp.float32)
    return cfg, params


def _hf_logits(model, tokens):
    with torch.no_grad():
        return model(torch.from_numpy(tokens)).logits.numpy()


@pytest.mark.parametrize("kind", ["mistral", "qwen2", "mixtral"])
def test_prefill_and_decode_match_hf(kind):
    model = _build(kind)
    cfg, params = _convert(model)
    rng = np.random.default_rng(0)
    # 11 tokens > Mistral's sliding_window=6, so windowing is exercised.
    tokens = rng.integers(0, COMMON["vocab_size"], size=(2, 11), dtype=np.int64)
    expected = _hf_logits(model, tokens)

    cache = DenseKVCache.create(
        cfg.num_layers, 2, 32, cfg.num_kv_heads, cfg.head_dim, jnp.float32
    )
    logits, cache = llama.model_apply(
        cfg, params, jnp.asarray(tokens[:, :6]), cache,
        jnp.full((2,), 6, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(logits), expected[:, :6], atol=3e-4, rtol=2e-3
    )
    step = jax.jit(
        lambda p, t, c: llama.model_apply(cfg, p, t, c, jnp.ones((2,), jnp.int32))
    )
    for i in range(6, 11):
        logits, cache = step(params, jnp.asarray(tokens[:, i : i + 1]), cache)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), expected[:, i], atol=5e-4, rtol=2e-3,
            err_msg=f"{kind} decode step {i}",
        )


def test_qwen2_has_biases_and_tied_head():
    model = _build("qwen2")
    cfg, params = _convert(model)
    assert cfg.qkv_bias and cfg.tie_word_embeddings
    assert "bq" in params["layers"] and "lm_head" not in params


def test_mixtral_routes_all_experts():
    model = _build("mixtral")
    cfg, params = _convert(model)
    assert params["layers"]["we_g"].shape[1] == 4  # [L, E, H, I]


def test_registry_lookup_and_validation():
    assert registry.get_family("mistral").sliding_window
    assert registry.get_family(ModelConfig(family="llama")).name == "llama"
    with pytest.raises(KeyError):
        registry.get_family("gpt2")
    with pytest.raises(ValueError):
        registry.validate_config(
            ModelConfig(family="llama", sliding_window=128)
        )
    with pytest.raises(ValueError):
        registry.validate_config(ModelConfig(family="mistral", num_experts=4))
