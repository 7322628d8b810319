"""Per-block checkpoint streaming against an on-disk tiny HF-format checkpoint.

Mirrors the reference loader's contract
(``/root/reference/distributed_llm_inference/utils/model.py:27-52``): prefix
filtering by layer, opening only the shard files that hold the requested
layers, legacy torch ``.bin`` support.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.utils import checkpoint

CFG = ModelConfig(
    vocab_size=64,
    hidden_size=16,
    intermediate_size=32,
    num_layers=4,
    num_heads=4,
    num_kv_heads=2,
    head_dim=4,
    max_position_embeddings=64,
)


def _hf_state(cfg: ModelConfig, seed: int = 0):
    """Random HF-keyed state dict in torch's [out, in] linear layout."""
    r = np.random.RandomState(seed)
    h, d = cfg.hidden_size, cfg.head_dim
    state = {
        "model.embed_tokens.weight": r.randn(cfg.vocab_size, h).astype(np.float32),
        "model.norm.weight": r.randn(h).astype(np.float32),
        "lm_head.weight": r.randn(cfg.vocab_size, h).astype(np.float32),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        state.update({
            p + "input_layernorm.weight": r.randn(h).astype(np.float32),
            p + "self_attn.q_proj.weight": r.randn(cfg.num_heads * d, h).astype(np.float32),
            p + "self_attn.k_proj.weight": r.randn(cfg.num_kv_heads * d, h).astype(np.float32),
            p + "self_attn.v_proj.weight": r.randn(cfg.num_kv_heads * d, h).astype(np.float32),
            p + "self_attn.o_proj.weight": r.randn(h, cfg.num_heads * d).astype(np.float32),
            p + "post_attention_layernorm.weight": r.randn(h).astype(np.float32),
            p + "mlp.gate_proj.weight": r.randn(cfg.intermediate_size, h).astype(np.float32),
            p + "mlp.up_proj.weight": r.randn(cfg.intermediate_size, h).astype(np.float32),
            p + "mlp.down_proj.weight": r.randn(h, cfg.intermediate_size).astype(np.float32),
        })
    return state


def _write_sharded(tmp_path, state):
    """Two shards: layers 0-1 + embed in shard 1; layers 2-3 + norm/head in 2."""
    from distributed_llm_inference_tpu.utils.checkpoint import save_safetensors

    def shard_of(key):
        for i in (2, 3):
            if key.startswith(f"model.layers.{i}."):
                return "model-00002-of-00002.safetensors"
        if key in ("model.norm.weight", "lm_head.weight"):
            return "model-00002-of-00002.safetensors"
        return "model-00001-of-00002.safetensors"

    shards = {}
    weight_map = {}
    for k, v in state.items():
        s = shard_of(k)
        shards.setdefault(s, {})[k] = v
        weight_map[k] = s
    for name, tensors in shards.items():
        save_safetensors(tensors, os.path.join(tmp_path, name))
    with open(os.path.join(tmp_path, "model.safetensors.index.json"), "w") as f:
        json.dump({"weight_map": weight_map}, f)
    with open(os.path.join(tmp_path, "config.json"), "w") as f:
        json.dump({
            "model_type": "llama",
            "vocab_size": CFG.vocab_size,
            "hidden_size": CFG.hidden_size,
            "intermediate_size": CFG.intermediate_size,
            "num_hidden_layers": CFG.num_layers,
            "num_attention_heads": CFG.num_heads,
            "num_key_value_heads": CFG.num_kv_heads,
            "head_dim": CFG.head_dim,
            "rms_norm_eps": 1e-5,
        }, f)


def test_load_model_params_matches_direct_conversion(tmp_path):
    state = _hf_state(CFG)
    _write_sharded(str(tmp_path), state)
    params = checkpoint.load_model_params(str(tmp_path), CFG, jnp.float32)
    ref = llama.convert_hf_state_dict(CFG, state, None, jnp.float32)
    for name in ref["layers"]:
        np.testing.assert_array_equal(
            np.asarray(params["layers"][name]), np.asarray(ref["layers"][name])
        )
    np.testing.assert_array_equal(np.asarray(params["embed"]), np.asarray(ref["embed"]))
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"]), np.asarray(ref["lm_head"])
    )


def test_block_load_opens_only_needed_shards(tmp_path):
    state = _hf_state(CFG)
    _write_sharded(str(tmp_path), state)
    opened = []
    base = checkpoint._default_resolve(str(tmp_path))

    def resolve(name):
        opened.append(name)
        return base(name)

    params = checkpoint.load_block_params(
        str(tmp_path), CFG, [2, 3], jnp.float32, resolve=resolve
    )
    shards = [n for n in opened if n.endswith(".safetensors")]
    assert shards == ["model-00002-of-00002.safetensors"], (
        "a node serving layers [2,3] must not read shard 1"
    )
    # Layer 2's weights land at stacked index 0.
    ref = llama.convert_hf_state_dict(CFG, state, [2, 3], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["wq"]), np.asarray(ref["layers"]["wq"])
    )
    assert params["layers"]["wq"].shape[0] == 2


def test_block_load_forward_matches_full_model_slice(tmp_path):
    """Loading layers [1,2] as a block and running block_apply matches the
    same layers inside a full-model load."""
    from distributed_llm_inference_tpu.cache.dense import DenseKVCache

    state = _hf_state(CFG)
    _write_sharded(str(tmp_path), state)
    full = checkpoint.load_model_params(str(tmp_path), CFG, jnp.float32)
    block = checkpoint.load_block_params(str(tmp_path), CFG, [1, 2], jnp.float32)

    x = np.random.RandomState(1).randn(1, 5, CFG.hidden_size).astype(np.float32)
    num_new = jnp.full((1,), 5, jnp.int32)

    def run(layer_params):
        cache = DenseKVCache.create(2, 1, 8, CFG.num_kv_heads, CFG.head_dim, jnp.float32)
        out, _ = llama.block_apply(CFG, layer_params, jnp.asarray(x), cache, num_new)
        return np.asarray(out)

    sliced = {k: v[1:3] for k, v in full["layers"].items()}
    np.testing.assert_allclose(run(block["layers"]), run(sliced), rtol=1e-6)


def test_torch_bin_fallback(tmp_path):
    torch = pytest.importorskip("torch")
    state = _hf_state(CFG)
    torch.save(
        {k: torch.from_numpy(v) for k, v in state.items()},
        os.path.join(tmp_path, "pytorch_model.bin"),
    )
    with open(os.path.join(tmp_path, "config.json"), "w") as f:
        json.dump({"model_type": "llama"}, f)
    params = checkpoint.load_model_params(str(tmp_path), CFG, jnp.float32)
    ref = llama.convert_hf_state_dict(CFG, state, None, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["wd"]), np.asarray(ref["layers"]["wd"])
    )


def test_load_config(tmp_path):
    state = _hf_state(CFG)
    _write_sharded(str(tmp_path), state)
    cfg = checkpoint.load_config(str(tmp_path))
    assert cfg.hidden_size == CFG.hidden_size
    assert cfg.num_layers == CFG.num_layers
    assert cfg.num_kv_heads == CFG.num_kv_heads


def test_missing_index_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        checkpoint.block_state_dict(str(tmp_path), [0])


# ---------------------------------------------------------------------------
# Pre-converted on-disk weight cache (SURVEY §5.4)
# ---------------------------------------------------------------------------


def test_weights_cache_roundtrip_and_hit(tmp_path, monkeypatch):
    state = _hf_state(CFG)
    model_dir = str(tmp_path / "model")
    os.makedirs(model_dir)
    _write_sharded(model_dir, state)
    cache_dir = str(tmp_path / "wcache")

    ref = checkpoint.load_model_params(model_dir, CFG, jnp.float32)
    out = checkpoint.load_model_params(
        model_dir, CFG, jnp.float32, cache_dir=cache_dir
    )
    entries = [f for f in os.listdir(cache_dir) if f.endswith(".safetensors")]
    assert len(entries) == 1

    # Second load must come from the cache: poison the slow path.
    def boom(*a, **k):
        raise AssertionError("cache miss: block_state_dict called")

    monkeypatch.setattr(checkpoint, "block_state_dict", boom)
    cached = checkpoint.load_model_params(
        model_dir, CFG, jnp.float32, cache_dir=cache_dir
    )
    for tree in (out, cached):
        assert set(tree) == set(ref) and set(tree["layers"]) == set(ref["layers"])
        for name in ref["layers"]:
            np.testing.assert_array_equal(
                np.asarray(tree["layers"][name]), np.asarray(ref["layers"][name])
            )
        np.testing.assert_array_equal(np.asarray(tree["embed"]), np.asarray(ref["embed"]))


def test_weights_cache_block_key_varies_by_span_and_dtype(tmp_path):
    state = _hf_state(CFG)
    model_dir = str(tmp_path / "model")
    os.makedirs(model_dir)
    _write_sharded(model_dir, state)
    cache_dir = str(tmp_path / "wcache")

    checkpoint.load_block_params(model_dir, CFG, [0, 1], jnp.float32, cache_dir=cache_dir)
    checkpoint.load_block_params(model_dir, CFG, [2, 3], jnp.float32, cache_dir=cache_dir)
    checkpoint.load_block_params(model_dir, CFG, [0, 1], jnp.bfloat16, cache_dir=cache_dir)
    entries = [f for f in os.listdir(cache_dir) if f.endswith(".safetensors")]
    assert len(entries) == 3  # distinct keys, no collisions


def test_weights_cache_invalidated_by_checkpoint_change(tmp_path):
    state = _hf_state(CFG)
    model_dir = str(tmp_path / "model")
    os.makedirs(model_dir)
    _write_sharded(model_dir, state)
    cache_dir = str(tmp_path / "wcache")

    a = checkpoint.load_block_params(model_dir, CFG, [0], jnp.float32, cache_dir=cache_dir)
    # "Re-download" the checkpoint with different weights.
    state2 = _hf_state(CFG, seed=9)
    _write_sharded(model_dir, state2)
    os.utime(checkpoint.find_index(checkpoint._default_resolve(model_dir)))
    b = checkpoint.load_block_params(model_dir, CFG, [0], jnp.float32, cache_dir=cache_dir)
    assert not np.array_equal(
        np.asarray(a["layers"]["wq"]), np.asarray(b["layers"]["wq"])
    )


def test_weights_cache_corrupt_entry_rebuilds(tmp_path):
    state = _hf_state(CFG)
    model_dir = str(tmp_path / "model")
    os.makedirs(model_dir)
    _write_sharded(model_dir, state)
    cache_dir = tmp_path / "wcache"

    ref = checkpoint.load_block_params(model_dir, CFG, [0], jnp.float32,
                                       cache_dir=str(cache_dir))
    entry = next(cache_dir.glob("*.safetensors"))
    entry.write_bytes(b"garbage")
    again = checkpoint.load_block_params(model_dir, CFG, [0], jnp.float32,
                                         cache_dir=str(cache_dir))
    np.testing.assert_array_equal(
        np.asarray(ref["layers"]["wq"]), np.asarray(again["layers"]["wq"])
    )


def test_weights_cache_invalidated_by_shard_change_only(tmp_path):
    """Replacing a shard while the index file stays byte-identical must still
    invalidate the cache (the key covers shard identities too)."""
    state = _hf_state(CFG)
    model_dir = str(tmp_path / "model")
    os.makedirs(model_dir)
    _write_sharded(model_dir, state)
    cache_dir = str(tmp_path / "wcache")

    a = checkpoint.load_block_params(model_dir, CFG, [0], jnp.float32,
                                     cache_dir=cache_dir)
    # Rewrite ONE shard with different weights; index json untouched.
    state2 = _hf_state(CFG, seed=9)
    shard1 = {k: v for k, v in state2.items()
              if not any(k.startswith(f"model.layers.{i}.") for i in (2, 3))
              and k not in ("model.norm.weight", "lm_head.weight")}
    checkpoint.save_safetensors(
        shard1, os.path.join(model_dir, "model-00001-of-00002.safetensors")
    )
    b = checkpoint.load_block_params(model_dir, CFG, [0], jnp.float32,
                                     cache_dir=cache_dir)
    assert not np.array_equal(
        np.asarray(a["layers"]["wq"]), np.asarray(b["layers"]["wq"])
    )


def test_load_config_rejects_unsupported_family(tmp_path):
    with open(tmp_path / "config.json", "w") as f:
        json.dump({"model_type": "gpt2", "vocab_size": 64, "hidden_size": 16,
                   "num_hidden_layers": 2, "num_attention_heads": 2}, f)
    with pytest.raises(KeyError):
        checkpoint.load_config(str(tmp_path))
    cfg = checkpoint.load_config(str(tmp_path), validate=False)
    assert cfg.family == "gpt2"


# -- load → quantize → place: the unquantized whole is never on one device ----


def test_loaded_layer_stacks_stay_on_the_host(tmp_path):
    """A 7B bf16 tree placed whole is 14.5 GB of a 16 GB chip before its
    int8 copy exists: the loader hands the layer stacks over unplaced
    (fresh and from the pre-converted cache alike) and the engine places
    them, quantized leaf by leaf."""
    import jax

    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.ops.quant import (
        QuantizedTensor, quantize_params,
    )

    _write_sharded(str(tmp_path), _hf_state(CFG))
    cache_dir = str(tmp_path / "wcache")
    fresh = checkpoint.load_model_params(
        str(tmp_path), CFG, jnp.float32, cache_dir=cache_dir
    )
    cached = checkpoint.load_model_params(
        str(tmp_path), CFG, jnp.float32, cache_dir=cache_dir
    )
    for params in (fresh, cached):
        assert all(
            isinstance(a, np.ndarray) for a in params["layers"].values()
        )
    engine = InferenceEngine(
        CFG, fresh,
        EngineConfig(max_batch_size=2, prefill_buckets=(8,), max_seq_len=16,
                     dtype="float32", quantization="int8"),
        CacheConfig(kind="dense"),
    )
    served = engine.params["layers"]
    assert isinstance(served["wq"], QuantizedTensor)
    assert all(
        isinstance(a, jax.Array) for a in jax.tree.leaves(engine.params)
    )
    # Host leaves quantize to the bytes device leaves do.
    placed = quantize_params(jax.device_put(fresh))["layers"]
    for name in ("wq", "wd"):
        np.testing.assert_array_equal(served[name].q, placed[name].q)
        np.testing.assert_array_equal(served[name].scale, placed[name].scale)


def test_convert_consume_frees_the_state_as_it_goes():
    """``consume=True`` (the loader owns its state dict): same params, and
    every layer tensor is gone from the dict afterwards — the host holds
    the checkpoint once, not three times."""
    state = _hf_state(CFG)
    ref = llama.convert_hf_state_dict(CFG, dict(state), None, jnp.float32)
    out = llama.convert_hf_state_dict(
        CFG, state, None, jnp.float32, consume=True
    )
    assert not [k for k in state if k.startswith("model.layers.")]
    for name in ref["layers"]:
        np.testing.assert_array_equal(out["layers"][name], ref["layers"][name])
    np.testing.assert_array_equal(
        np.asarray(out["embed"]), np.asarray(ref["embed"])
    )
