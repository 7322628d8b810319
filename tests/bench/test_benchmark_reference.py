"""The benchmark's plain references against the system, at a tiny size on
the CPU: the system's prefill and then 16 decode steps through its paged
cache (``benchmark.server.probe``: the engine's own cache class, pad width
and decode program) must agree with the reference's one full forward pass.

Tolerance: everything is float32 here, so only the order of sums differs
and the relative distance of the logits stays under 1e-4 (measured: 1e-7 to
3e-7). That is tight enough to fail a path that computes in a lower
precision than the configuration states: the same engine with int8 KV pages
sits at 2e-3, twenty times over. A reference with a wrong term (no window,
other rotary angles, another norm constant) sits at 0.005 to 1: fifty times
over at the least.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import server
from benchmark.reference import dense_gqa, mixtral_moe

BENCH = os.path.dirname(os.path.abspath(server.__file__))
TOLERANCE = 1e-4


def tiny(name, **over):
    conf = server.load_config(os.path.join(BENCH, "configs", name + ".json"), True)
    conf.update(over)
    return conf


def engine_for(conf, kv_quant=None):
    import importlib

    from distributed_llm_inference_tpu.config import (
        CacheConfig, EngineConfig, ModelConfig,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine

    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    maker = importlib.import_module(f"benchmark.weights.{conf['serve']['weight_maker']}")
    params = maker.make(cfg, 5, jnp.float32, "float32")
    ekw = dict(conf["serve"]["engine"])
    ekw["prefill_buckets"] = tuple(ekw["prefill_buckets"])
    cache = {**conf["serve"]["cache"], "kv_quant": kv_quant}
    return cfg, InferenceEngine(
        cfg, params, EngineConfig(dtype="float32", **ekw), CacheConfig(**cache)
    )


def distances(conf, kv_quant=None, hf_override=None):
    cfg, engine = engine_for(conf, kv_quant)
    if hf_override:
        conf = {**conf, **hf_override}
    conf = {**conf, "correct": {"probe_prompt_tokens": 30, "decode_steps": 16,
                                "tolerance": TOLERANCE}}
    return server.check_numerics(conf, cfg, engine, seed=3)


@pytest.mark.parametrize("name", ["mistral-7b", "mixtral-8x7b-8l"])
def test_prefill_then_decode_through_the_paged_cache_agrees_with_the_reference(name):
    # 30 prompt tokens + 16 steps against a window of 24: the mask matters
    out = distances(tiny(name, sliding_window=24))
    assert out["ok"], out
    assert out["prefill"] < TOLERANCE and out["decode_max"] < TOLERANCE
    assert out["unrelated"] > 0.5


def test_a_lower_precision_than_stated_fails_the_tolerance():
    out = distances(tiny("mistral-7b"), kv_quant="int8")
    assert not out["ok"]
    assert TOLERANCE * 5 < out["decode_max"] < 0.05


@pytest.mark.parametrize("broken", [
    {"sliding_window": None},           # the window mask left out
    {"rope_theta": 500.0},              # other rotary angles
    {"num_key_value_heads": 4, "num_attention_heads": 8, "rms_norm_eps": 0.5},
])
def test_a_reference_with_a_wrong_term_is_far_from_the_system(broken):
    out = distances(tiny("mistral-7b", sliding_window=24), hf_override=broken)
    assert not out["ok"] and max(out["prefill"], out["decode_max"]) > 20 * TOLERANCE, out


def test_router_is_softmax_top2_renormalised():
    conf = tiny("mixtral-8x7b-8l")
    cfg = server.hf_block(conf)
    rng = np.random.default_rng(0)
    h, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_local_experts"]
    lp = {
        "router": jnp.asarray(rng.normal(size=(h, e)), jnp.float32),
        "we_g": jnp.asarray(rng.normal(size=(e, h, f)) * 0.1, jnp.float32),
        "we_u": jnp.asarray(rng.normal(size=(e, h, f)) * 0.1, jnp.float32),
        "we_d": jnp.asarray(rng.normal(size=(e, f, h)) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(5, h)), jnp.float32)
    got = np.asarray(mixtral_moe.moe(cfg, lp, x))
    want = np.zeros_like(got)
    for t in range(5):
        logits = np.asarray(x[t] @ lp["router"], np.float64)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p)[:2]
        for i in top:
            xe = np.asarray(x[t], np.float64)
            g = xe @ np.asarray(lp["we_g"][i], np.float64)
            u = xe @ np.asarray(lp["we_u"][i], np.float64)
            y = (g / (1 + np.exp(-g)) * u) @ np.asarray(lp["we_d"][i], np.float64)
            want[t] += p[i] / p[top].sum() * y
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_stored_int8_form_is_dequantized_where_it_is_used():
    q = jnp.asarray([[1, -2], [3, 4]], jnp.int8)
    scale = jnp.asarray([0.5, 2.0], jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(dense_gqa.weight({"q": q, "scale": scale})),
        np.asarray([[0.5, -4.0], [1.5, 8.0]], np.float32),
    )


def test_the_reference_imports_nothing_of_the_program():
    for module in (dense_gqa, mixtral_moe):
        with open(module.__file__) as f:
            source = f.read()
        assert "distributed_llm_inference_tpu" not in source


def test_every_configuration_file_names_what_exists_and_cuts_no_width():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), entry["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == entry["name"] and conf["source"] == entry["source"]
        assert sorted(conf["reduced"]) == sorted(entry["reduced"])
        for kind in ("weight_maker", "reference"):
            sub = "weights" if kind == "weight_maker" else "reference"
            assert os.path.exists(os.path.join(BENCH, sub, conf["serve"][kind] + ".py"))
        assert (conf["hidden_size"], conf["intermediate_size"]) == (4096, 14336)
        assert conf["num_attention_heads"] == 32 and conf["num_key_value_heads"] == 8
        assert conf["correct"]["tolerance"] < 0.7 and conf["correct"]["reason"]
