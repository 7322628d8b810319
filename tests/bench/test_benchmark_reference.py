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

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import server
from benchmark.reference import dense_gqa, mixtral_moe
from test_benchmark_rehearsal import bench, one_chip_configurations

BENCH = os.path.dirname(os.path.abspath(server.__file__))
TOLERANCE = 1e-4
BENCHMARK = bench()
#: the cases come from ``BENCHMARK.json``: a configuration that a later PR
#: adds there is tested here with no edit to this file
CONFIGS = [c["name"] for c in BENCHMARK["configs"]]
#: those that serve on one chip; a mesh configuration shares its family's
#: reference and is run on virtual devices in ``test_benchmark_extend.py``
ONE_CHIP = [name for name, _ in one_chip_configurations()]
#: the three configurations the benchmark had before PR 25, and
#: ``benchmark/server.py``'s list of then: what reached the program and the
#: reference. A regression for THESE files (the whole block must give them
#: what the list gave); a configuration of a later PR has keys outside the
#: list by design and is no case of it
BEFORE_PR25 = ("mistral-7b", "mixtral-8x7b-8l", "mistral-7b-bf16-tp4")
OLD_HF_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "num_local_experts", "num_experts_per_tok", "rms_norm_eps",
    "rope_theta", "max_position_embeddings", "sliding_window",
    "tie_word_embeddings",
)


def config_path(name):
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == name)
    return os.path.join(os.path.dirname(BENCH), entry["file"])


def tiny(name, rehearse=True, **over):
    conf = server.load_config(config_path(name), rehearse)
    conf.update(over)
    return conf


def engine_for(conf, kv_quant=None):
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


@pytest.mark.parametrize("name", ONE_CHIP)
def test_prefill_then_decode_through_the_paged_cache_agrees_with_the_reference(name):
    # 30 prompt tokens + 16 steps against a window of 24: the mask matters
    # (where the family has a window at all: the file then carries the key)
    conf = tiny(name)
    if "sliding_window" in conf:
        conf["sliding_window"] = 24
    out = distances(conf)
    assert out["ok"], out
    assert out["prefill"] < TOLERANCE and out["decode_max"] < TOLERANCE
    assert out["unrelated"] > 0.5


@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_lower_precision_than_stated_fails_the_tolerance(name):
    """The control, at a size a test run holds: float32 is stated here, and
    the program's own int8 pages are the step below that would tempt. (At
    the cells' size, where int8 is stated, the control is the reference over
    weights cut to int4: read on the chip, ``PERF.md`` section 6, PR 25.)"""
    out = distances(tiny(name), kv_quant="int8")
    assert not out["ok"]
    assert TOLERANCE * 5 < out["decode_median"] and out["decode_max"] < 0.05


def test_a_routed_model_is_judged_by_its_third_least_disturbed_position():
    # ``mixtral-8x7b-8l.rag`` on the chip, seed 2500000083 (PR 25): the
    # served path, whose median PR 22's limit of 0.69 refused, and the control
    # in int4
    sound = [0.7476, 0.4711, 0.4064, 1.3899, 0.502, 0.8325, 1.2577, 0.4194, 0.9114,
             0.3541, 0.2206, 0.9211, 0.5975, 1.0354, 0.8611, 0.4728, 1.1507]
    control = [1.3008, 0.8967, 0.93, 1.0395, 1.034, 1.0213, 1.0974, 1.2226, 1.3837,
               0.9419, 0.9685, 1.0102, 1.0619, 1.2809, 1.3069, 1.1772, 1.2336]
    limit = tiny("mixtral-8x7b-8l", rehearse=False)["correct"]["tolerance"]
    assert server.judged_numbers(sound, "third_least") == [0.4064]
    assert server.judged_numbers(control, "third_least") == [0.9419]
    assert 0.4064 < limit < 0.9419
    assert server.judged_numbers(sound, "each") == [0.7476, (0.5975 + 0.8325) / 2]
    with pytest.raises(ValueError):
        server.judged_numbers(sound, "median")


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


@pytest.mark.parametrize("name", ONE_CHIP)
def test_the_reference_imports_nothing_of_the_program(name):
    module = importlib.import_module(
        f"benchmark.reference.{tiny(name)['serve']['reference']}"
    )
    with open(module.__file__) as f:
        assert "distributed_llm_inference_tpu" not in f.read()


#: a width by its name, in the words of the benchmark's contract: a hidden,
#: intermediate, expert, latent, state or projection size (``*_size``,
#: ``*_dim``, ``*_rank``, ``*_width``), a head size, a window, an expansion
#: factor, and what every token runs: the experts per token, shared experts
#: among them
WIDTH = re.compile(
    r"\w*(_size|_dim|_rank|_width|_window|_per_tok(en)?|_mult|_multiplier)"
    r"|\w*(expand|expansion|state|top_?k|shared_experts)\w*"
)


def may_be_cut(key: str) -> bool:
    """What a configuration may list under ``reduced``: depth and what depth
    drags along (``num_hidden_layers``, ``layer_types``), or the chip's share
    of a stated deployment (a count of experts, of heads, of vocabulary rows
    held here), never a width. A match on the key's NAME, as the contract's
    own rule is: it refuses what is named like a width and lets the rest
    pass, because a closed list of what may be cut, in a file that the PR
    that adds a configuration may not edit, would refuse the first family
    whose depth is also a list by layer. Whether a key that passes is depth
    or a share is the reviewer's to judge, from the file's ``why``."""
    return key == "vocab_size" or WIDTH.fullmatch(key) is None


def test_depth_and_the_chips_share_may_be_cut_and_no_width():
    cut = ("num_hidden_layers", "layer_types", "max_window_layers", "vocab_size",
           "num_local_experts", "n_routed_experts", "num_attention_heads",
           "num_key_value_heads")
    kept = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
            "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
            "v_head_dim", "sliding_window", "num_experts_per_tok", "n_shared_experts",
            "ssm_state_size", "state_size", "d_state", "expand", "conv_kernel_size",
            "moe_topk", "lru_width")
    assert [k for k in cut if not may_be_cut(k)] == []
    assert [k for k in kept if may_be_cut(k)] == []


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_file_names_what_exists_and_cuts_no_width(name):
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == name)
    with open(config_path(name)) as f:
        conf = json.load(f)
    assert conf["name"] == entry["name"] and conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    for kind in ("weight_maker", "reference"):
        sub = "weights" if kind == "weight_maker" else "reference"
        assert os.path.exists(os.path.join(BENCH, sub, conf["serve"][kind] + ".py"))
    for key, cut in conf["reduced"].items():
        assert {"from", "to", "why"} <= set(cut) and cut["why"], key
        assert conf[key] == cut["to"] != cut["from"], f"{key}: the file runs {conf[key]}"
        assert may_be_cut(key), f"{key} is a width, or neither depth nor a share"
    assert conf["correct"]["tolerance"] < 0.7 and conf["correct"]["reason"]
    assert server.judged_numbers([0.3, 0.1, 0.2], conf["correct"].get("judge", "each"))
    # every key but the harness's own reaches the program and the reference
    assert set(server.hf_block(conf)) == set(conf) - set(server.HARNESS_KEYS)


def model_config(block):
    from distributed_llm_inference_tpu.config import ModelConfig

    return ModelConfig.from_hf_config(block)


@pytest.mark.parametrize("rehearse", [False, True], ids=["published", "rehearsal"])
@pytest.mark.parametrize("name", BEFORE_PR25)
def test_the_whole_block_gives_the_model_config_the_old_list_gave(name, rehearse):
    conf = server.load_config(config_path(name), rehearse)
    block = server.hf_block(conf)
    assert not set(server.HARNESS_KEYS) & set(block)
    assert set(block) == set(conf) - set(server.HARNESS_KEYS)
    # ``hidden_act`` (and tp4's ``torch_dtype``) now reach ``from_hf_config``,
    # which reads neither
    assert set(block) - set(OLD_HF_KEYS) <= {"hidden_act", "torch_dtype"}
    old = {k: conf[k] for k in OLD_HF_KEYS if k in conf}
    assert model_config(block) == model_config(old)


@pytest.mark.parametrize("name", BEFORE_PR25)
def test_the_probes_cache_is_the_one_the_old_formula_built(name):
    """``probe_cache`` reads the engine's cache; before PR 25 the probe took
    ``cfg.num_kv_heads`` and ``cfg.head_dim``. Class, static fields, shapes
    and dtypes must agree: at the rehearsal's size on a real engine's cache,
    and at the published widths on the same class described, not made."""
    conf = tiny(name)
    cfg, engine = engine_for(conf, conf["serve"]["cache"].get("kv_quant"))
    klass = type(engine.cache)
    published = model_config(server.hf_block(server.load_config(config_path(name), False)))
    described = jax.eval_shape(lambda: klass.create(
        published.num_layers, 32, 64, 64, 12, published.num_kv_heads,
        published.head_dim, jnp.bfloat16, use_kernel=True, use_ragged=True,
    ))
    for like, c, dtype in ((engine.cache, cfg, jnp.float32),
                           (described, published, jnp.bfloat16)):
        got = jax.eval_shape(lambda: server.probe_cache(like, 4, 6, dtype))
        want = jax.eval_shape(lambda: klass.create(
            c.num_layers, 1, 5, like.page_size, 6, c.num_kv_heads, c.head_dim,
            dtype, use_kernel=like.use_kernel, use_ragged=like.use_ragged,
        ).assign_pages(0, [1, 2, 3, 4]))
        assert type(got) is type(want) is klass
        assert jax.tree.structure(got) == jax.tree.structure(want)
        assert [(x.shape, x.dtype) for x in jax.tree.leaves(got)] == [
            (x.shape, x.dtype) for x in jax.tree.leaves(want)
        ]
        assert got.k_pages.shape == (
            c.num_layers, 5, c.num_kv_heads, like.page_size, c.head_dim
        )
