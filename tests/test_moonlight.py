"""Moonlight-16B-A3B's block (DeepSeek-V3: latent attention, sigmoid-routed
experts beside shared ones, a leading dense layer) on the engine's normal
path, at a small size on the CPU, against the benchmark's plain reference
``benchmark/reference/moonlight_mla_moe.py``: the UN-absorbed attention and
the routing rule as published, which shares no code with the program.

Size: the configuration file's rehearsal overlay, 1 dense + 2 expert layers,
8 experts of which 3 a token and 1 shared, rank 32 / nope 16 / rope 8 /
v 16, 4 heads.

Tolerances, with their reasons:

* float32 weights, activations and latent pool: only the order of sums
  differs between the absorbed and the un-absorbed algebra; the relative
  distance of the logits reads 2e-7 (``TOLERANCE`` 1e-4, as
  ``tests/bench/test_benchmark_reference.py``).
* the int8 latent pool, one precision below: 2e-3, twenty times over the
  tolerance, so a path that computes lower than stated fails; and under 0.05,
  so it is the pool's rounding and not a re-routed token.
* a reference with a wrong routing term: 0.03 to 1.2 (asserted: 100 times the
  tolerance); the selection bias left in the weights reads 0.0018 to 0.0028,
  because the seeded bias is small as a trained one is (standard deviation
  0.01: it moves a weight by 2%), so that case is asserted at 10 times.
"""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import server
from benchmark.reference import moonlight_mla_moe as reference
from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, LatentConfig, ModelConfig, TraceConfig,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import validate_config
from distributed_llm_inference_tpu.ops import moe
from distributed_llm_inference_tpu.ops.norms import rms_norm
from distributed_llm_inference_tpu.ops.quant import quantize_params
from distributed_llm_inference_tpu.ops.rotary import (
    apply_rope, rope_cos_sin, rope_inv_freq,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "moonlight-16b-a3b.json")
TOLERANCE = 1e-4


def tiny(**over):
    conf = server.load_config(CONFIG, rehearse=True)
    conf.update(over)
    return conf


def published_block():
    with open(CONFIG) as f:
        conf = json.load(f)
    block = server.hf_block(conf)
    block["num_hidden_layers"] = conf["reduced"]["num_hidden_layers"]["from"]
    return block


def engine_for(conf, kv_quant=None, **engine_kw):
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    maker = importlib.import_module(
        f"benchmark.weights.{conf['serve']['weight_maker']}"
    )
    params = maker.make(cfg, 5, jnp.float32, "float32")
    ekw = dict(conf["serve"]["engine"])
    ekw["prefill_buckets"] = tuple(ekw["prefill_buckets"])
    cache = {**conf["serve"]["cache"], "kv_quant": kv_quant}
    return cfg, InferenceEngine(
        cfg, params, EngineConfig(dtype="float32", **ekw), CacheConfig(**cache),
        **engine_kw,
    )


def distances(conf, kv_quant=None, hf_override=None):
    """Prefill of 30 tokens, then 16 decode steps through the paged latent
    cache (``server.probe``: the engine's cache class, pad width and decode
    program), against the reference's one full forward; logits, not tokens."""
    cfg, engine = engine_for(conf, kv_quant)
    assert type(engine.cache).__name__ == (
        "QuantizedLatentPagedKVCache" if kv_quant else "LatentPagedKVCache"
    )
    conf = {**conf, **(hf_override or {}),
            "correct": {"probe_prompt_tokens": 30, "decode_steps": 16,
                        "tolerance": TOLERANCE}}
    return server.check_numerics(conf, cfg, engine, seed=3)


# -- the system against the reference ---------------------------------------


def test_prefill_then_decode_through_the_latent_pool_agrees_with_the_reference():
    out = distances(tiny())
    assert out["ok"], out
    assert out["prefill"] < TOLERANCE and out["decode_max"] < TOLERANCE
    assert out["unrelated"] > 0.5 and out["layers"] == 3


def test_the_int8_latent_pool_is_a_precision_below_and_fails_the_tolerance():
    out = distances(tiny(), kv_quant="int8")
    assert not out["ok"]
    assert TOLERANCE * 5 < out["decode_median"] and out["decode_max"] < 0.05, out


def bias_weighs(cfg, lp, x):
    """``MoEGate`` with the selection bias left in the WEIGHTS."""
    scores = jax.nn.sigmoid(x @ lp["router"].astype(jnp.float32))
    biased = scores + lp["router_bias"].astype(jnp.float32)[None, :]
    top_w, top_i = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    return top_w * cfg["routed_scaling_factor"], top_i


@pytest.mark.parametrize("broken", [
    {"scoring_func": "softmax"},            # Mixtral's scores under this name
    {"routed_scaling_factor": 1.0},         # the scaling factor left out
    {"n_shared_experts": 0},                # the shared expert left out
    {"norm_topk_prob": False},              # the weights not normalised
    "bias in the weights",
    {"rope_theta": 500.0},                  # other rotary angles
], ids=str)
def test_a_reference_with_a_wrong_term_is_far_from_the_system(broken, monkeypatch):
    if broken == "bias in the weights":
        monkeypatch.setattr(reference, "routing", bias_weighs)
        broken, far = {}, 10 * TOLERANCE
    else:
        # 8 of a head's 24 query dims rotate: other angles read 0.003
        far = (10 if "rope_theta" in broken else 100) * TOLERANCE
    out = distances(tiny(), hf_override=broken)
    assert not out["ok"], out
    assert min(out["prefill"], out["decode_median"]) > far, out


# -- the routing rule --------------------------------------------------------


def restated(x, router, bias, k, scale):
    """Ten lines: sigmoid scores; the k largest of score + bias; weights the
    scores without the bias, over their sum, times the scaling factor."""
    out = np.zeros((x.shape[0], router.shape[1]))
    for t, row in enumerate(np.asarray(x, np.float64)):
        s = 1.0 / (1.0 + np.exp(-(row @ np.asarray(router, np.float64))))
        top = np.argsort(-(s + bias), kind="stable")[:k]
        out[t, top] = scale * s[top] / s[top].sum()
    return out


MOON = ModelConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
    num_heads=2, num_kv_heads=2, head_dim=16, num_experts=16,
    num_experts_per_tok=6, moe_intermediate_size=24, num_shared_experts=2,
    first_dense_layers=1, moe_scoring="sigmoid", moe_select_bias=True,
    moe_norm_topk=True, moe_routed_scale=2.446, family="mixtral",
)


def test_selection_uses_the_bias_and_the_weights_do_not():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 40, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 16)) * 0.3, jnp.float32)
    bias = rng.normal(size=16) * 0.2
    got = np.asarray(moe.router_weights(MOON, x, router, jnp.asarray(bias, jnp.float32)))[0]
    want = restated(np.asarray(x[0]), router, bias, 6, 2.446)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # six a token, and their weights sum to the scaling factor
    assert ((got > 0).sum(-1) == 6).all()
    np.testing.assert_allclose(got.sum(-1), 2.446, rtol=1e-5)
    # the bias chose: without it other experts are taken for some tokens ...
    plain = np.asarray(moe.router_weights(MOON, x, router))[0]
    assert ((plain > 0) != (got > 0)).any()
    # ... and where the choice is the same, so are the weights
    same = ((plain > 0) == (got > 0)).all(-1)
    assert same.any()
    np.testing.assert_allclose(plain[same], got[same], rtol=1e-6)


def test_groups_of_one_are_the_identity():
    """The published ``noaux_tc`` step with ``n_group = topk_group = 1``:
    the one group's score is the sum of its two best biased scores, the one
    group is kept, nothing is masked: the selection is the plain top-k of
    the biased scores, which is what ``route`` takes."""
    rng = np.random.default_rng(1)
    s = rng.uniform(size=(9, 16))
    bias = rng.normal(size=16) * 0.1
    biased = s + bias
    group_scores = np.sort(biased.reshape(9, 1, 16), -1)[..., -2:].sum(-1)  # [9, 1]
    kept = np.argsort(-group_scores, -1)[:, :1]                               # topk_group 1
    mask = np.zeros((9, 1)); np.put_along_axis(mask, kept, 1.0, -1)
    masked = np.where(np.repeat(mask, 16, -1) > 0, biased, -np.inf)
    assert (masked == biased).all()


@pytest.mark.parametrize("rule", ["mixtral", "deepseek_v2"])
def test_the_other_rules_are_the_same_function(rule):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, 12, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    cfg = dataclasses.replace(
        MOON, moe_scoring="softmax", moe_select_bias=False, moe_routed_scale=1.0,
        moe_norm_topk=rule == "mixtral",
    )
    got = np.asarray(moe.router_weights(cfg, x, router))[0]
    p = np.asarray(jax.nn.softmax(x[0] @ router, -1), np.float64)
    for t in range(12):
        top = np.argsort(-p[t])[:6]
        want = p[t, top] / (p[t, top].sum() if rule == "mixtral" else 1.0)
        np.testing.assert_allclose(got[t, top], want, rtol=1e-5)
        assert (np.delete(got[t], top) == 0).all()


# -- the stack as segments ---------------------------------------------------


def test_a_two_segment_stack_places_layer_i_at_cache_index_i():
    cfg = dataclasses.replace(MOON, num_experts=4, num_experts_per_tok=2)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert [s.key for s in cfg.segments] == ["layers_0_dense", "layers_1_moe"]
    assert [(s.start, s.count) for s in cfg.segments] == [(0, 1), (1, 2)]
    assert "layers" not in params and "router" not in params["layers_0_dense"]
    assert params["layers_1_moe"]["we_g"].shape == (2, 4, 32, 24)
    assert params["layers_1_moe"]["ws_g"].shape == (2, 32, 48)
    assert params["layers_1_moe"]["router_bias"].dtype == jnp.float32
    tokens = jnp.arange(1, 7, dtype=jnp.int32)[None]
    six = jnp.full((1,), 6, jnp.int32)
    cache = DenseKVCache.create(3, 1, 16, 2, 16, jnp.float32)
    logits, full = llama.model_apply(cfg, params, tokens, cache, six)
    # segment by segment, by hand: the second one starts at cache layer 1
    x = jnp.take(params["embed"], tokens, axis=0)
    x1, c1 = llama.block_apply(cfg, params["layers_0_dense"], x, cache, six)
    assert (np.asarray(c1.k[0, 0, :6]) != 0).any()
    assert (np.asarray(c1.k[1:]) == 0).all()
    x3, c3 = llama.block_apply(
        cfg, params["layers_1_moe"], x1, c1, six, first_layer=1
    )
    np.testing.assert_array_equal(np.asarray(full.k), np.asarray(c3.k))
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(llama.apply_head(cfg, params, x3))
    )
    # and layer 1's rows are the keys of the hidden state that ENTERS layer 1
    lp = jax.tree.map(lambda w: w[0], params["layers_1_moe"])
    h = rms_norm(x1, lp["attn_norm"], cfg.rms_norm_eps)
    cos, sin = rope_cos_sin(
        jnp.arange(6)[None], rope_inv_freq(16, cfg.rope_theta, None)
    )
    want = apply_rope((h @ lp["wk"]).reshape(1, 6, 2, 16), cos, sin)
    np.testing.assert_allclose(
        np.asarray(full.k[1, :, :6]), np.asarray(want), rtol=1e-5, atol=1e-6
    )
    for i in range(3):
        assert (np.asarray(full.k[i, 0, :6]) != 0).any(), i
        assert (np.asarray(full.k[i, 0, 6:]) == 0).all(), i


ONE_SEGMENT = {
    "mistral": ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, sliding_window=8,
        family="mistral",
    ),
    "mixtral": ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, num_experts=4,
        num_experts_per_tok=2, family="mixtral",
    ),
    "mla": ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=8, family="mla",
        latent=LatentConfig(rank=16, rope_head_dim=8),
    ),
}
MLP_LEAVES = {
    "mistral": {"wg", "wu", "wd"}, "mla": {"wg", "wu", "wd"},
    "mixtral": {"router", "we_g", "we_u", "we_d"},
}


@pytest.mark.parametrize("family", sorted(ONE_SEGMENT))
def test_a_one_segment_stack_is_the_tree_and_the_program_it_was(family):
    """What every model had before stacks were segments: one stacked dict
    under ``"layers"`` drawn from the same keys, and a forward that is one
    ``block_apply`` over it (bit for bit: the same jaxpr but for a scope's
    name)."""
    cfg = ONE_SEGMENT[family]
    validate_config(cfg)
    (seg,) = cfg.segments
    assert (seg.key, seg.start, seg.count) == ("layers", 0, 2)
    key = jax.random.PRNGKey(3)
    params = llama.init_params(cfg, key, jnp.float32)
    assert set(params) == {"embed", "layers", "final_norm", "lm_head"}
    assert MLP_LEAVES[family] <= set(params["layers"])
    assert not {"router_bias", "ws_g"} & set(params["layers"])
    # the layers are drawn from the second of three keys, as ever
    old = llama.init_layer_params(cfg, jax.random.split(key, 3)[1], 2, jnp.float32)
    assert jax.tree.structure(old) == jax.tree.structure(params["layers"])
    for a, b in zip(jax.tree.leaves(old), jax.tree.leaves(params["layers"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if family == "mla":
        return      # its cache is the engine's paged latent pool: see above
    tokens = jnp.arange(1, 6, dtype=jnp.int32)[None]
    five = jnp.full((1,), 5, jnp.int32)
    cache = DenseKVCache.create(2, 1, 16, 2, 8, jnp.float32)
    logits, after = llama.model_apply(cfg, params, tokens, cache, five)
    x, direct = llama.block_apply(
        cfg, params["layers"], jnp.take(params["embed"], tokens, axis=0),
        cache, five,
    )
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(llama.apply_head(cfg, params, x))
    )
    np.testing.assert_array_equal(np.asarray(after.k), np.asarray(direct.k))


def test_the_quantiser_takes_every_segment():
    cfg = dataclasses.replace(MOON, num_experts=4, num_experts_per_tok=2)
    q = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    for key, names in (("layers_0_dense", ("wq", "wo", "wg", "wu", "wd")),
                       ("layers_1_moe", ("wq", "wo", "we_g", "we_d", "ws_g", "ws_d"))):
        for name in names:
            assert q[key][name].q.dtype == jnp.int8, (key, name)
    assert q["layers_1_moe"]["router"].dtype == jnp.float32
    assert q["layers_1_moe"]["router_bias"].dtype == jnp.float32


# -- the published block -----------------------------------------------------


def test_from_hf_config_reads_the_published_block():
    cfg = ModelConfig.from_hf_config(published_block())
    validate_config(cfg)
    assert (cfg.family, cfg.num_layers, cfg.hidden_size, cfg.num_heads) == (
        "mla", 27, 2048, 16)
    assert cfg.latent == LatentConfig(
        rank=512, rope_head_dim=64, nope_head_dim=128, v_head_dim=128)
    assert cfg.latent.lat_dim == 576
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts) == (64, 6, 2)
    assert (cfg.intermediate_size, cfg.expert_intermediate_size) == (11264, 1408)
    assert (cfg.moe_scoring, cfg.moe_select_bias, cfg.moe_norm_topk) == (
        "sigmoid", True, True)
    assert cfg.moe_routed_scale == 2.446 and cfg.first_dense_layers == 1
    assert (cfg.rope_theta, cfg.vocab_size, cfg.tie_word_embeddings) == (
        50000, 163840, False)
    assert [(s.kind, s.start, s.count) for s in cfg.segments] == [
        ("dense", 0, 1), ("moe", 1, 26)]
    assert cfg.num_expert_layers == 26
    # an expert layer's parameters, as the configuration file's arithmetic has them
    shapes = jax.eval_shape(
        lambda: llama.init_layer_params(cfg, jax.random.PRNGKey(0), 1, kind="moe"))
    count = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert count["we_g"] + count["we_u"] + count["we_d"] == 553_648_128
    assert count["ws_g"] + count["ws_u"] + count["ws_d"] == 17_301_504
    assert shapes["wv_b"].shape == (1, 512, 16, 128) and shapes["wo"].shape == (1, 2048, 2048)
    assert shapes["wq"].shape == (1, 2048, 16 * 192)


@pytest.mark.parametrize("key,value", [
    ("topk_group", 4),
    ("n_group", 8),
    # YaRN is read since PR 47; what is still refused is a block whose
    # mscale and mscale_all_dim differ (cos and sin would be scaled)
    ("rope_scaling", {"type": "yarn", "factor": 40, "mscale": 1.0,
                      "mscale_all_dim": 0.707}),
    ("num_nextn_predict_layers", 1),
    ("moe_layer_freq", 2),
])
def test_from_hf_config_refuses_by_name_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**published_block(), key: value})


def test_deepseek_v2_lite_is_the_same_block_with_softmax_routing():
    block = {**published_block(), "model_type": "deepseek_v2",
             "scoring_func": "softmax", "topk_method": "greedy",
             "norm_topk_prob": False, "routed_scaling_factor": 1.0}
    cfg = ModelConfig.from_hf_config(block)
    validate_config(cfg)
    assert (cfg.moe_scoring, cfg.moe_select_bias, cfg.moe_norm_topk,
            cfg.moe_routed_scale) == ("softmax", False, False, 1.0)


def test_validate_config_composes_latent_and_experts_and_refuses_an_moe_llama():
    cfg = ModelConfig.from_hf_config(server.hf_block(tiny()))
    assert validate_config(cfg).name == "mla"
    with pytest.raises(ValueError, match="dense"):
        validate_config(dataclasses.replace(cfg, family="llama", latent=None))
    with pytest.raises(ValueError, match="latent"):
        validate_config(dataclasses.replace(cfg, family="mixtral"))
    with pytest.raises(ValueError, match="routed experts"):
        validate_config(dataclasses.replace(cfg, num_experts=0))
    with pytest.raises(ValueError, match="first_dense_layers"):
        validate_config(dataclasses.replace(cfg, first_dense_layers=3))


def test_convert_hf_layer_maps_the_deepseek_v3_keys():
    cfg = ModelConfig.from_hf_config(server.hf_block(tiny()))
    h, e, f, fd = 64, 8, 48, 160
    hq, dn, dr, dv, rank = 4, 16, 8, 16, 32
    rng = np.random.default_rng(0)
    w = lambda *s: rng.normal(size=s).astype(np.float32)
    state = {}
    for i in range(3):
        pre = f"model.layers.{i}."
        state.update({
            pre + "input_layernorm.weight": w(h),
            pre + "post_attention_layernorm.weight": w(h),
            pre + "self_attn.q_proj.weight": w(hq * (dn + dr), h),
            pre + "self_attn.kv_a_proj_with_mqa.weight": w(rank + dr, h),
            pre + "self_attn.kv_a_layernorm.weight": w(rank),
            pre + "self_attn.kv_b_proj.weight": w(hq * (dn + dv), rank),
            pre + "self_attn.o_proj.weight": w(h, hq * dv),
        })
        if i == 0:
            state.update({pre + f"mlp.{n}_proj.weight": w(*s) for n, s in
                          (("gate", (fd, h)), ("up", (fd, h)), ("down", (h, fd)))})
            continue
        state[pre + "mlp.gate.weight"] = w(e, h)
        state[pre + "mlp.gate.e_score_correction_bias"] = w(e)
        for x in range(e):
            state.update({pre + f"mlp.experts.{x}.{n}_proj.weight": w(*s) for n, s in
                          (("gate", (f, h)), ("up", (f, h)), ("down", (h, f)))})
        state.update({pre + f"mlp.shared_experts.{n}_proj.weight": w(*s) for n, s in
                      (("gate", (f, h)), ("up", (f, h)), ("down", (h, f)))})
    state["model.embed_tokens.weight"] = w(256, h)
    state["model.norm.weight"] = w(h)
    state["lm_head.weight"] = w(256, h)
    params = llama.convert_hf_state_dict(cfg, state, dtype=jnp.float32)
    want = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    assert got == jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    moe_l = params["layers_1_moe"]
    pre = "model.layers.2."
    np.testing.assert_array_equal(moe_l["router"][1], state[pre + "mlp.gate.weight"].T)
    np.testing.assert_array_equal(
        moe_l["router_bias"][1], state[pre + "mlp.gate.e_score_correction_bias"])
    np.testing.assert_array_equal(
        moe_l["we_u"][1, 5], state[pre + "mlp.experts.5.up_proj.weight"].T)
    np.testing.assert_array_equal(
        moe_l["we_d"][1, 7], state[pre + "mlp.experts.7.down_proj.weight"].T)
    np.testing.assert_array_equal(
        moe_l["ws_g"][1], state[pre + "mlp.shared_experts.gate_proj.weight"].T)
    np.testing.assert_array_equal(
        params["layers_0_dense"]["wd"][0], state["model.layers.0.mlp.down_proj.weight"].T)
    # kv_b_proj splits by head into the key and the value up-projection
    kvb = state[pre + "self_attn.kv_b_proj.weight"].T.reshape(rank, hq, dn + dv)
    np.testing.assert_array_equal(moe_l["wk_b"][1], kvb[..., :dn])
    np.testing.assert_array_equal(moe_l["wv_b"][1], kvb[..., dn:])
    # the rotary columns are de-interleaved (pairs side by side -> halves)
    wkv_a = state[pre + "self_attn.kv_a_proj_with_mqa.weight"].T
    np.testing.assert_array_equal(moe_l["wkv_a"][1][:, :rank], wkv_a[:, :rank])
    np.testing.assert_array_equal(
        moe_l["wkv_a"][1][:, rank:],
        np.concatenate([wkv_a[:, rank::2], wkv_a[:, rank + 1::2]], -1))
    wq = state[pre + "self_attn.q_proj.weight"].T.reshape(h, hq, dn + dr)
    got_q = moe_l["wq"][1].reshape(h, hq, dn + dr)
    np.testing.assert_array_equal(got_q[..., :dn], wq[..., :dn])
    np.testing.assert_array_equal(got_q[..., dn:dn + dr // 2], wq[..., dn::2])
    # a block a node serves lies inside one segment
    block = llama.convert_hf_state_dict(cfg, state, layer_ids=[1, 2], dtype=jnp.float32)
    assert set(block) == {"layers"} and block["layers"]["we_g"].shape == (2, e, h, f)
    with pytest.raises(ValueError, match="segment"):
        llama.convert_hf_state_dict(cfg, state, layer_ids=[0, 1], dtype=jnp.float32)


# -- the census --------------------------------------------------------------


def test_the_census_counts_latent_and_expert_work_exactly():
    cfg, engine = engine_for(tiny(), kv_quant="int8", trace_cfg=TraceConfig())
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    prompts = [list(range(1, 12)), list(range(3, 30))]        # 11 and 27 tokens
    engine.generate(prompts, SamplingOptions(max_new_tokens=5))
    m = engine.metrics
    seen = [(d[0], tuple(d[1]), d[2])
            for t in engine.flight.snapshot() for d in t.get("dispatches", ())]
    prefills = [d for d in seen if d[0] == "prefill"]
    decodes = [d for d in seen if d[0] == "decode"]
    assert prefills and decodes and all(len(d) == 3 for d in seen)
    # every dispatch read the latent stored form
    assert m.get_counter("latent_decompress_dispatches") == len(seen)
    valid = sum(d[2] for d in prefills)
    padded = sum(d[1][0] * d[1][1] for d in prefills)
    assert valid == 11 + 27
    assert m.get_counter("prefill_valid_tokens") == valid
    assert m.get_counter("prefill_padded_tokens") == padded
    assert m.get_counter("decode_live_positions") == sum(d[2] for d in decodes)
    assert m.get_counter("decode_grid_positions") == sum(
        d[1][0] * d[1][2] * engine.ccfg.page_size for d in decodes)
    # expert rows: 2 expert layers; a token needs 3 picks + 1 shared. These
    # dispatches each fit one row tile, so the program runs the live path:
    # a padded token passes through the shared expert and the experts its
    # dispatch's valid tokens are expected to pick between them, of 8
    decode_valid = m.get_counter("moe_expert_rows_needed") / (2 * 4) - valid
    # 4 decode tokens a request after the prefill's first: the active rows
    assert decode_valid == 2 * 4
    assert all(d[1][0] * d[1][1] <= 128 for d in prefills)
    # (2 active rows of the rehearsal's 4 slots: decode_valid above)
    live = lambda tokens: 8 * (1 - (1 - 3 / 8) ** tokens)
    computed = sum(d[1][0] * d[1][1] * (live(d[2]) + 1) for d in prefills)
    computed += sum(d[1][0] * d[1][1] * (live(2) + 1) for d in decodes)
    assert m.get_counter("moe_expert_rows_computed") == pytest.approx(2 * computed)
    assert m.get_counter("moe_dispatch_live") == len(seen)
    assert m.get_counter("moe_decode_experts_held") == sum(8 * d[1][1] for d in decodes)
    assert m.get_counter("moe_decode_experts_live") == pytest.approx(
        sum(live(2) * d[1][1] for d in decodes)
    )


# -- the int8 latent pool's write-behind tail, through the engine -------------


def kernel_conf():
    """The rehearsal's configuration with the cache's Pallas kernels on (the
    chip's default; here interpreted)."""
    conf = tiny()
    serve = conf["serve"]
    conf["serve"] = {**serve, "engine": {**serve["engine"], "use_pallas_attention": True}}
    return conf


def decode_dispatches(engine):
    return [tuple(d[1]) for t in engine.flight.snapshot()
            for d in t.get("dispatches", ()) if d[0] == "decode"]


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "no-kernel"])
def test_an_int8_latent_engine_decodes_sixteen_steps_a_dispatch(kernel):
    """With its kernel the int8 latent cache has the tail protocol, so the
    engine resolves ``decode_steps`` 16, pipelines its ticks and every decode
    dispatch is ``_decode_scan``'s ``(rows, 16, width)``; without it (and in
    float32) the cache says it has none and the engine keeps the one-token
    path: a watched engine's ``engine_decode_steps`` over
    ``engine_dispatches_decode`` is the steps a dispatch."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    cfg, engine = engine_for(
        kernel_conf() if kernel else tiny(), kv_quant="int8",
        trace_cfg=TraceConfig(),
    )
    assert engine.cache.has_tail is kernel
    assert engine.decode_steps == (16 if kernel else 1)
    assert engine._pipelined is kernel
    engine.flight.clock.lease(600.0)  # watched: the dispatch clock counts
    out = engine.generate(
        [list(range(1, 12)), list(range(3, 30))],
        SamplingOptions(max_new_tokens=20),
    )
    assert [len(o) for o in out] == [20, 20]
    steps = {d[1] for d in decode_dispatches(engine)}
    assert steps == ({16} if kernel else {1})
    dispatched = engine.metrics.get_counter("engine_dispatches_decode")
    assert engine.metrics.get_counter("engine_decode_steps") == (
        16 * dispatched if kernel else dispatched
    )
    assert dispatched >= (2 if kernel else 19)
    _, f32 = engine_for(kernel_conf())
    assert not f32.cache.has_tail and f32.decode_steps == 1


def test_a_row_of_the_fused_latent_engine_is_exported_and_resumed():
    """A session checkpointed between two fused windows, shipped through the
    codec and resumed on a fresh engine continues the uninterrupted stream:
    the snapshot is the pool's planes (``c`` and ``cs``: ``PLANE_FIELDS`` as
    it was), which after a window's flush hold what the one-token path
    writes."""
    from distributed_llm_inference_tpu.disagg.kv_codec import (
        decode_session, encode_session,
    )
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    conf = kernel_conf()
    prompt = [3, 5, 7, 11, 13, 17, 19]
    opts = SamplingOptions(max_new_tokens=40)

    def drain(engine, gid, until=None):
        got = []
        for _ in range(200):
            for g, tok, fin in engine.step():
                if g == gid and tok >= 0:
                    got.append(tok)
                if g == gid and fin:
                    return got
            if until is not None and len(got) >= until:
                return got
        raise AssertionError("the generation did not end")

    _, ref = engine_for(conf, kv_quant="int8")
    base = drain(ref, ref.submit(list(prompt), opts))
    assert len(base) == 40

    _, victim = engine_for(conf, kv_quant="int8")
    gid = victim.submit(list(prompt), opts)
    drain(victim, gid, until=6)
    snap = victim.export_session(gid)
    assert set(snap["planes"]) == {"c", "cs"}
    assert snap["planes"]["c"].dtype == np.int8
    assert snap["planes"]["c"].shape[-1] == victim.cfg.latent.lat_dim
    assert 6 <= len(snap["generated"]) < 40
    snap2, meta = decode_session(
        encode_session("mig", snap, page_size=victim.ccfg.page_size))
    assert meta["layout"] == "latent"
    _, fresh = engine_for(conf, kv_quant="int8")
    assert snap["generated"] + drain(fresh, fresh.resume_session(snap2)) == base
