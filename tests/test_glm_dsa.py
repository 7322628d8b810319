"""GLM-5.2's block (``glm_moe_dsa``: latent attention with compressed
queries, a learned top-k selection INSIDE it that some layers score and the
layers behind them reuse, sigmoid-routed experts of which this program holds
a share, beside a shared one, behind a leading dense layer) on the engine's
normal path, at a small size on the CPU, against the benchmark's plain
reference ``benchmark/reference/glm_mla_dsa_moe.py``: the UN-absorbed
attention, interleaved RoPE, the exact top-k, the sharing and the held share
as published, which shares no code with the program.

Size: the configuration file's rehearsal overlay: 5 layers ``full, shared,
shared, full, shared`` behind a dense first layer, topk 10 of 20 to 46
positions, 8 routed experts of which 4 are held and 3 a token, 4 heads,
q_lora_rank 32 / rank 32 / nope 16 / rope 8 / v 16, 4 index heads of 16.

Tolerances, with their reasons:

* float32 weights, activations, latent pool and index plane: only the order
  of sums differs between the absorbed and the un-absorbed algebra; the
  relative distance of the logits reads 2e-7 to 4e-7 (``TOLERANCE`` 1e-4, as
  ``tests/bench/test_benchmark_reference.py``).
* the int8 latent pool, one precision below: 5e-3 to 1.3e-2, fifty times
  over the tolerance, so a path that computes lower than stated fails; and
  under 0.05, so it is the pool's rounding and not a flipped selection or a
  re-routed token (the index plane stays float32: the selection is the
  reference's). The fused 16-step scan through the kernels (interpreted) is
  held to the same 0.05 at every step.
* a reference with a wrong term: 0.02 to 1 (asserted: 100 times the
  tolerance; 50 times for the compressed query's norm left out and 10 times
  for other rotary angles, which move less here: see the test).
"""

import dataclasses
import hashlib
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import server
from benchmark.reference import glm_mla_dsa_moe as reference
from benchmark.reference.dense_gqa import F32, rms_norm, weight
from distributed_llm_inference_tpu.cache.latent import (
    LatentPagedKVCache, QuantizedLatentPagedKVCache,
    indexed_latent_cache_class,
)
from distributed_llm_inference_tpu.cache.paged import (
    PagedKVCache, QuantizedPagedKVCache, indexed_cache_class,
    two_pool_cache_class,
)
from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, LatentConfig, ModelConfig,
    SparseAttentionConfig,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import validate_config
from distributed_llm_inference_tpu.ops import moe as moe_ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "glm-5.2.json")
TOLERANCE = 1e-4


def tiny(**over):
    conf = server.load_config(CONFIG, rehearse=True)
    conf.update(over)
    return conf


def file_block():
    with open(CONFIG) as f:
        return server.hf_block(json.load(f))


def published_block(**keep):
    """The published ``config.json``: the file's block with every cut key
    put back (``keep`` leaves some as the file runs them)."""
    with open(CONFIG) as f:
        conf = json.load(f)
    block = server.hf_block(conf)
    block.pop("expert_share")
    for key, cut in conf["reduced"].items():
        block[key] = cut["from"]
    block.update(keep)
    return block


def engine_for(conf, kv_quant=None, **engine_kw):
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    maker = importlib.import_module(
        f"benchmark.weights.{conf['serve']['weight_maker']}"
    )
    params = maker.make(cfg, 5, jnp.float32, "float32")
    ekw = dict(conf["serve"]["engine"])
    ekw["prefill_buckets"] = tuple(ekw["prefill_buckets"])
    ekw.update(engine_kw)
    cache = {**conf["serve"]["cache"], "kv_quant": kv_quant}
    return cfg, InferenceEngine(
        cfg, params, EngineConfig(dtype="float32", **ekw), CacheConfig(**cache)
    )


def distances(conf, kv_quant=None, hf_override=None, **engine_kw):
    """Prefill of 30 tokens, then 16 decode steps through the indexed latent
    cache (``server.probe``: the engine's cache class, pad width and decode
    program), against the reference's one full forward; logits, not tokens."""
    cfg, engine = engine_for(conf, kv_quant, **engine_kw)
    assert type(engine.cache).__name__ == (
        ("IndexedQuantizedLatentPagedKVCache" if kv_quant
         else "IndexedLatentPagedKVCache") + "16x2of5"
    )
    conf = {**conf, **(hf_override or {}),
            "correct": {"probe_prompt_tokens": 30, "decode_steps": 16,
                        "tolerance": TOLERANCE}}
    return server.check_numerics(conf, cfg, engine, seed=3), engine


# -- the system against the reference ---------------------------------------


def test_prefill_then_decode_through_the_float_pool_agrees_with_the_reference():
    out, engine = distances(tiny())
    assert out["ok"], out
    assert out["prefill"] < TOLERANCE and out["decode_max"] < TOLERANCE
    assert out["unrelated"] > 0.5 and out["layers"] == 5
    assert engine.decode_steps == 1     # the float pool: a token a dispatch


def test_the_int8_latent_pool_is_a_precision_below_and_fails_the_tolerance():
    out, _ = distances(tiny(), kv_quant="int8")
    assert not out["ok"]
    assert TOLERANCE * 5 < out["decode_median"] and out["decode_max"] < 0.05, out


@pytest.fixture(scope="module")
def chip_plan():
    """The engine as the chip builds it (the plan asked for a TPU's kernels,
    which run interpreted here): the ragged prefill kernel under the
    selection and the fused 16-step scan over the write-behind tail."""
    import functools

    from distributed_llm_inference_tpu.engine import engine as engine_mod
    from distributed_llm_inference_tpu.engine.plan import AttentionPlan

    was = engine_mod.AttentionPlan
    engine_mod.AttentionPlan = functools.partial(AttentionPlan, backend="tpu")
    yield
    engine_mod.AttentionPlan = was


def test_the_fused_scan_with_the_tail_through_the_kernels_agrees(chip_plan):
    out, engine = distances(tiny(), kv_quant="int8")
    assert engine.decode_steps == 16 and engine.cache.has_tail
    assert engine.cache.use_kernel and engine.cache.use_ragged
    assert out["prefill"] < 0.05 and out["decode_max"] < 0.05, out
    assert TOLERANCE * 5 < out["decode_median"]


def unnormed_query(cfg, lp, x):
    return x @ weight(lp["wq_a"])


def index_queries_from_the_hidden_state(cfg, lp, x, cq):
    """The index queries from the hidden state's first ``q_lora_rank`` dims
    (KeyeVL2's block, which has no compressed query, takes them from the
    hidden state)."""
    return (x[:, : cq.shape[1]] @ weight(lp["wq_i"])).reshape(
        -1, cfg["index_n_heads"], cfg["index_head_dim"]
    )


def every_layer_scores_with_a_neighbours_indexer(forward):
    """Each ``shared`` layer scores for itself, with the indexer of the
    ``full`` layer before it."""

    def wrong(cfg, params, tokens):
        params = dict(params)
        names = ("wq_i", "wk_i", "w_i", "k_i_norm", "k_i_norm_bias")
        last = None
        for key in sorted(k for k in params if k.startswith("layers")):
            stack = params[key]
            if "wk_i" in stack:
                last = {n: stack[n][-1:] for n in names}
            else:
                count = stack["attn_norm"].shape[0]
                params[key] = {**stack, **{
                    n: jnp.repeat(a, count, 0) for n, a in last.items()
                }}
        kinds = ["full"] * cfg["num_hidden_layers"]
        return forward({**cfg, "indexer_types": kinds}, params, tokens)

    return wrong


@pytest.mark.parametrize("broken", [
    {"index_topk": 4096},                   # the selection switched off
    "every shared layer reuses the first full layer's selection",
    "each shared layer scores for itself",
    "q_a_layernorm dropped",
    "index queries from the hidden state",
    {"rope_parameters": {"rope_theta": 500.0, "rope_type": "default"}},
    {"routed_scaling_factor": 1.0},
], ids=str)
def test_a_reference_with_a_wrong_term_is_far_from_the_system(broken, monkeypatch):
    forward = reference.forward
    if broken == "every shared layer reuses the first full layer's selection":
        monkeypatch.setattr(
            reference, "forward",
            lambda cfg, p, t: forward(cfg, p, t, share="first"),
        )
    elif broken == "each shared layer scores for itself":
        monkeypatch.setattr(
            reference, "forward",
            every_layer_scores_with_a_neighbours_indexer(forward),
        )
    elif broken == "q_a_layernorm dropped":
        monkeypatch.setattr(reference, "compressed_query", unnormed_query)
    elif broken == "index queries from the hidden state":
        monkeypatch.setattr(
            reference, "index_queries", index_queries_from_the_hidden_state
        )
    out, _ = distances(tiny(), hf_override=broken if isinstance(broken, dict) else None)
    assert not out["ok"], out
    # 8 of a head's 24 query dims rotate: other angles read 0.003. The
    # norm's gains are ones, so without it the queries are only shorter (the
    # softmax flatter; a positive factor moves no selection): 0.008-0.011
    far = 100
    if isinstance(broken, dict) and "rope_parameters" in broken:
        far = 10
    if broken == "q_a_layernorm dropped":
        far = 50
    assert min(out["prefill"], out["decode_median"]) > far * TOLERANCE, out


def test_interleaved_pairs_over_the_published_order_are_halves_over_the_stored_one():
    """The reference turns (2i, 2i + 1) pairs of a slice put back in the
    published order; the program rotates halves of the stored one: the same
    numbers in another order."""
    from distributed_llm_inference_tpu.ops.rotary import (
        apply_rope, rope_cos_sin, rope_inv_freq,
    )

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 3, 8), F32)
    pos = jnp.arange(7)[None] + 5
    cos, sin = rope_cos_sin(pos, rope_inv_freq(8, 8e6, None))
    halves = apply_rope(x, cos, sin)[0]
    turned = reference.rope_pairs(reference.pairs(x[0]), pos[0], 8e6)
    np.testing.assert_allclose(reference.pairs(halves), turned, atol=1e-6)


# -- the share adds up -------------------------------------------------------


def test_the_shares_parts_and_the_shared_expert_once_are_the_uncut_layer():
    shares, experts = 2, 8
    block = server.hf_block(tiny())
    whole_hf = {**block, "n_routed_experts": experts,
                "expert_share": {"router_experts": experts, "shares": 1, "index": 0}}
    whole = ModelConfig.from_hf_config(whole_hf)
    assert whole.num_held_experts == experts
    lp = jax.tree.map(
        lambda a: a[0],
        llama.init_layer_params(whole, jax.random.PRNGKey(2), 1, jnp.float32, "moe", "reuse"),
    )
    lp["router_bias"] = 0.01 * jax.random.normal(jax.random.PRNGKey(3), (experts,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole.hidden_size), F32)
    flat = x.reshape(-1, x.shape[-1])
    want = np.asarray(reference.moe(whole_hf, lp, flat))
    shared = np.asarray(moe_ops._shared_experts(lp, x)).reshape(want.shape)
    held = experts // shares
    total = np.zeros_like(want)
    for i in range(shares):
        hf = {**block, "n_routed_experts": held,
              "expert_share": {"router_experts": experts, "shares": shares, "index": i}}
        cfg = ModelConfig.from_hf_config(hf)
        assert (cfg.num_experts, cfg.num_held_experts, cfg.first_held_expert) == (
            experts, held, i * held
        )
        part = {**lp, **{
            n: lp[n][i * held:(i + 1) * held] for n in ("we_g", "we_u", "we_d")
        }}
        got = np.asarray(moe_ops.moe_mlp(cfg, part, x)).reshape(want.shape)
        # the plain reference, given the same share, computes the same part
        np.testing.assert_allclose(
            got, np.asarray(reference.moe(hf, part, flat)), rtol=2e-5, atol=2e-6
        )
        total += got - shared
    np.testing.assert_allclose(total + shared, want, rtol=2e-5, atol=2e-6)


# -- the configuration --------------------------------------------------------


def test_from_hf_config_reads_the_files_block():
    cfg = ModelConfig.from_hf_config(file_block())
    assert validate_config(cfg).name == "glm_moe_dsa" and cfg.family == "glm_moe_dsa"
    assert cfg.latent == LatentConfig(
        rank=512, rope_head_dim=64, nope_head_dim=192, v_head_dim=256,
        q_lora_rank=2048,
    )
    assert cfg.sparse == SparseAttentionConfig(
        index_heads=32, index_dim=128, topk=2048, rope_dim=64
    )
    assert cfg.index_layers == ("score", "reuse", "reuse", "reuse") * 2 + ("score",)
    assert cfg.index_scoring == (True, False, False, False) * 2 + (True,)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (
        9, 6144, 64, 19360
    )
    assert (cfg.num_experts, cfg.num_held_experts, cfg.num_experts_per_tok) == (256, 16, 8)
    assert (cfg.first_dense_layers, cfg.num_shared_experts) == (1, 1)
    assert (cfg.moe_scoring, cfg.moe_select_bias, cfg.moe_norm_topk,
            cfg.moe_routed_scale) == ("sigmoid", True, True, 2.5)
    assert cfg.rope_theta == 8_000_000 and cfg.rope_scaling is None
    assert [(s.key, s.kind, s.start, s.count, s.index, s.index_start)
            for s in cfg.segments] == [
        ("layers_0_dense", "dense", 0, 1, "score", 0),
        ("layers_1_moe", "moe", 1, 3, "reuse", 1),
        ("layers_2_moe", "moe", 4, 1, "score", 1),
        ("layers_3_moe", "moe", 5, 3, "reuse", 2),
        ("layers_4_moe", "moe", 8, 1, "score", 2),
    ]
    # an expert layer's parameters, as the configuration file's arithmetic has them
    shapes = jax.eval_shape(lambda: llama.init_layer_params(
        cfg, jax.random.PRNGKey(0), 1, kind="moe", index="score"))
    count = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert count["we_g"] + count["we_u"] + count["we_d"] == 603_979_776
    assert count["ws_g"] + count["ws_u"] + count["ws_d"] == 37_748_736
    assert count["wq_a"] + count["wq_b"] + count["wo"] == 146_800_640
    assert count["wkv_a"] + count["wk_b"] + count["wv_b"] == 18_219_008
    assert count["wq_i"] + count["wk_i"] + count["w_i"] == 9_371_648
    reuse = jax.eval_shape(lambda: llama.init_layer_params(
        cfg, jax.random.PRNGKey(0), 3, kind="moe", index="reuse"))
    assert not {"wq_i", "wk_i", "w_i", "k_i_norm"} & set(reuse)


def test_the_published_block_is_read_whole_but_for_its_prediction_layer():
    cfg = ModelConfig.from_hf_config(published_block(num_nextn_predict_layers=0))
    validate_config(cfg)
    assert cfg.num_layers == 78 and cfg.first_dense_layers == 3
    assert sum(cfg.index_scoring) == 21 and cfg.index_layers[:7] == (
        "score", "score", "score", "reuse", "reuse", "reuse", "score"
    )
    assert (cfg.num_experts, cfg.num_held_experts, cfg.vocab_size) == (256, 256, 154880)
    segs = cfg.segments
    assert [s.index for s in segs[:4]] == ["score", "reuse", "score", "reuse"]
    assert (segs[0].kind, segs[0].count, segs[1].kind) == ("dense", 3, "moe")
    assert sum(s.count for s in segs) == 78 and len(segs) == 38


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1),
    ("n_group", 8),
    ("topk_group", 4),
    ("indexer_types", ["shared"] + ["full"] * 8),
    ("indexer_types", ["full"] * 8),
    ("indexer_types", ["full"] * 8 + ["window"]),
    ("mlp_layer_types", ["sparse"] + ["dense"] * 8),
    ("first_k_dense_replace", 2),
    ("expert_share", {"router_experts": 256, "shares": 3, "index": 0}),
    ("expert_share", {"router_experts": 256, "shares": 16, "index": 16}),
    ("moe_layer_freq", 2),
])
def test_from_hf_config_refuses_by_the_keys_name(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**file_block(), key: value})


def test_a_period_without_the_list_is_not_read_as_a_pattern():
    block = file_block()
    block.pop("indexer_types")
    with pytest.raises(ValueError, match="index_topk_freq"):
        ModelConfig.from_hf_config(block)
    every = ModelConfig.from_hf_config({**block, "index_topk_freq": 1})
    assert every.index_layers is None and every.index_scoring == (True,) * 9
    assert [s.index for s in every.segments] == [None, None]


def test_compressed_queries_alone_are_the_mla_familys():
    """``q_lora_rank`` without an indexer is DeepSeek-V3's own block: read,
    served under ``mla``, and no longer refused."""
    block = {k: v for k, v in file_block().items() if not k.startswith("index")}
    cfg = ModelConfig.from_hf_config({**block, "model_type": "deepseek_v3"})
    assert validate_config(cfg).name == "mla" and cfg.latent.q_lora_rank == 2048
    assert cfg.sparse is None and cfg.index_layers is None
    shapes = jax.eval_shape(lambda: llama.init_layer_params(
        cfg, jax.random.PRNGKey(0), 1, kind="dense"))
    assert "wq" not in shapes and shapes["wq_b"].shape == (1, 2048, 64 * 256)


def test_the_registry_lets_latent_and_selection_compose_there_and_nowhere_else():
    cfg = ModelConfig.from_hf_config(server.hf_block(tiny()))
    validate_config(cfg)
    with pytest.raises(ValueError, match="learned key selection"):
        validate_config(dataclasses.replace(cfg, family="mla"))
    with pytest.raises(ValueError, match="latent"):
        validate_config(dataclasses.replace(cfg, family="keye_vl2"))
    with pytest.raises(ValueError, match="index_layers"):
        validate_config(dataclasses.replace(
            cfg, index_layers=("reuse",) + cfg.index_layers[1:]))
    with pytest.raises(ValueError, match="index_layers"):
        validate_config(dataclasses.replace(cfg, sparse=None))
    keye = ModelConfig(
        num_layers=2, family="keye_vl2", qk_norm=True,
        sparse=SparseAttentionConfig(4, 8, 4), index_layers=("score", "reuse"),
    )
    with pytest.raises(ValueError, match="every layer"):
        validate_config(keye)


def test_the_checkpoint_converter_maps_the_compressed_queries_and_refuses_an_indexer():
    cfg = ModelConfig.from_hf_config(server.hf_block(tiny()))
    with pytest.raises(ValueError, match="indexer"):
        llama.convert_hf_state_dict(cfg, {})
    lat = cfg.latent
    plain = dataclasses.replace(cfg, sparse=None, index_layers=None, family="mla")
    h, hq, qr = cfg.hidden_size, cfg.num_heads, lat.q_lora_rank
    dn, dr, dv = lat.nope_head_dim, lat.rope_head_dim, lat.v_head_dim
    rng = np.random.default_rng(0)
    pre = "model.layers.0."

    def w(*shape):
        return rng.normal(size=shape).astype(np.float32)

    state = {
        pre + "self_attn.q_a_proj.weight": w(qr, h),
        pre + "self_attn.q_a_layernorm.weight": w(qr),
        pre + "self_attn.q_b_proj.weight": w(hq * (dn + dr), qr),
        pre + "self_attn.kv_b_proj.weight": w(hq * (dn + dv), lat.rank),
    }
    out = llama.convert_hf_layer(plain, state, 0, jnp.float32)
    np.testing.assert_array_equal(out["wq_a"], state[pre + "self_attn.q_a_proj.weight"].T)
    np.testing.assert_array_equal(
        out["q_a_norm"], state[pre + "self_attn.q_a_layernorm.weight"])
    # the rotary columns of every head, (even, odd) pairs to halves
    wq_b = state[pre + "self_attn.q_b_proj.weight"].T.reshape(qr, hq, dn + dr)
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    want = np.concatenate([wq_b[..., :dn], wq_b[..., dn:][..., halves]], -1)
    np.testing.assert_array_equal(out["wq_b"], want.reshape(qr, -1))
    assert "wq" not in out


# -- the cache ----------------------------------------------------------------


def test_the_index_plane_has_rows_for_the_scoring_layers_only():
    cfg, engine = engine_for(tiny(), "int8")
    cache = engine.cache
    assert cache.k_pages.shape[0] == 5 and cache.ik_pages.shape[0] == 2
    assert cache.ik_pages.dtype == jnp.float32 and cache.ik_pages.shape[-1] == 16
    assert type(cache).SCORING == (True, False, False, True, False)
    assert type(cache).PLANE_FIELDS["ik"] == "ik_pages" and cache.sel is None
    # whoever builds "a cache like this one" from the pool's shape gets it too
    probe = jax.eval_shape(lambda: server.probe_cache(cache, 4, 6, jnp.float32))
    assert type(probe) is type(cache) and probe.ik_pages.shape == (2, 5, 1, 8, 16)
    # at the published widths: 3 of 9, in the model's dtype
    full = ModelConfig.from_hf_config(file_block())
    cls = indexed_latent_cache_class(True, 128, full.index_scoring)
    big = jax.eval_shape(lambda: cls.create(
        9, 16, 3584, 64, 256, 1, 576, jnp.bfloat16, use_kernel=True, use_ragged=True))
    assert big.ik_pages.shape == (3, 3584, 1, 64, 128) and big.ik_pages.dtype == jnp.bfloat16
    assert big.k_pages.shape == (9, 3584, 1, 64, 576) and big.k_pages.dtype == jnp.int8
    per_token = sum(
        getattr(big, f).shape[0] * getattr(big, f).dtype.itemsize
        * int(np.prod(getattr(big, f).shape[2:])) // 64
        for f in cls.PLANE_FIELDS.values()
    )
    assert per_token == 5988
    with pytest.raises(ValueError, match="9 layers"):
        cls.create(8, 1, 4, 64, 4, 1, 576, jnp.bfloat16)


def test_a_segments_view_says_which_row_of_each_stack_a_layer_owns():
    cls = indexed_latent_cache_class(True, 16, (True, False, False, True, False))
    cache = cls.create(5, 2, 8, 8, 4, 1, 24, jnp.float32)
    assert [a.shape[0] for a in cache.layer_stacks] == [5, 5, 2]
    score = cache.index_view("score", 1 - 3, 6)      # layer 3 owns row 1
    assert [a.shape for a in score.layer_stacks][2:] == [(2, 8, 1, 8, 16), (1, 2, 6, 32)]
    assert [int(r) for r in score.stack_rows(jnp.int32(3))] == [3, 3, 1, 0]
    reuse = score.with_layer_stacks(*score.layer_stacks).index_view("reuse", 2 - 4, 6)
    assert [a.shape[0] for a in reuse.layer_stacks] == [5, 5, 1]
    assert [int(r) for r in reuse.stack_rows(jnp.int32(4))] == [4, 4, 0]
    done = reuse.with_layer_stacks(*reuse.layer_stacks).advance(jnp.ones((2,), jnp.int32))
    assert done.sel is None and done.seg is None
    assert jax.tree.structure(done) == jax.tree.structure(cache)
    with pytest.raises(ValueError, match="index_view"):
        cache.attend(
            tuple(a[1] for a in cache.layer_stacks), jnp.zeros((2, 1, 4, 24)),
            jnp.zeros((2, 1, 1, 24)), None, None, jnp.zeros((2, 1), jnp.int32),
            jnp.ones((2,), jnp.int32), None, None,
        )


def test_export_and_ingest_carry_the_index_plane_of_the_scoring_layers():
    cfg, engine = engine_for(tiny(), "int8")
    gid = engine.submit(list(range(1, 21)), SamplingOptions(max_new_tokens=40))
    while not engine.sessions[gid].generated:
        engine.step()
    planes = engine.export_kv_row(engine.sessions[gid])
    assert set(planes) == {"c", "cs", "ik"}
    assert planes["c"].shape == (5, 20, 1, 40) and planes["ik"].shape == (2, 20, 1, 16)
    assert np.abs(planes["ik"]).max() > 0
    dev = engine._check_planes(planes, 20)
    assert dev["ik"].shape == (2, 1, 20, 1, 16)
    with pytest.raises(ValueError, match="shape"):
        engine._check_planes({**planes, "ik": np.zeros((5, 20, 1, 16), np.float32)}, 20)


def test_the_census_counts_the_layers_that_score_and_those_that_attend(chip_plan):
    cfg, engine = engine_for(tiny(), "int8")
    assert engine.plan.index_layers == (2, 5) and engine.plan.sparse_topk == 10
    engine.generate([list(range(1, 41))], SamplingOptions(max_new_tokens=17))
    counts = engine.metrics.snapshot()
    # 40 tokens are two chunks of 32 (the first token with the second), then
    # one dispatch of 16 steps
    assert counts["index_layers_scored"] == 2 * (2 + 16)
    assert counts["index_layers_attended"] == 5 * (2 + 16)
    assert 0 < counts["sparse_keys_selected"] < counts["sparse_keys_live"]


def test_what_cannot_carry_the_plane_is_refused_by_name():
    conf = tiny()
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="paged cache"):
        InferenceEngine(cfg, params, EngineConfig(dtype="float32"),
                        CacheConfig(kind="dense"))
    from distributed_llm_inference_tpu.config import MeshConfig

    with pytest.raises(ValueError, match="single-device"):
        InferenceEngine(cfg, params, EngineConfig(dtype="float32"),
                        CacheConfig(kind="paged"), mesh_cfg=MeshConfig(tp=2))
    with pytest.raises(ValueError, match="draft model"):
        InferenceEngine(
            cfg, params, EngineConfig(dtype="float32", speculative_k=2),
            CacheConfig(kind="paged"), draft=(cfg, params),
        )


# -- the stacks the benchmark had trace to what they traced to ----------------

#: ``sha256(str(jaxpr))[:16]`` of a Mistral-, a Mixtral-, a Moonlight-, a
#: Keye- and a K-EXAONE-shaped stack (prefill and the fused decode scan, over
#: the XLA paths and the kernels'), taken on the PARENT of PR 44 (commit
#: a45a1d5) with this file's ``old_stack_digests`` run against that tree under
#: this suite's ``conftest.py`` (its matmul precision is in the jaxprs); jax
#: 0.9.0. A later change to what these stacks trace to is not this test's
#: business to forbid: regenerate, and say why.
#: PR 46 regenerated the nineteen of the four ROUTED stacks: their decode
#: scans and these toy prefills (a few tokens: one row tile) take the live
#: path of ``ops/moe.py`` where they dense-combined, and a routed layer's
#: ``valid`` now marks a decode step's dead rows (traced here, off a TPU, with
#: ``grouped_matmul``'s plain-XLA reference). PR 58 regenerated the three
#: kernel prefills over the int8 pool proper (Mistral's, Mixtral's, K-EXAONE's
#: two pools): the piece is written by whole pages and read at (layer, page)
#: of the carried stacks (``QuantizedPagedKVCache.ragged_reads_whole_stacks``);
#: Keye's (the index plane's own ``attend``) and Moonlight's (the latent pool)
#: kernel prefills, and every decode scan, are the digests they were.
#: PR 61 regenerated the four kernel decode scans over the int8 pool by
#: copies (Mistral's, Mixtral's, Keye's under its selection, K-EXAONE's two
#: pools; pages of 64 in a table of 768 positions): the cache joins K's and
#: V's scale planes once a window and the sweep copies a live page's scale
#: rows itself, where the wrapper gathered every table slot's a layer a step
#: (``ops/paged_attention.py:joined_scale_rows``; the two forms bit-equal:
#: ``tests/test_paged_attention.py``); Moonlight's (one latent plane of 64
#: lanes: the gather stays) is the digest it was.
#: Mistral's other three are a45a1d5's still.
OLD_STACKS = {
    "mistral.float.prefill": "ce04728d66ae8e7a",
    "mistral.int8.prefill": "0332a71023c2ddb3",
    "mistral.int8.decode_scan": "6fe8c360a5b7a039",
    "mistral.kernel.decode_scan": "d216caf8622f0f4a",
    "mistral.kernel.prefill": "b05ce1beffbe99c6",
    "mixtral.float.prefill": "4e3b03187d0d0786",
    "mixtral.int8.prefill": "93ee1d0335f7b09d",
    "mixtral.int8.decode_scan": "9909d784bd1bcad9",
    "mixtral.kernel.decode_scan": "47f6be7be1ddda67",
    "mixtral.kernel.prefill": "cc5166cd764950bc",
    "moonlight.float.prefill": "eac83a25e1c45a4d",
    "moonlight.int8.prefill": "c3335515e1e0ab1c",
    "moonlight.kernel.decode_scan": "1a03c4bdef39fb36",
    "moonlight.kernel.prefill": "cc062e11f80c521d",
    "keye.float.prefill": "48e8f6a2f7dab6c5",
    "keye.int8.prefill": "d40dfc05f0515bef",
    "keye.int8.decode_scan": "739397ed2aa75e26",
    "keye.kernel.decode_scan": "4c60b200684cbe7c",
    "keye.kernel.prefill": "444e13ff9fa7c7ac",
    "exaone.float.prefill": "89a759304ca1689c",
    "exaone.int8.prefill": "40accbd22a444365",
    "exaone.int8.decode_scan": "7472671651de15b1",
    "exaone.kernel.decode_scan": "346843a413da70cc",
    "exaone.kernel.prefill": "99e11592f9999a59",
}


def old_stacks():
    small = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
                 num_layers=3, num_heads=4, head_dim=8)
    routed = dict(num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16)
    return {
        "mistral": ModelConfig(
            num_kv_heads=2, sliding_window=12, family="mistral", **small
        ),
        "mixtral": ModelConfig(
            num_kv_heads=2, num_experts=4, num_experts_per_tok=2,
            family="mixtral", **small
        ),
        "moonlight": ModelConfig(
            num_kv_heads=4, num_shared_experts=1, first_dense_layers=1,
            moe_scoring="sigmoid", moe_select_bias=True, moe_norm_topk=True,
            moe_routed_scale=2.5,
            latent=LatentConfig(rank=16, rope_head_dim=4, nope_head_dim=8,
                                v_head_dim=8),
            family="mla", **routed, **small
        ),
        "keye": ModelConfig(
            num_kv_heads=2, qk_norm=True, moe_scoring="softmax",
            sparse=SparseAttentionConfig(index_heads=2, index_dim=8, topk=6),
            family="keye_vl2", **routed, **small
        ),
        "exaone": ModelConfig(
            num_kv_heads=2, qk_norm=True, sliding_window=12,
            layer_attention=("window", "window", "full"),
            full_attention_rope=False, num_shared_experts=1,
            first_dense_layers=1, moe_scoring="sigmoid", moe_select_bias=True,
            moe_norm_topk=True, moe_routed_scale=2.5,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
            expert_shares=2, expert_share_index=1, family="exaone_moe", **small
        ),
    }


def old_stack_digests():
    def digest(jaxpr):
        text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def prefill(cfg):
        return lambda p, t, c, n: llama.model_apply(cfg, p, t, c, n, head="last")

    def scan(cfg):
        def fn(p, t, c, act):
            def step_fn(i, logits, alive):
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return nxt, alive.astype(jnp.int32), alive, nxt
            return llama.multi_decode_apply(
                cfg, p, t, c, 4, step_fn, act, act.astype(jnp.int32)
            )
        return fn

    def cache_of(cfg, quant, ps, slots, **kw):
        if cfg.use_latent:
            cls = QuantizedLatentPagedKVCache if quant else LatentPagedKVCache
            return jax.eval_shape(lambda: cls.create(
                cfg.num_layers, 2, 9, ps, slots, 1, cfg.latent.lat_dim, **kw
            ))
        cls, layers = (QuantizedPagedKVCache if quant else PagedKVCache), cfg.num_layers
        if cfg.use_sparse:
            cls = indexed_cache_class(quant, cfg.sparse.index_dim)
        if cfg.mixed_attention:
            cls = two_pool_cache_class(quant, cfg.attention_kinds, cfg.sliding_window)
            layers, kw = cls.num_layers_of("full"), {**kw, "window_pages": 9}
        return jax.eval_shape(lambda: cls.create(
            layers, 2, 9, ps, slots, cfg.num_kv_heads, cfg.head_dim,
            jnp.float32, **kw
        ))

    s = jax.ShapeDtypeStruct
    one, rows, act = s((2, 1), jnp.int32), s((2,), jnp.int32), s((2,), jnp.bool_)
    out = {}
    for name, cfg in old_stacks().items():
        params = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        )
        for quant in (False, True):
            cache = cache_of(cfg, quant, 4, 6)
            kind = "int8" if quant else "float"
            out[f"{name}.{kind}.prefill"] = digest(jax.make_jaxpr(prefill(cfg))(
                params, s((2, 8), jnp.int32), cache, rows
            ))
            if quant and not cfg.use_latent:
                out[f"{name}.int8.decode_scan"] = digest(
                    jax.make_jaxpr(scan(cfg))(params, one, cache, act)
                )
        # the kernels' paths: the in-place sweep, the latent pool's fused
        # one-plane form, the ragged kernels (under a selection, a window)
        cache = cache_of(cfg, True, 64, 12, use_kernel=True, use_ragged=True)
        out[f"{name}.kernel.decode_scan"] = digest(
            jax.make_jaxpr(scan(cfg))(params, one, cache, act)
        )
        out[f"{name}.kernel.prefill"] = digest(
            jax.make_jaxpr(prefill(cfg))(
                params, s((2, 16), jnp.int32), cache, rows
            )
        )
    return out


def test_the_stacks_the_benchmark_runs_trace_to_the_jaxprs_they_traced_to():
    stacks = old_stacks()
    assert all(s.index is None for c in stacks.values() for s in c.segments)
    assert [s.key for s in stacks["exaone"].segments] == [
        "layers_0_dense", "layers_1_moe", "layers_2_moe"]
    assert [s.key for s in stacks["keye"].segments] == ["layers"]
    assert old_stack_digests() == OLD_STACKS


if __name__ == "__main__":  # python tests/test_glm_dsa.py: the digests, to paste
    print(json.dumps(old_stack_digests(), indent=4))
