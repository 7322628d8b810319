"""The ``ouro`` family (Ouro-2.6B): a stack that every token crosses
``total_ut_steps`` times, each lap with its own rows of the cache, an exit
gate behind each lap (``ModelConfig.loop``; the lap scans of
``models/llama.py``), against the benchmark's plain reference (a full causal
forward, no cache) on seeded weights, on the CPU at small sizes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import server as bench_server
from benchmark.reference import ouro_looped_gqa as reference
from benchmark.weights import ouro_looped_gqa as maker
from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.cache.paged import (
    PagedKVCache, QuantizedPagedKVCache,
)
from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, LoopConfig, MeshConfig, ModelConfig,
    TraceConfig,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import (
    get_family, validate_config,
)

CATALOG = {  # the catalog's ``config`` block, whole
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152,
}
MECHANISM = "layers run several times: a lap must return to the first stage"
L, N, STEPS = 3, 20, 16


def tiny_hf(laps=4, threshold=1.0, layers=L):
    return {
        **CATALOG, "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": layers, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
        "max_position_embeddings": 512, "max_window_layers": layers,
        "layer_types": ["full_attention"] * layers,
        "total_ut_steps": laps, "early_exit_threshold": threshold,
    }


def tiny_model(laps=4, threshold=1.0, seed=3):
    """Float32 weights from the benchmark's maker: gains around 1 and a
    gate's bias around 0, so that a missing norm or gate shows."""
    hf = tiny_hf(laps, threshold)
    cfg = ModelConfig.from_hf_config(hf)
    validate_config(cfg)
    return hf, cfg, maker.make(cfg, seed, jnp.float32, None)


@pytest.fixture(scope="module")
def model():
    return tiny_model()


def probe_tokens(seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 256, size=N), rng.integers(1, 256, size=STEPS + 1)


def whole(prompt, forced):
    return jnp.asarray(np.concatenate([prompt, forced[:-1]]), jnp.int32)


def fresh_cache(cfg, kind, batch=1, positions=N + STEPS + 1, ps=8):
    if kind == "dense":
        return DenseKVCache.create(
            cfg.cache_layers, batch, 64, cfg.num_kv_heads, cfg.head_dim,
            jnp.float32,
        )
    pages = -(-positions // ps)
    cls = QuantizedPagedKVCache if kind == "paged-int8" else PagedKVCache
    cache = cls.create(
        cfg.cache_layers, batch, batch * pages + 1, ps, pages + 1,
        cfg.num_kv_heads, cfg.head_dim, jnp.float32,
    )
    for row in range(batch):
        cache = cache.assign_pages(
            row, list(range(1 + row * pages, 1 + (row + 1) * pages))
        )
    return cache


ONE = jnp.ones((1,), jnp.int32)


def prefill(cfg, params, cache, tokens, width=32, head="last"):
    padded = jnp.zeros((1, width), jnp.int32).at[0, : len(tokens)].set(
        jnp.asarray(tokens, jnp.int32)
    )
    return jax.jit(
        lambda p, t, c: llama.model_apply(
            cfg, p, t, c, len(tokens) * ONE, head=head
        )
    )(params, padded, cache)


def fused_decode(cfg, params, cache, forced):
    forced = jnp.asarray(forced, jnp.int32)
    (steps, laps), cache = jax.jit(
        lambda p, c: llama.multi_decode_apply(
            cfg, p, forced[:1][None], c, forced.shape[0] - 1,
            lambda i, logits, st: (forced[i + 1][None], ONE, st, logits),
            jnp.zeros(()), ONE, exit_laps=True,
        )
    )(params, cache)
    return np.asarray(steps[:, 0]), np.asarray(laps[:, 0]), cache


def stepwise_decode(cfg, params, cache, forced):
    step = jax.jit(
        lambda p, t, c: llama.model_apply(cfg, p, t[None, None], c, ONE)
    )
    out = []
    for token in forced[:-1]:
        logits, cache = step(params, jnp.asarray(token, jnp.int32), cache)
        out.append(np.asarray(logits[0, 0]))
    return np.asarray(out), cache


def off(ours, gold):
    ours, gold = np.asarray(ours, np.float64), np.asarray(gold, np.float64)
    return float(
        np.max(np.linalg.norm(ours - gold, axis=-1)
               / np.linalg.norm(gold, axis=-1))
    )


#: what the float32 program may lie off the float32 reference (the order of
#: sums); every dropped term below lies a hundred times farther
TOLERANCE = 1e-4


# -- the block -------------------------------------------------------------------------


def test_the_catalogs_block_reads_whole_into_the_family():
    cfg = ModelConfig.from_hf_config(CATALOG)
    assert validate_config(cfg) is get_family("ouro")
    assert cfg.loop == LoopConfig(steps=4, exit_threshold=1.0)
    assert (cfg.num_layers, cfg.loop_steps, cfg.cache_layers) == (48, 4, 192)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert cfg.sliding_window is None and cfg.rope_theta == 1000000
    assert len(cfg.segments) == 1 and cfg.segments[0].key == "layers"
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))
    )
    assert shapes["layers"]["attn_out_norm"].shape == (48, 2048)
    assert shapes["layers"]["mlp_out_norm"].shape == (48, 2048)
    assert shapes["exit_w"].shape == (2048,) and shapes["exit_b"].shape == ()
    # a stack that runs once is what it always was
    plain = ModelConfig.from_hf_config({**CATALOG, "model_type": "mistral"})
    assert plain.loop is None and plain.cache_layers == plain.num_layers == 48


@pytest.mark.parametrize("key, value", [
    ("use_sliding_window", True),
    ("layer_types", ["sliding_attention"] + ["full_attention"] * 47),
])
def test_what_the_block_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**CATALOG, key: value})


@pytest.mark.parametrize("bad, what", [
    (dict(loop=LoopConfig(steps=0)), "at least one lap"),
    (dict(loop=LoopConfig(exit_threshold=1.5)), "at least one lap"),
    (dict(loop=None), "requires"),
    (dict(family="mistral"), "does not use"),
])
def test_the_registry_holds_the_family_to_its_switch(model, bad, what):
    _, cfg, _ = model
    with pytest.raises(ValueError, match=what):
        validate_config(dataclasses.replace(cfg, **bad))


def test_the_makers_gains_and_gate_are_not_the_trivial_ones(model):
    _, cfg, params = model
    for name in maker.NORMS:
        gain = np.asarray(params["layers"][name])
        centre = maker.OUT_GAIN.get(name, 1.0)
        assert gain.shape == (L, 64) and np.abs(gain - 1).max() > 0.1
        assert np.abs(gain / centre - 1).max() <= maker.GAIN_SPREAD + 1e-6
    assert np.abs(np.asarray(params["final_norm"]) - 1).max() > 0.1
    assert abs(float(params["exit_b"])) > 1e-3
    assert params["exit_w"].shape == (64,) and params["exit_b"].dtype == jnp.float32
    # the same tree the program initialises
    want = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    got = jax.eval_shape(lambda: params)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert jax.tree.leaves(want) == jax.tree.leaves(got)


# -- against the reference -------------------------------------------------------------


@pytest.mark.parametrize("laps", [1, 2, 4])
@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_prefill_then_decoding_through_the_cache_is_the_references_forward(kind, laps):
    hf, cfg, params = tiny_model(laps)
    prompt, forced = probe_tokens(laps)
    gold = np.asarray(reference.forward(hf, params, whole(prompt, forced)))
    cache = fresh_cache(cfg, kind)
    assert cache.layer_stacks[0].shape[0] == laps * L
    first, cache = prefill(cfg, params, cache, prompt)
    assert off(first[0, 0], gold[N - 1]) < TOLERANCE
    steps, took, _ = fused_decode(cfg, params, cache, forced)
    assert off(steps, gold[N:]) < TOLERANCE
    assert (took == laps - 1).all()      # the published threshold: the last lap
    # every position of a prefill, the head over all of them
    every, _ = prefill(cfg, params, fresh_cache(cfg, kind), prompt, head="all")
    assert off(every[0, :N], gold[:N]) < TOLERANCE


@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_the_fused_scan_is_one_token_a_dispatch(model, kind):
    _, cfg, params = model
    prompt, forced = probe_tokens(7)
    _, cache = prefill(cfg, params, fresh_cache(cfg, kind), prompt)
    fused, _, after_fused = fused_decode(cfg, params, cache, forced)
    single, after_single = stepwise_decode(cfg, params, cache, forced)
    assert off(fused, single) < TOLERANCE
    np.testing.assert_array_equal(after_fused.lengths, after_single.lengths)
    for a, b in zip(after_fused.layer_stacks, after_single.layer_stacks):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_prompt_in_two_chunks_is_the_prompt_in_one(model):
    _, cfg, params = model
    prompt, _ = probe_tokens(5)
    want, one = prefill(cfg, params, fresh_cache(cfg, "paged"), prompt)
    _, part = prefill(cfg, params, fresh_cache(cfg, "paged"), prompt[:12], head="none")
    got, two = prefill(cfg, params, part, prompt[12:])
    assert off(got[0, 0], want[0, 0]) < TOLERANCE
    # and what decodes from the two caches (beyond a row's length a page
    # holds whatever the pad positions left there)
    _, forced = probe_tokens(6)
    assert off(
        fused_decode(cfg, params, two, forced)[0],
        fused_decode(cfg, params, one, forced)[0],
    ) < TOLERANCE


@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_the_served_keys_are_the_references_lap_by_lap_entry_by_entry(model, kind):
    """The cache is ``laps x layers`` layers and not ``layers``: row ``t x L
    + l`` holds the keys lap ``t`` of layer ``l`` made, and a lap's differ
    from every other's."""
    hf, cfg, params = model
    prompt, forced = probe_tokens(9)
    tokens = whole(prompt, forced)
    _, keys, _, _ = reference.run(hf, params, tokens)   # [T, L, S, Hkv, d]
    keys = np.asarray(keys)
    _, cache = prefill(cfg, params, fresh_cache(cfg, kind), prompt)
    _, _, cache = fused_decode(cfg, params, cache, forced)
    total = N + STEPS
    assert int(cache.lengths[0]) == total
    if kind == "dense":
        served = np.asarray(cache.k)[:, 0, :total]          # [T*L, S, H, d]
    else:
        pages = np.asarray(cache.k_pages)[:, np.asarray(cache.page_table[0])]
        served = pages.transpose(0, 1, 3, 2, 4).reshape(
            cfg.cache_layers, -1, cfg.num_kv_heads, cfg.head_dim
        )[:, :total]
    assert served.shape[0] == 4 * L
    for t in range(4):
        for layer in range(L):
            np.testing.assert_allclose(
                served[t * L + layer], keys[t, layer], atol=2e-5
            )
    for t in range(1, 4):
        assert np.abs(served[t * L] - served[0]).max() > 0.1


@pytest.mark.parametrize("control", [
    dict(drop=("attn_out_norm",)), dict(drop=("mlp_out_norm",)),
    dict(drop=("lap_norm",)), dict(laps=3), dict(share_lap_kv=True),
], ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()))
def test_a_reference_with_a_term_dropped_is_told_apart(model, control):
    hf, cfg, params = model
    prompt, forced = probe_tokens(2)
    tokens = whole(prompt, forced)
    gold = np.asarray(reference.forward(hf, params, tokens))[N - 1:]
    broken = np.asarray(reference.run(hf, params, tokens, **control)[0])[N - 1:]
    first, cache = prefill(cfg, params, fresh_cache(cfg, "paged"), prompt)
    steps, _, _ = fused_decode(cfg, params, cache, forced)
    ours = np.concatenate([np.asarray(first[0]), steps])
    assert off(ours, gold) < TOLERANCE
    away = np.linalg.norm(ours - broken, axis=-1) / np.linalg.norm(broken, axis=-1)
    assert away.min() > 100 * TOLERANCE


@pytest.mark.parametrize("threshold, bias, laps_seen", [
    (0.9, 1.0, {1}),        # sigmoid(1) = .73, then .93: crosses at lap 1
    (0.6, 0.0, None),       # a wide gate: positions leave at different laps
    (1.0, 1.0, {3}),        # the published threshold: never before the last
])
def test_the_exit_selection_is_the_references(threshold, bias, laps_seen):
    hf, cfg, params = tiny_model(4, threshold)
    scale = 20.0 if laps_seen is None else 0.05
    params = {
        **params, "exit_b": jnp.float32(bias),
        "exit_w": params["exit_w"] * scale,
    }
    prompt, forced = probe_tokens(4)
    tokens = whole(prompt, forced)
    gold, _, at, gates = reference.run(hf, params, tokens)
    gold, at = np.asarray(gold), np.asarray(at)
    assert set(at.tolist()) == laps_seen or (
        laps_seen is None and len(set(at.tolist())) >= 3
    )
    every, cache = prefill(
        cfg, params, fresh_cache(cfg, "paged"), prompt, head="all"
    )
    assert off(every[0, :N], gold[:N]) < TOLERANCE
    steps, took, _ = fused_decode(cfg, params, cache, forced)
    assert off(steps, gold[N:]) < TOLERANCE
    np.testing.assert_array_equal(took, at[N:])
    # the laps' hidden states differ, so the lap chosen shows in the logits
    if laps_seen != {3}:
        last = reference.forward({**hf, "early_exit_threshold": 1.0}, params, tokens)
        assert off(gold, last) > 0.01


# -- the engine ------------------------------------------------------------------------


def make_engine(cfg, params, rows=4, pages=96, **kw):
    return InferenceEngine(
        cfg, params,
        EngineConfig(
            max_batch_size=rows, prefill_buckets=(8, 16, 32), max_seq_len=128,
            dtype="float32", prefill_chunk_tokens=32,
        ),
        CacheConfig(kind="paged", page_size=8, num_pages=pages,
                    max_pages_per_session=16, **kw.pop("cache", {})),
        trace_cfg=TraceConfig(), **kw,
    )


GREEDY = SamplingOptions(max_new_tokens=20, temperature=0.0, eos_token_id=-1)


def prompts_of(sizes, seed=4):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 256, size=n)] for n in sizes]


def greedy_agrees(hf, params, prompt, generated, slack=1e-3):
    """Every generated token is the reference's argmax, or within ``slack``
    of it (seeded weights tie now and then)."""
    tokens = jnp.asarray(list(prompt) + list(generated[:-1]), jnp.int32)
    logits = np.asarray(reference.forward(hf, params, tokens))[len(prompt) - 1:]
    return all(
        row.max() - row[tok] <= slack for row, tok in zip(logits, generated)
    )


@pytest.fixture(scope="module")
def served(model):
    """Four rows at once through ``InferenceEngine``, and the counters."""
    hf, cfg, params = model
    prompts = prompts_of((27, 9, 40, 21, 14))
    engine = make_engine(cfg, params)
    got = engine.generate(prompts, GREEDY)
    return prompts, got, engine


def test_continuous_batching_of_four_rows_is_solo_generation(model, served):
    hf, cfg, params = model
    prompts, got, engine = served
    assert engine.cache.k_pages.shape[0] == cfg.cache_layers == 12
    assert engine.decode_steps == 16 and engine._pipelined
    for prompt, tokens in zip(prompts, got):
        assert len(tokens) == 20 and greedy_agrees(hf, params, prompt, tokens)
    solo = make_engine(cfg, params, rows=1)
    for prompt, tokens in zip(prompts[:3], got):
        assert solo.generate([prompt], GREEDY) == [tokens]
    assert engine.allocator.free_count == 95


def test_the_engine_counts_what_the_laps_cost(model, served):
    _, cfg, params = model
    prompts, got, engine = served
    seen = engine.metrics.snapshot()
    layer_bytes = sum(x.nbytes for x in jax.tree.leaves(params["layers"]))
    assert engine.plan.loop == (4, L, layer_bytes)
    prompt_tokens, new = sum(map(len, prompts)), sum(map(len, got))
    # every prompt token and every decode row-step crosses laps x layers
    assert seen["loop_layer_passes"] % (4 * L) == 0
    assert seen["loop_layer_passes"] >= 4 * L * (prompt_tokens + new - len(prompts))
    assert seen["loop_weight_bytes_read"] % (4 * layer_bytes) == 0
    # a prompt of n tokens attends n (n + 1) / 2 positions a cache layer
    least = sum(n * (n + 1) // 2 for n in map(len, prompts))
    assert seen["loop_kv_positions_read"] % (4 * L) == 0
    assert seen["loop_kv_positions_read"] > 4 * L * least
    # from the device, beside the tokens: the last lap at the threshold of 1
    assert seen["loop_exit_positions"] == new - len(prompts)
    assert seen["loop_exit_lap_sum"] == 3 * seen["loop_exit_positions"]
    assert "loop_exit_lap_sum_total" in engine.metrics.prometheus()


def test_an_early_exit_shows_in_the_engines_counters():
    hf, cfg, params = tiny_model(4, 0.9)
    params = {**params, "exit_b": jnp.float32(1.0), "exit_w": params["exit_w"] * 0.05}
    engine = make_engine(cfg, params, rows=2)
    (prompt,) = prompts_of((11,))
    (got,) = engine.generate([prompt], GREEDY)
    assert greedy_agrees(hf, params, prompt, got)
    seen = engine.metrics.snapshot()
    assert seen["loop_exit_lap_sum"] == seen["loop_exit_positions"] == 19


def test_int8_keys_and_values_are_within_their_tolerance(model):
    """``kv_quant="int8"`` through the harness's own probe: the prefill
    position and the decode steps lie within 0.05 of the float32 reference
    (the limit ``tests/bench`` holds an int8 pool to) and farther than
    float32 rounding."""
    hf, cfg, params = model
    engine = make_engine(cfg, params, cache={"kv_quant": "int8"})
    assert type(engine.cache) is QuantizedPagedKVCache
    assert engine.cache.k_pages.shape[0] == 12
    prompt, forced = probe_tokens(11)
    gold = np.asarray(reference.forward(hf, params, whole(prompt, forced)))[N - 1:]
    slots = -(-(N + STEPS + 1) // 8) + 1
    first, decoded = bench_server.probe(
        engine, cfg, engine.params, [int(t) for t in prompt],
        [int(t) for t in forced], slots, jnp.float32,
    )
    away = np.linalg.norm(
        np.concatenate([first[None], decoded]) - gold, axis=-1
    ) / np.linalg.norm(gold, axis=-1)
    assert 1e-4 < np.median(away) and away.max() < 0.05


@pytest.mark.parametrize("tp", [2, 4])
def test_tensor_parallel_on_the_virtual_mesh_is_one_device(model, served, tp):
    _, cfg, params = model
    prompts, got, _ = served
    engine = make_engine(cfg, params, mesh_cfg=MeshConfig(tp=tp))
    assert engine.mesh.shape["tp"] == tp
    assert engine.cache.k_pages.sharding.spec[2] == "tp"
    assert engine.generate(prompts[:3], GREEDY) == got[:3]
    assert engine.metrics.snapshot()["loop_exit_lap_sum"] > 0


def test_the_dense_cache_serves_it_too(model, served):
    _, cfg, params = model
    prompts, got, _ = served
    engine = InferenceEngine(
        cfg, params,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16, 32, 64),
                     max_seq_len=128, dtype="float32"),
        CacheConfig(kind="dense"),
    )
    assert engine.cache.k.shape[0] == 12
    assert engine.generate(prompts[:2], GREEDY) == got[:2]


def test_the_harness_builds_its_probes_cache_from_the_engines_own(served):
    _, _, engine = served
    cache = bench_server.probe_cache(engine.cache, 5, 6, jnp.float32)
    assert type(cache) is type(engine.cache)
    assert cache.k_pages.shape == (12, 6, 4, 8, 16)


# -- what passes a hidden state down once ----------------------------------------------


@pytest.mark.parametrize("where", ["pp", "draft", "looped-draft", "decoder-draft", "block"])
def test_what_passes_the_hidden_state_down_once_is_refused(model, where):
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend
    from distributed_llm_inference_tpu.engine.speculative import (
        SpeculativeDecoder,
    )

    _, cfg, params = model
    plain = ModelConfig.from_hf_config({**tiny_hf(), "model_type": "llama"})
    plain_params = llama.init_params(plain, jax.random.PRNGKey(0), jnp.float32)
    build = {
        "pp": lambda: make_engine(cfg, params, mesh_cfg=MeshConfig(pp=2)),
        "draft": lambda: make_engine(cfg, params, draft=(plain, plain_params)),
        "looped-draft": lambda: InferenceEngine(
            plain, plain_params,
            EngineConfig(max_batch_size=2, max_seq_len=64, dtype="float32",
                         speculative_k=2),
            CacheConfig(kind="dense"), draft=(cfg, params),
        ),
        "decoder-draft": lambda: SpeculativeDecoder(
            plain, plain_params, cfg, params
        ),
        # ``serve --layers 0:1``: a relay node's block short of the stack
        "block": lambda: BlockBackend(cfg, params["layers"], 0, 1),
    }[where]
    match = r"'ouro'.*ModelConfig\.loop" + (
        "" if where == "draft" else ".*" + MECHANISM
    )
    with pytest.raises(ValueError, match=match):
        build()


def test_a_staged_block_function_and_the_converter_refuse_it(model):
    _, cfg, params = model
    cache = fresh_cache(cfg, "dense")
    with pytest.raises(ValueError, match=MECHANISM):
        llama.model_apply(
            cfg, params, jnp.zeros((1, 8), jnp.int32), cache, 8 * ONE,
            block_fn=lambda *a: None,
        )
    with pytest.raises(ValueError, match="looped block"):
        llama.convert_hf_state_dict(cfg, {})
    with pytest.raises(ValueError, match="sp ring prefill"):
        make_engine(cfg, params, mesh_cfg=MeshConfig(sp=2))


# -- the trace's names -----------------------------------------------------------------


def test_a_decode_step_and_a_prefill_carry_the_laps_scopes(model):
    _, cfg, params = model
    cache = fresh_cache(cfg, "paged")
    forced = jnp.arange(1, 4, dtype=jnp.int32)
    decode = jax.jit(lambda p, c: llama.multi_decode_apply(
        cfg, p, forced[:1][None], c, 2,
        lambda i, logits, st: (forced[i + 1][None], ONE, st, logits),
        jnp.zeros(()), ONE,
    )).lower(params, cache).as_text(debug_info=True)
    fill = jax.jit(lambda p, c: llama.model_apply(
        cfg, p, jnp.zeros((1, 8), jnp.int32), c, 8 * ONE, head="last"
    )).lower(params, cache).as_text(debug_info=True)
    for text in (decode, fill):
        for scope in ("dense_stack", "loop_lap", "attention", "mlp",
                      "loop_exit", "head"):
            assert scope in text, scope


# -- the benchmark's counts and readers ------------------------------------------------


def conf_file():
    import json
    import pathlib

    return json.loads(
        (pathlib.Path(bench_server.REPO) / "benchmark/configs/ouro-2.6b.json").read_text()
    )


def test_the_configuration_file_carries_the_catalogs_block_whole():
    conf = conf_file()
    assert bench_server.hf_block(conf) == CATALOG
    assert conf["reduced"] == {} and conf["serve"]["weights"] == "bf16"
    assert set(conf["assumed"]) >= {
        "sandwich_norms", "final_norm_feeds_the_next_lap", "exit_gate",
        "every_lap_runs",
    }
    tiny = bench_server.load_config(
        str(pathlib_path("benchmark/configs/ouro-2.6b.json")), True
    )
    cfg = ModelConfig.from_hf_config(bench_server.hf_block(tiny))
    # the rehearsal keeps four laps and a group of one query a kv head
    assert cfg.loop_steps == 4 and cfg.num_heads == cfg.num_kv_heads == 4
    # the table is never narrower than the in-place sweep's least: the
    # gathered form under it is 2 x 2.25 GB at 192 cache layers and 16 rows
    # of 6 pages, and does not compile beside the weights and the pool
    ladder = conf["serve"]["engine"]["decode_windows"]
    assert min(ladder) >= QuantizedPagedKVCache.INPLACE_CTX
    assert max(ladder) == conf["serve"]["engine"]["max_seq_len"]


def pathlib_path(rel):
    import pathlib

    return pathlib.Path(bench_server.REPO) / rel


def test_the_yardsticks_count_the_laps_and_the_cache_layers():
    from benchmark import flops_looped_gqa as count

    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert count.layer_parameters(CATALOG) == layer == 51_380_224
    assert count.laps(CATALOG) == 4 and count.cache_layers(CATALOG) == 192
    # a position in the int8 pool: 2 x 16 x (128 + 4) B a cache layer
    assert count.kv_bytes_per_position(CATALOG, 1 + 4 / 128) == 192 * 4224 == 811_008
    assert count.kv_bytes_per_position(CATALOG, 2.0) == 192 * 8192
    head = 2048 * 49152
    assert count.stored_weight_bytes(CATALOG, 2.0) == (4 * 48 * layer + head) * 2
    assert count.layers_bytes(CATALOG, 2.0) == 48 * layer * 2
    # the head once, the gate once a lap, attention in every cache layer
    token = count.decode_token_flops(CATALOG, 400)
    assert token == (
        4 * (48 * (2 * layer + 4 * 16 * 128 * 400) + 2 * 2048) + 2 * head
    )
    assert count.prompt_flops(CATALOG, 1) == count.token_flops(CATALOG, 1.0) + 2 * head
    once = count.token_flops({**CATALOG, "total_ut_steps": 1}, 400)
    assert count.token_flops(CATALOG, 400) == 4 * once


def fake_run(**over):
    import types

    conf = conf_file()
    run = types.SimpleNamespace(
        conf=conf, seconds=45.0, cell={"chips": 1, "name": "ouro-2.6b.mathchat"},
        device={"kind": "TPU v5 lite"}, metrics_open={}, metrics_close={},
        ticks={}, closed={}, shapes={"decode_steps": 16}, records=[],
        t0=100.0, epoch_offset=0.0,
    )
    for k, v in over.items():
        setattr(run, k, v)
    return run


def test_the_counter_readers_weigh_the_laps_bytes():
    from benchmark import flops_looped_gqa as count
    from benchmark.layer_metrics import (
        loop_kv_share_of_step_bytes_pct as share,
        looped_gqa_hbm_util_pct as util,
    )

    assert util.read(fake_run()) is None and share.read(fake_run()) is None
    steps, rows, context = 1000, 16, 400
    weights = count.laps(CATALOG) * count.layers_bytes(CATALOG, 2.0) * steps
    positions = 192 * rows * context * steps
    run = fake_run(metrics_close={
        "loop_weight_bytes_read": weights, "loop_kv_positions_read": positions,
        "engine_decode_steps": steps, "decode_tokens": rows * steps,
    })
    kv = rows * context * steps * 811_008
    head = 2048 * 49152 * 2 * steps
    assert util.kv_bytes(run) == pytest.approx(kv)
    assert util.bytes_read(run) == pytest.approx(weights + kv + head)
    assert util.read(run) == pytest.approx(
        100 * (weights + kv + head) / (45 * 819e9)
    )
    assert share.read(run) == pytest.approx(100 * kv / (weights + kv + head))
    assert 19 < share.read(run) < 24
    # without the dispatch clock's count: tokens over the mean occupied rows
    del run.metrics_close["engine_decode_steps"]
    run.ticks = {1: {"t": 110.0, "occupancy": rows}}
    assert util.decode_steps(run) == steps


def test_the_mfu_reader_counts_each_token_at_its_context():
    import types

    from benchmark import flops_looped_gqa as count
    from benchmark.layer_metrics import looped_gqa_mfu_pct as mfu

    rec = types.SimpleNamespace(
        prompt_len=200, first_t=101.0, arrivals=[101.0, 101.5, 102.0, 146.0]
    )
    run = fake_run(records=[rec])
    want = (
        count.prompt_flops(CATALOG, 200)
        + count.decode_token_flops(CATALOG, 202)
        + count.decode_token_flops(CATALOG, 203)     # the fourth: past the window
    )
    assert mfu.read(run) == pytest.approx(100 * want / (45 * 197e12))
    run.conf = {**run.conf, "model_type": "mistral"}
    assert mfu.read(run) is None


def test_the_trace_readers_count_a_call_a_cache_layer_and_a_lap_a_step():
    from benchmark.kernels import quantized_paged_fused_attention as kernel
    from benchmark.layer_metrics import (
        loop_lap_device_ms as lap, looped_decode_attn_roofline_pct as roofline,
    )

    assert roofline.read(fake_run()) is None and lap.read(fake_run()) is None
    positions, dispatches = 16 * 400, 3
    calls = dispatches * 16 * 192
    trace = {
        "kernels_device0": {roofline.KERNEL: {"count": calls, "sum_s": 0.5}},
        "modules_device0_s": {"jit__decode_scan": [0.64, 0.66, 0.70]},
    }
    ticks = {
        i: {"t0_ns": (10 + i) * 1e9, "dispatches": [
            ["decode", [16, 16, 15], positions], ["prefill", [1, 512], 300],
        ]} for i in range(dispatches)
    }
    run = fake_run(closed={"trace": trace, "trace_epoch_s": [9.0, 20.0]}, ticks=ticks)
    least = calls * kernel.bytes_read(CATALOG, positions) / 819e9
    assert kernel.bytes_read(CATALOG, positions) == positions * 4224
    assert roofline.read(run) == pytest.approx(100 * least / 0.5)
    assert lap.read(run) == pytest.approx(0.66e3 / 16 / 4)
    # 48 calls a step is another model's count: nothing, not a wrong share
    trace["kernels_device0"][roofline.KERNEL]["count"] = dispatches * 16 * 48
    assert roofline.read(run) is None
    run.conf = {k: v for k, v in run.conf.items() if k != "total_ut_steps"}
    assert lap.read(run) is None


def test_the_benchmark_gains_the_configuration_the_cell_and_five_readers():
    import importlib
    import json

    bench = json.loads(pathlib_path("BENCHMARK.json").read_text())
    (config,) = [c for c in bench["configs"] if c["name"] == "ouro-2.6b"]
    assert config["reduced"] == [] and config["file"] == "benchmark/configs/ouro-2.6b.json"
    (cell,) = [w for w in bench["workloads"] if w["config"] == "ouro-2.6b"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b.mathchat", "mathchat-closed16", 1
    )
    traffic = json.loads(pathlib_path("benchmark/traffic/mathchat-closed16.json").read_text())
    assert traffic["clients"] == conf_file()["serve"]["engine"]["max_batch_size"]
    assert (traffic["prompt"]["min"], traffic["prompt"]["max"]) == (128, 384)
    assert (traffic["output"]["min"], traffic["output"]["max"]) == (192, 384)
    mine = [e for e in bench["per_layer"] if e.get("workloads") == [cell["name"]]]
    assert {e["name"] for e in mine} == {
        "looped_gqa_mfu_pct", "looped_gqa_hbm_util_pct",
        "looped_decode_attn_roofline_pct", "loop_kv_share_of_step_bytes_pct",
        "loop_lap_device_ms",
    }
    for entry in mine:
        reader = importlib.import_module(f"benchmark.layer_metrics.{entry['name']}")
        assert entry["moves"] == "tpot_ms_p50" and reader.LAYER == entry["layer"]
        assert reader.DEVICE_METRIC == (entry["source"] != "program_counter") or (
            entry["name"] == "looped_gqa_hbm_util_pct"
        )
