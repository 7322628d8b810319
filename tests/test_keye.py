"""Keye-VL-2.0-30B-A3B's language model (``KeyeVL2``: GQA under a learned
top-k key selection, per-head q/k norms, softmax-routed experts) on the
engine's normal path, at a small size on the CPU, against the benchmark's
plain reference ``benchmark/reference/keye_gqa_dsa_moe.py`` (``lax.top_k``
over float32 scores, one sequence, no cache), which shares no code with the
program.

Size: the configuration file's rehearsal overlay: 3 layers, 4 query / 2 kv
heads of 16, an indexer of 4 heads of 8, ``topk`` 12, 8 experts of which 3 a
token. Every case puts MORE than ``topk`` positions before the judged ones,
so the selection is a real choice.

Tolerances, with their reasons:

* float32 weights, activations, pool and index plane: only the order of
  sums differs (the program scores a block of queries against gathered
  index keys and selects by bisection; the reference sorts): the relative
  distance of the logits reads 2e-7 to 4e-7 (``TOLERANCE`` 1e-4, as
  ``tests/bench/test_benchmark_reference.py``).
* the int8 K and V pool (the index plane stays in the model's dtype): the
  MEDIAN of the 17 positions reads 0.008 to 0.012, twenty times over the
  tolerance and under 0.02: the pool's rounding. Single positions read 0.04
  to 0.23: a selection is a hard choice of 12 of 30 to 46 and a route of 3
  of 8, and the pool's rounding moves a near-tie in 3 to 5 of the 17 (the
  configuration's own probe, another prompt, is held to 0.05 at EVERY step
  by tests/bench). The dense path on the same weights reads 0.3 at the
  median: what the median must tell apart.
* the fused 16-step scan against 16 one-token steps over the same int8
  pool: the window's K and V sit in an unquantised tail until its flush
  (as the parent cache's do), so steps inside a window differ by the
  rounding of up to 15 keys: 0.001 to 0.003 at the median (limit 0.005),
  with at most 3 of 16 positions past 0.02 (a near-tie moved: 0.06 to
  0.16).
* one prefill against the same prompt in chunks, float32: the same sums in
  another order, 1e-4.
"""

import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import server
from benchmark.reference import keye_gqa_dsa_moe as reference
from distributed_llm_inference_tpu.cache.paged import (
    IndexedQuantizedPagedKVCache, PagedKVCache, QuantizedPagedKVCache,
    indexed_cache_class,
)
from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, MeshConfig, ModelConfig, PrefixConfig,
    SparseAttentionConfig, TraceConfig,
)
from distributed_llm_inference_tpu.disagg.kv_codec import (
    decode_kv, decode_session, encode_kv, encode_session,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.plan import AttentionPlan
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import validate_config
from distributed_llm_inference_tpu.ops import moe
from distributed_llm_inference_tpu.ops import paged_attention as pa
from distributed_llm_inference_tpu.ops.sparse_attention import select_topk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "keye-vl2-30b-a3b.json")
TOLERANCE = 1e-4
PS, INDEX_DIM = 8, 8


def tiny(**over):
    conf = server.load_config(CONFIG, rehearse=True)
    conf.update(over)
    return conf


def model(conf=None, **cfg_over):
    conf = conf or tiny()
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    cfg = dataclasses.replace(cfg, **cfg_over)
    maker = importlib.import_module("benchmark.weights.keye_gqa_dsa_moe")
    sized = dataclasses.replace(cfg, sparse=cfg.sparse or SparseAttentionConfig(4, 8, 12))
    return cfg, maker.make(sized, 5, jnp.float32, "float32")


def engine_for(conf=None, kv_quant=None, kernel=False, cache_over=None, **kw):
    conf = conf or tiny()
    cfg, params = model(conf)
    ekw = dict(conf["serve"]["engine"])
    ekw["prefill_buckets"] = tuple(ekw["prefill_buckets"])
    if kernel:
        ekw["use_pallas_attention"] = True
    ekw.update(kw.pop("engine_over", {}))
    cache = {**conf["serve"]["cache"], "kv_quant": kv_quant, **(cache_over or {})}
    return InferenceEngine(
        cfg, params, EngineConfig(dtype="float32", **ekw), CacheConfig(**cache),
        **kw,
    )


def rel(x, y):
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    return float(np.linalg.norm(x - y) / np.linalg.norm(y))


def gold(conf, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(
            reference.forward(server.hf_block(conf), params, jnp.asarray(tokens))
        )


def one_row_cache(quantized, pages=8, **flags):
    return indexed_cache_class(quantized, INDEX_DIM).create(
        3, 1, pages + 1, PS, pages, 2, 16, jnp.float32, **flags
    ).assign_pages(0, list(range(1, pages + 1)))


def walk(cfg, params, tokens, cache, chunks, steps):
    """Logits of the last prompt position and of ``steps`` one-token steps
    (teacher-forced), the prompt prefilled in ``chunks`` pieces."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0] - steps
    out, at = [], 0
    for size in chunks:
        logits, cache = llama.model_apply(
            cfg, params, tokens[None, at:at + size], cache,
            jnp.asarray([size], jnp.int32), head="last",
        )
        at += size
    assert at == n
    out.append(logits[0, 0])
    for i in range(steps):
        logits, cache = llama.model_apply(
            cfg, params, tokens[None, n + i - 1 + 1:n + i + 1], cache,
            jnp.ones((1,), jnp.int32),
        )
        out.append(logits[0, 0])
    return np.stack(out), cache


TOKENS = np.random.default_rng(7).integers(1, 256, size=46).tolist()


# -- the system against the reference ----------------------------------------


@pytest.mark.parametrize("pool", ["float32", "int8"])
def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(pool):
    """30 prompt tokens and 16 steps through the indexed cache, ``topk`` 12:
    every judged position selects 12 of 30 to 46."""
    conf = tiny()
    cfg, params = model(conf)
    assert cfg.sparse.topk == 12 < 30
    ours, _ = walk(cfg, params, TOKENS, one_row_cache(pool == "int8"), [30], 16)
    want = gold(conf, params, TOKENS)[29:]
    dist = [rel(o, g) for o, g in zip(ours, want[: len(ours)])]
    if pool == "float32":
        assert max(dist) < TOLERANCE, dist
    else:
        assert 5 * TOLERANCE < np.median(dist) < 0.02, dist


@pytest.mark.parametrize("chunks", [[16, 14], [8, 8, 14], [24, 6]])
def test_a_chunk_boundary_inside_the_selected_range_changes_nothing(chunks):
    """A later chunk's queries select among every earlier chunk's keys (the
    boundary at 16, 8 or 24 lies inside the 30 positions the last queries
    choose their 12 from): the same logits as one prefill, and the
    reference's."""
    conf = tiny()
    cfg, params = model(conf)
    whole, _ = walk(cfg, params, TOKENS[:34], one_row_cache(False), [30], 4)
    parts, _ = walk(cfg, params, TOKENS[:34], one_row_cache(False), chunks, 4)
    want = gold(conf, params, TOKENS[:34])[29:]
    assert max(rel(p, w) for p, w in zip(parts, whole[:5])) < TOLERANCE
    assert max(rel(p, g) for p, g in zip(parts, want)) < TOLERANCE


@pytest.mark.parametrize("kernel", [False, True], ids=["xla-tail", "kernel"])
def test_the_fused_scan_agrees_with_sixteen_one_token_steps(kernel):
    """``multi_decode_apply`` over the indexed int8 cache (the write-behind
    tail with its index tail; with ``kernel`` the fused in-place sweep under
    the selection and both flush kernels, interpreted) against 16
    ``model_apply`` steps over the same cache, and against the reference."""
    conf = tiny()
    cfg, params = model(conf)
    flags = {"use_kernel": True, "use_ragged": True} if kernel else {}
    toks = jnp.asarray(TOKENS, jnp.int32)
    _, cache = walk(cfg, params, TOKENS[:30], one_row_cache(True, **flags), [30], 0)
    forced = toks[29:46]
    one = jnp.ones((1,), jnp.int32)
    fused, flushed = llama.multi_decode_apply(
        cfg, params, forced[1:2][None], cache, 16,
        lambda i, logits, st: (forced[jnp.minimum(i + 2, 16)][None], one, st, logits),
        jnp.zeros(()), one,
    )
    stepped, stepped_cache = walk(
        cfg, params, TOKENS, one_row_cache(True, **flags), [30], 16
    )
    want = gold(conf, params, TOKENS)[30:]
    apart = [rel(f, s) for f, s in zip(fused[:, 0], stepped[1:])]
    assert np.median(apart) < 0.005 and sum(d > 0.02 for d in apart) <= 3, apart
    assert np.median([rel(f, g) for f, g in zip(fused[:, 0], want)]) < 0.02
    # the flush writes where the one-token path writes, and (to the K and V
    # tail's rounding, which later layers' hidden states carry) what
    np.testing.assert_array_equal(flushed.lengths, stepped_cache.lengths)
    a, b = np.asarray(flushed.ik_pages), np.asarray(stepped_cache.ik_pages)
    np.testing.assert_array_equal(a != 0, b != 0)
    np.testing.assert_allclose(a, b, atol=0.05)


@pytest.mark.parametrize("step", [0, 3], ids=["step0", "stepKT-1"])
@pytest.mark.parametrize("g", [4, 1], ids=["G4", "G1"])
def test_the_sweep_under_a_selection_attends_a_full_block_as_one_tile(g, step):
    """The selection form of the fused in-place kernel
    (``sparse_paged_fused_attention``) over the rows of
    ``tests/test_paged_attention.py``: exactly ``n``, ``n + 1``, ``2n`` and
    ``2n + 3`` live pages among them, so a full block's pages, scale rows
    AND selection rows lie side by side in one tile; every page no live
    token owns poisoned (NaN scale rows, +-127 values), a selection that
    keeps about half of every row's positions, some rows' none."""
    from test_paged_attention import _fused_inputs, _fused_oracle, _fused_rows

    a = _fused_inputs(seed=11 + step, g=g, step=step, select=True)
    out, *tails = pa.quantized_paged_fused_attention(
        **a, name="sparse_paged_fused_attention"
    )
    ref, want = _fused_oracle(a, None)
    out = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(out).all(), "a dead page was read"
    _, active, _ = _fused_rows()
    assert (out[~active] == 0).all()
    np.testing.assert_allclose(out, np.asarray(ref), atol=0.03, rtol=0.02)
    for got, exact in zip(tails, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exact))


@pytest.mark.parametrize("pool", ["float32", "int8"])
def test_topk_at_least_the_context_is_dense_causal_attention(pool):
    """With every position selected the result is the dense GQA path's on
    the same weights: the plain paged cache, no index plane, no mask."""
    conf = tiny()
    cfg, params = model(conf)
    wide = dataclasses.replace(
        cfg, sparse=dataclasses.replace(cfg.sparse, topk=64)
    )
    dense = dataclasses.replace(cfg, sparse=None)
    cls = QuantizedPagedKVCache if pool == "int8" else PagedKVCache
    plain = cls.create(3, 1, 9, PS, 8, 2, 16, jnp.float32).assign_pages(
        0, list(range(1, 9))
    )
    ours, _ = walk(wide, params, TOKENS, one_row_cache(pool == "int8"), [30], 16)
    theirs, _ = walk(dense, params, TOKENS, plain, [30], 16)
    assert max(rel(o, t) for o, t in zip(ours, theirs)) < TOLERANCE
    narrow, _ = walk(cfg, params, TOKENS, one_row_cache(pool == "int8"), [30], 16)
    assert min(rel(n, t) for n, t in zip(narrow, theirs)) > 0.01


@pytest.mark.parametrize("seed,n,k", [(0, 40, 12), (1, 64, 1), (2, 33, 33), (3, 50, 60)])
def test_the_selection_is_exactly_the_k_largest_with_ties_to_the_lower_index(seed, n, k):
    rng = np.random.default_rng(seed)
    scores = rng.normal(size=(3, n)).astype(np.float32)
    scores[:, ::5] = 0.0                      # ties, and zeros of both signs
    scores[:, 10] = -0.0
    scores[1, ::3] = scores[1, 1]
    valid = rng.random((3, n)) < 0.8
    got = np.asarray(select_topk(jnp.asarray(scores), jnp.asarray(valid), k))
    for row in range(3):
        order = sorted(
            (i for i in range(n) if valid[row, i]),
            key=lambda i: (-float(scores[row, i]), i),
        )
        assert sorted(np.flatnonzero(got[row])) == sorted(order[:k])


# -- the index plane travels wherever planes travel ----------------------------


def test_copy_on_write_and_page_round_trips_carry_the_index_plane():
    conf = tiny()
    cfg, params = model(conf)
    for quantized in (False, True):
        _, cache = walk(cfg, params, TOKENS[:30], one_row_cache(quantized), [30], 0)
        assert "ik" in cache.PLANE_FIELDS and np.abs(np.asarray(cache.ik_pages[:, 2])).max() > 0
        copied = cache.copy_page(7, 2)
        for f in cache.PLANE_FIELDS.values():
            np.testing.assert_array_equal(
                np.asarray(getattr(copied, f)[:, 7]), np.asarray(getattr(cache, f)[:, 2])
            )
        tiles = cache.read_page(2)
        assert tiles["ik"].shape == (3, 1, PS, INDEX_DIM)
        again = cache.write_page(6, tiles)
        np.testing.assert_array_equal(
            np.asarray(again.ik_pages[:, 6]), np.asarray(cache.ik_pages[:, 2])
        )
        with pytest.raises(ValueError):
            cache.write_page(6, {k: v for k, v in tiles.items() if k != "ik"})


PROMPT_A = list(range(1, 30))
PROMPT_B = list(range(50, 90))


def drain(engine, gid, until=None):
    got = []
    for _ in range(400):
        for g, tok, fin in engine.step():
            if g == gid and tok >= 0:
                got.append(tok)
            if g == gid and fin:
                return got
        if until is not None and len(got) >= until:
            return got
    raise AssertionError("the generation did not end")


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_a_prefix_store_round_trip_carries_the_index_plane(kv_quant):
    """Prefix pages evicted to the host arena and reloaded: the streams are
    those of an engine that shares nothing, so the reloaded pages select
    what the first run's selected."""
    opts = SamplingOptions(max_new_tokens=4, eos_token_id=-1)
    spilling = engine_for(
        kv_quant=kv_quant,
        cache_over={"prefix_caching": True, "num_pages": 8},
        prefix_cfg=PrefixConfig(spill_bytes_max=1 << 20),
    )
    a = spilling.generate([PROMPT_A], opts)[0]
    b = spilling.generate([PROMPT_B], opts)[0]
    assert spilling.metrics.snapshot().get("prefix_spilled_pages", 0) >= 1
    a2 = spilling.generate([PROMPT_A], opts)[0]
    snap = spilling.metrics.snapshot()
    assert snap.get("prefix_spill_reloads", 0) >= 1
    assert snap.get("prefix_reload_errors", 0) == 0
    alone = engine_for(kv_quant=kv_quant)
    assert [a, b, a2] == [
        alone.generate([p], opts)[0] for p in (PROMPT_A, PROMPT_B, PROMPT_A)
    ]


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_the_disagg_codec_ships_the_index_plane(kv_quant):
    opts = SamplingOptions(max_new_tokens=6)
    base = engine_for(kv_quant=kv_quant).generate([PROMPT_A], opts)[0]
    src, dst = engine_for(kv_quant=kv_quant), engine_for(kv_quant=kv_quant)
    planes, first, chain = src.prefill_export(list(PROMPT_A), opts)
    assert planes["ik"].shape == (3, len(PROMPT_A), 1, INDEX_DIM)
    frames = encode_kv("ship", planes, len(PROMPT_A), first, chain,
                       page_size=PS, quant="ks" in planes, max_frame_bytes=2048)
    dec, meta = decode_kv(frames)
    gid = dst.admit_prefilled(list(PROMPT_A), dec, meta["first_token"], options=opts)
    assert drain(dst, gid) == base
    without = {k: v for k, v in dec.items() if k != "ik"}
    with pytest.raises(ValueError, match="cache family"):
        engine_for(kv_quant=kv_quant).admit_prefilled(
            list(PROMPT_A), without, meta["first_token"], options=opts)


@pytest.mark.parametrize("kv_quant,kernel", [(None, False), ("int8", False), ("int8", True)])
def test_a_row_preempted_and_resumed_selects_what_it_selected(kv_quant, kernel):
    """A session checkpointed mid-decode, shipped through the codec and
    resumed on a fresh engine continues the uninterrupted stream."""
    opts = SamplingOptions(max_new_tokens=40)
    base_engine = engine_for(kv_quant=kv_quant, kernel=kernel)
    base = drain(base_engine, base_engine.submit(list(PROMPT_A), opts))
    victim = engine_for(kv_quant=kv_quant, kernel=kernel)
    gid = victim.submit(list(PROMPT_A), opts)
    drain(victim, gid, until=6)
    snap = victim.export_session(gid)
    assert "ik" in snap["planes"]
    snap2, _ = decode_session(encode_session("mig", snap, page_size=PS))
    fresh = engine_for(kv_quant=kv_quant, kernel=kernel)
    assert snap["generated"] + drain(fresh, fresh.resume_session(snap2)) == base


# -- the engine's path, its census, and what a dense engine keeps --------------


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "no-kernel"])
def test_the_indexed_int8_engine_decodes_sixteen_steps_a_dispatch(kernel):
    """The indexed int8 cache inherits the tail protocol, so the engine
    resolves ``decode_steps`` 16 with pipelined ticks, with and without the
    kernels; the census counts selected and live keys exactly."""
    engine = engine_for(kv_quant="int8", kernel=kernel, trace_cfg=TraceConfig())
    assert isinstance(engine.cache, IndexedQuantizedPagedKVCache)
    assert type(engine.cache) is indexed_cache_class(True, INDEX_DIM)
    assert engine.decode_steps == 16 and engine._pipelined
    engine.flight.clock.lease(600.0)  # watched: the dispatch clock counts
    out = engine.generate(
        [list(range(1, 12)), list(range(3, 40))], SamplingOptions(max_new_tokens=20)
    )
    assert [len(o) for o in out] == [20, 20]
    records = [d for t in engine.flight.snapshot() for d in t.get("dispatches", ())]
    assert {d[1][1] for d in records if d[0] == "decode"} == {16}
    decoded = engine.metrics.get_counter("engine_dispatches_decode")
    assert engine.metrics.get_counter("engine_decode_steps") == 16 * decoded > 0
    assert all(len(d) == 4 and d[3][0] <= d[3][1] for d in records)
    selected = engine.metrics.get_counter("sparse_keys_selected")
    live = engine.metrics.get_counter("sparse_keys_live")
    assert sum(d[3][0] for d in records) == selected
    assert sum(d[3][1] for d in records) == live
    assert 0 < selected < live
    # the 11- and 37-token prompts' prefills alone, by hand
    assert sum(min(12, t + 1) for n in (11, 37) for t in range(n)) <= selected


@pytest.mark.parametrize("spans,topk", [
    ([(0, 30)], 12), ([(16, 14), (5, 1)], 12), ([(2047, 3)], 2048), ([(0, 0), (7, 16)], 4),
])
def test_the_census_of_selected_keys_is_the_sum_it_says(spans, topk):
    plan = AttentionPlan(EngineConfig(), CacheConfig())
    plan.sparse_topk = topk
    want_live = sum(t + 1 for s, n in spans for t in range(s, s + n))
    want_sel = sum(min(topk, t + 1) for s, n in spans for t in range(s, s + n))
    assert plan._sparse_keys(spans) == (want_sel, want_live)


def test_a_dense_engines_pool_has_no_index_plane_and_its_kernel_call_is_unchanged():
    conf = tiny()
    cfg, params = model(conf, sparse=None)
    dense = InferenceEngine(
        cfg, params,
        EngineConfig(dtype="float32", max_batch_size=2, max_seq_len=64,
                     prefill_buckets=(8, 16)),
        CacheConfig(kind="paged", kv_quant="int8", page_size=8, num_pages=16,
                    max_pages_per_session=8),
    )
    assert type(dense.cache) is QuantizedPagedKVCache
    assert not hasattr(dense.cache, "ik_pages")
    assert set(dense.cache.PLANE_FIELDS) == {"k", "v", "ks", "vs"}
    assert dense.plan.sparse_topk is None

    # the fused decode kernel without a selection: the operands it had
    pool = (jnp.zeros((2, 4, 2, 8, 16), jnp.int8), jnp.zeros((2, 4, 2, 8), jnp.float32))
    tail = (jnp.zeros((2, 1, 2, 16, 16), jnp.int8), jnp.zeros((2, 1, 2, 16), jnp.float32))
    args = (
        jnp.zeros((1, 1, 4, 16)), jnp.zeros((1, 1, 2, 16)), jnp.zeros((1, 1, 2, 16)),
        *pool, *pool, *tail, *tail, jnp.int32(0), jnp.int32(0),
        jnp.zeros((1, 3), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
    )
    plain = jax.make_jaxpr(pa.quantized_paged_fused_attention)(*args)
    call = next(e for e in plain.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert len(call.invars) == 6 + 1 + 2 + 4 + 4       # scalars, q, fresh, tails, pools
    assert "sparse" not in str(plain) and "index" not in call.params["name"]
    select = (jnp.ones((1, 3, 1, 8)), jnp.ones((1, 1, 16)))
    masked = jax.make_jaxpr(
        lambda *a: pa.quantized_paged_fused_attention(*a, select=select)
    )(*args)
    call = next(e for e in masked.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert len(call.invars) == 6 + 1 + 2 + 4 + 4 + 2


def test_a_dispatch_wider_than_2048_walks_the_dense_combine_in_blocks(monkeypatch):
    """Past ``DENSE_COMBINE_TOKENS`` the experts see a block of tokens at a
    time: the same numbers, a quarter of the transient."""
    cfg, params = model()
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, cfg.hidden_size))
    whole = moe.moe_mlp(cfg, lp, x)
    monkeypatch.setattr(moe, "DENSE_COMBINE_TOKENS", 16)
    monkeypatch.setattr(moe, "DENSE_COMBINE_BLOCK", 8)
    np.testing.assert_allclose(moe.moe_mlp(cfg, lp, x), whole, rtol=1e-5, atol=1e-7)


# -- the configuration's keys ---------------------------------------------------


def published_block():
    import json

    with open(CONFIG) as f:
        conf = json.load(f)
    return server.hf_block(conf)


def test_from_hf_config_reads_the_published_block():
    cfg = ModelConfig.from_hf_config(published_block())
    assert cfg.family == "keye_vl2" and validate_config(cfg).name == "keye_vl2"
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert cfg.sparse == SparseAttentionConfig(16, 64, 2048) and cfg.qk_norm
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (128, 8)
    assert cfg.expert_intermediate_size == 768 and cfg.moe_norm_topk
    assert cfg.moe_scoring == "softmax" and cfg.num_shared_experts == 0
    assert cfg.sliding_window is None and cfg.vocab_size == 151936
    assert [s.kind for s in cfg.segments] == ["moe"]


@pytest.mark.parametrize("key,value", [
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("use_sliding_window", True),
])
def test_from_hf_config_refuses_by_name_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**published_block(), key: value})


def test_an_indexer_of_more_than_one_key_head_is_refused():
    block = published_block()
    block["sa_config"] = {**block["sa_config"], "indexer_num_kv_heads": 2}
    with pytest.raises(ValueError, match="indexer_num_kv_heads"):
        ModelConfig.from_hf_config(block)


def test_the_checkpoint_converter_refuses_the_family_by_name():
    cfg = ModelConfig.from_hf_config(published_block())
    with pytest.raises(ValueError, match="keye_vl2"):
        llama.convert_hf_state_dict(cfg, {})


@pytest.mark.parametrize("family", ["llama", "mistral", "mixtral", "mla"])
def test_validate_config_refuses_the_selection_and_the_norms_elsewhere(family):
    base = ModelConfig(family=family)
    with pytest.raises(ValueError, match="selection"):
        validate_config(dataclasses.replace(base, sparse=SparseAttentionConfig()))
    with pytest.raises(ValueError, match="qk_norm"):
        validate_config(dataclasses.replace(base, qk_norm=True))


def test_a_selection_needs_the_paged_cache_on_one_device():
    cfg, params = model()
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(cfg, params, EngineConfig(dtype="float32"),
                        CacheConfig(kind="dense"))
    with pytest.raises(ValueError, match="single-device"):
        InferenceEngine(cfg, params, EngineConfig(dtype="float32"),
                        CacheConfig(kind="paged"), mesh_cfg=MeshConfig(tp=2))


def test_the_selected_prefill_kernel_in_bf16_agrees_with_the_gather_path():
    """The chip's form of a sparse prefill chunk (bf16 queries against the
    int8 pages through the ragged kernel under the mask) against the same
    cache's gather path in float32: bf16's rounding of the inputs, under
    2e-2 of the result's norm; the float32 kernel is the gather path to
    1e-5."""
    from distributed_llm_inference_tpu.ops.attention import gqa_attention
    from distributed_llm_inference_tpu.ops.rotary import RopeAngles, rope_cos_sin, rope_inv_freq
    from distributed_llm_inference_tpu.ops.sparse_attention import IndexInputs

    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    s, hq, hkv, d = 24, 4, 2, 16
    q = jax.random.normal(keys[0], (1, s, hq, d))
    k = jax.random.normal(keys[1], (1, s, hkv, d))
    v = jax.random.normal(keys[2], (1, s, hkv, d))
    index = IndexInputs(
        jax.random.normal(keys[3], (1, s, 2, INDEX_DIM)),
        jax.random.normal(keys[4], (1, s, INDEX_DIM)),
        jax.random.normal(keys[5], (1, s, 2)), 6,
    )
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    rope = RopeAngles(None, *rope_cos_sin(pos, rope_inv_freq(d, 1e4)))
    n = jnp.asarray([s], jnp.int32)

    def run(dtype, **flags):
        cache = indexed_cache_class(True, INDEX_DIM).create(
            1, 1, 5, PS, 4, hkv, d, dtype, **flags
        ).assign_pages(0, [1, 2, 3, 4])
        state = tuple(x[0] for x in cache.layer_stacks)
        cast = lambda x: x.astype(dtype)
        out, _ = cache.attend(
            state, cast(q), cast(k), cast(v), rope, pos, n, None, gqa_attention,
            d ** -0.5, index=IndexInputs(cast(index.q), cast(index.k), cast(index.w), 6),
        )
        return np.asarray(out, np.float64)

    want = run(jnp.float32)
    got = run(jnp.bfloat16, use_kernel=True, use_ragged=True)
    assert rel(got, want) < 0.02
    assert rel(run(jnp.float32, use_kernel=True, use_ragged=True), want) < 1e-5
