"""K-EXAONE's block (``exaone_moe``): window and full layers in one stack over
a cache that holds the two kinds apart, and an expert layer that holds a
share of its experts.

The plain reference is ``benchmark/reference/exaone_swa_moe.py`` (float32
``jax.numpy``, nothing of the program); the sizes are toys, the control flow
the cell's.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import exaone_swa_moe as reference
from benchmark.weights import exaone_swa_moe as maker
from distributed_llm_inference_tpu.cache.latent import (
    LatentPagedKVCache, QuantizedLatentPagedKVCache,
)
from distributed_llm_inference_tpu.cache.paged import (
    PagedKVCache, QuantizedPagedKVCache, two_pool_cache_class,
    window_pages_bound,
)
from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, LatentConfig, MeshConfig, ModelConfig,
    TraceConfig,
)
from distributed_llm_inference_tpu.engine import engine as engine_mod
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import validate_config
from distributed_llm_inference_tpu.ops import moe as moe_ops


def tiny_hf(layers=8, window=8, shares=2, index=1, experts=8):
    kinds = (["sliding_attention"] * 3 + ["full_attention"]) * (layers // 4)
    return dict(
        model_type="exaone_moe", vocab_size=128, hidden_size=64,
        intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-5,
        rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
        layer_types=kinds, sliding_window=window,
        sliding_windows=[window if k == "sliding_attention" else 0 for k in kinds],
        mlp_layer_types=["dense"] + ["sparse"] * (layers - 1),
        first_k_dense_replace=1, num_experts=experts // shares,
        num_experts_per_tok=3, num_shared_experts=1, scoring_func="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.5, n_group=1,
        topk_group=1, num_nextn_predict_layers=0, tie_word_embeddings=False,
        max_position_embeddings=512,
        expert_share={"router_experts": experts, "shares": shares, "index": index},
    )


@pytest.fixture(scope="module")
def model():
    hf = tiny_hf()
    cfg = ModelConfig.from_hf_config(hf)
    validate_config(cfg)
    return hf, cfg, maker.make(cfg, 3, jnp.float32, None)


def one_row_cache(cfg, quantized, pages, ps, **kw):
    cls = two_pool_cache_class(quantized, cfg.attention_kinds, cfg.sliding_window)
    return cls.create(
        cls.num_layers_of("full"), 1, pages + 1, ps, pages + 1,
        cfg.num_kv_heads, cfg.head_dim, jnp.float32, **kw,
    ).assign_pages(0, list(range(1, pages + 1)))


def rel(x, y):
    return float(np.linalg.norm(x - y) / np.linalg.norm(y))


# -- (a) the program against the plain reference -------------------------------


def test_the_stack_is_runs_of_like_layers_each_with_its_part_of_the_cache(model):
    _, cfg, _ = model
    assert cfg.mixed_attention and cfg.num_experts == 8 and cfg.num_held_experts == 4
    segs = cfg.segments
    assert [(s.kind, s.attention, s.start, s.count) for s in segs] == [
        ("dense", "window", 0, 1), ("moe", "window", 1, 2), ("moe", "full", 3, 1),
        ("moe", "window", 4, 3), ("moe", "full", 7, 1),
    ]
    assert [(s.pool, s.cache_start) for s in segs] == [
        ("window", 0), ("window", 1), ("full", 0), ("window", 3), ("full", 1),
    ]
    assert all(s.rope == (s.attention == "window") for s in segs)
    assert all(s.window == (8 if s.attention == "window" else None) for s in segs)
    assert [s.key for s in segs] == [
        "layers_0_dense", "layers_1_moe", "layers_2_moe", "layers_3_moe",
        "layers_4_moe",
    ]


def test_prefill_then_decode_through_the_float_two_pool_cache_is_the_reference(model):
    hf, cfg, params = model
    t, steps, ps = 37, 6, 4                 # over four windows, pages crossed
    toks = np.random.default_rng(0).integers(1, cfg.vocab_size, size=t + steps)
    gold = np.asarray(reference.forward(hf, params, jnp.asarray(toks)))
    cache = one_row_cache(cfg, False, -(-(t + steps) // ps), ps)
    assert cache.k_pages.shape[0] == 2 and cache.wk_pages.shape[0] == 6
    padded = jnp.zeros((1, 48), jnp.int32).at[0, :t].set(jnp.asarray(toks[:t]))
    one = jnp.ones((1,), jnp.int32)

    @jax.jit
    def run(params, padded, rest, cache):
        logits, cache = llama.model_apply(cfg, params, padded, cache, t * one)

        def token(cache, tok):
            step, cache = llama.model_apply(cfg, params, tok[None, None], cache, one)
            return cache, step[0, 0]

        cache, steps = jax.lax.scan(token, cache, rest)
        return logits[0, :t], steps, cache

    logits, stepped, cache = run(params, padded, jnp.asarray(toks[t:]), cache)
    assert np.abs(np.asarray(logits) - gold[:t]).max() < 2e-5
    assert np.abs(np.asarray(stepped) - gold[t:]).max() < 2e-5
    assert int(cache.lengths[0]) == t + steps


@pytest.mark.parametrize("step", [0, 3], ids=["step0", "stepKT-1"])
@pytest.mark.parametrize("pages", ["a-block", "a-block-and-four"])
def test_the_window_pools_sweep_attends_a_full_block_as_one_tile(pages, step):
    """The window form of the fused in-place kernel under the window pool's
    name, over the rows of ``tests/test_paged_attention.py`` (``n`` pages a
    block): a window that leaves a long row exactly one block of live pages
    from a first page that is no multiple of ``n``, and one that leaves
    ``n + 4`` (a full block, then four pages in a tile padded to ``n``).
    Every page no live token owns is poisoned, those wholly before the window too."""
    from distributed_llm_inference_tpu.ops import paged_attention as pa
    from test_paged_attention import (
        _FUSED, _fused_inputs, _fused_oracle, _fused_rows,
    )

    lengths, active, n = _fused_rows()
    ps = _FUSED["ps"]
    window = ((n - 1) * ps + 3) if pages == "a-block" else (n + 3) * ps + 3
    lo, hi = pa._live_pages(
        lengths, lengths + step, ps, _FUSED["t"], window, np
    )
    want_live = n if pages == "a-block" else n + 4
    assert ((hi - lo == want_live) & (lo % n != 0) & active).any(), (lo, hi)
    a = _fused_inputs(seed=13 + step, g=4, step=step, window=window)
    out, *tails = pa.quantized_paged_fused_attention(
        **a, sliding_window=window, name="window_paged_fused_attention"
    )
    ref, want = _fused_oracle(a, window)
    out = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(out).all(), "a dead page was read"
    assert (out[~active] == 0).all()
    np.testing.assert_allclose(out, np.asarray(ref), atol=0.03, rtol=0.02)
    for got, exact in zip(tails, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exact))


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_prefill_then_the_fused_scan_through_the_int8_two_pool_cache(model, kernels):
    """The int8 class: one prefill, then the write-behind-tail scan over both
    pools (each pool its own tail and flush), against the reference's full
    forward. With the kernels (interpreted): the ragged prefill and the fused
    in-place sweep under the window pool's own names."""
    hf, cfg, params = model
    t, steps, ps = 29, 5, 8
    slots = 96 if kernels else -(-(t + steps) // ps)   # 768 positions: in place
    toks = np.random.default_rng(1).integers(1, cfg.vocab_size, size=t + steps + 1)
    gold = np.asarray(reference.forward(hf, params, jnp.asarray(toks[:-1])))
    cls = two_pool_cache_class(True, cfg.attention_kinds, cfg.sliding_window)
    cache = cls.create(
        2, 1, 6, ps, slots, cfg.num_kv_heads, cfg.head_dim, jnp.float32,
        use_kernel=kernels, use_ragged=kernels,
    ).assign_pages(0, [1, 2, 3, 4, 5])
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :t].set(jnp.asarray(toks[:t]))
    forced = jnp.asarray(toks[t:], jnp.int32)
    one = jnp.ones((1,), jnp.int32)

    def run(params, padded, forced, cache):
        first, cache = llama.model_apply(
            cfg, params, padded, cache, t * one, head="last"
        )
        scanned, cache = llama.multi_decode_apply(
            cfg, params, forced[:1][None], cache, steps,
            lambda i, logits, st: (forced[i + 1][None], one, st, logits),
            jnp.zeros(()), one,
        )
        return first[0, 0], scanned[:, 0], cache

    first, scanned, cache = jax.jit(run)(params, padded, forced, cache)
    ours = np.concatenate([np.asarray(first)[None], np.asarray(scanned)])
    dist = [rel(o, g) for o, g in zip(ours, gold[t - 1:])]
    assert max(dist) < 0.05 and min(dist) > 1e-5, dist      # int8 pages
    assert int(cache.lengths[0]) == t + steps
    if kernels:
        text = str(jax.make_jaxpr(run)(params, padded, forced, cache))
        for name in ("window_paged_fused_attention", "window_tail_flush",
                     "window_ragged_paged_attention",
                     "quantized_paged_fused_attention", "paged_tail_flush",
                     "quantized_ragged_paged_attention"):
            assert name in text, name


def test_the_fused_scan_through_the_float32_two_pool_cache(model):
    """The value-dtype class has the tail too (since PR 49, without a
    kernel: each pool's table span gathered once a window, each pool its own
    tail and flush): one prefill, then the scan, against the reference's
    full forward at float32's own distance, with a window (8) shorter than
    the context."""
    hf, cfg, params = model
    t, steps, ps = 29, 5, 8
    toks = np.random.default_rng(1).integers(1, cfg.vocab_size, size=t + steps + 1)
    gold = np.asarray(reference.forward(hf, params, jnp.asarray(toks[:-1])))
    cls = two_pool_cache_class(False, cfg.attention_kinds, cfg.sliding_window)
    cache = cls.create(
        2, 1, 6, ps, -(-(t + steps) // ps), cfg.num_kv_heads, cfg.head_dim,
        jnp.float32,
    ).assign_pages(0, [1, 2, 3, 4, 5])
    assert cache.has_tail and not cache.use_kernel
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :t].set(jnp.asarray(toks[:t]))
    forced = jnp.asarray(toks[t:], jnp.int32)
    one = jnp.ones((1,), jnp.int32)

    def run(params, padded, forced, cache):
        first, cache = llama.model_apply(
            cfg, params, padded, cache, t * one, head="last"
        )
        scanned, cache = llama.multi_decode_apply(
            cfg, params, forced[:1][None], cache, steps,
            lambda i, logits, st: (forced[i + 1][None], one, st, logits),
            jnp.zeros(()), one,
        )
        return first[0, 0], scanned[:, 0], cache

    first, scanned, cache = jax.jit(run)(params, padded, forced, cache)
    ours = np.concatenate([np.asarray(first)[None], np.asarray(scanned)])
    assert max(rel(o, g) for o, g in zip(ours, gold[t - 1:])) < 1e-4
    assert int(cache.lengths[0]) == t + steps


def test_every_layer_full_is_another_model(model):
    """The control the cell's ``correct`` rests on: with the window opened
    wide the reference is far from itself as published."""
    hf, _, params = model
    toks = jnp.asarray(np.random.default_rng(2).integers(1, 128, size=40))
    gold = np.asarray(reference.forward(hf, params, toks))
    wide = np.asarray(reference.forward({**hf, "sliding_window": 10 ** 6}, params, toks))
    assert rel(wide[5], gold[5]) < 1e-6            # inside the first window
    assert rel(wide[-1], gold[-1]) > 0.02


# -- (b) the share adds up ------------------------------------------------------


def test_the_shares_parts_and_the_shared_expert_once_are_the_uncut_layer():
    shares, experts = 4, 8
    whole_hf = tiny_hf(shares=1, index=0, experts=experts)
    whole = ModelConfig.from_hf_config(whole_hf)
    stack = maker.make(whole, 7, jnp.float32, None)["layers_1_moe"]
    lp = jax.tree.map(lambda a: a[0], stack)
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(2, 9, whole.hidden_size)), jnp.float32
    )
    want = np.asarray(reference.moe(whole_hf, lp, x.reshape(-1, x.shape[-1])))
    shared = np.asarray(moe_ops._shared_experts(lp, x)).reshape(want.shape)
    held = experts // shares
    total = np.zeros_like(want)
    for i in range(shares):
        hf = tiny_hf(shares=shares, index=i, experts=experts)
        cfg = ModelConfig.from_hf_config(hf)
        assert (cfg.num_experts, cfg.num_held_experts, cfg.first_held_expert) == (
            experts, held, i * held
        )
        part = {
            k: v[i * held:(i + 1) * held] if k.startswith("we_") else v
            for k, v in lp.items()
        }
        got = np.asarray(moe_ops.moe_mlp(cfg, part, x)).reshape(want.shape)
        # the plain reference, given the same share, computes the same part
        np.testing.assert_allclose(
            got, np.asarray(reference.moe(hf, part, x.reshape(want.shape))),
            rtol=2e-5, atol=2e-6,
        )
        # ... and so does dense-combine, the form every mesh program runs
        dense = moe_ops._dense_combine(cfg, part, x)
        np.testing.assert_allclose(
            np.asarray(dense).reshape(want.shape) + shared, got,
            rtol=2e-5, atol=2e-6,
        )
        total += got - shared
    np.testing.assert_allclose(total + shared, want, rtol=2e-5, atol=2e-6)
    # a token's needed rows are an expectation where the layer holds a share
    cfg = ModelConfig.from_hf_config(tiny_hf(shares=4, experts=8))
    # ... and so are the held experts ONE token makes live (the live path);
    # under a mesh the program runs both held ones
    assert moe_ops.expert_rows_per_token(cfg, 1) == (3 * 2 / 8 + 1, 2 * 3 / 8 + 1)
    assert moe_ops.expert_rows_per_token(cfg, 1, sharded=True) == (3 * 2 / 8 + 1, 2 + 1)
    assert moe_ops.expert_rows_per_token(whole, 1) == (3 + 1, 3 + 1)
    assert moe_ops.expert_rows_per_token(whole, 1, sharded=True) == (3 + 1, 8 + 1)


# -- (c) pages really leave ------------------------------------------------------


def serve(cfg, params, prompts, new_tokens=24):
    engine = InferenceEngine(
        cfg, params,
        EngineConfig(
            max_batch_size=3, prefill_buckets=(8, 16, 32), max_seq_len=256,
            dtype="float32", ragged_attention=True, prefill_chunk_tokens=32,
        ),
        CacheConfig(kind="paged", page_size=4, num_pages=160,
                    max_pages_per_session=64),
        trace_cfg=TraceConfig(),
    )
    gids = [
        engine.submit(p, SamplingOptions(
            max_new_tokens=new_tokens, temperature=0.0, eos_token_id=-1
        )) for p in prompts
    ]
    owners, most = {}, 0
    while engine.has_work():
        engine.step()
        for s in engine.sessions.values():
            if s.slot is not None:
                most = max(most, len(s.window_pages))
                for page in s.window_pages.values():
                    owners.setdefault(page, set()).add(s.generation_id)
    done = engine.collect_finished()
    return engine, [done[g].generated for g in gids], owners, most


def test_window_pages_leave_rows_and_serve_others_and_nothing_changes(model, monkeypatch):
    _, cfg, params = model
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(1, 128, size=n)) for n in (70, 9, 100, 21, 45)]
    engine, tokens, owners, most = serve(cfg, params, prompts)
    ps, window = 4, cfg.sliding_window
    assert isinstance(engine.cache, two_pool_cache_class(False, cfg.attention_kinds, window))
    # rows passed many windows; pages were released and taken by other rows
    assert engine.metrics.snapshot()["window_pages_released"] > 30
    assert max(len(gids) for gids in owners.values()) > 1
    # a row's window pages stay under the bound at any context: the window,
    # what one dispatch writes (a 32-token chunk here), and a page
    assert most <= window_pages_bound(window, ps, 32) < -(-100 // ps)
    # both pools are whole again
    assert engine.allocator.free_count == engine.allocator.num_pages - 1
    assert engine.window_allocator.free_count == engine.window_allocator.num_pages - 1
    last = engine.flight.snapshot()[-1]
    assert last["free_window_pages"] == engine.window_allocator.num_pages - 1
    assert last["kv_pages_held"] == [0, 0]
    # the census by kind: a window layer's queries saw a fraction of their
    # contexts
    seen = engine.metrics.snapshot()
    assert 0 < seen["window_keys_seen"] < 0.5 * seen["window_keys_in_context"]
    # against a run whose window pool never reuses a page
    monkeypatch.setattr(engine_mod, "window_pool_pages", lambda *a: 1024)
    monkeypatch.setattr(InferenceEngine, "_window_release", lambda self, s, t: None)
    roomy, same, _, most = serve(cfg, params, prompts)
    assert "window_pages_released" not in roomy.metrics.snapshot()
    assert most >= -(-100 // ps)        # a row kept every page of its context
    assert same == tokens


def test_what_a_two_pool_stack_cannot_do_is_refused_by_name(model):
    _, cfg, params = model
    ecfg = EngineConfig(max_batch_size=2, prefill_buckets=(8, 16), max_seq_len=64,
                        dtype="float32")
    paged = dict(kind="paged", page_size=4, num_pages=32, max_pages_per_session=16)
    with pytest.raises(ValueError, match="prefix_caching"):
        InferenceEngine(cfg, params, ecfg, CacheConfig(prefix_caching=True, **paged))
    with pytest.raises(ValueError, match="a mesh"):
        InferenceEngine(cfg, params, ecfg, CacheConfig(**paged), mesh_cfg=MeshConfig(tp=2))
    with pytest.raises(ValueError, match="requires the paged cache"):
        InferenceEngine(cfg, params, ecfg, CacheConfig(kind="dense"))
    engine = InferenceEngine(cfg, params, ecfg, CacheConfig(**paged))
    for call in (
        lambda: engine.prefill_export([1, 2, 3]),
        lambda: engine.export_session("x"),
        lambda: engine.resume_session(None),
        lambda: engine.admit_prefilled([1, 2, 3], None, 1),
    ):
        with pytest.raises(ValueError, match="window and full layers"):
            call()
    with pytest.raises(NotImplementedError, match="window and full layers"):
        engine.cache.read_page(1)


# -- (d) the stacks the benchmark had trace to what they traced to -----------------

#: ``sha256(str(jaxpr))[:16]`` of a Mistral-shaped stack (one window, every
#: layer a window layer) and a Moonlight-shaped one (latent attention, a
#: leading dense layer, every expert here: share 0 of 1), taken on the
#: PARENT of PR 35 (commit cdb55a4) with this file's ``old_stack_digests``
#: run against that tree under this suite's ``conftest.py`` (its matmul
#: precision is in the jaxprs); jax 0.9.0. A later change to what these stacks
#: trace to is not this test's business to forbid: regenerate, and say why.
#: PR 42 regenerated the three decode scans that hold the in-place sweep by
#: copies (this suite's narrow latent pool takes it too): its body attends a
#: block of live pages as one tile. PR 46 regenerated Moonlight's six: its
#: decode scans and these toy prefills (a few tokens: one row tile) take the
#: live path of ``ops/moe.py`` where they dense-combined (traced here, off a
#: TPU, with ``grouped_matmul``'s plain-XLA reference). PR 58 regenerated
#: Mistral's two kernel prefills: the int8 pool under the ragged kernel is
#: written by whole pages and read at (layer, page) of the carried stacks
#: there (``QuantizedPagedKVCache.ragged_reads_whole_stacks``; the other
#: eleven, the int8 prefill without the kernel and every decode scan among
#: them, are untouched by it). PR 61 regenerated ONE, Mistral's decode scan
#: over pages of 64 in a table of 12 (768 positions: the in-place sweep by
#: copies at a page size whose two scale rows are a whole 128-lane tile): the
#: cache joins K's and V's scale planes once a window and the kernel copies a
#: live page's rows itself, where the wrapper gathered every table slot's a
#: layer a step (``ops/paged_attention.py:joined_scale_rows``); the latent
#: pool's one plane of 64 lanes keeps the gather, and its scan stands.
#: Mistral's other four are cdb55a4's (and PR 42's) still.
OLD_STACKS = {
    "mistral.float.prefill": "ce04728d66ae8e7a",
    "mistral.int8.prefill": "0332a71023c2ddb3",
    "mistral.int8.decode_scan": "6fe8c360a5b7a039",
    "mistral.kernel.8x4.decode_scan": "36b335e3f969605d",
    "mistral.kernel.8x4.prefill": "08a1619b4f8eea2a",
    "mistral.kernel.64x12.decode_scan": "d216caf8622f0f4a",
    "mistral.kernel.64x12.prefill": "b05ce1beffbe99c6",
    "moonlight.float.prefill": "eac83a25e1c45a4d",
    "moonlight.int8.prefill": "c3335515e1e0ab1c",
    "moonlight.kernel.8x4.decode_scan": "ddd63de5e8155649",
    "moonlight.kernel.8x4.prefill": "2426df667f55c3a6",
    "moonlight.kernel.64x12.decode_scan": "1a03c4bdef39fb36",
    "moonlight.kernel.64x12.prefill": "cc062e11f80c521d",
}


def old_stacks():
    small = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
                 num_layers=3, num_heads=4, head_dim=8)
    return {
        "mistral": ModelConfig(
            num_kv_heads=2, sliding_window=12, family="mistral", **small
        ),
        "moonlight": ModelConfig(
            num_kv_heads=4, num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=16, num_shared_experts=1,
            first_dense_layers=1, moe_scoring="sigmoid", moe_select_bias=True,
            moe_norm_topk=True, moe_routed_scale=2.5,
            latent=LatentConfig(rank=16, rope_head_dim=4, nope_head_dim=8,
                                v_head_dim=8),
            family="mla", **small
        ),
    }


def old_stack_digests():
    def digest(jaxpr):
        text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def prefill(cfg, width):
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
        cls = QuantizedPagedKVCache if quant else PagedKVCache
        return jax.eval_shape(lambda: cls.create(
            cfg.num_layers, 2, 9, ps, slots, cfg.num_kv_heads, cfg.head_dim,
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
            out[f"{name}.{kind}.prefill"] = digest(jax.make_jaxpr(prefill(cfg, 8))(
                params, s((2, 8), jnp.int32), cache, rows
            ))
            if quant and not cfg.use_latent:
                out[f"{name}.int8.decode_scan"] = digest(
                    jax.make_jaxpr(scan(cfg))(params, one, cache, act)
                )
        # the kernels' paths: the gathered fused window (32 positions), the
        # in-place sweep (768), the latent pool's fused one-plane form
        for ps, slots in ((8, 4), (64, 12)):
            cache = cache_of(cfg, True, ps, slots, use_kernel=True, use_ragged=True)
            out[f"{name}.kernel.{ps}x{slots}.decode_scan"] = digest(
                jax.make_jaxpr(scan(cfg))(params, one, cache, act)
            )
            out[f"{name}.kernel.{ps}x{slots}.prefill"] = digest(
                jax.make_jaxpr(prefill(cfg, 16))(
                    params, s((2, 16), jnp.int32), cache, rows
                )
            )
    return out


def test_one_window_and_share_0_of_1_trace_to_the_jaxprs_they_traced_to():
    stacks = old_stacks()
    assert [s.key for s in stacks["mistral"].segments] == ["layers"]
    assert stacks["mistral"].attention_kinds == ("window",) * 3
    assert [(s.key, s.start, s.cache_start, s.pool) for s in stacks["moonlight"].segments] == [
        ("layers_0_dense", 0, 0, None), ("layers_1_moe", 1, 1, None),
    ]
    assert stacks["moonlight"].num_held_experts == 4
    assert old_stack_digests() == OLD_STACKS


# -- (e) from_hf_config ------------------------------------------------------------


def test_from_hf_config_reads_the_blocks_keys():
    hf = tiny_hf(layers=4, shares=1, index=0)
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.family == "exaone_moe" and cfg.rope_theta == 1e6
    assert cfg.layer_attention == ("window", "window", "window", "full")
    assert cfg.sliding_window == 8 and not cfg.full_attention_rope and cfg.qk_norm
    assert (cfg.first_dense_layers, cfg.num_shared_experts) == (1, 1)
    assert (cfg.moe_scoring, cfg.moe_select_bias, cfg.moe_norm_topk) == ("sigmoid", True, True)
    assert cfg.moe_routed_scale == 2.5 and cfg.moe_intermediate_size == 32
    assert (cfg.num_experts, cfg.expert_shares, cfg.expert_share_index) == (8, 1, 0)
    # a nested theta is read for any family, a top-level one wins
    assert ModelConfig.from_hf_config(
        {"rope_parameters": {"rope_theta": 5e5}}
    ).rope_theta == 5e5
    assert ModelConfig.from_hf_config(
        {"rope_theta": 1e4, "rope_parameters": {"rope_theta": 5e5}}
    ).rope_theta == 1e4
    nested = ModelConfig.from_hf_config({"rope_parameters": {
        "rope_theta": 5e5, "rope_type": "linear", "factor": 2.0,
    }})
    assert nested.rope_scaling.rope_type == "linear" and nested.rope_scaling.factor == 2.0
    # every layer a window layer, or every layer full, is one kind
    alike = dict(hf, layer_types=["sliding_attention"] * 4, sliding_windows=[8] * 4)
    assert ModelConfig.from_hf_config(alike).layer_attention is None
    full = dict(hf, layer_types=["full_attention"] * 4, sliding_windows=[0] * 4)
    assert ModelConfig.from_hf_config(full).sliding_window is None


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1),
    ("n_group", 2),
    ("scoring_func", "tanh"),
    ("layer_types", ["sliding_attention", "linear_attention"] * 2),
    ("layer_types", ["sliding_attention"] * 3),
    ("sliding_window", None),
    ("sliding_windows", [8, 8, 0, 8]),
    ("mlp_layer_types", ["sparse", "dense", "sparse", "sparse"]),
    ("first_k_dense_replace", 2),
    ("expert_share", {"router_experts": 8, "shares": 3, "index": 0}),
    ("expert_share", {"router_experts": 8, "shares": 1, "index": 1}),
])
def test_from_hf_config_refuses_by_the_keys_name(key, value):
    hf = tiny_hf(layers=4, shares=1, index=0)
    hf[key] = value
    with pytest.raises(ValueError, match=f"config key '{key}'"):
        ModelConfig.from_hf_config(hf)


def test_the_registry_refuses_the_switches_elsewhere():
    with pytest.raises(ValueError, match="does not mix window and full"):
        validate_config(ModelConfig(
            num_layers=2, layer_attention=("window", "full"), sliding_window=4,
            family="mistral",
        ))
    with pytest.raises(ValueError, match="expert_shares"):
        validate_config(ModelConfig(
            num_experts=8, expert_shares=3, family="mixtral",
        ))
