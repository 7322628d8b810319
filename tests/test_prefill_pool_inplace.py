"""A prefill-family dispatch over the int8 page pool writes whole pages and
reads at ``(layer, page)`` of the carried stacks (ISSUE 58:
``QuantizedPagedKVCache.ragged_reads_whole_stacks``, ``_write_pages``,
``ops/paged_attention.py:paged_piece_write``, the stacked form of
``ops/ragged_attention.py:quantized_ragged_paged_attention``,
``models/llama.py:block_apply``'s hand-off), against the form it replaces
there: a layer's planes sliced out of the carry, ``_scatter_planes`` position
by position, the plane-form kernel, the planes written back.

On the CPU at toy sizes, the kernels interpreted: the logits equal and the
pool's planes bit-equal on every page but the null page 0, which is where the
scatter diverts the writes of a dispatch's pad positions (several to one
place, in no defined order) and which the page form never writes. The pools
start from noise, so a write that should not have happened shows.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.paged import (
    QuantizedPagedKVCache, two_pool_cache_class,
)
from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import validate_config

PS = 8


@contextlib.contextmanager
def the_scatter_form():
    """The program before ISSUE 58: no cache hands its stacks over."""
    was = QuantizedPagedKVCache.ragged_reads_whole_stacks
    QuantizedPagedKVCache.ragged_reads_whole_stacks = property(lambda self: False)
    try:
        yield
    finally:
        QuantizedPagedKVCache.ragged_reads_whole_stacks = was


def noise(cache, seed=7):
    """Every plane of the pool filled with noise (scales positive)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), len(cache.LAYER_FIELDS)))
    planes = {}
    for f in cache.LAYER_FIELDS:
        a = getattr(cache, f)
        planes[f] = (
            jax.random.randint(next(keys), a.shape, -127, 128, jnp.int32).astype(a.dtype)
            if a.dtype == jnp.int8
            else jax.random.uniform(next(keys), a.shape, a.dtype, 0.01, 1.0)
        )
    return cache.replace(**planes)


def dense_model(layers=3):
    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_layers=layers, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=512,
    )
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)


def int8_cache(layers, rows, pages, slots, cfg, table, lengths, cls=QuantizedPagedKVCache, **kw):
    cache = cls.create(
        layers, rows, pages, PS, slots, cfg.num_kv_heads, cfg.head_dim,
        jnp.float32, use_kernel=True, use_ragged=True, **kw,
    )
    return noise(cache).replace(
        page_table=jnp.asarray(table, jnp.int32),
        lengths=jnp.asarray(lengths, jnp.int32),
    )


def tokens(rows, width, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(1, 128, size=(rows, width)), jnp.int32
    )


def whole(cfg, params, toks, cache, num_new):
    """Every row of the cache in the dispatch (a verify step's shape)."""
    return llama.model_apply(cfg, params, toks, cache, num_new, head="last")


def one_row(row):
    """``engine.py:_prefill_row``."""
    def run(cfg, params, toks, cache, num_new):
        sub = cache.select_row(row)
        logits, sub = llama.model_apply(cfg, params, toks, sub, num_new, head="last")
        return logits, cache.merge_row(sub, row)
    return run


def rows_of(rows):
    """``engine.py:_prefill_rows``: a padding entry names a row past the
    cache's, is clamped on the way in and dropped on the way out."""
    def run(cfg, params, toks, cache, num_new):
        at = jnp.asarray(rows, jnp.int32)
        sub = cache.select_rows(at)
        logits, sub = llama.model_apply(cfg, params, toks, sub, num_new, head="last")
        return logits, cache.merge_rows(sub, at)
    return run


def fresh_piece():
    """(a) a fresh row's one-piece prompt: 29 tokens under a 32-wide pad, the
    last page filled in part."""
    cfg, params = dense_model()
    cache = int8_cache(3, 2, 12, 5, cfg, [[3, 5, 7, 9, 0], [2, 4, 0, 0, 0]], [0, 9])
    return cfg, params, tokens(1, 32), cache, [29], one_row(0), "paged_piece_write"


def a_chunk_inside_its_pages():
    """(b) a continuation chunk that starts inside a page (13 = page 1,
    offset 5) and ends inside one (29 = page 3, offset 5): both edge pages
    keep what they held outside the piece."""
    cfg, params = dense_model()
    cache = int8_cache(3, 2, 12, 5, cfg, [[3, 5, 7, 9, 0], [2, 4, 0, 0, 0]], [13, 9])
    return cfg, params, tokens(1, 16), cache, [16], one_row(0), "paged_piece_write"


def short_rows_and_an_idle_one():
    """(c) ``num_new`` short of the pad width, a row with ``num_new`` 0 (its
    table maps pages that hold another state: nothing of them may move), and
    a one-token row (a decode row of a mixed dispatch)."""
    cfg, params = dense_model()
    cache = int8_cache(
        3, 3, 12, 4, cfg, [[3, 5, 7, 0], [2, 4, 0, 0], [6, 8, 0, 0]], [2, 9, 11],
    )
    return cfg, params, tokens(3, 16), cache, [11, 0, 1], whole, "paged_piece_write"


def a_group_of_rows():
    """(d) ``_prefill_rows``: three admitted rows of one pad width and a
    padding entry (row 9 of 4: clamped to the last row, ``num_new`` 0)."""
    cfg, params = dense_model()
    cache = int8_cache(
        3, 4, 16, 3, cfg,
        [[3, 5, 7], [2, 4, 6], [8, 9, 10], [11, 12, 13]], [0, 0, 0, 17],
    )
    return (cfg, params, tokens(4, 24), cache, [24, 7, 17, 0],
            rows_of([2, 0, 1, 9]), "paged_piece_write")


def two_pools_and_a_page_that_left_the_window():
    """(e) K-EXAONE's stack over the two-pool int8 cache: row 0's second
    chunk (positions 24..39, window 8). Its window table's first two slots
    hold STALE ids, pages the engine released behind the window and gave to
    row 1 (9 and 10): the chunk neither writes nor reads them."""
    from test_exaone import tiny_hf
    from benchmark.weights import exaone_swa_moe as maker

    cfg = ModelConfig.from_hf_config(tiny_hf())
    validate_config(cfg)
    params = maker.make(cfg, 3, jnp.float32, None)
    cls = two_pool_cache_class(True, cfg.attention_kinds, cfg.sliding_window)
    cache = int8_cache(
        cls.num_layers_of("full"), 2, 14, 6, cfg,
        [[1, 2, 3, 4, 5, 0], [6, 7, 0, 0, 0, 0]], [24, 12], cls=cls,
    ).replace(w_page_table=jnp.asarray(
        [[9, 10, 3, 4, 5, 0], [9, 10, 0, 0, 0, 0]], jnp.int32
    ))
    return (cfg, params, tokens(1, 16), cache, [16], one_row(0),
            "window_piece_write")


def ouros_laps():
    """(f) a looped stack: lap ``t`` of layer ``l`` writes and reads cache
    row ``t x L + l`` (``first_layer + i``), over 3 x 4 = 12 cache layers."""
    from test_ouro import tiny_model

    _, cfg, params = tiny_model()
    assert cfg.cache_layers == 12
    cache = int8_cache(12, 2, 12, 5, cfg, [[3, 5, 7, 9, 0], [2, 4, 0, 0, 0]], [5, 9])
    return cfg, params, tokens(1, 16), cache, [14], one_row(0), "paged_piece_write"


def behind_a_shared_prefix():
    """(g) a row whose first two pages are a shared prefix (row 1 maps the
    same pages 3 and 5): the piece starts at the third slot and the shared
    pages are read, never written."""
    cfg, params = dense_model()
    cache = int8_cache(3, 2, 12, 5, cfg, [[3, 5, 7, 9, 0], [3, 5, 4, 0, 0]], [16, 20])
    return cfg, params, tokens(1, 16), cache, [13], one_row(0), "paged_piece_write"


CASES = [
    fresh_piece, a_chunk_inside_its_pages, short_rows_and_an_idle_one,
    a_group_of_rows, two_pools_and_a_page_that_left_the_window, ouros_laps,
    behind_a_shared_prefix,
]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__ for c in CASES])
def test_the_page_form_leaves_the_pool_and_the_logits_of_the_scatter_form(case):
    cfg, params, toks, cache, num_new, run, kernel = case()
    num_new = jnp.asarray(num_new, jnp.int32)

    def traced_and_run():
        # a function of its own a form: a trace is cached by the function
        def program(params, toks, cache, num_new):
            return run(cfg, params, toks, cache, num_new)

        args = (params, toks, cache, num_new)
        return str(jax.make_jaxpr(program)(*args)), jax.jit(program)(*args)

    text, (logits, new) = traced_and_run()
    assert kernel in text
    with the_scatter_form():
        old_text, (want_logits, want) = traced_and_run()
    assert "piece_write" not in old_text and "scatter[" in old_text
    assert text.count("scatter[") == old_text.count("scatter[") - 4 * (
        text.count("name=quantized_ragged") + text.count("name=window_ragged")
    )    # the four planes' scatters of every layer body, and no other, went
    live = np.asarray(num_new) > 0
    np.testing.assert_array_equal(
        np.asarray(logits)[live], np.asarray(want_logits)[live]
    )
    for f in cache.LAYER_FIELDS:
        got, exp, was = (np.asarray(getattr(c, f)) for c in (new, want, cache))
        np.testing.assert_array_equal(got[:, 1:], exp[:, 1:], err_msg=f)
        np.testing.assert_array_equal(got[:, 0], was[:, 0], err_msg=f"{f}: null page")
        assert (got != was).any(), f"{f}: nothing was written"
    for f in cache.TABLE_FIELDS + ("lengths",):
        np.testing.assert_array_equal(
            np.asarray(getattr(new, f)), np.asarray(getattr(want, f)), err_msg=f
        )


def test_what_the_cache_says_and_who_says_otherwise():
    """The property is the class's whose ``attend`` it is: the window pool's
    view and the two-pool class inherit it; the indexed pool attends its own
    way and keeps a layer's planes; without the ragged kernel nobody hands
    the stacks over; a decode step (S == 1) keeps ``step`` as it is."""
    from distributed_llm_inference_tpu.cache.paged import (
        PagedKVCache, _WindowQuantizedPagedKVCache, indexed_cache_class,
    )

    def made(cls, **kw):
        return jax.eval_shape(lambda: cls.create(2, 1, 4, PS, 2, 2, 16, **kw))

    on = dict(use_kernel=True, use_ragged=True)
    assert made(QuantizedPagedKVCache, **on).ragged_reads_whole_stacks
    assert not made(QuantizedPagedKVCache).ragged_reads_whole_stacks
    two = two_pool_cache_class(True, ("window", "full", "full"), 8)
    cache = made(two, **on)
    assert cache.ragged_reads_whole_stacks
    assert type(cache.pool_view("window")) is _WindowQuantizedPagedKVCache
    assert cache.pool_view("window").ragged_reads_whole_stacks
    assert cache.pool_view("full").ragged_reads_whole_stacks
    assert not made(indexed_cache_class(True, 8), **on).ragged_reads_whole_stacks
    assert not getattr(made(PagedKVCache, **on), "ragged_reads_whole_stacks", False)

    cfg, params = dense_model(2)
    cache = int8_cache(2, 2, 6, 2, cfg, [[1, 2], [3, 4]], [3, 5])
    step = str(jax.make_jaxpr(
        lambda p, t, c: whole(cfg, p, t, c, jnp.ones((2,), jnp.int32))
    )(params, tokens(2, 1), cache))
    assert "piece_write" not in step


def test_the_kernel_tells_the_two_forms_apart_by_rank():
    from distributed_llm_inference_tpu.ops import ragged_attention as ra

    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(-127, 128, size=(3, 6, 2, PS, 16)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 1, size=(3, 6, 2, PS)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 8, 4, 16)), jnp.float32)
    table = jnp.asarray([[1, 2, 0], [4, 5, 3]], jnp.int32)
    rest = (table, jnp.asarray([13, 20], jnp.int32), jnp.asarray([8, 5], jnp.int32))
    for layer in range(3):
        plane = ra.quantized_ragged_paged_attention(
            q, k[layer], ks[layer], k[layer][::-1], ks[layer][::-1], *rest
        )
        stack = ra.quantized_ragged_paged_attention(
            q, k, ks, k[:, ::-1], ks[:, ::-1], *rest, layer=jnp.int32(layer)
        )
        np.testing.assert_array_equal(np.asarray(plane), np.asarray(stack))
    with pytest.raises(ValueError, match="whole"):
        ra.quantized_ragged_paged_attention(q, k, ks, k, ks, *rest)
    with pytest.raises(ValueError, match="whole"):
        ra.quantized_ragged_paged_attention(
            q, k[0], ks[0], k[0], ks[0], *rest, layer=jnp.int32(0)
        )


@pytest.mark.parametrize("cache_kw,counter", [
    (dict(kind="paged", kv_quant="int8", page_size=PS, num_pages=24,
          max_pages_per_session=8), "prefill_pool_inplace_rows"),
    (dict(kind="paged", page_size=PS, num_pages=24, max_pages_per_session=8),
     "prefill_pool_scatter_rows"),
    (dict(kind="dense"), "prefill_pool_scatter_rows"),
], ids=["int8-pool", "value-dtype-pool", "dense-rows"])
def test_the_engine_counts_a_prefills_rows_by_what_its_cache_says(
    monkeypatch, cache_kw, counter
):
    """``engine.py:_note_prefill``: every real row of a prefill-family
    dispatch moves ONE of the two counters, chosen once by the cache's
    ``ragged_reads_whole_stacks`` (a cache without the property counts as a
    layer's planes handed out). The plan answers for the chip, as the
    benchmark's rehearsal has it, so the kernels' branches run, interpreted."""
    import functools

    from distributed_llm_inference_tpu.engine import engine as engine_mod
    from distributed_llm_inference_tpu.engine.plan import AttentionPlan
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    monkeypatch.setattr(
        engine_mod, "AttentionPlan", functools.partial(AttentionPlan, backend="tpu")
    )
    cfg, params = dense_model(2)
    engine = InferenceEngine(
        cfg, params,
        EngineConfig(
            max_batch_size=2, prefill_buckets=(8, 16), max_seq_len=64,
            dtype="float32", ragged_attention=True, prefill_chunk_tokens=16,
        ),
        CacheConfig(**cache_kw),
    )
    inplace = counter == "prefill_pool_inplace_rows"
    assert bool(getattr(engine.cache, "ragged_reads_whole_stacks", False)) == inplace
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 128, size=n).tolist() for n in (5, 21)]
    engine.generate(prompts, SamplingOptions(max_new_tokens=3, eos_token_id=-1))
    snap = engine.metrics.snapshot()
    other = ({"prefill_pool_inplace_rows", "prefill_pool_scatter_rows"} - {counter}).pop()
    # 5 tokens: one piece; 21 tokens: a 16-wide chunk and its tail
    assert snap.get(counter, 0) >= 3 and snap.get(other, 0) == 0
