"""Paged cache correctness: must be semantically identical to the dense cache
(same tokens in → same logits out), plus allocator invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

import jax

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.cache.paged import PagedKVCache, PageAllocator
from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.models import llama

CFG = ModelConfig(
    vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=16,
)


def _paged(batch, alloc_rows):
    cache = PagedKVCache.create(
        CFG.num_layers, batch, num_pages=32, page_size=4,
        max_pages_per_session=8, num_kv_heads=CFG.num_kv_heads,
        head_dim=CFG.head_dim, dtype=jnp.float32,
    )
    allocator = PageAllocator(32)
    for row, n_pages in alloc_rows:
        cache = cache.assign_pages(row, allocator.alloc(n_pages))
    return cache, allocator


def test_paged_matches_dense_prefill_and_decode():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, CFG.vocab_size)

    dense = DenseKVCache.create(
        CFG.num_layers, 2, 32, CFG.num_kv_heads, CFG.head_dim, dtype=jnp.float32
    )
    paged, _ = _paged(2, [(0, 8), (1, 8)])

    num_new = jnp.asarray([9, 6], jnp.int32)  # ragged rows
    ld, dense = llama.model_apply(CFG, params, tokens, dense, num_new)
    lp, paged = llama.model_apply(CFG, params, tokens, paged, num_new)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(ld), atol=1e-5, rtol=1e-5)

    one = jnp.ones((2,), jnp.int32)
    for i in range(5):
        t = tokens[:, i : i + 1]
        ld, dense = llama.model_apply(CFG, params, t, dense, one)
        lp, paged = llama.model_apply(CFG, params, t, paged, one)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ld), atol=1e-5, rtol=1e-5)


def test_padding_tokens_cannot_corrupt_other_sessions():
    """Row 1 has no pages mapped beyond its range; its padding writes must land
    on the null page, leaving row 0's data intact."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, CFG.vocab_size)

    paged, _ = _paged(2, [(0, 2), (1, 2)])  # 8-token capacity each
    num_new = jnp.asarray([8, 3], jnp.int32)  # row 1: 5 padding tokens
    l_joint, paged = llama.model_apply(CFG, params, tokens, paged, num_new)

    # Row 0 in the shared pool must match a solo run of row 0 (tolerance is
    # fp32 epsilon: XLA fusion order differs with batch size; corruption from
    # a stray write would be O(1), not 1e-7).
    solo, _ = _paged(1, [(0, 2)])
    l_solo, solo = llama.model_apply(
        CFG, params, tokens[:1], solo, jnp.asarray([8], jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(l_joint[0]), np.asarray(l_solo[0]), atol=1e-5, rtol=1e-5
    )

    # …including a subsequent decode step from the shared cache.
    one = jnp.ones((1,), jnp.int32)
    nxt = tokens[:1, :1]
    l_d_joint, _ = llama.model_apply(
        CFG, params, jnp.concatenate([nxt, nxt], 0), paged, jnp.ones((2,), jnp.int32)
    )
    l_d_solo, _ = llama.model_apply(CFG, params, nxt, solo, one)
    np.testing.assert_allclose(
        np.asarray(l_d_joint[0]), np.asarray(l_d_solo[0]), atol=1e-5, rtol=1e-5
    )


def test_reset_rows_frees_session_state():
    paged, _ = _paged(2, [(0, 4), (1, 4)])
    paged = paged.advance(jnp.asarray([5, 7], jnp.int32))
    paged = paged.reset_rows(jnp.asarray([True, False]))
    assert paged.lengths.tolist() == [0, 7]
    assert paged.page_table[0].tolist() == [0] * 8
    assert paged.page_table[1].tolist() != [0] * 8


def test_allocator_invariants():
    a = PageAllocator(8)
    pages = a.alloc(7)
    assert 0 not in pages and sorted(pages) == list(range(1, 8))
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(pages[:3])
    assert a.free_count == 3
    with pytest.raises(ValueError):
        a.free([pages[0]])  # double free
    with pytest.raises(ValueError):
        a.free([0])  # null page


def test_ingest_masks_unowned_table_slots():
    """Ring ingest must scatter only the first ceil(n_valid/page_size)
    table slots: pages mapped in later slots (e.g. shared prefix pages a
    future caller leaves installed) must come through byte-identical, not
    overwritten with ring padding."""
    paged, _ = _paged(1, [(0, 4)])  # pages 1..4 mapped, page_size=4
    marker = jnp.full(paged.k_pages.shape[2:], 7.25, jnp.float32)
    victim = int(paged.page_table[0, 3])
    paged = paged.replace(
        k_pages=paged.k_pages.at[:, victim].set(marker),
        v_pages=paged.v_pages.at[:, victim].set(marker),
    )

    # 5 valid tokens own ceil(5/4) = 2 slots; slots 2-3 are unowned.
    n_valid = 5
    ks = jnp.arange(
        CFG.num_layers * 8 * CFG.num_kv_heads * CFG.head_dim, dtype=jnp.float32
    ).reshape(CFG.num_layers, 1, 8, CFG.num_kv_heads, CFG.head_dim)
    out = paged.ingest_row(ks, ks * 2.0, n_valid)

    assert out.lengths.tolist() == [n_valid]
    assert (np.asarray(out.k_pages[:, victim]) == 7.25).all()
    assert (np.asarray(out.v_pages[:, victim]) == 7.25).all()
    # The owned run did land: first page holds the first page_size tokens.
    first_page = int(out.page_table[0, 0])
    got = np.swapaxes(np.asarray(out.k_pages[:, first_page]), 1, 2)
    want = np.asarray(ks[:, 0, :4])
    np.testing.assert_array_equal(got, want)


def test_quantized_paged_engine_matches_exact():
    """int8 page pool (kernel, fused-tail, and XLA-gather paths) agrees with
    the exact bf16 paged engine."""
    import numpy as np

    from distributed_llm_inference_tpu.cache.paged import QuantizedPagedKVCache
    from distributed_llm_inference_tpu.config import (
        CacheConfig,
        EngineConfig,
        ModelConfig,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.models import llama

    cfg = ModelConfig(vocab_size=128, hidden_size=64, intermediate_size=160,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(51)
    ps_ = [rng.integers(0, 128, size=int(rng.integers(3, 12))).tolist()
           for _ in range(5)]
    opts = SamplingOptions(max_new_tokens=8)

    def run(kv_quant, K, kernel):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=4, prefill_buckets=(8, 16, 32),
                         max_seq_len=64, dtype="float32", decode_steps=K,
                         use_pallas_attention=kernel),
            CacheConfig(kind="paged", page_size=8, num_pages=64,
                        max_pages_per_session=8, kv_quant=kv_quant),
        )
        if kv_quant:
            assert isinstance(eng.cache, QuantizedPagedKVCache)
        return eng.generate(ps_, opts)

    ref = run(None, 1, False)
    for name, out in (("kernel", run("int8", 1, True)),
                      ("tail", run("int8", 4, True)),
                      ("gather", run("int8", 1, False))):
        agree = sum(a == b for a, b in zip(ref, out))
        assert agree >= len(ref) - 1, (name, ref, out)


@pytest.mark.parametrize(
    "PS,SLOTS,prompt_lens",
    [(8, 4, [9, 14, 5]), (64, 12, [70, 130, 5])],
    ids=["gathered-cap32", "inplace-cap768"],
)
def test_paged_fused_kernel_tail_matches_xla_path(PS, SLOTS, prompt_lens):
    """kernel-mode fused decode (in-kernel quantize + io-aliased int8 tail +
    the big segment in one Pallas call) emits the same tokens as the XLA
    two-segment path and leaves the pool within 1 int8 LSB (the XLA path's
    bf16 tail rounds once more before its flush-quantize; the kernel
    quantizes the full-precision values directly). Under a table capacity of
    ``INPLACE_CTX`` the big segment is the gathered stack
    (``quantized_fused_decode_attention``); at 768 it is the pool itself,
    read in place by ``quantized_paged_fused_attention``."""
    import numpy as np

    from distributed_llm_inference_tpu.cache.paged import (
        PageAllocator,
        QuantizedPagedKVCache,
    )
    from distributed_llm_inference_tpu.models import llama

    cfg = ModelConfig(vocab_size=128, hidden_size=64, intermediate_size=160,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, K = 3, 4
    inplace = PS * SLOTS >= QuantizedPagedKVCache.INPLACE_CTX

    def run(use_kernel):
        cache = QuantizedPagedKVCache.create(
            cfg.num_layers, B, B * SLOTS + 1, PS, SLOTS, cfg.num_kv_heads,
            cfg.head_dim, jnp.float32, use_kernel=use_kernel,
        )
        alloc = PageAllocator(B * SLOTS + 1)
        for r in range(B):
            cache = cache.assign_pages(r, alloc.alloc(SLOTS))
        assert cache._fused_inplace == (use_kernel and inplace)
        lens = jnp.asarray(prompt_lens, jnp.int32)
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (B, -(-max(prompt_lens) // 16) * 16), 0,
            cfg.vocab_size,
        )
        logits, cache = llama.model_apply(cfg, params, toks, cache, lens)
        active = jnp.ones((B,), bool)

        def step_fn(i, lg, alive):
            nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            return nxt, alive.astype(jnp.int32), alive, nxt

        first = jnp.argmax(
            logits[jnp.arange(B), lens - 1], -1
        )[:, None].astype(jnp.int32)
        emits, cache = llama.multi_decode_apply(
            cfg, params, first, cache, K, step_fn, active,
            active.astype(jnp.int32),
        )
        return np.asarray(emits), cache

    e0, c0 = run(False)
    e1, c1 = run(True)
    np.testing.assert_array_equal(e0, e1)
    np.testing.assert_array_equal(
        np.asarray(c0.lengths), np.asarray(c1.lengths)
    )
    dk = np.abs(
        np.asarray(c0.k_pages, np.int32) - np.asarray(c1.k_pages, np.int32)
    )
    dv = np.abs(
        np.asarray(c0.v_pages, np.int32) - np.asarray(c1.v_pages, np.int32)
    )
    assert dk.max() <= 1 and dv.max() <= 1, (dk.max(), dv.max())


def test_kernel_less_tail_matches_per_step_path():
    """The write-behind tail WITHOUT the kernel (the gathered XLA form a mesh
    engine and the CPU take): 16 fused steps over a float32 ``PagedKVCache``
    give the logits of 16 ``model_apply`` steps and, after the flush, the
    pages the per-step writes leave. Rows stop inside the window, one row
    has ``num_new`` 0 from the start and no page mapped, the sliding window
    is shorter than the contexts, and no row's table is mapped to its end."""
    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, sliding_window=12,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, K, PS, SLOTS = 4, 16, 4, 12
    lens = jnp.asarray([9, 14, 5, 0], jnp.int32)
    budget = jnp.asarray([16, 5, 11, 0], jnp.int32)
    cache = PagedKVCache.create(
        cfg.num_layers, B, 32, PS, SLOTS, cfg.num_kv_heads, cfg.head_dim,
        dtype=jnp.float32,
    )
    assert cache.has_tail and not cache.use_kernel
    alloc = PageAllocator(32)
    for row in range(3):  # the pages a row writes, and no slot past them
        need = -(-int(lens[row] + budget[row]) // PS)
        cache = cache.assign_pages(row, alloc.alloc(need))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 16), 0, 128)
    logits, cache = llama.model_apply(cfg, params, tokens, cache, lens)
    first = jnp.argmax(
        logits[jnp.arange(B), jnp.maximum(lens - 1, 0)], -1
    )[:, None].astype(jnp.int32)
    alive0 = budget > 0

    def advance(i, lg, alive):
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
        return nxt, alive & (i + 1 < budget)

    def step_fn(i, lg, alive):
        nxt, still = advance(i, lg, alive)
        return nxt, still.astype(jnp.int32), still, (nxt, lg, alive)

    (toks, lgs, alive), fused = jax.jit(
        lambda c: llama.multi_decode_apply(
            cfg, params, first, c, K, step_fn, alive0,
            alive0.astype(jnp.int32),
        )
    )(cache)

    one = jax.jit(
        lambda t, c, n: llama.model_apply(cfg, params, t, c, n)
    )
    tok, ref, live = first, cache, alive0
    for i in range(K):
        lg, ref = one(tok, ref, live.astype(jnp.int32))
        np.testing.assert_array_equal(np.asarray(alive[i]), np.asarray(live))
        rows = np.asarray(live)
        np.testing.assert_allclose(
            np.asarray(lgs[i])[rows], np.asarray(lg[:, 0])[rows],
            atol=2e-5, rtol=2e-5,
        )
        nxt, live = advance(i, lg[:, 0], live)
        np.testing.assert_array_equal(
            np.asarray(toks[i])[rows], np.asarray(nxt)[rows]
        )
        tok = nxt[:, None]
    np.testing.assert_array_equal(
        np.asarray(fused.lengths), np.asarray(lens + budget)
    )
    np.testing.assert_array_equal(
        np.asarray(fused.lengths), np.asarray(ref.lengths)
    )
    # page 0 absorbs the writes of rows that stopped, in both forms
    for got, want in ((fused.k_pages, ref.k_pages),
                      (fused.v_pages, ref.v_pages)):
        np.testing.assert_allclose(
            np.asarray(got[:, 1:]), np.asarray(want[:, 1:]),
            atol=1e-6, rtol=0,
        )


@pytest.mark.parametrize(
    "PS,K,SLOTS", [(8, 16, 8), (64, 16, 4), (4, 16, 12), (16, 4, 6)],
    ids=["three-pages", "inside-a-page", "five-pages", "short-window"],
)
def test_kernel_less_flush_writes_what_the_scatter_writes(PS, K, SLOTS):
    """``PagedKVCache._flush_rows`` (a row's pages read, merged, written in
    place) against the prefill scatter it stands for, bit for bit: a row at
    length 0, a row that ends on the table's last position, a row that
    wrote part of its window, a row that wrote nothing, and a row whose
    window runs past the table (diverted to the null page in both)."""
    L, B, H, D, P = 2, 5, 2, 8, 64
    rng = np.random.default_rng(PS)
    cache = PagedKVCache.create(L, B, P, PS, SLOTS, H, D, jnp.float32)
    cache = cache.replace(
        k_pages=jax.random.normal(jax.random.PRNGKey(1), cache.k_pages.shape),
        v_pages=jax.random.normal(jax.random.PRNGKey(2), cache.v_pages.shape),
    )
    cap = PS * SLOTS
    lens = np.array([0, cap - K, rng.integers(1, cap - K),
                     rng.integers(1, cap - K), cap - 3], np.int32)
    wrote = np.array([K, K, rng.integers(1, K), 0, K], np.int32)
    table, at = np.zeros((B, SLOTS), np.int32), 1
    for r in range(B):
        n = min(-(-int(lens[r] + wrote[r]) // PS), SLOTS)
        table[r, :n] = np.arange(at, at + n)
        at += n
    cache = cache.replace(
        page_table=jnp.asarray(table), lengths=jnp.asarray(lens)
    )
    tail = tuple(
        jax.random.normal(jax.random.PRNGKey(3 + i), t.shape)
        for i, t in enumerate(cache.tail_init(K))
    )                                             # [L, B, H, K, D]
    got = jax.jit(lambda c, t, n: c.tail_flush(t, n))(
        cache, tail, jnp.asarray(wrote)
    )
    q_pos = jnp.asarray(lens)[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
    want_k, want_v = jax.vmap(
        lambda lk, lv, tk, tv: cache._scatter(
            lk, lv, tk, tv, q_pos, jnp.asarray(wrote)
        )
    )(cache.k_pages, cache.v_pages,
      jnp.moveaxis(tail[0], 2, 3), jnp.moveaxis(tail[1], 2, 3))
    np.testing.assert_array_equal(np.asarray(got.lengths), lens + wrote)
    np.testing.assert_array_equal(
        np.asarray(got.k_pages[:, 1:]), np.asarray(want_k[:, 1:])
    )
    np.testing.assert_array_equal(
        np.asarray(got.v_pages[:, 1:]), np.asarray(want_v[:, 1:])
    )


# (page size, piece width S, valid tokens, the row's mapped slots of 6)
_FRESH_INSTALLS = {
    "page-aligned": (8, 16, 16, 2),
    "ragged-last-page": (8, 32, 19, 3),
    "no-tokens": (8, 16, 0, 2),
    "slots-past-the-run": (4, 16, 3, 5),
    "a-slot-left-unmapped": (8, 32, 21, 2),
    "piece-wider-than-the-table": (4, 32, 24, 6),
}


@pytest.mark.parametrize("case", sorted(_FRESH_INSTALLS))
def test_a_fresh_rows_install_leaves_what_the_scatter_leaves(case):
    """A fresh row's K/V installed as whole page tiles (``ingest_row``, what
    the engine's ``_prefill_row_fresh`` does with its scratch cache) against
    the position-by-position ``_scatter`` of the same K/V at positions
    ``0..n``: the table and ``lengths`` equal, every page but the null page
    equal value for value at the positions the row owns (a page-aligned run,
    a ragged last page, no token at all), every page past the run untouched
    though the table maps it and the piece is wider, and a slot the table
    leaves at 0 diverted to the null page in both. Past ``n`` in the last
    page the install leaves the K/V's own where the scatter leaves the
    pool's: no reader looks there (``lengths``)."""
    PS, S, n, mapped = _FRESH_INSTALLS[case]
    L, B, H, D, P, SLOTS, row = 2, 3, 2, 8, 24, 6, 1
    cache = PagedKVCache.create(L, B, P, PS, SLOTS, H, D, jnp.float32)
    cache = cache.replace(
        k_pages=jax.random.normal(jax.random.PRNGKey(1), cache.k_pages.shape),
        v_pages=jax.random.normal(jax.random.PRNGKey(2), cache.v_pages.shape),
    )
    table = np.zeros((B, SLOTS), np.int32)
    table[0, :3] = (1, 2, 3)                      # a neighbour's pages
    table[row, :mapped] = np.arange(7, 7 + mapped)
    cache = cache.replace(page_table=jnp.asarray(table))
    k, v = (
        jax.random.normal(jax.random.PRNGKey(s), (L, 1, S, H, D))
        for s in (3, 4)
    )

    @jax.jit
    def install(cache, k, v):
        sub = cache.select_row(row).ingest_row(k, v, jnp.int32(n))
        return cache.merge_row(sub, row)

    @jax.jit
    def scatter(cache, k, v):
        sub = cache.select_row(row)
        q_pos, num_new = sub.q_positions(S), jnp.full((1,), n, jnp.int32)
        new_k, new_v = jax.vmap(
            lambda lk, lv, kk, vv: sub._scatter(lk, lv, kk, vv, q_pos, num_new)
        )(sub.k_pages, sub.v_pages, k, v)
        sub = sub.replace(k_pages=new_k, v_pages=new_v).advance(num_new)
        return cache.merge_row(sub, row)

    got, want = install(cache, k, v), scatter(cache, k, v)
    np.testing.assert_array_equal(np.asarray(got.page_table), table)
    np.testing.assert_array_equal(
        np.asarray(got.lengths), np.asarray(want.lengths)
    )
    assert got.lengths.tolist() == [0, n, 0]
    owned = min(-(-n // PS), SLOTS)
    last = table[row, owned - 1] if n % PS and owned else None
    for plane, ref, src in (("k_pages", want.k_pages, k),
                            ("v_pages", want.v_pages, v)):
        new, ref = np.asarray(getattr(got, plane)), np.array(ref)
        if last:                    # the ragged page's positions past n
            at = (owned - 1) * PS
            ref[:, last, :, n % PS:] = np.swapaxes(
                np.asarray(src)[:, 0, at + n % PS:at + PS], 1, 2
            )
        np.testing.assert_array_equal(new[:, 1:], ref[:, 1:])
        untouched = [p for p in range(1, P) if p not in table[row, :owned]]
        np.testing.assert_array_equal(
            new[:, untouched], np.asarray(getattr(cache, plane))[:, untouched]
        )


def _subclasses(cls):
    for sub in cls.__subclasses__():
        yield sub
        yield from _subclasses(sub)


def test_only_the_value_dtype_pool_installs_a_fresh_rows_kv():
    """``fresh_install()`` (what the engine's fresh-row prefill asks) is True
    for the value-dtype pool ALONE: every subclass there is (int8, latent,
    indexed, two-pool, the window views, retention, and the classes their
    factories make) says False without a line of its own, and so does one
    written later, until it says otherwise itself."""
    from distributed_llm_inference_tpu.cache import latent, paged, retention

    made = [
        retention.retention_cache_class(16, 1e-6),
        retention.retention_cache_class(16, 1e-6, quantized=True),
        paged.indexed_cache_class(False, 8), paged.indexed_cache_class(True, 8),
    ]

    class Later(PagedKVCache):
        pass

    assert PagedKVCache.fresh_install() is True
    others = set(_subclasses(PagedKVCache))
    assert others >= {
        paged.QuantizedPagedKVCache, paged.IndexedPagedKVCache,
        paged.TwoPoolPagedKVCache, paged._WindowPagedKVCache,
        latent.LatentPagedKVCache, retention.RetentionPagedKVCache,
        Later, *made,
    }
    for cls in others:
        assert cls.fresh_install() is False, cls.__name__
