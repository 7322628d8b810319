"""Pallas paged-attention decode kernel vs the gather+XLA oracle.

The kernel (``ops/paged_attention.py``) must reproduce
``update_and_gather`` + ``gqa_attention`` exactly (same masks, same softmax
semantics) for every table/length pattern the allocator can produce, and the
engine must produce identical streams with it enabled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.paged import PagedKVCache, PageAllocator
from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig, ModelConfig
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.ops.attention import causal_mask, gqa_attention
from distributed_llm_inference_tpu.ops.paged_attention import paged_attention


def _random_pool(key, *, b, t_pages, page_size, hq, hkv, d, lengths):
    """Build a random page pool + per-row tables covering ``lengths``."""
    num_pages = b * t_pages + 1
    kk, kv, kq = jax.random.split(key, 3)
    k_pages = jax.random.normal(kk, (num_pages, hkv, page_size, d), jnp.float32)
    v_pages = jax.random.normal(kv, (num_pages, hkv, page_size, d), jnp.float32)
    q = jax.random.normal(kq, (b, 1, hq, d), jnp.float32)

    alloc = PageAllocator(num_pages)
    table = np.zeros((b, t_pages), np.int32)
    for row in range(b):
        n = -(-int(lengths[row]) // page_size)  # ceil
        table[row, :n] = alloc.alloc(n)
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


def _oracle(q, k_pages, v_pages, table, lengths, sliding_window=None):
    b, t_pages = table.shape
    hkv, page_size, d = k_pages.shape[1:]
    max_len = t_pages * page_size
    k_all = jnp.take(k_pages, table, axis=0).transpose(0, 1, 3, 2, 4).reshape(
        b, max_len, hkv, d
    )
    v_all = jnp.take(v_pages, table, axis=0).transpose(0, 1, 3, 2, 4).reshape(
        b, max_len, hkv, d
    )
    kv_pos = jnp.broadcast_to(jnp.arange(max_len, dtype=jnp.int32)[None], (b, max_len))
    q_pos = lengths[:, None] - 1
    mask = causal_mask(q_pos, kv_pos, kv_pos < lengths[:, None], sliding_window)
    return gqa_attention(q, k_all, v_all, mask)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_kernel_matches_oracle(hq, hkv):
    lengths = [1, 7, 17, 32]
    q, kp, vp, table, lens = _random_pool(
        jax.random.PRNGKey(0), b=4, t_pages=4, page_size=8, hq=hq, hkv=hkv,
        d=16, lengths=lengths,
    )
    out = paged_attention(q, kp, vp, table, lens)
    ref = _oracle(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_kernel_sliding_window():
    lengths = [5, 23, 32, 9]
    q, kp, vp, table, lens = _random_pool(
        jax.random.PRNGKey(1), b=4, t_pages=4, page_size=8, hq=4, hkv=2,
        d=16, lengths=lengths,
    )
    out = paged_attention(q, kp, vp, table, lens, sliding_window=6)
    ref = _oracle(q, kp, vp, table, lens, sliding_window=6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_kernel_rejects_prefill_shapes():
    q = jnp.zeros((1, 4, 4, 16))
    kp = jnp.zeros((4, 2, 8, 16))
    with pytest.raises(ValueError):
        paged_attention(q, kp, kp, jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32))


def test_cache_attend_kernel_matches_gather():
    """Full decoder-layer decode step via cache.attend: kernel vs gather."""
    cfg = ModelConfig(
        vocab_size=64, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)

    def run(use_kernel):
        cache = PagedKVCache.create(
            cfg.num_layers, 2, num_pages=32, page_size=4,
            max_pages_per_session=8, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, dtype=jnp.float32, use_kernel=use_kernel,
        )
        alloc = PageAllocator(32)
        for row in range(2):
            cache = cache.assign_pages(row, alloc.alloc(4))
        num_new = jnp.asarray([9, 6], jnp.int32)
        logits, cache = llama.model_apply(cfg, params, tokens, cache, num_new)
        outs = [logits]
        one = jnp.ones((2,), jnp.int32)
        for i in range(4):
            logits, cache = llama.model_apply(
                cfg, params, tokens[:, i : i + 1], cache, one
            )
            outs.append(logits)
        return outs

    ref, out = run(False), run(True)
    # Prefill (S>1) takes the gather path in both; decode steps diverge paths.
    for a, b in zip(ref, out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5, rtol=1e-5)


def test_engine_with_kernel_matches_without():
    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    reqs = [rng.integers(0, cfg.vocab_size, size=rng.integers(3, 12)).tolist()
            for _ in range(6)]

    def run(use_pallas):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(
                max_batch_size=4, prefill_buckets=(8, 16), max_seq_len=64,
                dtype="float32", use_pallas_attention=use_pallas,
            ),
            CacheConfig(kind="paged", page_size=8, num_pages=64,
                        max_pages_per_session=8),
        )
        return eng.generate(reqs, SamplingOptions(max_new_tokens=8))

    assert run(False) == run(True)


def test_paged_tail_engine_parity():
    """Paged cache + kernel + fused K-step decode (pool read-only, tail
    merged via joint softmax) reproduces plain per-token decoding."""
    import numpy as np

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
    rng = np.random.default_rng(31)
    ps_ = [rng.integers(0, 128, size=int(rng.integers(3, 12))).tolist()
           for _ in range(5)]
    opts = SamplingOptions(max_new_tokens=9)

    calls = {"tail": 0}
    real = llama.multi_decode_apply

    def spy(*a, **k):
        calls["tail"] += 1
        return real(*a, **k)

    def run(K, kernel):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=4, prefill_buckets=(8, 16, 32),
                         max_seq_len=64, dtype="float32", decode_steps=K,
                         use_pallas_attention=kernel),
            CacheConfig(kind="paged", page_size=8, num_pages=64,
                        max_pages_per_session=8),
        )
        return eng.generate(ps_, opts)

    llama.multi_decode_apply = spy
    try:
        tail_out = run(4, True)
    finally:
        llama.multi_decode_apply = real
    assert calls["tail"] > 0, (
        "paged tail path never ran (vacuous parity — the engine gate is dead)"
    )
    assert tail_out == run(1, False)


def test_paged_kernel_stats_merge_oracle():
    """paged_attention(return_stats=True) + merge_softmax_segments over a
    tail == one full attention over pool∪tail."""
    import numpy as np

    from distributed_llm_inference_tpu.ops.attention import (
        causal_mask,
        gqa_attention,
        merge_softmax_segments,
    )

    rng = np.random.default_rng(5)
    B, HKV, G, D, PS, SLOTS, K = 3, 2, 2, 16, 8, 3, 5
    HQ = HKV * G
    pool_pages = SLOTS * B + 1
    kp = jnp.asarray(rng.normal(size=(pool_pages, HKV, PS, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pool_pages, HKV, PS, D)), jnp.float32)
    table = jnp.asarray(
        np.arange(1, B * SLOTS + 1).reshape(B, SLOTS), jnp.int32
    )
    base_len = jnp.asarray([13, 7, 0], jnp.int32)
    tail_len = jnp.asarray([3, 2, 1], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, HQ, D)), jnp.float32)
    tk = jnp.asarray(rng.normal(size=(B, K, HKV, D)), jnp.float32)
    tv = jnp.asarray(rng.normal(size=(B, K, HKV, D)), jnp.float32)
    tail_valid = jnp.arange(K)[None, :] < tail_len[:, None]

    from distributed_llm_inference_tpu.ops.paged_attention import paged_attention

    out_pool, m, l = paged_attention(
        q, kp, vp, table, base_len, q_positions=base_len + tail_len - 1,
        return_stats=True,
    )
    merged = merge_softmax_segments(q, out_pool, m, l, tk, tv, tail_valid)

    # Oracle: gather pool rows contiguous, concat tail, one dense attention.
    T = SLOTS * PS
    gk = kp[table].transpose(0, 1, 3, 2, 4).reshape(B, T, HKV, D)
    gv = vp[table].transpose(0, 1, 3, 2, 4).reshape(B, T, HKV, D)
    k_all = jnp.concatenate([gk, tk], axis=1)
    v_all = jnp.concatenate([gv, tv], axis=1)
    pos = jnp.arange(T + K)[None, :]
    valid = jnp.where(
        pos < T, pos < base_len[:, None],
        (pos - T) < tail_len[:, None],
    )
    mask = valid[:, None, :]
    ref = gqa_attention(q, k_all, v_all, mask)
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


# -- the in-place fused decode kernel (int8 pool, write-behind tail) ----------

_FUSED = dict(ps=8, t=20, kt=4, d=16, layers=2, layer=1)


def _fused_rows():
    """Row lengths the sweep's bounds must get right: an empty slot, one
    token, a page less one / exactly / plus one, a count of live pages the
    block does not divide (with a partial last page), the full table, an
    INACTIVE row (nothing in the pool, nothing valid in the tail), an
    inactive row whose length is STALE (a released slot: the engine resets
    a row at its next admission; every page of it is poisoned, and the
    kernel must not believe the length), a row whose length is longer
    than the table: the sweep stops at the table's end and reads no page
    id past it; and rows of exactly ``n``, ``n + 1``, ``2n`` and ``2n + 3``
    live pages (``n`` pages a block): a block is ONE tile, a row's last one
    padded to ``n`` pages under the mask."""
    from distributed_llm_inference_tpu.ops.paged_attention import (
        _pages_per_block,
    )

    f = _FUSED
    n = _pages_per_block(f["t"], 2, f["ps"], f["d"], f["kt"])
    assert 2 < n and 2 * n + 3 < f["t"] and f["t"] % n, "want partial blocks"
    ps = f["ps"]
    lengths = [0, 1, ps - 1, ps, ps + 1, (n + 3) * ps - 5, f["t"] * ps, 0,
               3 * ps + 1, (f["t"] + 2) * ps + 3,
               n * ps, (n + 1) * ps - 3, 2 * n * ps, (2 * n + 3) * ps - 2]
    active = [True] * 7 + [False, False] + [True] * 5
    return np.asarray(lengths, np.int32), np.asarray(active), n


def _fused_inputs(seed, g, step, window=None, select=False):
    f = _FUSED
    ps, t, kt, d, layers = f["ps"], f["t"], f["kt"], f["d"], f["layers"]
    hkv = 2
    lengths, active, _ = _fused_rows()
    b = len(lengths)
    rng = np.random.default_rng(seed)
    pages = b * t + 1
    pool = [rng.integers(-127, 128, (layers, pages, hkv, ps, d)).astype(np.int8)
            for _ in range(2)]
    scales = [rng.uniform(0.01, 0.03, (layers, pages, hkv, ps)).astype(np.float32)
              for _ in range(2)]
    # A shuffled, non-contiguous table: slot order is not pool order.
    table = (rng.permutation(pages - 1)[: b * t] + 1).reshape(b, t).astype(np.int32)
    # Poison what no live token owns: the null page, every page past a
    # row's length and every page wholly before its window. A read of one
    # shows as NaN (or as a gross error).
    live_pages = np.where(active, np.minimum(-(-lengths // ps), t), 0)
    first_page = np.zeros(b, np.int64)
    if window is not None:
        first_page = np.minimum(
            np.maximum(lengths + step - window + 1, 0) // ps, live_pages
        )
    dead = [0] + [int(table[r, s]) for r in range(b) for s in range(t)
                  if not first_page[r] <= s < live_pages[r]]
    for plane in pool:
        plane[:, dead] = np.where(rng.random(plane[:, dead].shape) < 0.5, 127, -127)
    for plane in scales:
        plane[:, dead] = np.nan
    tail = [rng.integers(-127, 128, (layers, b, hkv, kt, d)).astype(np.int8)
            for _ in range(2)]
    tscale = [rng.uniform(0.01, 0.03, (layers, b, hkv, kt)).astype(np.float32)
              for _ in range(2)]
    bf16 = lambda x: jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
    q = bf16(rng.normal(size=(b, 1, hkv * g, d)))
    k_new = bf16(rng.normal(size=(b, 1, hkv, d)))
    v_new = bf16(rng.normal(size=(b, 1, hkv, d)))
    tail_valid_len = np.where(active, step + 1, 0).astype(np.int32)
    q_positions = (lengths + step).astype(np.int32)
    more = {}
    if select:  # positive = attend: about half of every row's slots
        more["select"] = (
            jnp.asarray(rng.normal(size=(b, t, 1, ps)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, 1, kt)), jnp.float32),
        )
    return dict(
        **more,
        q=q, k_new=k_new, v_new=v_new,
        pool_k=jnp.asarray(pool[0]), pool_ks=jnp.asarray(scales[0]),
        pool_v=jnp.asarray(pool[1]), pool_vs=jnp.asarray(scales[1]),
        tail_k=jnp.asarray(tail[0]), tail_ks=jnp.asarray(tscale[0]),
        tail_v=jnp.asarray(tail[1]), tail_vs=jnp.asarray(tscale[1]),
        layer_idx=jnp.asarray(f["layer"], jnp.int32),
        step_idx=jnp.asarray(step, jnp.int32),
        page_table=jnp.asarray(table), base_len=jnp.asarray(lengths),
        tail_valid_len=jnp.asarray(tail_valid_len),
        q_positions=jnp.asarray(q_positions),
    )


def _fused_oracle(a, window):
    """float32 ``jax.numpy`` over the dequantised pool and tail: the new
    token quantised (symmetric absmax, as ``_scatter_q``) into tail slot
    ``step``, then one softmax over pool positions ``< base_len`` (of a row
    that is decoding: ``tail_valid_len > 0``) and tail slots
    ``< tail_valid_len``, the window measured from ``q_positions``."""
    f32 = jnp.float32
    layer, step = int(a["layer_idx"]), int(a["step_idx"])
    table, base_len = a["page_table"], a["base_len"]
    b, t = table.shape
    hkv, ps, d = a["pool_k"].shape[2:]
    kt = a["tail_k"].shape[3]

    @jax.jit  # compiled like the kernel: XLA turns ``/ 127`` into a multiply
    def quant(x):  # [B, 1, Hkv, D] -> int8 [B, Hkv, D], f32 [B, Hkv]
        x = x[:, 0].astype(f32)
        sc = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0
        return jnp.clip(jnp.round(x / sc[..., None]), -127, 127).astype(jnp.int8), sc

    kq, ksc = quant(a["k_new"])
    vq, vsc = quant(a["v_new"])
    tails = (
        a["tail_k"].at[layer, :, :, step].set(kq),
        a["tail_ks"].at[layer, :, :, step].set(ksc),
        a["tail_v"].at[layer, :, :, step].set(vq),
        a["tail_vs"].at[layer, :, :, step].set(vsc),
    )

    pos = jnp.arange(t * ps)[None, :]
    decoding = a["tail_valid_len"][:, None] > 0
    pool_valid = (pos < base_len[:, None]) & decoding
    tpos = jnp.arange(kt)[None, :]
    tail_valid = tpos < a["tail_valid_len"][:, None]
    if window is not None:
        qp = a["q_positions"][:, None]
        pool_valid &= pos > qp - window
        tail_valid &= base_len[:, None] + tpos > qp - window
    if "select" in a:
        pool_valid &= a["select"][0].reshape(b, t * ps) > 0
        tail_valid &= a["select"][1][:, 0] > 0
    valid = jnp.concatenate([pool_valid, tail_valid], axis=1)  # [B, T*PS+KT]

    def deq(pages, scales, tail, tscale):  # -> [B, Hkv, T*PS + KT, D] f32
        x = pages[layer][table].astype(f32) * scales[layer][table][..., None]
        x = x.transpose(0, 2, 1, 3, 4).reshape(b, hkv, t * ps, d)
        y = tail[layer].astype(f32) * tscale[layer][..., None]
        x = jnp.concatenate([x, y], axis=2)
        return jnp.where(valid[:, None, :, None], x, 0.0)

    k = deq(a["pool_k"], a["pool_ks"], tails[0], tails[1])
    v = deq(a["pool_v"], a["pool_vs"], tails[2], tails[3])
    g = a["q"].shape[2] // hkv
    q = a["q"][:, 0].astype(f32).reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bhtd->bhgt", q, k, precision="highest") * d**-0.5
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid[:, None, None, :], jnp.exp(s - jnp.where(m > -jnp.inf, m, 0.0)), 0.0)
    out = jnp.einsum("bhgt,bhtd->bhgd", p, v, precision="highest")
    out = out / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-20)
    return out.reshape(b, 1, hkv * g, d), tails


def _fused_windows():
    """No window; one of 19 that cuts whole leading pages off the long rows
    (and none off the short ones); one of ``n + 3`` pages and 3 positions,
    under which the ``2n``- and ``2n + 3``-page rows keep ``n + 4`` live
    pages from a first page that is no multiple of ``n``: the window cuts
    into what would be a full block, and the blocks start at ``lo``."""
    n = _fused_rows()[2]
    return [None, 2 * _FUSED["ps"] + 3, (n + 3) * _FUSED["ps"] + 3]


@pytest.mark.parametrize("step", [0, _FUSED["kt"] - 1], ids=["step0", "stepKT-1"])
@pytest.mark.parametrize("g", [4, 1], ids=["G4", "G1"])
@pytest.mark.parametrize(
    "window", _fused_windows(), ids=["nowindow", "window19", "window-cuts-a-block"]
)
def test_fused_inplace_kernel_matches_oracle(window, g, step):
    """``quantized_paged_fused_attention`` — the kernel every int8 paged
    engine decodes through past ``INPLACE_CTX`` — against the oracle, with
    every page no live token owns poisoned."""
    from distributed_llm_inference_tpu.ops.paged_attention import (
        quantized_paged_fused_attention,
    )

    a = _fused_inputs(seed=7 + step, g=g, step=step, window=window)
    out, tk, tks, tv, tvs = quantized_paged_fused_attention(
        **a, sliding_window=window
    )
    ref, tails = _fused_oracle(a, window)
    out = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(out).all(), "a dead page was read"
    lengths, active, _ = _fused_rows()
    assert (out[~active] == 0).all(), "an inactive row attends to nothing"
    # bf16 operands into f32 sums: the probabilities round to 8 bits before
    # they meet V (|V| <= 127 * 0.03).
    np.testing.assert_allclose(out, np.asarray(ref), atol=0.03, rtol=0.02)
    for got, want in zip((tk, tks, tv, tvs), tails):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("window", [None, 2 * _FUSED["ps"] + 3],
                         ids=["nowindow", "window19"])
def test_fused_inplace_kernel_a_page_a_block(window, monkeypatch):
    """A table so wide that the budget leaves a block ONE page
    (``k-exaone-236b-a23b.mixedlen``'s 227 slots): the tile the sweep always
    had, no place of it padded, over the same poisoned rows."""
    from distributed_llm_inference_tpu.ops import paged_attention as pa

    a = _fused_inputs(seed=9, g=4, step=1, window=window)
    _, active, _ = _fused_rows()
    monkeypatch.setattr(pa, "_pages_per_block", lambda *a, **k: 1)
    out, *tails = pa.quantized_paged_fused_attention(**a, sliding_window=window)
    ref, want = _fused_oracle(a, window)
    out = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(out).all(), "a dead page was read"
    assert (out[~active] == 0).all()
    np.testing.assert_allclose(out, np.asarray(ref), atol=0.03, rtol=0.02)
    for got, exact in zip(tails, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exact))


def test_the_window_case_cuts_into_a_full_block():
    """What the third window of :func:`_fused_windows` is there for, by the
    kernel's own arithmetic: rows whose first live page is no multiple of
    ``n`` and that still hold a full block and more."""
    from distributed_llm_inference_tpu.ops.paged_attention import _live_pages

    lengths, active, n = _fused_rows()
    f, window = _FUSED, _fused_windows()[2]
    lo, hi = _live_pages(lengths, lengths, f["ps"], f["t"], window, np)
    cut = active & (lo % n != 0) & (hi - lo > n)
    assert cut.sum() >= 2, (lo, hi)
    assert any((hi - lo)[cut] % n), "and what is left is a tile padded to n"


# -- the scale rows by the live page (PR 61) -----------------------------------

_BY_PAGE = {
    # two planes at keye's, Mistral's and ouro's kv heads (8, 4 and 2 pages a
    # block)
    "hkv4": dict(hkv=4),
    "hkv8": dict(hkv=8),
    "hkv16": dict(hkv=16),
    "window-of-2-pages-on-a-wide-table": dict(hkv=2, t=40, window=2 * 64),
    "selection": dict(hkv=4, select=True),
    # the first row's pages carry NaN scale rows (its results are NaN in
    # both forms): they stay in the kernel's scale buffer under the dead
    # places of the next rows' last blocks
    "nan-rows-in-the-dead-places": dict(hkv=2, nan_row=True),
    # two rows of a 32-token page are 64 lanes: Mosaic would refuse the copy
    "fallback-page-of-32": dict(hkv=2, ps=32, by_page=False),
}


def _by_page_inputs(hkv, ps=64, t=20, window=None, select=False,
                    nan_row=False, **_):
    """A call of the copies' form at a page size whose two scale rows are
    whole tiles (``ps`` 64): rows of one token, a page and a bit, a last
    block short of ``n`` pages, whole blocks, the full table, an empty
    decoding row, and a row that is NOT decoding under a stale length; what
    no live token owns is poisoned (values at the int8 ends, scales NaN)."""
    from distributed_llm_inference_tpu.ops.paged_attention import (
        _pages_per_block,
    )

    d, kt, layers, layer, step, g = 16, 4, 2, 1, 2, 2
    n = _pages_per_block(t, hkv, ps, d, kt)
    lengths = np.asarray(
        [n * ps, 1, ps + 5, (n + 1) * ps - 3, 2 * n * ps, t * ps, 0,
         3 * ps + 1], np.int32,
    )
    active = np.asarray([True] * 7 + [False])
    assert 1 < n and 2 * n <= t, "want a last block short of n pages"
    b = len(lengths)
    rng = np.random.default_rng(61 + hkv)
    pages = b * t + 1
    pool = [rng.integers(-127, 128, (layers, pages, hkv, ps, d)).astype(np.int8)
            for _ in range(2)]
    scales = [rng.uniform(0.01, 0.03, (layers, pages, hkv, ps)).astype(np.float32)
              for _ in range(2)]
    table = (rng.permutation(pages - 1)[: b * t] + 1).reshape(b, t).astype(np.int32)
    hi = np.where(active, np.minimum(-(-lengths // ps), t), 0)
    lo = np.zeros(b, np.int64)
    if window is not None:
        lo = np.minimum(np.maximum(lengths + step - window + 1, 0) // ps, hi)
    dead = [0] + [int(table[r, s]) for r in range(b) for s in range(t)
                  if not lo[r] <= s < hi[r]]
    if nan_row:
        dead += [int(p) for p in table[0, : hi[0]]]
    for plane in pool:
        plane[:, dead] = np.where(rng.random(plane[:, dead].shape) < 0.5, 127, -127)
    for plane in scales:
        plane[:, dead] = np.nan
    bf16 = lambda x: jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
    a = dict(
        q=bf16(rng.normal(size=(b, 1, hkv * g, d))),
        k_new=bf16(rng.normal(size=(b, 1, hkv, d))),
        v_new=bf16(rng.normal(size=(b, 1, hkv, d))),
        pool_k=jnp.asarray(pool[0]), pool_ks=jnp.asarray(scales[0]),
        pool_v=jnp.asarray(pool[1]), pool_vs=jnp.asarray(scales[1]),
        layer_idx=jnp.asarray(layer, jnp.int32),
        step_idx=jnp.asarray(step, jnp.int32),
        page_table=jnp.asarray(table), base_len=jnp.asarray(lengths),
        tail_valid_len=jnp.asarray(np.where(active, step + 1, 0), jnp.int32),
        q_positions=jnp.asarray(lengths + step, jnp.int32),
        sliding_window=window,
    )
    for name, plane in (("k", 0), ("v", 1)):
        a[f"tail_{name}"] = jnp.asarray(rng.integers(
            -127, 128, (layers, b, hkv, kt, d)).astype(np.int8))
        a[f"tail_{name}s"] = jnp.asarray(rng.uniform(
            0.01, 0.03, (layers, b, hkv, kt)).astype(np.float32))
    if select:
        a["select"] = (
            jnp.asarray(rng.normal(size=(b, t, 1, ps)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, 1, kt)), jnp.float32),
        )
    return a, active


@pytest.mark.parametrize("case", _BY_PAGE, ids=list(_BY_PAGE))
def test_scale_rows_by_the_page_are_bit_equal_to_the_gather(case):
    """The copies' form handed ONE joined scale plane (what
    ``QuantizedPagedKVCache.tail_big_stacks`` hands it: the kernel copies a
    live page's scale rows beside its K and V) against the same call handed
    the two planes as stored (the wrapper's XLA gather of every table
    slot's rows, kept as the fall-back by the shape and here as the plain
    reference): results and written tail bit for bit, and no gather left
    beside the kernel."""
    from distributed_llm_inference_tpu.ops import paged_attention as pa

    spec = _BY_PAGE[case]
    a, active = _by_page_inputs(**spec)
    joined = pa.joined_scale_rows(a["pool_ks"], a["pool_vs"])
    assert (joined is not None) == spec.get("by_page", True)
    by_page = dict(a) if joined is None else {
        **a, "pool_ks": joined, "pool_vs": None,
    }

    def call(kw):  # (results, the program's text); the window is static
        kw = dict(kw)
        window = kw.pop("sliding_window")
        fn = lambda x: pa.quantized_paged_fused_attention(
            **x, sliding_window=window
        )
        return fn(kw), str(jax.make_jaxpr(fn)(kw))

    got, program = call(by_page)
    want, reference = call(a)
    assert "gather" in reference
    assert ("gather" in program) == (joined is None)
    for x, y in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(x.astype(jnp.float32)), np.asarray(y.astype(jnp.float32))
        )
    out = np.asarray(got[0].astype(jnp.float32))
    clean = active & (np.arange(len(active)) > 0 if spec.get("nan_row")
                      else True)
    assert np.isfinite(out[clean]).all(), "a dead page's scale row was used"
    assert (out[~active] == 0).all(), "an inactive row attends to nothing"
    if spec.get("nan_row"):
        assert np.isnan(out[0]).all()
    if joined is None:  # and one plane of 64 lanes is refused, not miscopied
        with pytest.raises(ValueError, match="pass both planes"):
            pa.quantized_paged_fused_attention(**{
                **a, "pool_ks": jnp.concatenate(
                    [a["pool_ks"], a["pool_vs"]], -1), "pool_vs": None,
            })


# (table slots, kv heads, page size, stored width, tail slots, stored planes)
# of the pool a cell's decode sweeps (its pinned table; the cap where the
# cell's decode takes another path: tp4's, brumby's) -> pages a block
_CELL_TILES = {
    "mistral-7b.chat": ((47, 8, 64, 128, 16, 2), 4),
    "mistral-7b.reason": ((38, 8, 64, 128, 16, 2), 4),
    "mixtral-8x7b-8l.rag": ((64, 8, 64, 128, 16, 2), 4),
    "mistral-7b-bf16-tp4.chat32": ((64, 8, 64, 128, 16, 2), 4),
    "moonlight-16b-a3b.reason1k": ((59, 1, 64, 576, 16, 1), 8),
    "keye-vl2-30b-a3b.longdoc": ((182, 4, 64, 128, 16, 2), 8),
    "k-exaone-236b-a23b.mixedlen": ((227, 8, 64, 128, 16, 2), 1),
    "glm-5.2.codebase": ((227, 1, 64, 576, 16, 1), 8),
    "xing4.0-29b-a4b.reason1k": ((59, 1, 64, 576, 16, 1), 8),
    "brumby-14b.longgen": ((256, 8, 64, 128, 16, 2), 1),
    "ouro-2.6b.mathchat": ((16, 16, 64, 128, 16, 2), 2),
}


@pytest.mark.parametrize("cell", sorted(_CELL_TILES))
def test_the_tile_keeps_its_width_at_every_cells_shape(cell):
    """``_pages_per_block`` at the eleven cells' shapes returns what it
    returned on the parent of PR 61 (commit 6669722), though a row's scale
    rows no longer lie in VMEM: the slot term of ``_SWEEP_VMEM_BUDGET`` stays
    as a load budget (a wider tile at mixedlen's 227 slots read ``setup_s``
    73.7 -> 93.3 s: PERF.md §6, PR 42), and the wider tile is a later
    issue's."""
    from distributed_llm_inference_tpu.ops.paged_attention import (
        _pages_per_block,
    )

    shape, n = _CELL_TILES[cell]
    assert _pages_per_block(*shape) == n
