"""AttentionPlan + ragged mixed-phase attention contract tests.

The licence for turning ``ragged_attention`` on at all is byte-exact
parity with the legacy bucketed dispatch across the serving matrix —
greedy AND sampled (the plan keeps the legacy admission partition and
PRNG key order; only padded dispatch widths change, which sampling is
invariant to). The ops-level cases pin the ragged kernel itself against
its XLA reference oracle in interpret mode; the engine cases pin the
plan's dispatch-shape policy, chunk/decode co-scheduling, and the
single-widen admission-burst rule (one cache growth per tick, not one
per ladder rung).

Deliberately NOT marked 'slow': these are the correctness gate for the
plan-owned dispatch path and must run in every tier-1 pass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.plan import (
    CHUNKED,
    DECODE,
    PREFILL,
    AttentionPlan,
)
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.ops.ragged_attention import (
    _prep,
    _tile_live,
    latent_ragged_paged_attention,
    quantized_latent_ragged_paged_attention,
    quantized_ragged_paged_attention,
    ragged_attention_reference,
    ragged_paged_attention,
)

CFG = ModelConfig(
    vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=16,
)
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_engine(ragged=None, kind="paged", batch=4, chunk=None, share=0.5,
                kv_quant=None, **ekw):
    return InferenceEngine(
        CFG, PARAMS,
        EngineConfig(
            max_batch_size=batch, prefill_buckets=(8, 16, 32), max_seq_len=64,
            dtype="float32", ragged_attention=ragged,
            prefill_chunk_tokens=chunk, chunk_decode_share=share, **ekw,
        ),
        CacheConfig(
            kind=kind, page_size=8, num_pages=64, max_pages_per_session=8,
            window_length=32, num_sink_tokens=2, kv_quant=kv_quant,
        ),
    )


def prompts(n, lo=3, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, CFG.vocab_size, size=rng.integers(lo, hi)).tolist()
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# Ops level: ragged kernel vs XLA reference oracle (interpret mode on CPU)
# ---------------------------------------------------------------------------

def _mixed_phase_inputs(seed=0, dtype=jnp.float32):
    """One grid call serving a decode row, a chunked row, a full prefill,
    and a short prefill — the kernel's whole reason to exist."""
    rng = np.random.default_rng(seed)
    B, S, Hq, Hkv, D, PS, P, T = 4, 16, 4, 2, 16, 8, 32, 6
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), dtype)
    k_pages = jnp.asarray(rng.standard_normal((P, Hkv, PS, D)), dtype)
    v_pages = jnp.asarray(rng.standard_normal((P, Hkv, PS, D)), dtype)
    table = jnp.asarray(
        rng.permutation(P - 1)[: B * T].reshape(B, T) + 1, jnp.int32
    )
    kv_len = jnp.asarray([40, 33, 16, 5], jnp.int32)  # post-write lengths
    num_new = jnp.asarray([1, 16, 16, 5], jnp.int32)
    kv_len = jnp.minimum(kv_len, T * PS)
    return q, k_pages, v_pages, table, kv_len, num_new


@pytest.mark.parametrize("sliding_window", [None, 12])
def test_ragged_kernel_matches_reference(sliding_window):
    q, kp, vp, table, kv_len, num_new = _mixed_phase_inputs()
    out = ragged_paged_attention(
        q, kp, vp, table, kv_len, num_new,
        sliding_window=sliding_window, interpret=True,
    )
    ref = ragged_attention_reference(
        q, kp, vp, table, kv_len, num_new, sliding_window=sliding_window
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("sliding_window", [None, 12])
def test_quantized_ragged_kernel_matches_reference(sliding_window):
    rng = np.random.default_rng(3)
    q, kp, vp, table, kv_len, num_new = _mixed_phase_inputs(seed=3)
    ks = jnp.asarray(
        0.5 + rng.random(kp.shape[:3]).astype(np.float32)
    )
    vs = jnp.asarray(0.5 + rng.random(vp.shape[:3]).astype(np.float32))
    kq = jnp.asarray(
        np.clip(np.round(np.asarray(kp) / np.asarray(ks)[..., None]),
                -127, 127), jnp.int8,
    )
    vq = jnp.asarray(
        np.clip(np.round(np.asarray(vp) / np.asarray(vs)[..., None]),
                -127, 127), jnp.int8,
    )
    out = quantized_ragged_paged_attention(
        q, kq, ks, vq, vs, table, kv_len, num_new,
        sliding_window=sliding_window, interpret=True,
    )
    ref = ragged_attention_reference(
        q, kq, vq, table, kv_len, num_new, ks_pages=ks, vs_pages=vs,
        sliding_window=sliding_window,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ragged_kernel_multi_query_block():
    """An odd length that spans several q blocks (block_q < S)."""
    rng = np.random.default_rng(9)
    B, S, Hq, Hkv, D, PS, T = 2, 13, 4, 2, 16, 8, 4
    P = 16
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, Hkv, PS, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, Hkv, PS, D)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(P - 1)[: B * T].reshape(B, T) + 1, jnp.int32
    )
    kv_len = jnp.asarray([25, 13], jnp.int32)
    num_new = jnp.asarray([13, 13], jnp.int32)
    out = ragged_paged_attention(
        q, kp, vp, table, kv_len, num_new, block_q=4, interpret=True
    )
    ref = ragged_attention_reference(q, kp, vp, table, kv_len, num_new)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --- the live-tile guard: one predicate for fetch, compute and census -----

@pytest.mark.parametrize("windowed", [False, True])
@pytest.mark.parametrize("seed", range(4))
def test_tile_live_is_exactly_the_element_masks_any(seed, windowed):
    """Over random rows, block sizes and table widths a tile is live if and
    only if the kernels' element mask ``valid`` has a true element: the
    guard never skips a tile with a valid pair and never runs one without.
    ``kv_len`` is drawn on its own, not as ``q_start + num_new``, so keys
    newer than every query and a length that cuts a page are both met."""
    rng = np.random.default_rng(100 * seed + windowed)
    seen = {True: 0, False: 0}
    for _ in range(150):
        bq = int(rng.choice([1, 4, 8, 16]))
        ps = int(rng.choice([4, 8, 16]))
        nq, t = int(rng.integers(1, 6)), int(rng.integers(1, 9))
        q_start = int(rng.integers(0, t * ps))
        num_new = int(rng.integers(0, nq * bq + 1))
        kv_len = int(rng.integers(0, t * ps + 1))
        window = int(rng.integers(1, 2 * t * ps)) if windowed else None
        q_rel = np.arange(nq * bq)[:, None]
        pos = np.arange(t * ps)[None, :]
        valid = (
            (pos < kv_len) & (pos <= q_start + q_rel) & (q_rel < num_new)
        )
        if windowed:
            valid &= pos > q_start + q_rel - window
        brute = valid.reshape(nq, bq, t, ps).any(axis=(1, 3))
        live = _tile_live(
            np.arange(nq)[:, None], np.arange(t)[None, :], q_start, num_new,
            kv_len, block_q=bq, page_size=ps, sliding_window=window,
        )
        np.testing.assert_array_equal(
            np.broadcast_to(live, brute.shape), brute,
            err_msg=f"{bq=} {ps=} {q_start=} {num_new=} {kv_len=} {window=}",
        )
        seen[True] += int(brute.sum())
        seen[False] += int((~brute).sum())
    assert seen[True] > 100 and seen[False] > 100  # both sides exercised


_GUARD_S, _GUARD_BQ, _GUARD_PS, _GUARD_T = 32, 8, 8, 8
# num_new of one, a part of a block, several blocks, the whole width
_GUARD_NEW = {"one": 1, "part": 5, "several": 19, "whole": 32}
# (q_start of row 0, sliding window)
_GUARD_VARIANTS = {"fresh": (0, None), "chunk": (21, None),
                   "chunk_window": (21, 12)}


def _guard_call(kernel, q, pool, scales, table, kv_len, num_new, q_start,
                window):
    kw = dict(q_start=q_start, sliding_window=window, block_q=_GUARD_BQ,
              interpret=True)
    if kernel == "bf16":
        return ragged_paged_attention(
            q, pool[0], pool[1], table, kv_len, num_new, **kw)
    if kernel == "int8":
        return quantized_ragged_paged_attention(
            q, pool[0], scales[0], pool[1], scales[1], table, kv_len,
            num_new, **kw)
    if kernel == "latent":
        return latent_ragged_paged_attention(
            q, pool[0], table, kv_len, num_new, **kw)
    return quantized_latent_ragged_paged_attention(
        q, pool[0], scales[0], table, kv_len, num_new, **kw)


@pytest.mark.parametrize("variant", list(_GUARD_VARIANTS))
@pytest.mark.parametrize("new", list(_GUARD_NEW))
@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("kernel", ["bf16", "int8", "latent", "int8_latent"])
def test_guarded_kernel_equals_reference_and_the_narrowest_call(
    kernel, rows, new, variant
):
    """Both kernel bodies and both latent wrappers, interpret mode: a wide
    dispatch (most q-blocks and pages dead) equals the XLA oracle, and is
    BIT-EQUAL to the same call made at the narrowest width that holds
    ``num_new`` (no dead q-block), whose pad rows are zeros. A second row
    carries another length, so the rows' live spans differ."""
    rng = np.random.default_rng(7)
    S, PS, T = _GUARD_S, _GUARD_PS, _GUARD_T
    latent = "latent" in kernel
    hq, hkv, d, pages = 4, (1 if latent else 2), 16, rows * T + 1
    start0, window = _GUARD_VARIANTS[variant]
    news = [_GUARD_NEW[new], 11][:rows]
    starts = [start0, 3][:rows]
    q = jnp.asarray(rng.standard_normal((rows, S, hq, d)), jnp.float32)
    pool = [
        jnp.asarray(rng.standard_normal((pages, hkv, PS, d)), jnp.float32)
        for _ in range(1 if latent else 2)
    ]
    scales = None
    if "int8" in kernel:
        scales = [
            jnp.asarray(
                0.02 + 0.01 * rng.random((pages, hkv, PS)), jnp.float32
            )
            for _ in pool
        ]
        pool = [
            jnp.clip(jnp.round(40 * p), -127, 127).astype(jnp.int8)
            for p in pool
        ]
    table = jnp.asarray(
        1 + rng.permutation(pages - 1)[: rows * T].reshape(rows, T), jnp.int32
    )
    num_new = jnp.asarray(news, jnp.int32)
    q_start = jnp.asarray(starts, jnp.int32)
    kv_len = q_start + num_new
    assert int(kv_len.max()) <= T * PS

    wide = _guard_call(
        kernel, q, pool, scales, table, kv_len, num_new, q_start, window
    )
    ref = ragged_attention_reference(
        q, pool[0], pool[-1], table, kv_len, num_new,
        ks_pages=scales[0] if scales else None,
        vs_pages=scales[-1] if scales else None,
        q_start=q_start, sliding_window=window,
    )
    np.testing.assert_allclose(np.asarray(wide), np.asarray(ref), atol=2e-5)

    narrow_s = -(-max(news) // _GUARD_BQ) * _GUARD_BQ
    narrow = _guard_call(
        kernel, q[:, :narrow_s], pool, scales, table, kv_len, num_new,
        q_start, window,
    )
    np.testing.assert_array_equal(
        np.asarray(wide[:, :narrow_s]), np.asarray(narrow)
    )
    assert not np.asarray(wide[:, narrow_s:]).any()


# ---------------------------------------------------------------------------
# Plan unit contracts
# ---------------------------------------------------------------------------

def _plans(ragged):
    e = EngineConfig(
        prefill_buckets=(8, 16, 32), ragged_attention=ragged,
        max_batch_size=4,
    )
    return AttentionPlan(e, CacheConfig(kind="paged"))


def test_plan_classify_and_shapes():
    p = _plans(True)
    assert p.classify(1, 40) == DECODE
    assert p.classify(8, 40) == CHUNKED
    assert p.classify(12, 12) == PREFILL
    # Legacy partition key is unchanged by ragged mode...
    assert p.bucket_for(5) == 8 and p.bucket_for(17) == 32
    assert p.bucket_for(99) == 32
    # ...but every prefill-family pad width collapses to one stride.
    assert p.prefill_stride(32) == 32
    assert p.final_shape(5, 32) == 32
    assert p.group_shape(8, 32) == 32
    legacy = _plans(False)
    assert legacy.final_shape(5, 32) == 8  # the old per-bucket pad
    assert legacy.group_shape(8, 32) == 8
    small, big = p.install_pads(4, 8)
    assert small == 4 and big == 8 and (big & (big - 1)) == 0


def test_plan_credit_accumulator():
    p = _plans(True)
    p.share = 0.5
    grants = [p.take_chunk_credit(True) for _ in range(8)]
    assert sum(grants) == 4  # every other decode tick carries a chunk
    assert p.take_chunk_credit(False)  # no decode => full speed, no credit


def test_plan_recompile_counter_first_seen_only():
    from distributed_llm_inference_tpu.utils.metrics import Metrics

    m = Metrics()
    e = EngineConfig(prefill_buckets=(8,), ragged_attention=True)
    p = AttentionPlan(e, CacheConfig(kind="paged"), metrics=m)
    p.note_dispatch("prefill", (1, 8), 5)
    p.note_dispatch("prefill", (1, 8), 3)
    p.note_dispatch("decode", (4, 16, 64))
    assert m.get_counter("attn_recompiles") == 2.0
    assert m.get_counter("attn_ragged_dispatches") == 2.0
    # the census is cumulative (every dispatch, not the last): 5 + 3 valid
    # of 8 + 8 padded; a decode dispatch without its live count adds nothing
    assert m.get_counter("prefill_valid_tokens") == 8.0
    assert m.get_counter("prefill_padded_tokens") == 16.0
    assert m.get_counter("decode_grid_positions") == 0.0
    p.note_dispatch("decode", (4, 16, 64), 100)
    assert m.get_counter("decode_live_positions") == 100.0
    # rows x table width x page size (CacheConfig's default page)
    assert m.get_counter("decode_grid_positions") == (
        4 * 64 * CacheConfig(kind="paged").page_size
    )


@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_census_counters_on_a_two_row_engine(kind):
    """``prefill_*_tokens`` and ``decode_*_positions`` against values
    computed by hand: two prompts of 3 and 10 tokens admitted alone (buckets
    8 and 16), then decode ticks of one token over both rows."""
    eng = make_engine(ragged=False, kind=kind, batch=2, decode_steps=1)
    a, b = [1, 2, 3], list(range(1, 11))
    opts = SamplingOptions(max_new_tokens=3)
    eng.submit(a, opts), eng.submit(b, opts)
    eng.step()  # admits both (one prefill each), then one decode dispatch
    m = eng.metrics
    assert m.get_counter("prefill_valid_tokens") == 3 + 10
    assert m.get_counter("prefill_padded_tokens") == 8 + 16
    # each row holds its prompt and the token its prefill sampled
    assert m.get_counter("decode_live_positions") == (3 + 1) + (10 + 1)
    if kind == "paged":
        width = eng.cache.page_table.shape[1] * eng.ccfg.page_size
    else:
        width = eng.cache.max_len
    assert m.get_counter("decode_grid_positions") == 2 * width
    # a paged pool's rows hold cdiv(4, 8) + cdiv(11, 8) pages before the
    # step (one layer's, one step's); no in-place sweep runs here
    assert m.get_counter("decode_pages_live") == (3 if kind == "paged" else 0)
    assert m.get_counter("decode_pages_joint") == 0
    eng.step()  # both rows one token longer, the same grid again
    assert m.get_counter("decode_live_positions") == (4 + 11) + (5 + 12)
    assert m.get_counter("decode_grid_positions") == 2 * (2 * width)
    assert m.get_counter("prefill_padded_tokens") == 8 + 16  # no new prefill


SWEPT = {
    # layers as (window, count); sweep_pool; table width; page size
    "one-kind": (((None, 1),), (8, 128, 768), 47, 64),
    "under-the-capacity": (((None, 1),), (8, 128, 768), 11, 64),
    "no-sweep": (((None, 1),), None, 47, 64),
    "window-and-full": (((128, 9), (None, 3)), (8, 128, 768), 160, 64),
    "a-page-a-block": (((128, 9), (None, 3)), (8, 128, 768), 227, 64),
    "a-selection's-table": (((None, 1),), (4, 128, 0), 182, 64),
    # two scale rows of a 32-token page are 64 lanes: the kernel may not copy
    # them, the wrapper gathers every slot's
    "a-page-of-32": (((128, 9), (None, 3)), (8, 128, 768), 47, 32),
}


@pytest.mark.parametrize("case", sorted(SWEPT))
def test_swept_pages_are_what_the_kernels_own_arithmetic_gives(case):
    """``decode_pages_live`` / ``decode_pages_joint`` of a recorded decode
    dispatch against ``ops/paged_attention.py``'s ``_live_pages`` and
    ``_pages_per_block`` walked row by row, step by step: a row's pool
    length is its first query's position all through the dispatch, a window
    moves with the query, and a page is joint where it lies in a full block
    of the row's live pages counted from the first."""
    from distributed_llm_inference_tpu.ops.paged_attention import (
        _live_pages, _pages_per_block,
    )
    from distributed_llm_inference_tpu.utils.metrics import Metrics

    layers, pool, width, ps = SWEPT[case]
    m, steps = Metrics(), 16
    p = AttentionPlan(
        EngineConfig(prefill_buckets=(8,)),
        CacheConfig(kind="paged", page_size=ps), metrics=m,
    )
    p.attention_layers, p.sweep_pool = layers, pool
    rng = np.random.default_rng(3)
    block = 0
    if pool is not None and width * ps >= pool[2]:
        block = _pages_per_block(width, pool[0], ps, pool[1], steps)
    n = block if block > 1 else 4
    # exactly n, n + 1, 2n and 2n + 3 pages among the rows, one past the table
    first = [n * ps - 1, n * ps, 2 * n * ps - 5, (2 * n + 3) * ps - 9, 1, 0,
             (width + 2) * ps, *rng.integers(1, width * ps, 9).tolist()]
    spans = [(int(f), steps) for f in first]
    p.note_dispatch("decode", (32, steps, width), sum(first) + len(first),
                    16, query_spans=spans)
    live = joint = 0
    for window, count in layers:
        for start in first:
            for step in range(steps):
                lo, hi = _live_pages(start, start + step, ps, width, window, np)
                live += count * int(hi - lo)
                if block > 1:
                    joint += count * (int(hi - lo) // block * block)
    assert live > 0 and m.get_counter("decode_pages_live") == live
    assert m.get_counter("decode_pages_joint") == joint
    assert (joint > 0) == (case in ("one-kind", "window-and-full",
                                    "a-selection's-table", "a-page-of-32"))
    # the in-place sweep's scale rows: a live page's of both stored planes
    # by the kernel's own copy, none gathered; at a page of 32 every table
    # slot's of every row, a layer a step, gathered; neither where another
    # path decodes
    by_page, gathered = 2 * live * (block > 0), 0
    if case == "a-page-of-32":
        by_page, gathered = 0, 32 * width * 2 * (9 + 3) * steps
    assert m.get_counter("decode_scale_rows_by_page") == by_page
    assert m.get_counter("decode_scale_rows_gathered") == gathered
    assert (by_page + gathered > 0) == (
        case not in ("under-the-capacity", "no-sweep")
    )
    if case == "window-and-full":
        # a window of 128 is 3 pages of 64 at most: only full layers' are joint
        assert block == 4 and joint % 3 == 0
    if case == "a-page-a-block":
        # so wide a table leaves the sweep the tile it always had: none joint
        assert block == 1
    if case == "a-selection's-table":
        assert block == 8      # half the heads: a block of twice the pages
    # a dispatch that does not say where its queries are counts neither
    p.note_dispatch("decode", (32, steps, width), 100, 16)
    assert m.get_counter("decode_pages_live") == live


@pytest.mark.parametrize("width", [59, 256, 5], ids=["reason1k", "codebase", "narrow"])
def test_walked_steps_are_what_the_sweeps_own_list_walks(width):
    """``decode_sweep_steps_walked`` / ``decode_sweep_steps_grid`` of a
    recorded decode dispatch over a pool whose sweep walks a list
    (``walked_pool``: the latent pool's one 576-wide plane) against the
    kernel's own list (``_sweep_walk``, its host twin) over a table laid out
    for the dispatch's rows: every step of the dispatch walks what its first
    does (the pool's lengths stand), an idle row one step, and the grid is
    rows x the table's blocks."""
    from distributed_llm_inference_tpu.ops.paged_attention import (
        _pages_per_block, _sweep_walk,
    )
    from distributed_llm_inference_tpu.utils.metrics import Metrics

    m, steps, rows, ps = Metrics(), 16, 32, 64
    p = AttentionPlan(
        EngineConfig(prefill_buckets=(8,)),
        CacheConfig(kind="paged", page_size=ps), metrics=m,
    )
    p.walked_pool = (1, 576)
    n = _pages_per_block(width, 1, ps, 576, steps, 1)
    assert n == min(8, 1 << (width.bit_length() - 1))
    rng = np.random.default_rng(width)
    first = [n * ps - 1, n * ps, n * ps + 1, 1, 0, (width + 2) * ps,
             *rng.integers(1, width * ps, 14).tolist()]
    active = rng.permutation(rows)[: len(first)]
    # (an idle row's length is stale: whatever its last tenant left)
    lens, vlen = np.full(rows, 7 * ps, np.int64), np.zeros(rows, np.int64)
    lens[active], vlen[active] = first, 1
    walked = _sweep_walk(
        np.zeros((rows, width), np.int64), lens, vlen, lens, n, ps, None, np
    )[0]
    p.note_dispatch("decode", (rows, steps, width), sum(first) + len(first),
                    len(first), query_spans=[(int(f), steps) for f in first])
    assert m.get_counter("decode_sweep_steps_walked") == steps * int(walked)
    assert m.get_counter("decode_sweep_steps_grid") == (
        steps * rows * -(-width // n))
    assert rows <= walked < rows * -(-width // n) or width == 5
    # a pool swept by copies, and a token a dispatch, count neither
    p.note_dispatch("decode", (rows, 1, width), 100, 4, query_spans=[(9, 1)])
    p.walked_pool = None
    p.note_dispatch("decode", (rows, steps, width), 100, 4,
                    query_spans=[(9, steps)])
    assert m.get_counter("decode_sweep_steps_walked") == steps * int(walked)


@pytest.mark.parametrize("block_q", [8, 16])
def test_ragged_tile_census_on_a_two_row_engine(block_q):
    """``ragged_attn_tiles_live`` / ``_grid`` against a count made by hand.
    Pad width 16, pages of 8; a 3-token prompt is one prefill, a 20-token
    prompt a 16-token chunk and a 4-token final at position 16. With
    q-blocks of 8 (two a dispatch): the short prompt's one valid block sees
    page 0 (1 tile); the chunk's blocks see pages {0} and {0, 1} (3); the
    final's block, queries 16-19, sees pages {0, 1, 2} (3). With one block
    of 16 a dispatch: 1, 2 and 3. The grid is q-blocks x the table's width
    a dispatch, live or not."""
    eng = make_engine(ragged=True, batch=2, chunk=16, decode_steps=1)
    # the engine hands the plan the kernel's OWN choice of q block
    pool = eng.cache.k_pages
    q = jax.ShapeDtypeStruct((1, 16, CFG.num_heads, CFG.head_dim), jnp.float32)
    assert eng.plan.ragged_block_q(16) == _prep(
        q, jax.ShapeDtypeStruct(pool.shape[1:], pool.dtype), None
    )[4] == 16
    eng.plan.ragged_block_q = lambda width: block_q
    width = eng.cache.page_table.shape[1]
    opts = SamplingOptions(max_new_tokens=2)
    eng.submit([1, 2, 3], opts), eng.submit(list(range(1, 21)), opts)
    for _ in range(12):
        eng.step()
    assert not eng.has_work()
    assert eng.cache.page_table.shape[1] == width  # no growth on the way
    m = eng.metrics
    assert m.get_counter("prefill_valid_tokens") == 3 + 16 + 4
    live = {8: 1 + 3 + 3, 16: 1 + 2 + 3}[block_q]
    assert m.get_counter("ragged_attn_tiles_live") == live
    grid = 3 * (16 // block_q) * width
    assert m.get_counter("ragged_attn_tiles_grid") == grid


def test_ragged_tile_census_counts_nothing_off_the_ragged_plan():
    """The legacy plan runs no ragged kernel, and a dense cache has no page
    table: neither keeps the census."""
    for kw in ({"ragged": False}, {"ragged": True, "kind": "dense"}):
        eng = make_engine(**kw)
        eng.submit([1, 2, 3], SamplingOptions(max_new_tokens=2))
        for _ in range(4):
            eng.step()
        assert eng.metrics.get_counter("prefill_valid_tokens") == 3
        assert eng.metrics.get_counter("ragged_attn_tiles_grid") == 0


# ---------------------------------------------------------------------------
# Engine parity: ragged on/off must be byte-exact across the matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["paged", "dense"])
@pytest.mark.parametrize("kv_quant", [None, "int8"])
@pytest.mark.parametrize("sampled", [False, True])
def test_ragged_parity_matrix(kind, kv_quant, sampled):
    ps = prompts(6)
    opts = (
        SamplingOptions(max_new_tokens=5, temperature=0.9, top_k=40)
        if sampled else SamplingOptions(max_new_tokens=5)
    )
    base = make_engine(ragged=False, kind=kind, kv_quant=kv_quant).generate(
        ps, opts
    )
    rag = make_engine(ragged=True, kind=kind, kv_quant=kv_quant).generate(
        ps, opts
    )
    assert base == rag


def test_chunked_admission_mid_decode_parity():
    """A long greedy prompt landing beside live decode rows chunk-admits
    (attn_chunked_rows > 0) and still produces the legacy tokens."""
    rng = np.random.default_rng(7)
    mix = [prompts(2)[0], rng.integers(0, 128, size=30).tolist(),
           prompts(2)[1]]
    opts = SamplingOptions(max_new_tokens=6)
    base = make_engine(ragged=False).generate(mix, opts)
    eng = make_engine(ragged=True, chunk=8, share=0.5)
    assert eng.generate(mix, opts) == base
    assert eng.metrics.get_counter("attn_chunked_rows") > 0


def test_chunked_admission_sampled_rider_parity():
    """Sampled SHORT sessions ride beside a chunking greedy prompt: their
    key-draw positions must be untouched by the parked admission."""
    rng = np.random.default_rng(11)
    mix = [prompts(2, seed=5)[0], rng.integers(0, 128, size=28).tolist()]
    opts = SamplingOptions(max_new_tokens=6, temperature=0.8, top_k=30)
    base = make_engine(ragged=False).generate(mix, opts)
    eng = make_engine(ragged=True, chunk=8, share=0.5)
    assert eng.generate(mix, opts) == base


@pytest.mark.parametrize("overlap", [False, True])
def test_chunked_admission_pipelined_parity(overlap):
    rng = np.random.default_rng(13)
    mix = [prompts(3, seed=2)[0], rng.integers(0, 128, size=30).tolist(),
           prompts(3, seed=2)[2]]
    opts = SamplingOptions(max_new_tokens=6)
    base, eng = make_engine(ragged=False), make_engine(ragged=True, chunk=8)
    assert base._pipelined and eng._pipelined
    if not overlap:
        # both held to the synchronous admission path
        base._overlap_ok = eng._overlap_ok = lambda: False
    assert eng.generate(mix, opts) == base.generate(mix, opts)
    assert eng.metrics.get_counter("attn_chunked_rows") > 0


def test_cancel_mid_chunk_releases_row():
    """Cancel landing while a session is parked mid chunked-prefill emits
    the terminal event and frees its pages; its partially-written pages
    must NOT be registered as shareable prefix content."""
    eng = make_engine(ragged=True, chunk=8, share=0.25, batch=2)
    short = prompts(1, seed=3)[0]
    longp = np.random.default_rng(5).integers(0, 128, size=30).tolist()
    opts = SamplingOptions(max_new_tokens=32)
    eng.submit(short, opts)
    gid = eng.submit(longp, opts)
    eng.step()  # admits both; long prompt parks for chunking
    s = eng.sessions[gid]
    assert s.chunking and s.slot is not None
    eng.cancel(gid)
    evs = eng.step()
    assert (gid, -1, True) in evs
    assert eng.sessions[gid].pages == []
    assert not eng.sessions[gid].chunking
    assert gid not in eng.slots
    # Drain the survivor; the engine must stay healthy.
    while eng.has_work():
        eng.step()


def test_deadline_mid_chunk_reaps():
    eng = make_engine(ragged=True, chunk=8, share=0.25, batch=2)
    short = prompts(1, seed=4)[0]
    longp = np.random.default_rng(6).integers(0, 128, size=30).tolist()
    import time as _time

    eng.submit(short, SamplingOptions(max_new_tokens=16))
    gid = eng.submit(longp, SamplingOptions(max_new_tokens=16),
                     deadline=_time.monotonic() + 0.2)
    eng.step()
    assert eng.sessions[gid].chunking
    _time.sleep(0.25)
    evs = eng.step()
    assert (gid, -1, True) in evs
    assert eng.sessions[gid].finish_reason == "deadline"
    while eng.has_work():
        eng.step()


def test_admit_prefilled_onto_ragged_engine():
    """Disaggregated admission lands on a plan-managed engine unchanged:
    export KV from one ragged engine, import into another, tokens match a
    straight local run."""
    opts = SamplingOptions(max_new_tokens=6)
    p = prompts(1, seed=8)[0]
    local = make_engine(ragged=True).generate([p], opts)[0]
    src = make_engine(ragged=True)
    planes, first, _chain = src.prefill_export(p)
    dst = make_engine(ragged=True)
    gid = dst.admit_prefilled(p, planes, first, options=opts)
    toks = []
    while dst.has_work():
        for g, tok, fin in dst.step():
            if g == gid and tok != -1:
                toks.append(tok)
    assert toks == local


def test_admission_burst_single_growth():
    """Satellite regression: an admission burst spanning ladder rungs in
    ONE tick widens the table ONCE (max of the burst), not once per rung
    — the one-shape-per-bucket growth recompile when an oversized backlog
    and a growth tick land together."""
    # A 4-rung ladder (slots 2/4/6/8) so the burst spans several rungs.
    eng = make_engine(ragged=True, decode_windows=(16, 32, 48, 64))
    base = int(eng.metrics.get_counter("cache_growths"))
    rng = np.random.default_rng(17)
    for n in (10, 25, 40, 56):
        eng.submit(rng.integers(0, 128, size=n).tolist(),
                   SamplingOptions(max_new_tokens=2))
    eng.step()  # one tick admits all four (lengths 10→56: rungs 2,4,6,8)
    grown = int(eng.metrics.get_counter("cache_growths")) - base
    assert grown == 1, f"burst admission grew the cache {grown}x in one tick"
    while eng.has_work():
        eng.step()


def test_zero_recompiles_after_warmup():
    """Steady-state mixed-length traffic must add NO first-seen dispatch
    shapes once the warm set exists (the plan's single-shape contract)."""
    eng = make_engine(ragged=True)
    opts = SamplingOptions(max_new_tokens=4)
    # Warm the finite shape set explicitly: a 4-row group, a 2-row group,
    # and a single (group pads are width-invariant under ragged mode, so
    # only the ROW-COUNT pow2s and the one single/final width exist).
    eng.generate([[1] * 6] * 4, opts)
    eng.generate([[2] * 6] * 2, opts)
    eng.generate([[3] * 20], opts)
    warm = eng.metrics.get_counter("attn_recompiles")
    assert warm > 0
    # Steady state: mixed-length traffic over warm executables.
    eng.generate(prompts(6, seed=22), opts)
    eng.generate(prompts(6, lo=3, hi=12, seed=23), opts)
    assert eng.metrics.get_counter("attn_recompiles") == warm


def test_legacy_mode_shapes_unchanged():
    """ragged_attention=False must reproduce the legacy per-bucket pads
    (the plan is a refactor, not a behavior change, when disabled)."""
    eng = make_engine(ragged=False)
    eng.generate(prompts(4, seed=30), SamplingOptions(max_new_tokens=2))
    assert eng.metrics.get_counter("attn_chunked_rows") == 0
    assert eng.metrics.get_counter("attn_ragged_dispatches") == 0
