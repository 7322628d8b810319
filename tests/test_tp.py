"""Tensor/data-parallel sharding correctness on the 8-device virtual mesh.

SURVEY §4(b): multi-device tests on one host via XLA host-platform device
emulation — mesh sharding + collective correctness without a real pod. The
oracle is the identical computation run unsharded on one device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import MeshConfig, ModelConfig
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.parallel import (
    build_mesh,
    cache_pspecs,
    param_pspecs,
    shard_pytree,
    validate_tp,
)

CFG = ModelConfig(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=8,
    num_kv_heads=4,
    head_dim=8,
    max_position_embeddings=64,
)


def _forward(params, tokens, cache):
    n = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    logits, cache = llama.model_apply(CFG, params, tokens, cache, n)
    return logits, cache


def _make_inputs(batch=4, seq=16):
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, CFG.vocab_size)
    cache = DenseKVCache.create(
        CFG.num_layers, batch, 32, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    return params, tokens, cache


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(dp=1, pp=1, tp=4, sp=1),
    MeshConfig(dp=2, pp=1, tp=2, sp=1),
    MeshConfig(dp=2, pp=1, tp=4, sp=1),
])
def test_tp_dp_matches_single_device(mesh_cfg):
    params, tokens, cache = _make_inputs()
    ref_logits, ref_cache = jax.jit(_forward)(params, tokens, cache)

    validate_tp(CFG, mesh_cfg.tp)
    mesh = build_mesh(mesh_cfg)
    from jax.sharding import NamedSharding, PartitionSpec as P

    sp_params = shard_pytree(params, mesh, param_pspecs(params))
    sp_cache = shard_pytree(cache, mesh, cache_pspecs(cache))
    sp_tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))

    out_logits, out_cache = jax.jit(_forward)(sp_params, sp_tokens, sp_cache)

    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(out_cache.k), np.asarray(ref_cache.k), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_array_equal(
        np.asarray(out_cache.lengths), np.asarray(ref_cache.lengths)
    )


def test_tp_decode_after_prefill_matches():
    params, tokens, cache = _make_inputs()
    logits, cache1 = jax.jit(_forward)(params, tokens, cache)
    next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    ref_logits, _ = jax.jit(_forward)(params, next_tok, cache1)

    mesh = build_mesh(MeshConfig(dp=2, pp=1, tp=2, sp=1))
    from jax.sharding import NamedSharding, PartitionSpec as P

    params2, tokens2, cache2 = _make_inputs()
    sp_params = shard_pytree(params2, mesh, param_pspecs(params2))
    sp_cache = shard_pytree(cache2, mesh, cache_pspecs(cache2))
    tok_sharding = NamedSharding(mesh, P("dp", None))
    sp_tokens = jax.device_put(tokens2, tok_sharding)

    logits_s, sp_cache = jax.jit(_forward)(sp_params, sp_tokens, sp_cache)
    next_s = jnp.argmax(logits_s[:, -1:], axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(next_s), np.asarray(next_tok))
    out, _ = jax.jit(_forward)(sp_params, jax.device_put(next_s, tok_sharding), sp_cache)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("kind", ["paged", "sink"])
def test_tp_sharded_paged_and_sink_caches(kind):
    from distributed_llm_inference_tpu.cache.paged import PagedKVCache
    from distributed_llm_inference_tpu.cache.sink import SinkKVCache

    batch, seq = 4, 16
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, CFG.vocab_size)

    def mk():
        if kind == "paged":
            c = PagedKVCache.create(
                CFG.num_layers, batch, 16, 8, 4, CFG.num_kv_heads, CFG.head_dim,
                jnp.float32,
            )
            # Each row gets 3 pages (ids 1..12), enough for seq+decode.
            table = jnp.asarray(
                [[1 + 3 * r + i for i in range(3)] + [0] for r in range(batch)],
                jnp.int32,
            )
            return c.replace(page_table=table)
        return SinkKVCache.create(
            CFG.num_layers, batch, 32, 2, CFG.num_kv_heads, CFG.head_dim, jnp.float32
        )

    ref_logits, ref_cache = jax.jit(_forward)(params, tokens, mk())

    mesh = build_mesh(MeshConfig(dp=2, pp=1, tp=2, sp=1))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sp_params = shard_pytree(params, mesh, param_pspecs(params))
    sp_cache = shard_pytree(mk(), mesh, cache_pspecs(mk()))
    sp_tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    out_logits, out_cache = jax.jit(_forward)(sp_params, sp_tokens, sp_cache)

    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )
    ref_k = ref_cache.k_pages if kind == "paged" else ref_cache.k
    out_k = out_cache.k_pages if kind == "paged" else out_cache.k
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(ref_k), rtol=2e-5, atol=2e-5)


def test_validate_tp_rejects_bad_degrees():
    with pytest.raises(ValueError):
        validate_tp(CFG, 3)
    with pytest.raises(ValueError):
        validate_tp(CFG, 2, sp=3)


def test_build_mesh_layout_failure_is_an_error(monkeypatch):
    """A shape ``create_device_mesh`` cannot lay over the slice used to
    warn and serve from an enumeration-order mesh; on real chips that
    hides a degraded layout, so it raises."""
    from jax.experimental import mesh_utils

    def refuse(*a, **k):
        raise RuntimeError("cannot assign this mesh to the slice")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    with pytest.raises(RuntimeError, match="cannot assign"):
        build_mesh(MeshConfig(dp=2, tp=4))


def test_tp4_paged_engine_decodes_fused_and_matches_a_token_a_dispatch():
    """A ``tp=4`` engine over the value-dtype paged cache has no kernel (the
    plan is off under a mesh) and has the write-behind tail all the same
    (``PagedKVCache``'s gathered form): it resolves 16 steps a dispatch,
    pipelined, and its greedy streams are the ``decode_steps=1`` engine's
    token for token, across an admission in mid-run (five prompts, two
    slots), rows that stop on an EOS inside a window, and a table that grows
    (contexts pass the first rung of the ladder)."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    fused = _mesh_engine(MeshConfig(tp=4), batch=2, decode_steps=None)
    single = _mesh_engine(MeshConfig(tp=4), batch=2, decode_steps=1,
                          pipelined=False)
    assert not fused.cache.use_kernel and fused.cache.has_tail
    assert fused.decode_steps == 16 and fused._pipelined
    assert single.decode_steps == 1 and not single._pipelined
    rng = np.random.default_rng(49)
    prompts = [rng.integers(0, CFG.vocab_size, size=n).tolist()
               for n in (5, 11, 7, 3, 9)]
    free = SamplingOptions(max_new_tokens=40)
    want = single.generate(prompts, free)
    assert [len(w) for w in want] == [40] * 5
    assert fused.generate(prompts, free) == want
    assert fused.metrics.snapshot().get("cache_growths", 0) >= 1
    # a token the first stream emits inside its second window ends it there
    # (and whichever other stream meets it)
    eos = SamplingOptions(max_new_tokens=40, eos_token_id=want[0][20])
    stopped = single.generate(prompts, eos)
    assert len(stopped[0]) <= 21 and stopped != want
    assert fused.generate(prompts, eos) == stopped


# -- overlapped admission under a tp-only mesh ---------------------------------

_OVERLAP_CACHES = {
    "paged": dict(kind="paged", page_size=8, num_pages=96,
                  max_pages_per_session=8),
    "paged_int8": dict(kind="paged", kv_quant="int8", page_size=8,
                       num_pages=96, max_pages_per_session=8),
}


def _mesh_engine(mesh_cfg, cache="paged", overlap=True, batch=3, rng_seed=7,
                 decode_steps=4, pipelined=True):
    """A mesh engine, by default with short ticks (``decode_steps=4``, as
    ``tests/test_engine.py:_overlap_engine``), so a session's budget spans
    several ticks and an admission meets one in flight. ``overlap=False`` is
    the same pipelined engine held to the synchronous admission."""
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine

    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    eng = InferenceEngine(
        CFG, params,
        EngineConfig(max_batch_size=batch, prefill_buckets=(8, 16),
                     max_seq_len=64, dtype="float32",
                     decode_windows=(16, 32, 64), decode_steps=decode_steps),
        CacheConfig(**_OVERLAP_CACHES[cache]),
        mesh_cfg=mesh_cfg, rng=jax.random.PRNGKey(rng_seed),
    )
    assert eng._pipelined is pipelined
    if not overlap:
        eng._overlap_ok = lambda: False
    return eng


def _overlap_prompts(n, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=int(k)).tolist()
            for k in rng.integers(3, 14, size=n)]


def _staggered(eng, ps, opts, cancel_last=False):
    """Two residents first, the rest once a pipelined tick is in flight (as
    ``_churn_run`` does), so the later admissions land behind a tick. With
    ``cancel_last`` the last prompt is cancelled right after the step that
    admitted it: on an overlapping engine its prefill is in flight then."""
    gids = [eng.submit(p, o) for p, o in zip(ps[:2], opts[:2])]
    eng.step()  # the residents admit synchronously: no tick in flight yet
    eng.step()  # the first pipelined tick is in flight
    gids += [eng.submit(p, o) for p, o in zip(ps[2:], opts[2:])]
    last, inflight = eng.sessions[gids[-1]], None
    while eng.has_work():
        eng.step()
        if cancel_last and inflight is None and last.slot is not None:
            inflight = last.prefill_inflight
            eng.cancel(gids[-1])
    return ([eng.sessions[g].generated for g in gids],
            eng.metrics.snapshot(), inflight)


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("cache", sorted(_OVERLAP_CACHES))
def test_tp4_overlapped_admission_matches_the_synchronous_flow(cache, sampled):
    """Under a mesh whose only axis over 1 is ``tp`` an admission behind a
    tick in flight defers its first token's fetch, and the streams are those
    of the same engine with ``_overlap_ok = lambda: False``, token for token:
    seven prompts over three slots with budgets that end rows on different
    ticks and grow the table; then, with both engines' keys wound back to
    the start, a row that stops on an EOS inside a window and a cancellation
    while a prefill is in flight."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    kw = dict(temperature=0.9, top_p=0.95) if sampled else {}
    budgets = (30, 9, 22, 13, 26, 11, 40)
    ps = _overlap_prompts(len(budgets), seed=54)

    on = _mesh_engine(MeshConfig(tp=4), cache, rng_seed=11)
    off = _mesh_engine(MeshConfig(tp=4), cache, overlap=False, rng_seed=11)
    assert on._batch_whole and on.decode_steps == 4
    opts = [SamplingOptions(max_new_tokens=n, **kw) for n in budgets]
    got, snap, _ = _staggered(on, ps, opts)
    want, snap_off, _ = _staggered(off, ps, opts)
    assert [len(g) for g in got] == list(budgets)
    assert got == want
    assert snap.get("admit_overlap_sessions", 0) > 0
    assert snap_off.get("admit_overlap_sessions", 0) == 0
    assert snap_off.get("admit_sync_sessions", 0) == len(ps)
    assert snap.get("cache_growths", 0) >= 1
    # the sixth token of the first stream (the first step of its second
    # window) ends whichever stream meets it, and the last prompt is
    # cancelled while its prefill is in flight
    on.rng = off.rng = jax.random.PRNGKey(11)
    eos = [SamplingOptions(max_new_tokens=n, eos_token_id=got[0][5], **kw)
           for n in budgets]
    got2, snap2, inflight = _staggered(on, ps, eos, cancel_last=True)
    want2, _, inflight_off = _staggered(off, ps, eos, cancel_last=True)
    assert inflight is True and inflight_off is False
    assert got2[-1] == [] and len(want2[-1]) == 1  # the deferred token dropped
    assert got2[:-1] == want2[:-1]
    assert len(got2[0]) <= 6 and got2[0] == got[0][:len(got2[0])]
    assert snap2["admit_overlap_sessions"] > snap["admit_overlap_sessions"]
    assert not on._inflight_admits and not on._admit_pend.any()
    assert on.allocator.free_count == off.allocator.free_count


def test_tp4_admission_flood_spills_past_the_cap_and_matches(monkeypatch):
    """A flood past ``OVERLAP_MAX_INFLIGHT`` spills to the synchronous path
    under the mesh as on one chip (a mesh engine admits a row a dispatch, so
    the second admission of a tick already meets the cap of 1), and the
    streams do not move."""
    from distributed_llm_inference_tpu.engine import engine as engine_mod
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    monkeypatch.setattr(engine_mod, "OVERLAP_MAX_INFLIGHT", 1)
    ps = _overlap_prompts(6, seed=55)
    opts = [SamplingOptions(max_new_tokens=14)] * 6

    def run(overlap):
        eng = _mesh_engine(MeshConfig(tp=4), overlap=overlap, batch=6)
        return _staggered(eng, ps, opts)

    (on, snap, _), (off, _, _) = run(True), run(False)
    assert on == off
    assert snap.get("admit_overlap_sessions", 0) > 0
    assert snap.get("admit_overlap_spill", 0) > 0
    assert snap.get("admit_sync_sessions", 0) > 2  # the residents and the spill


@pytest.mark.parametrize("mesh_cfg,pipelined", [
    (MeshConfig(dp=2, tp=2), True), (MeshConfig(pp=2), False),
], ids=["dp2_tp2", "pp2"])
def test_a_mesh_that_shards_the_batch_admits_synchronously(mesh_cfg, pipelined):
    """Where the batch axis is sharded the deferred scatter has no place to
    land: such an engine answers ``_overlap_ok()`` False with a tick in
    flight (stood in for: a ``pp`` engine never pipelines), and every
    admission of a staggered run, whose later ones meet a real tick in
    flight on the ``dp`` mesh, is synchronous."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    ps = _overlap_prompts(4, seed=56)
    opts = [SamplingOptions(max_new_tokens=n) for n in (24, 9, 12, 7)]
    eng = _mesh_engine(mesh_cfg, batch=2, pipelined=pipelined)
    assert not eng._batch_whole
    eng._pipelined, eng._pending = True, ("a tick in flight",)
    assert not eng._overlap_ok()
    eng._pipelined, eng._pending = pipelined, None
    got, snap, _ = _staggered(eng, ps, opts)
    assert [len(g) for g in got] == [24, 9, 12, 7]
    assert snap.get("admit_overlap_sessions", 0) == 0
    assert snap.get("admit_sync_sessions", 0) == len(ps)
    assert snap.get("admit_overlap_spill", 0) == 0


# -- a fresh row's prefill against its own K/V ---------------------------------

def _fresh_engine():
    """A ``tp=4`` value-dtype paged engine with prefix caching: it has the
    fresh-row program."""
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine

    return InferenceEngine(
        CFG, llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32),
        EngineConfig(max_batch_size=3, prefill_buckets=(8, 16),
                     max_seq_len=64, dtype="float32",
                     decode_windows=(16, 32, 64), decode_steps=4),
        CacheConfig(prefix_caching=True, **_OVERLAP_CACHES["paged"]),
        mesh_cfg=MeshConfig(tp=4), rng=jax.random.PRNGKey(55),
    )


@pytest.fixture(scope="module")
def fresh_pair():
    """One engine that takes the fresh-row program and one held to the
    page-table path (what it was before there was one), for the case
    below."""
    on, off = _fresh_engine(), _fresh_engine()
    assert on._prefill_fresh is not None and not on.cache.use_ragged
    off._prefill_fresh = None
    return on, off


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_tp4_fresh_rows_prefill_against_their_own_kv_and_match(fresh_pair, sampled):
    """A fresh one-piece prompt a bucket (5 and 13 tokens), a prompt of two
    pieces (30 tokens past the 16-wide bucket: a chunk, then a tail with
    history) and a prefix hit (the 13-token prompt's first page, then 6
    tokens of its own) on the ``tp=4`` engine: the two fresh ones take
    ``_prefill_row_fresh`` (their K/V attended in place and installed as
    whole pages), the two others the page-table path, and every stream is
    that of the same engine without the fresh program, token for token,
    greedy and sampled; the later admissions still ride behind a tick."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    on, off = fresh_pair
    rng = np.random.default_rng(55 + sampled)   # no page of the other case's
    draw = lambda n: rng.integers(0, CFG.vocab_size, size=n).tolist()
    five, thirteen, thirty = draw(5), draw(13), draw(30)
    ps = [thirteen, five, thirty, thirteen[:8] + draw(6)]
    kw = dict(temperature=0.9, top_p=0.95) if sampled else {}
    opts = [SamplingOptions(max_new_tokens=n, **kw) for n in (14, 9, 11, 12)]
    on.rng = off.rng = jax.random.PRNGKey(7)
    before = [e.metrics.snapshot() for e in (on, off)]
    got, snap, _ = _staggered(on, ps, opts)
    want, snap_off, _ = _staggered(off, ps, opts)
    assert [len(g) for g in got] == [14, 9, 11, 12]
    assert got == want
    moved = lambda s, b, k: s.get(k, 0) - b.get(k, 0)
    assert moved(snap, before[0], "prefill_fresh_rows") == 2
    assert moved(snap, before[0], "prefill_table_rows") == 2
    assert moved(snap_off, before[1], "prefill_fresh_rows") == 0
    assert moved(snap_off, before[1], "prefill_table_rows") == 4
    assert moved(snap, before[0], "prefix_cached_tokens") == 8
    assert moved(snap, before[0], "admit_overlap_sessions") > 0
    assert on.allocator.free_count == off.allocator.free_count


def test_tp4_a_tail_finds_the_table_program_loaded_at_every_fresh_width():
    """Fresh prompts load ``_prefill_row_fresh`` at their pad widths and,
    while no row with history has come, no page-table program at all. The
    first chunked prompt (a 16-token piece, then a 14-token tail at width
    16) loads ``_prefill_row`` at its own width AND at the other width the
    fresh program has (8); a width first seen fresh afterwards is paired at
    once. So a later tail of 3 tokens (width 8) compiles nothing: what a
    warm-up of every width once and one chunked prompt leaves a window. The
    table is pinned at its widest, as a warm-up's anchor pins it (a program
    is keyed by the table's width too)."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    eng = _fresh_engine()
    eng._ensure_capacity(eng.ecfg.max_seq_len)
    slots = eng.cache.page_table.shape[1]
    table, fresh = eng._prefill.__wrapped__, eng._prefill_fresh.__wrapped__
    rng = np.random.default_rng(5)
    draw = lambda n: rng.integers(0, CFG.vocab_size, size=n).tolist()
    opts = SamplingOptions(max_new_tokens=3)
    eng.generate([draw(5)], opts)
    assert (eng._fresh_widths, eng._table_widths) == ({(slots, 8)}, set())
    assert (fresh._cache_size(), table._cache_size()) == (1, 0)
    before = eng.metrics.snapshot()
    eng.generate([draw(30)], opts)
    assert eng._table_widths == {(slots, 8), (slots, 16)}
    assert eng._fresh_widths == {(slots, 8)} and table._cache_size() == 2
    eng.generate([draw(13)], opts)              # 16 wide, fresh: paired already
    assert eng._fresh_widths == eng._table_widths
    eng.generate([draw(19)], opts)              # a tail of 3 at width 8
    assert (fresh._cache_size(), table._cache_size()) == (2, 2)
    assert eng.cache.page_table.shape[1] == slots
    snap = eng.metrics.snapshot()
    moved = lambda k: snap.get(k, 0) - before.get(k, 0)
    # the loads are no row's prefill: neither counter saw them
    assert (moved("prefill_fresh_rows"), moved("prefill_table_rows")) == (1, 2)
