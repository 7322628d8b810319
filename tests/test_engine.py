"""Continuous-batching engine tests.

SURVEY §4(c): integration tests running a tiny random-weight model end-to-end
through the serving stack in-process. The key properties: continuous batching
must not change any session's tokens vs a solo run; sessions of different
lengths interleave; pages are reclaimed; sampling controls behave.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_llm_inference_tpu.engine.engine as engine_mod
from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig, ModelConfig
from distributed_llm_inference_tpu.engine.engine import (
    IDLE_SHRINK_S,
    InferenceEngine,
)
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama

CFG = ModelConfig(
    vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=16,
)
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_engine(kind="paged", batch=4, decode_steps=None, **cache_kw):
    cache_defaults = dict(
        kind=kind, page_size=8, num_pages=64, max_pages_per_session=8,
        window_length=32, num_sink_tokens=2,
    )
    cache_defaults.update(cache_kw)
    return InferenceEngine(
        CFG, PARAMS,
        EngineConfig(
            max_batch_size=batch, prefill_buckets=(8, 16, 32), max_seq_len=64,
            dtype="float32", decode_steps=decode_steps,
        ),
        CacheConfig(**cache_defaults),
    )


def prompts(n, lo=3, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, CFG.vocab_size, size=rng.integers(lo, hi)).tolist()
        for _ in range(n)
    ]


def test_greedy_batched_equals_solo():
    """8 sessions through a 4-slot engine must reproduce solo-run tokens."""
    ps = prompts(8)
    opts = SamplingOptions(max_new_tokens=6)

    batched = make_engine().generate(ps, opts)
    for i, p in enumerate(ps):
        solo = make_engine(batch=1).generate([p], opts)[0]
        assert batched[i] == solo, f"session {i} diverged: {batched[i]} vs {solo}"


def test_more_sessions_than_slots_all_finish():
    eng = make_engine(batch=2)
    ps = prompts(7, seed=1)
    outs = eng.generate(ps, SamplingOptions(max_new_tokens=4))
    assert all(len(o) == 4 for o in outs)
    assert not eng.has_work()
    # all pages returned to the pool
    assert eng.allocator.free_count == 63  # 64 pages minus null page


def test_dense_engine_matches_paged_engine():
    ps = prompts(5, seed=2)
    opts = SamplingOptions(max_new_tokens=5)
    out_paged = make_engine("paged").generate(ps, opts)
    out_dense = make_engine("dense").generate(ps, opts)
    assert out_paged == out_dense


def test_sink_engine_streams_past_window():
    eng = make_engine("sink", batch=2, window_length=16, num_sink_tokens=2)
    outs = eng.generate(prompts(2, seed=3), SamplingOptions(max_new_tokens=40))
    assert all(len(o) == 40 for o in outs)


def test_eos_stops_generation():
    eng = make_engine()
    ps = prompts(3, seed=4)
    # pick an EOS that greedy decoding actually emits for session 0
    ref = make_engine().generate([ps[0]], SamplingOptions(max_new_tokens=6))[0]
    eos = ref[2]
    outs = eng.generate(ps, SamplingOptions(max_new_tokens=6, eos_token_id=eos))
    s0 = outs[0]
    assert s0[-1] == eos and len(s0) <= 6
    for gid, s in eng.sessions.items():
        assert s.finish_reason in ("eos", "length")


def test_sampling_temperature_reproducible_and_varied():
    ps = prompts(2, seed=5)
    opts = SamplingOptions(temperature=1.0, top_p=0.9, max_new_tokens=8)
    e1 = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(16,), max_seq_len=64,
                     dtype="float32"),
        CacheConfig(kind="dense"), rng=jax.random.PRNGKey(7),
    )
    e2 = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(16,), max_seq_len=64,
                     dtype="float32"),
        CacheConfig(kind="dense"), rng=jax.random.PRNGKey(7),
    )
    o1 = e1.generate(ps, opts)
    o2 = e2.generate(ps, opts)
    assert o1 == o2  # same rng → same tokens
    o3 = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(16,), max_seq_len=64,
                     dtype="float32"),
        CacheConfig(kind="dense"), rng=jax.random.PRNGKey(8),
    ).generate(ps, opts)
    assert o1 != o3  # different rng → (overwhelmingly) different tokens


def test_capacity_finish_dense():
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=1, prefill_buckets=(16,), max_seq_len=16,
                     dtype="float32"),
        CacheConfig(kind="dense"),
    )
    out = eng.generate([list(range(10))], SamplingOptions(max_new_tokens=50))[0]
    s = next(iter(eng.sessions.values()))
    assert s.finish_reason == "capacity"
    assert len(out) + 10 <= 16


def test_metrics_and_ttft_recorded():
    eng = make_engine()
    eng.generate(prompts(3, seed=6), SamplingOptions(max_new_tokens=3))
    snap = eng.metrics.snapshot()
    assert snap["sessions_submitted"] == 3
    assert snap["sessions_finished"] == 3
    assert snap["decode_tokens"] > 0
    for s in eng.sessions.values():
        assert s.ttft is not None and s.ttft >= 0


def test_cancel_while_waiting_never_runs():
    eng = make_engine(batch=1)
    a = eng.submit(prompts(1, seed=8)[0], SamplingOptions(max_new_tokens=50))
    b = eng.submit(prompts(1, seed=9)[0], SamplingOptions(max_new_tokens=3))
    eng.cancel(b)  # b is still WAITING behind a
    while eng.has_work():
        eng.step()
    assert eng.sessions[b].generated == []
    assert eng.sessions[b].finish_reason == "cancelled"
    assert len(eng.sessions[a].generated) == 50


def test_capacity_events_use_sentinel_token():
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=1, prefill_buckets=(16,), max_seq_len=16,
                     dtype="float32"),
        CacheConfig(kind="dense"),
    )
    # over-long prompt: rejected at admission with a finished event
    gid = eng.submit(list(range(20)), SamplingOptions(max_new_tokens=4))
    events = eng.step()
    assert (gid, -1, True) in events
    # capacity exhaustion mid-decode: finish event is the -1 sentinel and the
    # stream of real tokens has no duplicates vs session.generated
    gid2 = eng.submit(list(range(10)), SamplingOptions(max_new_tokens=50))
    streamed = []
    while eng.has_work():
        for g, tok, fin in eng.step():
            if g == gid2 and tok >= 0:
                streamed.append(tok)
    assert streamed == eng.sessions[gid2].generated
    assert eng.sessions[gid2].finish_reason == "capacity"


def test_collect_finished_reaps_sessions():
    eng = make_engine(batch=2)
    eng.generate(prompts(3, seed=10), SamplingOptions(max_new_tokens=2))
    assert len(eng.sessions) == 3
    done = eng.collect_finished()
    assert len(done) == 3 and len(eng.sessions) == 0


def test_concurrent_submit_while_stepping():
    """SURVEY §5.2: request threads submit/cancel while a server thread
    steps; every session must finish with its solo-run tokens."""
    import threading

    eng = make_engine(kind="paged", batch=3)
    solo = {}
    for i, p in enumerate(prompts(12, seed=21)):
        ref_eng = make_engine(kind="paged", batch=3)
        solo[i] = (p, ref_eng.generate([p], SamplingOptions(max_new_tokens=6))[0])

    ids = {}
    ids_lock = threading.Lock()

    def producer(lo, hi):
        for i in range(lo, hi):
            gid = eng.submit(solo[i][0], SamplingOptions(max_new_tokens=6))
            with ids_lock:
                ids[i] = gid

    threads = [threading.Thread(target=producer, args=(i * 4, (i + 1) * 4))
               for i in range(3)]
    stop = threading.Event()

    def server():
        while not stop.is_set() or eng.has_work():
            eng.step()

    srv = threading.Thread(target=server)
    srv.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    srv.join(timeout=120)
    assert not srv.is_alive()

    for i, (prompt, expect) in solo.items():
        got = eng.sessions[ids[i]].generated
        assert got == expect, (i, got, expect)


def test_engine_tp_mesh_matches_single_device():
    """One replica served tp-sharded across the CPU mesh == unsharded."""
    from distributed_llm_inference_tpu.config import MeshConfig

    reqs = prompts(5, seed=31)
    plain = make_engine(kind="dense").generate(
        reqs, SamplingOptions(max_new_tokens=6)
    )
    sharded_eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=4, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32"),
        CacheConfig(kind="dense"),
        mesh_cfg=MeshConfig(tp=2),
    )
    assert sharded_eng.generate(reqs, SamplingOptions(max_new_tokens=6)) == plain


def test_engine_mesh_rejects_bad_configs():
    from distributed_llm_inference_tpu.config import MeshConfig

    with pytest.raises(ValueError):  # ring prefill: dense/paged only (the
        InferenceEngine(                 # sink ring evicts on write)
            CFG, PARAMS, EngineConfig(max_batch_size=2, dtype="float32"),
            CacheConfig(kind="sink"), mesh_cfg=MeshConfig(sp=2),
        )
    with pytest.raises(ValueError):  # sp does not compose with pp serving
        InferenceEngine(
            CFG, PARAMS, EngineConfig(max_batch_size=4, dtype="float32"),
            CacheConfig(kind="dense"), mesh_cfg=MeshConfig(pp=2, sp=2),
        )
    with pytest.raises(ValueError):  # batch must divide by pp*dp
        InferenceEngine(
            CFG, PARAMS, EngineConfig(max_batch_size=3, dtype="float32"),
            CacheConfig(kind="dense"), mesh_cfg=MeshConfig(dp=2),
        )
    with pytest.raises(ValueError):  # pp: dense/paged only (sink has no
        InferenceEngine(                 # staged write-behind tail)
            CFG, PARAMS, EngineConfig(max_batch_size=4, dtype="float32"),
            CacheConfig(kind="sink"), mesh_cfg=MeshConfig(pp=2),
        )


def _ring_engine(kv_quant=None, sp=2, batch=2):
    from distributed_llm_inference_tpu.config import MeshConfig

    return InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=batch, prefill_buckets=(8, 16),
                     max_seq_len=64, dtype="float32"),
        CacheConfig(kind="dense", kv_quant=kv_quant),
        mesh_cfg=MeshConfig(sp=sp),
    )


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_engine_ring_prefill_matches_solo(kv_quant):
    """Prompts past the ring threshold prefill sequence-sharded over sp and
    decode to the SAME tokens as the plain single-device engine (VERDICT r2
    order 5: the capability must be servable, not a library function)."""
    rng = np.random.default_rng(7)
    long_prompts = [
        rng.integers(0, CFG.vocab_size, size=n).tolist() for n in (24, 37)
    ]
    opts = SamplingOptions(max_new_tokens=6)
    plain = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16),
                     max_seq_len=64, dtype="float32"),
        CacheConfig(kind="dense", kv_quant=kv_quant),
    ).generate(long_prompts, opts)
    eng = _ring_engine(kv_quant)
    assert eng.generate(long_prompts, opts) == plain
    assert eng.metrics.snapshot().get("ring_prefills") == 2


def test_engine_ring_prefill_short_prompts_keep_bucketed_path():
    """Prompts at/below the threshold keep the chunked bucketed prefill."""
    ps = prompts(3, lo=3, hi=10, seed=21)
    opts = SamplingOptions(max_new_tokens=5)
    plain = make_engine("dense", batch=2).generate(ps, opts)
    eng = _ring_engine()
    assert eng.generate(ps, opts) == plain
    assert eng.metrics.snapshot().get("ring_prefills") is None


def test_engine_ring_prefill_composes_with_tp():
    """sp=2 × tp=2: ring prefill inside a mesh that also tensor-shards."""
    from distributed_llm_inference_tpu.config import MeshConfig

    rng = np.random.default_rng(9)
    long_prompts = [rng.integers(0, CFG.vocab_size, size=29).tolist()]
    opts = SamplingOptions(max_new_tokens=5)
    plain = make_engine("dense", batch=2).generate(long_prompts, opts)
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16),
                     max_seq_len=64, dtype="float32"),
        CacheConfig(kind="dense"),
        mesh_cfg=MeshConfig(sp=2, tp=2),
    )
    assert eng.generate(long_prompts, opts) == plain
    assert eng.metrics.snapshot().get("ring_prefills") == 1


def test_engine_tp_pp_dp_continuous_batching_matches_solo():
    """CONFIGS.md config 5's serving shape: a tp=2 x pp=2 x dp=2 mesh under
    the UNCHANGED continuous-batching scheduler reproduces solo tokens."""
    from distributed_llm_inference_tpu.config import MeshConfig

    ps = prompts(7, seed=11)
    opts = SamplingOptions(max_new_tokens=6)
    plain = make_engine("dense").generate(ps, opts)
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=4, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32"),
        CacheConfig(kind="dense"),
        mesh_cfg=MeshConfig(tp=2, pp=2, dp=2),
    )
    assert eng.generate(ps, opts) == plain


def test_engine_pp_multi_step_decode_matches_solo():
    """pp serving composes with decode_steps>1 (per-step pipelined scan)."""
    from distributed_llm_inference_tpu.config import MeshConfig

    ps = prompts(5, seed=12)
    opts = SamplingOptions(max_new_tokens=7)
    plain = make_engine("dense").generate(ps, opts)
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=4, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32", decode_steps=4),
        CacheConfig(kind="dense"),
        mesh_cfg=MeshConfig(pp=2, dp=2),
    )
    assert eng.generate(ps, opts) == plain


def test_engine_ep_mesh_moe():
    """Mixtral served with experts sharded over ep == unsharded."""
    from distributed_llm_inference_tpu.config import MeshConfig

    mcfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, family="mixtral",
    )
    mparams = llama.init_params(mcfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    reqs = prompts(3, seed=5)

    def run(mesh_cfg):
        eng = InferenceEngine(
            mcfg, mparams,
            EngineConfig(max_batch_size=2, prefill_buckets=(8, 16),
                         max_seq_len=48, dtype="float32"),
            CacheConfig(kind="dense"),
            mesh_cfg=mesh_cfg,
        )
        return eng.generate(reqs, SamplingOptions(max_new_tokens=5))

    assert run(MeshConfig(ep=2)) == run(None)


def test_decode_windows_do_not_change_tokens():
    """Window bucketing is a bandwidth optimization only: streams must be
    identical with windows on (default ladder), custom, and off."""
    reqs = prompts(6, seed=41)

    def run(decode_windows):
        eng = InferenceEngine(
            CFG, PARAMS,
            EngineConfig(max_batch_size=3, prefill_buckets=(8, 16, 32),
                         max_seq_len=64, dtype="float32",
                         decode_windows=decode_windows),
            CacheConfig(kind="dense"),
        )
        return eng.generate(reqs, SamplingOptions(max_new_tokens=9))

    off = run(())
    assert run(None) == off            # auto ladder
    assert run((16, 40, 64)) == off    # custom buckets
    # And for the quantized cache.
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=3, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32"),
        CacheConfig(kind="dense", kv_quant="int8"),
    )
    q_on = eng.generate(reqs, SamplingOptions(max_new_tokens=9))
    eng2 = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=3, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32", decode_windows=()),
        CacheConfig(kind="dense", kv_quant="int8"),
    )
    assert q_on == eng2.generate(reqs, SamplingOptions(max_new_tokens=9))


def test_cache_growth_and_idle_shrink():
    # decode_steps=1 (the synchronous engine): this test inspects max_len
    # between generates, and the pipelined flow's trailing admit shrinks the
    # idle cache before generate() returns (growth itself is covered by the
    # counter assert and by test_pipelined_growth_ladder below).
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 32), max_seq_len=64,
                     dtype="float32", decode_steps=1),
        CacheConfig(kind="dense"),
    )
    first_bucket = eng._windows[0]
    assert eng.cache.max_len == first_bucket
    long_prompt = prompts(1, lo=30, hi=31, seed=50)[0]
    out = eng.generate([long_prompt], SamplingOptions(max_new_tokens=10))[0]
    assert len(out) == 10
    assert eng.metrics.snapshot().get("cache_growths", 0) >= 1
    grown = eng.cache.max_len
    assert grown >= 41
    # Next admission with everything idle (for IDLE_SHRINK_S: aged here)
    # shrinks back to the first bucket (then regrows as needed for the new
    # prompt).
    eng._idle_since -= IDLE_SHRINK_S
    eng.generate([prompts(1, lo=3, hi=4, seed=51)[0]],
                 SamplingOptions(max_new_tokens=2))
    assert eng.cache.max_len < grown


def test_decode_windows_validation():
    with pytest.raises(ValueError):
        InferenceEngine(
            CFG, PARAMS,
            EngineConfig(max_batch_size=2, max_seq_len=64, dtype="float32",
                         decode_windows=(128, 256)),
            CacheConfig(kind="dense"),
        )
    with pytest.raises(ValueError):
        InferenceEngine(
            CFG, PARAMS,
            EngineConfig(max_batch_size=2, max_seq_len=64, dtype="float32",
                         decode_windows=(-32, 64)),
            CacheConfig(kind="dense"),
        )


@pytest.mark.parametrize("keep_alive", [False, True], ids=["idle-long", "idle-a-moment"])
def test_paged_table_growth_and_shrink(keep_alive):
    eng = make_engine(kind="paged", batch=2)
    first_slots = eng.cache.page_table.shape[1]
    assert first_slots < eng.ccfg.max_pages_per_session
    long_prompt = prompts(1, lo=30, hi=31, seed=60)[0]
    ref_eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32", decode_windows=()),
        CacheConfig(kind="paged", page_size=8, num_pages=64,
                    max_pages_per_session=8),
    )
    ref = ref_eng.generate([long_prompt], SamplingOptions(max_new_tokens=12))
    out = eng.generate([long_prompt], SamplingOptions(max_new_tokens=12))
    assert out == ref
    assert eng.metrics.snapshot().get("cache_growths", 0) >= 1
    grown = eng.cache.page_table.shape[1]
    assert grown > first_slots
    # Idle admission shrinks the table back — once the engine has been
    # idle for IDLE_SHRINK_S; a moment between two requests keeps the
    # high-water table (and the executables already compiled for it).
    if not keep_alive:
        eng._idle_since -= IDLE_SHRINK_S  # half a minute later
    eng.generate([prompts(1, lo=3, hi=4, seed=61)[0]],
                 SamplingOptions(max_new_tokens=2))
    if keep_alive:
        assert eng.cache.page_table.shape[1] == grown
        eng._idle_since -= IDLE_SHRINK_S  # ... and half a minute later
        eng.generate([prompts(1, lo=3, hi=4, seed=62)[0]],
                     SamplingOptions(max_new_tokens=2))
    assert eng.cache.page_table.shape[1] < grown


# -- multi-token on-device decode (decode_steps > 1) --------------------------


def make_engine_k(K, kind="dense", batch=4, **cache_kw):
    cache_defaults = dict(
        kind=kind, page_size=8, num_pages=64, max_pages_per_session=8,
        window_length=32, num_sink_tokens=2,
    )
    cache_defaults.update(cache_kw)
    return InferenceEngine(
        CFG, PARAMS,
        EngineConfig(
            max_batch_size=batch, prefill_buckets=(8, 16, 32), max_seq_len=64,
            dtype="float32", decode_steps=K,
        ),
        CacheConfig(**cache_defaults),
    )


@pytest.mark.parametrize("kind", ["dense", "paged", "sink"])
def test_decode_steps_matches_single_step(kind):
    """K-step fused decode must reproduce per-token greedy decode exactly."""
    ps = prompts(6, seed=7)
    opts = SamplingOptions(max_new_tokens=11)  # not a multiple of K
    ref = make_engine_k(1, kind).generate(ps, opts)
    out = make_engine_k(4, kind).generate(ps, opts)
    assert out == ref


def test_decode_steps_eos_mid_scan():
    """A row hitting EOS inside the scan stops exactly there."""
    ps = prompts(3, seed=8)
    ref = make_engine_k(1).generate([ps[0]], SamplingOptions(max_new_tokens=9))[0]
    eos = ref[4]  # EOS lands mid-scan for K=4 (step 5 of 9)
    opts = SamplingOptions(max_new_tokens=9, eos_token_id=eos)
    ref_eng = make_engine_k(1)
    out_eng = make_engine_k(4)
    ref_outs = ref_eng.generate(ps, opts)
    outs = out_eng.generate(ps, opts)
    assert outs == ref_outs
    assert outs[0][-1] == eos and len(outs[0]) <= 9
    for eng in (ref_eng, out_eng):
        for s in eng.sessions.values():
            assert s.finish_reason in ("eos", "length")


def test_decode_steps_paged_page_growth():
    """K-step decode crossing page boundaries pre-allocates enough pages."""
    ps = prompts(4, seed=9, lo=5, hi=9)
    opts = SamplingOptions(max_new_tokens=20)  # crosses several 8-token pages
    ref = make_engine_k(1, "paged").generate(ps, opts)
    eng = make_engine_k(8, "paged")
    out = eng.generate(ps, opts)
    assert out == ref
    assert eng.allocator.free_count == 63  # all pages reclaimed


def test_decode_steps_capacity_finish():
    """Dense rows stop at max_seq_len even when K overshoots it."""
    eng = make_engine_k(8, "dense")
    long_prompt = prompts(1, seed=10, lo=58, hi=59)[0]  # 58 + 1 + k <= 64
    outs = eng.generate([long_prompt], SamplingOptions(max_new_tokens=50))
    s = list(eng.sessions.values())[0]
    assert s.finish_reason == "capacity"
    assert len(outs[0]) <= 64 - 58


def test_cancel_active_session_frees_slot():
    """Cancelling a running session releases its slot at the next tick and
    admits queued work (cancel() is a flag; the scheduler owns state)."""
    from distributed_llm_inference_tpu.engine.session import SessionState

    # a token a tick: what is counted below is ticks
    eng = make_engine(batch=1, decode_steps=1)
    a = eng.submit(prompts(1, seed=13)[0], SamplingOptions(max_new_tokens=50))
    b = eng.submit(prompts(1, seed=14)[0], SamplingOptions(max_new_tokens=3))
    for _ in range(3):
        eng.step()  # a is active, b waits
    assert eng.sessions[a].state == SessionState.ACTIVE
    eng.cancel(a)
    while eng.has_work():
        eng.step()
    assert eng.sessions[a].state == SessionState.CANCELLED
    assert eng.sessions[a].finish_reason == "cancelled"
    assert len(eng.sessions[a].generated) <= 5  # stopped promptly
    assert len(eng.sessions[b].generated) == 3  # b got the slot and finished


@pytest.mark.parametrize("mesh_kw", [dict(pp=2), dict(pp=2, dp=2)])
def test_engine_growth_ladder_under_pp_dp(mesh_kw):
    """The decode-window growth ladder works under pp/dp serving meshes
    (VERDICT r2 order 6): the buffer starts at the smallest bucket, grows
    mid-serving (per-bucket pipelined executables + re-shard), and tokens
    match the solo engine exactly."""
    from distributed_llm_inference_tpu.config import MeshConfig

    rng = np.random.default_rng(13)
    ps = [rng.integers(0, CFG.vocab_size, size=6).tolist() for _ in range(4)]
    opts = SamplingOptions(max_new_tokens=24)  # 6 + 24 > first bucket 16
    plain = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=4, prefill_buckets=(8, 16), max_seq_len=64,
                     dtype="float32", decode_windows=(16, 64)),
        CacheConfig(kind="dense"),
    ).generate(ps, opts)
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=4, prefill_buckets=(8, 16), max_seq_len=64,
                     dtype="float32", decode_windows=(16, 64)),
        CacheConfig(kind="dense"),
        mesh_cfg=MeshConfig(**mesh_kw),
    )
    assert eng.generate(ps, opts) == plain
    assert eng.metrics.snapshot().get("cache_growths", 0) >= 1
    assert eng.cache.max_len == 64  # grew off the first bucket


def test_pipelined_growth_ladder():
    """Pipelined engine grows the buffer mid-serving (conservative budgets
    include the in-flight tick) and produces the same tokens as the
    non-pipelined engine."""
    mk = lambda pipelined: InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 32), max_seq_len=64,
                     dtype="float32", decode_steps=None if pipelined else 1),
        CacheConfig(kind="dense"),
    )
    long_prompt = prompts(1, lo=30, hi=31, seed=50)[0]
    opts = SamplingOptions(max_new_tokens=10)
    ref = mk(False).generate([long_prompt], opts)
    eng = mk(True)
    assert eng._pipelined
    assert eng.generate([long_prompt], opts) == ref
    assert eng.metrics.snapshot().get("cache_growths", 0) >= 1


def test_pipelined_matches_sync_mixed_sessions():
    """Token-exact equivalence of the two flows under churn: staggered
    lengths, EOS stops, capacity pressure."""
    ps = prompts(7, lo=3, hi=14, seed=33)
    opts = SamplingOptions(max_new_tokens=9)
    mk = lambda pipelined: InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=3, prefill_buckets=(8, 16), max_seq_len=32,
                     dtype="float32", decode_steps=None if pipelined else 1),
        CacheConfig(kind="dense"),
    )
    assert mk(True).generate(ps, opts) == mk(False).generate(ps, opts)
    # EOS mid-stream: pick a token the greedy path actually emits
    ref = mk(False).generate([ps[0]], opts)[0]
    eos_opts = SamplingOptions(max_new_tokens=9, eos_token_id=ref[3])
    assert (
        mk(True).generate(ps, eos_opts) == mk(False).generate(ps, eos_opts)
    )


@pytest.mark.parametrize("decode_steps", [None, 1], ids=["defaults", "one_token"])
def test_pipelined_paged_matches_sync(decode_steps):
    """A paged engine built with the defaults pipelines (conservative page
    growth against the in-flight tick) and overlaps an admission that lands
    behind a tick in flight; at ``decode_steps=1`` it does neither. Both are
    token-exact vs the same engine held to the synchronous flow (an int8
    pool's tail makes a 16-step tick's numbers its own, so the one-token
    engine is no reference here), pages reclaimed."""
    ps = prompts(6, lo=3, hi=12, seed=41)
    opts = SamplingOptions(max_new_tokens=24)  # past one 16-step tick
    mk = lambda steps: InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=3, prefill_buckets=(8, 16), max_seq_len=48,
                     dtype="float32", decode_steps=steps),
        CacheConfig(kind="paged", kv_quant="int8", page_size=8, num_pages=64,
                    max_pages_per_session=6),
    )
    sync = mk(decode_steps)
    sync._pipelined = False  # each tick resolved before the next dispatch
    ref = sync.generate(ps, opts)
    eng, pipelined = mk(decode_steps), decode_steps is None
    gids = [eng.submit(ps[0], opts)]
    eng.step()  # admitted synchronously: no tick in flight yet
    eng.step()  # a pipelined engine's first tick is now in flight
    assert eng._pipelined is pipelined and eng._overlap_ok() is pipelined
    gids += [eng.submit(p, opts) for p in ps[1:]]
    while eng.has_work():
        eng.step()
    assert [eng.sessions[g].generated for g in gids] == ref
    overlapped = eng.metrics.snapshot().get("admit_overlap_sessions", 0)
    assert (overlapped > 0) is pipelined
    assert eng.allocator.free_count == 63  # all pages back (minus null page)


def test_batched_admission_matches_single_row_prefill():
    """r4 batched multi-row prefill: a burst of admissions goes through ONE
    bucketed dispatch per prompt-bucket group (counted via the
    batched_prefills metric) and produces EXACTLY the tokens the
    single-row path produces, across cache kinds."""
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    # 5 prompts over batch 4: the first admission wave is a FULL group of
    # 4 (no padding) and, after one retires, a later wave plus the 3-prompt
    # case below covers PADDED groups (3 -> nr 4), whose pad rows must not
    # clobber a real row's prefill (r4 review finding: duplicate-index
    # scatters are undefined-order).
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 12, 13, 14, 15, 16, 17],
               [21, 22], [31, 32, 33]]
    opts = SamplingOptions(max_new_tokens=8, temperature=0.0)

    def run(kind, kv_quant, force_single):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=4, max_seq_len=64, dtype="float32",
                         prefill_buckets=(8, 16)),
            CacheConfig(kind=kind, kv_quant=kv_quant, num_pages=24,
                        page_size=8, max_pages_per_session=8),
        )
        if force_single:
            eng._batch_admission = False
        out = eng.generate(prompts, opts)
        return out, eng.metrics.snapshot()

    for kind, kv in (("dense", "int8"), ("paged", "int8")):
        single, _ = run(kind, kv, True)
        batched, counters = run(kind, kv, False)
        assert single == batched, (kind, kv)
        assert counters.get("batched_prefills", 0) >= 4, (kind, counters)


def test_batched_admission_padded_group_preserves_every_row():
    """3 same-bucket admissions pad to a 4-row dispatch: the pad row is
    OUT-OF-RANGE (clamped gather, dropped scatter) — padding by
    duplicating a real row made the merge scatter undefined-order and
    clobbered row 0's freshly written prompt KV with stale content
    (caught by review, reproduced: row 0's stream diverged after a few
    tokens)."""
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 12, 13]]
    opts = SamplingOptions(max_new_tokens=8, temperature=0.0)

    def run(kind, force_single):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=4, max_seq_len=64, dtype="float32",
                         prefill_buckets=(8,)),
            CacheConfig(kind=kind, kv_quant="int8", num_pages=24,
                        page_size=8, max_pages_per_session=8),
        )
        if force_single:
            eng._batch_admission = False
        out = eng.generate(prompts, opts)
        return out, eng.metrics.snapshot()

    for kind in ("dense", "paged"):
        single, _ = run(kind, True)
        batched, counters = run(kind, False)
        assert single == batched, (kind, single, batched)
        assert counters.get("batched_prefills", 0) == 3, counters



@pytest.mark.parametrize("mesh_kw,kv_quant", [
    (dict(pp=2), None),
    (dict(pp=2, dp=2), None),
    (dict(pp=2, tp=2), None),
    (dict(pp=2), "int8"),
])
def test_engine_pp_paged_matches_solo(mesh_kw, kv_quant):
    """CONFIGS.md's configs 4+5 composed (VERDICT r4 ask 9): the vLLM-style
    paged pool serves under a pipeline-parallel mesh. The pool's layer axis
    leads every array, so each pp stage holds its own layers' pages
    (pipeline SHARED_FIELDS pass-through); page installs ride the chunked
    GSPMD-safe DUS path. Tokens match the solo paged engine exactly."""
    from distributed_llm_inference_tpu.config import MeshConfig

    ps = prompts(6, seed=17)
    opts = SamplingOptions(max_new_tokens=6)
    kw = dict(
        max_batch_size=4, prefill_buckets=(8, 16, 32), max_seq_len=64,
        dtype="float32",
    )
    cc = CacheConfig(kind="paged", kv_quant=kv_quant, page_size=8,
                     num_pages=64, max_pages_per_session=8)
    plain = InferenceEngine(
        CFG, PARAMS, EngineConfig(**kw), cc,
    ).generate(ps, opts)
    eng = InferenceEngine(
        CFG, PARAMS, EngineConfig(**kw), cc, mesh_cfg=MeshConfig(**mesh_kw),
    )
    assert eng.generate(ps, opts) == plain


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_engine_ring_prefill_paged_matches_solo(kv_quant):
    """r5: long-context ring prefill FEEDS THE PAGED POOL (VERDICT r4 weak
    #7's second half — previously sp>1 required the dense cache): prompts
    past the ring threshold prefill sequence-sharded over sp, the ring KV
    ingests into the session's pages (PagedKVCache.ingest_row), and decode
    proceeds on the paged pool with tokens matching the plain paged
    engine."""
    from distributed_llm_inference_tpu.config import MeshConfig

    rng = np.random.default_rng(23)
    long_prompts = [
        rng.integers(0, CFG.vocab_size, size=n).tolist() for n in (24, 37)
    ]
    opts = SamplingOptions(max_new_tokens=6)
    cc = CacheConfig(kind="paged", kv_quant=kv_quant, page_size=8,
                     num_pages=64, max_pages_per_session=8)
    kw = dict(max_batch_size=2, prefill_buckets=(8, 16), max_seq_len=64,
              dtype="float32")
    plain = InferenceEngine(
        CFG, PARAMS, EngineConfig(**kw), cc,
    ).generate(long_prompts, opts)
    eng = InferenceEngine(
        CFG, PARAMS, EngineConfig(**kw), cc, mesh_cfg=MeshConfig(sp=2),
    )
    assert eng.generate(long_prompts, opts) == plain
    assert eng.metrics.snapshot().get("ring_prefills") == 2


# -- overlapped (stall-free) admission ----------------------------------------


def _overlap_engine(kind, overlap, rng_seed=7, batch=3, **ekw):
    cache_kw = dict(kind="dense")
    if kind == "paged":
        # kv_quant="int8" so the paged pool is tail-capable on CPU (the
        # bf16 pool needs the Pallas kernel to pipeline).
        cache_kw = dict(kind="paged", kv_quant="int8", page_size=8,
                        num_pages=64, max_pages_per_session=8)
    ekw.setdefault("max_batch_size", batch)
    ekw.setdefault("prefill_buckets", (8, 16))
    ekw.setdefault("max_seq_len", 64)
    # Short fused ticks (4 decode steps) so a session's budget spans
    # several ticks: admissions then land while a tick is genuinely in
    # flight, exercising the deferred-fetch overlap path. With the
    # default 16-step tick, these tiny max_new budgets fit in ONE tick
    # and every admission would (correctly) fall back to sync.
    ekw.setdefault("decode_steps", 4)
    eng = InferenceEngine(
        CFG, PARAMS, EngineConfig(dtype="float32", **ekw),
        CacheConfig(**cache_kw), rng=jax.random.PRNGKey(rng_seed),
    )
    assert eng._pipelined
    if not overlap:
        # the same pipelined engine held to the synchronous admission path
        eng._overlap_ok = lambda: False
    return eng


def _churn_run(kind, overlap, ps, opts, rng_seed=7):
    """Run ``ps`` to completion with staggered admissions: two residents
    first, then the rest submitted once a pipelined tick is in flight, so
    later admissions land mid-tick and (overlap on) take the deferred-
    fetch path. A single up-front generate() would admit lockstep cohorts
    whose members all finish exactly when the dispatch runs dry — pending
    would be None at every churn admission and overlap would never
    engage."""
    eng = _overlap_engine(kind, overlap, rng_seed=rng_seed)
    gids = [eng.submit(ps[0], opts), eng.submit(ps[1], opts)]
    eng.step()  # admit the residents synchronously (no tick in flight)
    eng.step()  # first pipelined tick now in flight
    gids += [eng.submit(p, opts) for p in ps[2:]]
    while eng.has_work():
        eng.step()
    return [eng.sessions[g].generated for g in gids], eng.metrics.snapshot()


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_overlap_admission_parity_greedy(kind):
    """Byte-exact token parity of overlapped against synchronous
    admission under churn (7 prompts over 3 slots: later admissions land while a
    pipelined tick is in flight and take the deferred-fetch path)."""
    ps = prompts(7, lo=3, hi=14, seed=71)
    opts = SamplingOptions(max_new_tokens=10)
    on, snap = _churn_run(kind, True, ps, opts)
    off, snap_off = _churn_run(kind, False, ps, opts)
    assert on == off
    # The overlap engine actually exercised the deferred path (without
    # this the parity assert could pass vacuously).
    assert snap.get("admit_overlap_sessions", 0) > 0
    assert snap_off.get("admit_overlap_sessions", 0) == 0


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_overlap_admission_parity_sampled(kind):
    """Sampled streams (temperature/top_p) are byte-exact too: the overlap
    path defers only the token FETCH — device programs and RNG-key order
    are identical, so sampling draws the same values."""
    ps = prompts(7, lo=3, hi=14, seed=72)
    opts = SamplingOptions(max_new_tokens=10, temperature=1.0, top_p=0.9)
    on, snap = _churn_run(kind, True, ps, opts, rng_seed=11)
    off, _ = _churn_run(kind, False, ps, opts, rng_seed=11)
    assert on == off
    assert snap.get("admit_overlap_sessions", 0) > 0


def test_cancel_during_inflight_prefill():
    """A cancel that lands while a session's overlapped prefill is in
    flight drops the deferred first token (no tokens ever delivered) and
    frees the slot and pages at the next tick boundary."""
    eng = _overlap_engine("paged", True, batch=2)
    free0 = eng.allocator.free_count
    a = eng.submit(prompts(1, seed=80)[0], SamplingOptions(max_new_tokens=64))
    eng.step()  # admit a synchronously (no tick in flight yet)
    eng.step()  # dispatch the first pipelined tick
    b = eng.submit(prompts(1, seed=81)[0], SamplingOptions(max_new_tokens=64))
    eng.step()  # admit b OVERLAPPED behind the in-flight tick
    sb = eng.sessions[b]
    assert sb.prefill_inflight and sb.generated == []
    assert eng.metrics.get_gauge("admit_overlap_inflight") == 1
    eng.cancel(b)
    eng.step()  # resolve drops b's token; the reap frees slot + pages
    assert sb.finish_reason == "cancelled"
    assert sb.generated == [] and sb.slot is None and sb.pages == []
    assert not sb.prefill_inflight
    assert eng.metrics.get_gauge("admit_overlap_inflight") == 0
    eng.cancel(a)
    while eng.has_work():
        eng.step()
    assert eng.allocator.free_count == free0  # every page reclaimed


def test_deadline_during_inflight_prefill():
    """A deadline expiring while the prefill is in flight reaps the
    session at the next tick boundary (finish_reason "deadline"), exactly
    like the synchronous path — at most the deferred first token is
    delivered before the terminal event."""
    import time as _time

    eng = _overlap_engine("paged", True, batch=2)
    free0 = eng.allocator.free_count
    a = eng.submit(prompts(1, seed=82)[0], SamplingOptions(max_new_tokens=64))
    eng.step()
    eng.step()
    b = eng.submit(prompts(1, seed=83)[0],
                   SamplingOptions(max_new_tokens=64),
                   deadline=_time.monotonic() + 60.0)
    eng.step()  # overlapped admission
    sb = eng.sessions[b]
    assert sb.prefill_inflight
    sb.deadline = _time.monotonic() - 0.001  # expire while in flight
    eng.step()
    assert sb.finish_reason == "deadline"
    assert len(sb.generated) <= 1 and sb.slot is None and sb.pages == []
    eng.cancel(a)
    while eng.has_work():
        eng.step()
    assert eng.allocator.free_count == free0


def test_overlap_admission_flood_backpressure(monkeypatch):
    """An admission flood past OVERLAP_MAX_INFLIGHT spills to
    the synchronous path (bounded in-flight device work) and still
    produces byte-exact streams."""
    ps = prompts(9, lo=3, hi=15, seed=90)
    opts = SamplingOptions(max_new_tokens=7)
    monkeypatch.setattr(engine_mod, "OVERLAP_MAX_INFLIGHT", 1)

    def run(overlap):
        eng = _overlap_engine("dense", overlap, batch=8)
        # One resident session keeps a tick in flight, then the flood of 8
        # arrives in a single admission pass spanning both prompt buckets.
        first = eng.submit(ps[0], opts)
        eng.step()
        eng.step()
        rest = [eng.submit(p, opts) for p in ps[1:]]
        while eng.has_work():
            eng.step()
        outs = [eng.sessions[g].generated for g in [first] + rest]
        return outs, eng.metrics.snapshot()

    on, snap = run(True)
    off, _ = run(False)
    assert on == off
    assert snap.get("admit_overlap_sessions", 0) > 0  # some overlapped
    assert snap.get("admit_overlap_spill", 0) > 0     # cap forced a spill
    assert snap.get("admit_sync_sessions", 0) > 0     # ...to the sync path
    assert snap.get("admit_to_merge_count", 0) >= 1   # latency observed
