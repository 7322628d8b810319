"""Speculative decoding: exact equivalence with target-only greedy decode.

The invariant under test (the whole point of the design): speculation changes
how many target forwards happen, never the tokens produced.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.engine.speculative import SpeculativeDecoder
from distributed_llm_inference_tpu.models import llama

TARGET = ModelConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
    num_heads=4, num_kv_heads=2, head_dim=8, max_position_embeddings=128,
)
DRAFT = ModelConfig(
    vocab_size=64, hidden_size=16, intermediate_size=32, num_layers=1,
    num_heads=2, num_kv_heads=1, head_dim=8, max_position_embeddings=128,
)


def _greedy(cfg, params, prompt, steps):
    cache = DenseKVCache.create(
        cfg.num_layers, 1, 128, cfg.num_kv_heads, cfg.head_dim, jnp.float32
    )
    logits, cache = llama.model_apply(
        cfg, params, jnp.asarray([prompt], jnp.int32), cache,
        jnp.full((1,), len(prompt), jnp.int32),
    )
    tok = int(jnp.argmax(logits[0, len(prompt) - 1]))
    out = [tok]
    for _ in range(steps - 1):
        logits, cache = llama.model_apply(
            cfg, params, jnp.asarray([[tok]], jnp.int32), cache,
            jnp.ones((1,), jnp.int32),
        )
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


@pytest.mark.parametrize("k", [1, 3, 4])
def test_speculative_equals_greedy_weak_draft(k):
    """A draft with unrelated weights: low acceptance, identical output."""
    tp = llama.init_params(TARGET, jax.random.PRNGKey(0), jnp.float32)
    dp = llama.init_params(DRAFT, jax.random.PRNGKey(7), jnp.float32)
    dec = SpeculativeDecoder(TARGET, tp, DRAFT, dp, k=k, max_seq_len=128,
                             dtype=jnp.float32)
    got = dec.generate([3, 14, 15], max_new_tokens=20)
    assert got == _greedy(TARGET, tp, [3, 14, 15], 20)
    assert 0.0 <= dec.acceptance_rate <= 1.0


def test_speculative_equals_greedy_perfect_draft():
    """Draft == target: every proposal accepted, identical output."""
    tp = llama.init_params(TARGET, jax.random.PRNGKey(1), jnp.float32)
    dec = SpeculativeDecoder(TARGET, tp, TARGET, tp, k=4, max_seq_len=128,
                             dtype=jnp.float32)
    got = dec.generate([9, 2, 5, 5], max_new_tokens=17)
    assert got == _greedy(TARGET, tp, [9, 2, 5, 5], 17)
    assert dec.acceptance_rate == 1.0
    # k+1 tokens per verify step: far fewer target steps than tokens.
    assert dec.stats["steps"] <= (17 // 5) + 1


def test_speculative_respects_eos():
    tp = llama.init_params(TARGET, jax.random.PRNGKey(2), jnp.float32)
    dp = llama.init_params(DRAFT, jax.random.PRNGKey(3), jnp.float32)
    ref = _greedy(TARGET, tp, [1, 2], 30)
    eos = ref[5]  # force an eos hit mid-stream
    dec = SpeculativeDecoder(TARGET, tp, DRAFT, dp, k=3, max_seq_len=128,
                             dtype=jnp.float32)
    got = dec.generate([1, 2], max_new_tokens=30, eos_token_id=eos)
    assert got == ref[: ref.index(eos) + 1]


def test_rejects_mismatched_vocab():
    bad = ModelConfig(vocab_size=32, hidden_size=16, intermediate_size=32,
                      num_layers=1, num_heads=2, num_kv_heads=1, head_dim=8)
    tp = llama.init_params(TARGET, jax.random.PRNGKey(0), jnp.float32)
    bp = llama.init_params(bad, jax.random.PRNGKey(1), jnp.float32)
    with pytest.raises(ValueError):
        SpeculativeDecoder(TARGET, tp, bad, bp)


# -- engine-integrated speculative decoding -----------------------------------

CFG = TARGET
PARAMS = llama.init_params(TARGET, jax.random.PRNGKey(0), jnp.float32)
DCFG = DRAFT
DPARAMS = llama.init_params(DRAFT, jax.random.PRNGKey(7), jnp.float32)


def _engine(draft=None, kind="dense", K=1, batch=4):
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine

    return InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=batch, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32", speculative_k=3,
                     decode_steps=K),
        CacheConfig(kind=kind, page_size=8, num_pages=64,
                    max_pages_per_session=8),
        draft=draft,
    )


def _prompts(n, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=int(rng.integers(3, 10))).tolist()
            for _ in range(n)]


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_engine_speculative_matches_plain_greedy(kind):
    """Speculative and normal sessions share a batch; all outputs equal the
    non-speculative greedy engine's."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    ps = _prompts(6, 21)
    plain = _engine(kind=kind).generate(ps, SamplingOptions(max_new_tokens=9))

    eng = _engine(draft=(DCFG, DPARAMS), kind=kind)
    subs = []
    for i, p in enumerate(ps):
        subs.append(eng._submit_session(
            p, SamplingOptions(max_new_tokens=9, speculative=(i % 2 == 0))
        ))
    while eng.has_work():
        eng.step()
    assert [s.generated for s in subs] == plain
    assert eng.spec_stats["steps"] > 0


def test_engine_speculative_self_draft_full_acceptance():
    """Draft == target: every proposal accepted (the catch-up path runs)."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    ps = _prompts(3, 22)
    plain = _engine().generate(ps, SamplingOptions(max_new_tokens=8))
    eng = _engine(draft=(CFG, PARAMS))
    outs = eng.generate(
        ps, SamplingOptions(max_new_tokens=8, speculative=True)
    )
    assert outs == plain
    assert eng.spec_stats["accepted"] == eng.spec_stats["proposed"]


def test_engine_speculative_requires_rollback_cache():
    with pytest.raises(ValueError):
        _engine(draft=(DCFG, DPARAMS), kind="sink")


def test_engine_speculative_survives_capacity_disable_and_resume():
    """Paged pool pressure disables speculation for some ticks (plain decode);
    when pages free up and speculation resumes, the draft cache must have
    been caught up — with draft == target, acceptance stays total. Without
    the catch-up, the draft desyncs and acceptance collapses to ~0."""
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    def mk(draft):
        return InferenceEngine(
            CFG, PARAMS,
            EngineConfig(max_batch_size=2, prefill_buckets=(8, 16),
                         max_seq_len=32, dtype="float32", speculative_k=3),
            CacheConfig(kind="paged", page_size=4, num_pages=6,
                        max_pages_per_session=8),
            draft=draft,
        )

    pa, pb = [3, 14, 15, 9], [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5]
    ref = mk(None)
    ra = ref._submit_session(pa, SamplingOptions(max_new_tokens=8))
    rb = ref._submit_session(pb, SamplingOptions(max_new_tokens=2))
    while ref.has_work():
        ref.step()

    eng = mk((CFG, PARAMS))  # self-draft: every in-sync proposal accepted
    sa = eng._submit_session(
        pa, SamplingOptions(max_new_tokens=8, speculative=True)
    )
    sb = eng._submit_session(pb, SamplingOptions(max_new_tokens=2))
    while eng.has_work():
        eng.step()
    assert (sa.generated, sb.generated) == (ra.generated, rb.generated)
    st = eng.spec_stats
    assert st["proposed"] > 0
    assert st["accepted"] == st["proposed"], st


def test_engine_speculative_composes_with_tp_pp_mesh():
    """CONFIGS.md config 5's full shape: hybrid TP×PP serving WITH speculative
    decoding in the same engine — verify runs the pipelined program while
    draft proposals ride unsharded."""
    from distributed_llm_inference_tpu.config import (
        CacheConfig,
        EngineConfig,
        MeshConfig,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    # num_layers=3 doesn't divide pp=2 — use a 4-layer model.
    import jax as _jax
    cfg4 = CFG.__class__(**{**CFG.__dict__, "num_layers": 4})
    params4 = llama.init_params(cfg4, _jax.random.PRNGKey(2), jnp.float32)

    ps = _prompts(4, 31)
    opts_plain = SamplingOptions(max_new_tokens=7)

    def mk(mesh, draft):
        return InferenceEngine(
            cfg4, params4,
            EngineConfig(max_batch_size=4, prefill_buckets=(8, 16, 32),
                         max_seq_len=64, dtype="float32", speculative_k=3),
            CacheConfig(kind="dense"),
            mesh_cfg=mesh, draft=draft,
        )

    plain = mk(None, None).generate(ps, opts_plain)
    eng = mk(MeshConfig(tp=2, pp=2, dp=1), (cfg4, params4))
    outs = eng.generate(
        ps, SamplingOptions(max_new_tokens=7, speculative=True)
    )
    assert outs == plain
    assert eng.spec_stats["steps"] > 0
    assert eng.spec_stats["accepted"] == eng.spec_stats["proposed"]


def test_engine_adaptive_suspends_on_low_acceptance_and_output_identical():
    """The adaptive controller (VERDICT r4 weak #1: 'k is static — no
    adaptation when acceptance sags'): with a draft whose proposals never
    agree, the measured tokens-per-round EMA falls below the probe gate,
    the engine probes the plain fused path, and — token streams being
    bit-identical either way — the output still equals the plain engine's.
    The draft resync on a later re-probe is exercised by the controller's
    probe_period cadence."""
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    ps = _prompts(3, 33)
    opts = SamplingOptions(max_new_tokens=60, speculative=True)
    plain = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=3, prefill_buckets=(8, 16, 32),
                     max_seq_len=128, dtype="float32", decode_steps=4),
        CacheConfig(kind="dense"),
    ).generate(ps, SamplingOptions(max_new_tokens=60))

    def adaptive_engine():
        return InferenceEngine(
            CFG, PARAMS,
            EngineConfig(max_batch_size=3, prefill_buckets=(8, 16, 32),
                         max_seq_len=128, dtype="float32", speculative_k=3,
                         decode_steps=4, speculative_rounds=1,
                         speculative_probe_len=2,
                         speculative_probe_period=6),
            CacheConfig(kind="dense"),
            draft=(DCFG, DPARAMS),  # unrelated weights: low acceptance
        )

    eng = adaptive_engine()
    outs = eng.generate(ps, opts)
    assert outs == plain
    snap = eng.metrics.snapshot()
    # The controller actually engaged: it probed the plain path at least
    # once (the unrelated draft's acceptance is far below the gate).
    assert snap.get("spec_adapt_probes", 0) >= 1


def test_engine_adaptive_keeps_speculating_with_perfect_draft():
    """Full acceptance never trips the probe gate: the controller stays in
    spec mode (no probes), and output is identical to plain."""
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    ps = _prompts(2, 34)
    plain = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16, 32),
                     max_seq_len=128, dtype="float32", decode_steps=4),
        CacheConfig(kind="dense"),
    ).generate(ps, SamplingOptions(max_new_tokens=40))
    eng = InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16, 32),
                     max_seq_len=128, dtype="float32", speculative_k=3,
                     decode_steps=4, speculative_rounds=1,
                     speculative_probe_len=2,
                     speculative_probe_period=6),
        CacheConfig(kind="dense"),
        draft=(CFG, PARAMS),  # draft == target: acceptance 1
    )
    outs = eng.generate(ps, SamplingOptions(max_new_tokens=40,
                                            speculative=True))
    assert outs == plain
    assert eng.metrics.snapshot().get("spec_adapt_probes", 0) == 0


def test_engine_cancel_all_speculative_drains_pipeline():
    """Cancelling every speculative session with a fused tick in flight
    must not leave has_work() true forever (the r5 bench's cancel+drain
    between acceptance points hung exactly here: the orphaned _spec_pending
    was only flushed inside _decode_tick, which needs an occupied slot)."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    eng = _engine(draft=(DCFG, DPARAMS), K=4)
    opts = SamplingOptions(max_new_tokens=10_000, speculative=True)
    subs = [eng._submit_session(p, opts) for p in _prompts(4, 55)]
    eng.step()  # admit + prefill + dispatch the first fused tick
    eng.step()  # keep one tick in flight
    for s in subs:
        eng.cancel(s.generation_id)
    for _ in range(20):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work(), "orphaned in-flight speculative tick"
    assert all(s.state.name == "CANCELLED" for s in subs)
