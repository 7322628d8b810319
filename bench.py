"""Headline benchmark: Llama-7B decode tokens/sec/chip + p50 TTFT at bs=1.

Matches BASELINE.json's primary metric ("Llama-7B tokens/sec/chip; p50 TTFT at
bs=1"; north star 1000 tok/s/chip on v5e). Runs the real Llama-2-7B shape in
bf16 on the TPU chip (weights zero-initialized on device — throughput is
shape/dtype-bound, not value-bound). A device phase that finds no TPU fails;
the host-scope phases (relay, disagg, recovery, prefix, kvbytes, traffic,
elastic) pin themselves to the CPU on purpose. Prints ONE JSON line per
phase child, and the parent's summary last; any failed phase makes the exit
code non-zero.

One process per chip. ``python bench.py`` is a PARENT that runs every phase
as a child (``--phase NAME``) and never holds a device: everything above
the ``__main__`` check below is standard library, and the parent leaves
through that check before the first ``import jax``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

NORTH_STAR_TOK_S_CHIP = 1000.0

# The parent's run order (it cannot read PHASES without importing jax; the
# two are asserted equal where PHASES is defined).
PHASE_NAMES = (
    "bf16", "int8", "int4", "int8_kvq", "int4_kvq", "int8_kvq_pallas",
    "paged_pallas", "paged_kvq", "mistral_paged_swa", "llama3_8b_int8_kvq",
    "int8_kvq_1k", "int8_kvq_2k", "paged_kvq_1k", "sink_1k", "mixtral",
    "speculative", "engine_int8_kvq", "distributed", "disagg", "prefill",
    "mixed",
)


def _phase_in_subprocess(name: str) -> dict:
    """Run one phase isolated in a child process; the child owns the chip
    for its lifetime and frees it (and every buffer) by exiting."""
    # The speculative phase measures FIVE acceptance points (p=1/.85/.7/.5/0)
    # back to back on one engine — ~20 min with compiles; everything else
    # fits comfortably in 20.
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        capture_output=True, text=True,
        timeout=2700 if name == "speculative" else 1200,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"phase {name} subprocess failed rc={out.returncode}: "
            f"{out.stderr.strip()[-300:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _parent() -> int:
    results = {}
    failed = {}
    for name in PHASE_NAMES:
        assert "jax" not in sys.modules, "the parent must never import jax"
        try:
            results[name] = _phase_in_subprocess(name)
        except Exception as e:  # recorded; the exit code reports it
            failed[name] = repr(e)[:300]
    if failed:
        print(json.dumps({"failed_phases": failed}))
    # Headline = best full-context decode phase. The speculative phase's
    # number is measured at acceptance=1.0 by construction and the sink ring
    # reads a bounded window — neither is comparable decode work.
    _NON_HEADLINE = {"speculative", "sink_1k", "llama3_8b_int8_kvq",
                     "mistral_paged_swa", "mixtral", "distributed",
                     "disagg", "prefill", "mixed"}
    headline = [n for n in results if n not in _NON_HEADLINE]
    if not headline:
        return 1
    best_dtype = max(headline, key=lambda n: results[n]["tok_s"])
    best = results[best_dtype]
    # The engine phase's TTFT ("scope" key) measures submit→first-token
    # through the scheduler — a different scope than the prefill-only phases;
    # keep it out of the prefill-TTFT aggregate.
    ttfts = [
        r["ttft_ms"] for r in results.values()
        if r.get("ttft_ms") is not None and "scope" not in r
    ]
    dev_ttfts = [
        r.get("ttft_device_ms") for r in results.values()
        if r.get("ttft_device_ms")
    ]
    eng = results.get("engine_int8_kvq", {})
    print(json.dumps({
        # VERDICT r4 ask 6 disposition: this bench host has NO network
        # egress (DNS resolution fails; verified r5), so the real-checkpoint
        # accuracy run cannot pull a TinyLlama-class model here. The shape
        # proxy (tools/quant_accuracy.py --shape) and the synthetic
        # planted-outlier tests (tests/test_quant.py) stand in; the harness
        # un-gates automatically when DLI_ACCURACY_CKPT points at a local
        # checkpoint copy.
        "accuracy_note": "no egress on bench host; real-checkpoint KL "
                         "gated on DLI_ACCURACY_CKPT",
        "metric": "llama2_7b_decode_tok_per_sec_per_chip",
        "value": best["tok_s"],
        "unit": "tokens/sec/chip",
        "vs_baseline": round(best["tok_s"] / NORTH_STAR_TOK_S_CHIP, 4),
        "engine_tok_s": eng.get("tok_s"),
        "llama3_8b_tok_s": results.get("llama3_8b_int8_kvq", {}).get("tok_s"),
        "p50_ttft_ms_bs1_prompt128": min(ttfts) if ttfts else None,
        "p50_ttft_device_ms": min(dev_ttfts) if dev_ttfts else None,
        "batch": best["batch"],
        "weights": {"bf16": "bfloat16"}.get(best_dtype, best_dtype),
        **results,
        "backend": best.get("backend", "unknown"),
        "device": best.get("device", "unknown"),
        "model": best.get("model", "unknown"),
    }))

    # The LAST stdout line is a compact per-phase headline summary: the
    # driver's tail capture truncates the full record above (hundreds of
    # keys), which parsed as null. Keep this to one short JSON line.
    summary = {
        "tok_s": best["tok_s"],
        "vs_baseline": round(best["tok_s"] / NORTH_STAR_TOK_S_CHIP, 4),
        "batch": best["batch"],
        "backend": best.get("backend", "unknown"),
    }
    for name, r in results.items():
        if isinstance(r, dict) and r.get("tok_s") is not None:
            summary[name] = r["tok_s"]
    for name in failed:
        summary[name] = "failed"
    if eng.get("admit_burst_ms") is not None:
        summary["admit_burst_ms"] = eng["admit_burst_ms"]
        ab = eng.get("admit_burst") or {}
        if ab.get("burst_vs_steady_pct") is not None:
            summary["burst_vs_steady_pct"] = ab["burst_vs_steady_pct"]
    pf = results.get("prefill", {})
    pf_ms = {
        k.replace("prompt_", "p"): v["device_ms_p50"]
        for k, v in pf.items()
        if isinstance(v, dict) and v.get("device_ms_p50") is not None
    }
    if pf_ms:
        summary["prefill_device_ms_p50"] = pf_ms
    print(json.dumps(summary, separators=(",", ":")))
    return 1 if failed else 0


if __name__ == "__main__" and "--phase" not in sys.argv:
    sys.exit(_parent())

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inference_tpu.cache.dense import (
    DenseKVCache,
    QuantizedDenseKVCache,
)
from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.ops.quant import (
    INT4_WEIGHTS,
    QuantizedTensor,
    QUANTIZED_WEIGHTS,
)
from distributed_llm_inference_tpu.utils.compile_cache import (
    enable_compile_cache,
)

# Published peaks of one chip, keyed by ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s). A
# device that is not in the table is an error, never a default.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0, "TPU v5e": 197.0}


def _peak_bf16_tflops() -> float:
    kind = str(jax.devices()[0].device_kind)
    if kind not in PEAK_BF16_TFLOPS:
        raise RuntimeError(
            f"no published peak for device_kind {kind!r}; add it to "
            "PEAK_BF16_TFLOPS with its source"
        )
    return PEAK_BF16_TFLOPS[kind]


def _require_tpu() -> None:
    """Device phases measure the chip or fail: no model is swapped in for a
    CPU and nothing is printed under a device metric's name without one."""
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"this phase needs a TPU; JAX found {jax.default_backend()!r}"
        )


LLAMA2_7B = ModelConfig(
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=11008,
    num_layers=32,
    num_heads=32,
    num_kv_heads=32,
    head_dim=128,
    rope_theta=10000.0,
    max_position_embeddings=4096,
)

# The NORTH-STAR model (BASELINE.json: "serve Llama-3-8B … ≥1k tok/s/chip").
# GQA (8 kv heads) reads 1/4 the KV bytes of the 7B MHA shape and puts the
# decode attention contractions on the MXU (G=4 query rows per kv head).
LLAMA3_8B = ModelConfig(
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=500000.0,
    max_position_embeddings=8192,
)


def _zero_params(cfg: ModelConfig, dtype=jnp.bfloat16):
    """Device-resident zero weights of the exact model shape (fast to build;
    decode cost is independent of weight values). MoE configs get stacked
    expert tensors instead of the dense MLP."""
    h, d = cfg.hidden_size, cfg.head_dim
    L, hq, hkv, inter = cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
    z = lambda *s: jnp.zeros(s, dtype)
    layers = {
        "attn_norm": jnp.ones((L, h), dtype),
        "wq": z(L, h, hq * d),
        "wk": z(L, h, hkv * d),
        "wv": z(L, h, hkv * d),
        "wo": z(L, hq * d, h),
        "mlp_norm": jnp.ones((L, h), dtype),
    }
    if cfg.num_experts > 0:
        e = cfg.num_experts
        layers.update(
            router=z(L, h, e),
            we_g=z(L, e, h, inter),
            we_u=z(L, e, h, inter),
            we_d=z(L, e, inter, h),
        )
    else:
        layers.update(
            wg=z(L, h, inter), wu=z(L, h, inter), wd=z(L, inter, h)
        )
    return {
        "embed": z(cfg.vocab_size, h),
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": z(h, cfg.vocab_size),
        "layers": layers,
    }


def _zero_tree(cfg: ModelConfig, quantized_names, make_leaf, dtype=jnp.bfloat16):
    """Zero-weight pytree from config shapes (quantizing a materialized
    13.5 GB bf16 tree would peak above the 16 GB HBM): ``make_leaf`` builds
    the quantized leaves, everything else is zeros (norm gains: ones)."""
    shapes = jax.eval_shape(lambda: _zero_params(cfg, dtype))

    def q(name, w):
        if name not in quantized_names:
            return jnp.ones(w.shape, w.dtype) if "norm" in name else jnp.zeros(
                w.shape, w.dtype
            )
        return make_leaf(w)

    out = {k: q(k, v) for k, v in shapes.items() if k != "layers"}
    out["layers"] = {k: q(k, v) for k, v in shapes["layers"].items()}
    return out


def _zero_qparams(cfg: ModelConfig, dtype=jnp.bfloat16):
    """int8 zero-weight pytree."""
    return _zero_tree(cfg, QUANTIZED_WEIGHTS, lambda w: QuantizedTensor(
        q=jnp.zeros(w.shape, jnp.int8),
        scale=jnp.ones(w.shape[:-2] + w.shape[-1:], dtype),
    ), dtype)


def _zero_q4s_params(cfg: ModelConfig, dtype=jnp.bfloat16):
    """int4 zero-weight pytree in the half-split Pallas-kernel layout
    (``ops/quant_matmul.py`` — the r3 throughput configuration; the grouped
    pair-packed layout is the accuracy configuration and keeps unit-test
    coverage in tests/test_quant.py)."""
    from distributed_llm_inference_tpu.ops.quant import QuantizedTensor4Split
    from distributed_llm_inference_tpu.ops.quant_matmul import (
        _BIN, _BOUTP, _pad_to,
    )

    def leaf(w):
        *lead, in_dim, out_dim = w.shape
        in_p = _pad_to(in_dim, _BIN)
        out_p = _pad_to(out_dim, 2 * _BOUTP)
        return QuantizedTensor4Split(
            q=jnp.zeros((*lead, in_p, out_p // 2), jnp.int8),
            scale_lo=jnp.ones((*lead, 1, out_p // 2), jnp.float32),
            scale_hi=jnp.ones((*lead, 1, out_p // 2), jnp.float32),
            in_dim=in_dim, out_dim=out_dim,
        )

    return _zero_tree(cfg, INT4_WEIGHTS, leaf, dtype)


def _try_decode_bench(
    cfg, params, batch, ctx, steps=32, cache_cls=DenseKVCache, scan_k=16,
    use_kernel=False,
):
    """Decode throughput at ``batch``: tokens/sec on this one chip.

    ``scan_k > 1`` uses the engine's fused-decode fast path
    (``llama.multi_decode_apply`` — K steps per dispatch, big KV buffers
    read-only with a write-behind tail), exactly what the serving engine
    runs with ``EngineConfig.decode_steps``; ``scan_k=1`` is the per-token
    dispatch path.
    """
    # Buffer sized to the bucket this workload reaches (ctx//2 live + every
    # token the warmup AND timed calls write) — the serving engine's growth
    # ladder does the same: decode bandwidth tracks live context, with ctx
    # as the virtual cap. Under-sizing would silently clamp the last calls'
    # writes and fake the measured traffic.
    k = scan_k if scan_k > 1 else 1
    # Timed calls write steps tokens; the warmup call's k tokens are erased
    # by resetting lengths afterwards (its writes land below the timed
    # range and are overwritten), so the buffer needs only the timed span.
    writes = max(max(1, steps // k) * k, k)
    buf = min(ctx, ctx // 2 + writes)
    _require_tpu()
    kw = {"use_kernel": True} if use_kernel else {}
    cache = cache_cls.create(
        cfg.num_layers, batch, buf, cfg.num_kv_heads, cfg.head_dim,
        jnp.bfloat16, **kw,
    )
    cache = cache.replace(lengths=jnp.full((batch,), ctx // 2, jnp.int32))
    num_new = jnp.ones((batch,), jnp.int32)
    donate = {"donate_argnums": (2,)}

    if scan_k > 1 and hasattr(cache, "tail_init"):
        active = jnp.ones((batch,), bool)

        def decode(params, tokens, cache):
            def step_fn(i, logits, alive):
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return nxt, alive.astype(jnp.int32), alive, nxt

            emits, cache = llama.multi_decode_apply(
                cfg, params, tokens, cache, scan_k, step_fn, active,
                active.astype(jnp.int32),
            )
            return emits[-1][:, None], cache

        tokens_per_call = scan_k
    else:
        def decode(params, tokens, cache):
            logits, cache = llama.model_apply(
                cfg, params, tokens, cache, num_new
            )
            return (
                jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None],
                cache,
            )

        tokens_per_call = 1

    decode = jax.jit(decode, **donate)

    calls = max(1, steps // tokens_per_call)
    tokens = jnp.zeros((batch, 1), jnp.int32)
    tokens, cache = decode(params, tokens, cache)  # compile + warm
    jax.block_until_ready(tokens)
    cache = cache.replace(lengths=jnp.full((batch,), ctx // 2, jnp.int32))
    t0 = time.perf_counter()
    for _ in range(calls):
        tokens, cache = decode(params, tokens, cache)
    jax.block_until_ready(tokens)
    dt = time.perf_counter() - t0
    return batch * calls * tokens_per_call / dt


def _device_time_ms_per_call(fn, reps=3):
    """Profiled DEVICE time per call of ``fn(rep)`` (jax.profiler trace →
    xplane parse), or None when no device trace is available (CPU).

    ``fn`` takes the rep index so every call can vary its inputs (kept from
    an installation that served repeated identical calls from a memo; whether
    identical inputs matter on a directly attached chip is not measured).
    """
    import tempfile

    from distributed_llm_inference_tpu.utils.xplane import device_time_ps

    try:
        with tempfile.TemporaryDirectory() as td:
            with jax.profiler.trace(td):
                for i in range(reps):
                    jax.block_until_ready(fn(i))
            ps = device_time_ps(td)
        return round(ps / 1e9 / reps, 2) if ps else None
    except Exception:
        return None


def _ttft_bench(cfg, params, prompt_len=128, reps=5, cache_cls=DenseKVCache):
    """p50 time-to-first-token at bs=1 (prefill + argmax sample):
    ``(wall_ms, device_ms)``.

    The wall figure counts the host's synchronous dispatch and fetch, which
    the pipelined decode loop hides; the profiled DEVICE time (the second
    element — jax.profiler trace, xplane op total) does not. Neither has
    been measured on a directly attached chip.
    """
    cache = cache_cls.create(
        cfg.num_layers, 1, prompt_len + 8, cfg.num_kv_heads, cfg.head_dim,
        jnp.bfloat16,
    )
    num_new = jnp.full((1,), prompt_len, jnp.int32)

    @jax.jit
    def prefill(params, tokens, cache):
        logits, cache = llama.model_apply(cfg, params, tokens, cache, num_new)
        return jnp.argmax(logits[:, prompt_len - 1], -1)

    tokens = jnp.zeros((1, prompt_len), jnp.int32)
    jax.block_until_ready(prefill(params, tokens, cache))  # compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(prefill(params, tokens, cache))
        times.append((time.perf_counter() - t0) * 1e3)
    # Vary the tokens per rep (see _device_time_ms_per_call); (i % 17) + 1
    # so rep 0 does not repeat the all-zeros buffer the warmup and
    # wall-timed calls used.
    device_ms = _device_time_ms_per_call(
        lambda i: prefill(
            params, jnp.full((1, prompt_len), (i % 17) + 1, jnp.int32), cache
        )
    )
    return float(np.percentile(times, 50)), device_ms


def _decode_ladder(cfg, params, ladder, cache_cls=DenseKVCache,
                   use_kernel=False):
    """Largest-batch decode throughput that fits; ``(tok_s, batch)``.

    Each batch tries the fused K-step path first, then per-token dispatch:
    besides OOM on the tight 7B-in-16GB fit, some (shape, K) points crash
    the platform's remote AOT compiler (HTTP 500), and the per-token
    executable usually still compiles there.
    """
    err = None
    # Two independent descents — the fused K-step path and per-token
    # dispatch — each stopping at its first batch that fits/compiles (some
    # shapes OOM or crash the remote AOT compiler); report the better.
    # Neither dominates: fused wins at large batch, but when only small
    # fused batches compile, a larger per-token batch can still be faster.
    best = None
    for scan_k in (16, 1):
        for batch, ctx in ladder:
            try:
                tok_s = _try_decode_bench(
                    cfg, params, batch, ctx, cache_cls=cache_cls,
                    scan_k=scan_k, use_kernel=use_kernel,
                )
            except Exception as e:
                # repr, not the exception: a held traceback pins the failed
                # attempt's device buffers and starves the next retry.
                err = repr(e)
                continue
            if best is None or tok_s > best[0]:
                best = (tok_s, batch)
            break
    if best is None:
        raise RuntimeError(f"all decode configs failed: {err}")
    return best


def _try_paged_decode_bench(cfg, params, batch, ctx, steps=32, scan_k=16,
                            cls=None, page_size=64):
    """Decode over the paged pool with the Pallas paged-attention kernel
    reading pages in place (the long-fragmented-context serving
    configuration). ``scan_k > 1`` runs the fused write-behind-tail path
    (pool read-only through K steps, pool-segment + tail joint softmax)."""
    k = scan_k if scan_k > 1 else 1
    writes = max(max(1, steps // k) * k, k)  # warmup erased by length reset
    cache = _make_paged_cache(
        cfg.num_layers, batch, min(ctx, ctx // 2 + writes), cfg.num_kv_heads,
        cfg.head_dim,
        dtype=jnp.bfloat16,
        cls=cls, page_size=page_size,
    )
    cache = cache.replace(lengths=jnp.full((batch,), ctx // 2, jnp.int32))
    num_new = jnp.ones((batch,), jnp.int32)
    donate = {"donate_argnums": (2,)}

    if scan_k > 1 and cache.use_kernel:
        active = jnp.ones((batch,), bool)

        def decode(params, tokens, cache):
            def step_fn(i, logits, alive):
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return nxt, alive.astype(jnp.int32), alive, nxt

            emits, cache = llama.multi_decode_apply(
                cfg, params, tokens, cache, scan_k, step_fn, active,
                active.astype(jnp.int32),
            )
            return emits[-1][:, None], cache

        per_call = scan_k
    else:
        def decode(params, tokens, cache):
            logits, cache = llama.model_apply(
                cfg, params, tokens, cache, num_new
            )
            return (
                jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None],
                cache,
            )

        per_call = 1

    decode = jax.jit(decode, **donate)
    tokens = jnp.zeros((batch, 1), jnp.int32)
    tokens, cache = decode(params, tokens, cache)
    jax.block_until_ready(tokens)
    cache = cache.replace(lengths=jnp.full((batch,), ctx // 2, jnp.int32))
    calls = max(1, steps // per_call)
    t0 = time.perf_counter()
    for _ in range(calls):
        tokens, cache = decode(params, tokens, cache)
    jax.block_until_ready(tokens)
    return batch * calls * per_call / (time.perf_counter() - t0)


def _try_sink_decode_bench(cfg, params, batch, window, sinks=4, steps=32,
                           scan_k=16):
    """Decode throughput of the SINK ring cache mid-stream (ring full, every
    step evicts) — the reference's signature StreamingLLM capability
    (``/root/reference/distributed_llm_inference/models/llama/cache.py:111-133``).
    r4: the int8 ``QuantizedSinkKVCache`` serves the same fused
    write-behind-tail path as the dense cache (keys stored abs-rotated,
    eviction is an in-kernel mask — ``cache/sink.py``), replacing r3's bf16
    per-step re-rotation scan (108 tok/s at this window)."""
    from distributed_llm_inference_tpu.cache.sink import QuantizedSinkKVCache

    _require_tpu()
    cache = QuantizedSinkKVCache.create(
        cfg.num_layers, batch, window, sinks, cfg.num_kv_heads, cfg.head_dim,
        use_kernel=True,
    )
    # Mid-stream state: the ring has wrapped (seen > window), so every timed
    # step exercises the eviction masking + mod-ring flush path.
    cache = cache.replace(lengths=jnp.full((batch,), window + 7, jnp.int32))
    active = jnp.ones((batch,), bool)
    donate = {"donate_argnums": (2,)}

    def decode(params, tokens, cache):
        def step_fn(i, logits, alive):
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return nxt, alive.astype(jnp.int32), alive, nxt

        emits, cache = llama.multi_decode_apply(
            cfg, params, tokens, cache, scan_k, step_fn, active,
            active.astype(jnp.int32),
        )
        return emits[-1][:, None], cache

    decode = jax.jit(decode, **donate)
    tokens = jnp.zeros((batch, 1), jnp.int32)
    tokens, cache = decode(params, tokens, cache)  # compile + warm
    jax.block_until_ready(tokens)
    calls = max(1, steps // scan_k)
    t0 = time.perf_counter()
    for _ in range(calls):
        tokens, cache = decode(params, tokens, cache)
    jax.block_until_ready(tokens)
    return batch * calls * scan_k / (time.perf_counter() - t0)


def _sink_phase() -> dict:
    _require_tpu()
    cfg = LLAMA2_7B
    params = _zero_qparams(cfg, jnp.bfloat16)
    jax.block_until_ready(params)
    window = 1024
    err, best = None, None
    for batch in (32, 24, 16, 8):
        try:
            tok_s = _try_sink_decode_bench(cfg, params, batch, window)
        except Exception as e:
            err = repr(e)
            continue
        best = (tok_s, batch)
        break
    if best is None:
        raise RuntimeError(f"all sink configs failed: {err}")
    return {
        "tok_s": round(best[0], 2), "batch": best[1], "ttft_ms": None,
        "window": window, "cache": "sink+int8",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0].device_kind),
        "model": "llama-2-7b-shape",
    }


def _make_paged_cache(num_layers, batch, max_len, num_kv_heads, head_dim,
                      dtype=jnp.bfloat16, page_size=64, cls=None):
    """Paged pool sized for ``max_len`` tokens per row, every row's pages
    pre-assigned (the single bring-up recipe for both the decode and TTFT
    paged phases)."""
    from distributed_llm_inference_tpu.cache.paged import (
        PageAllocator,
        PagedKVCache,
    )

    if cls is None:
        cls = PagedKVCache
    slots = -(-max_len // page_size)
    cache = cls.create(
        num_layers, batch, batch * slots + 1, page_size, slots, num_kv_heads,
        head_dim, dtype, use_kernel=True,
    )
    alloc = PageAllocator(batch * slots + 1)
    for row in range(batch):
        cache = cache.assign_pages(row, alloc.alloc(slots))
    return cache


class _PagedTTFTCache:
    """Adapter so the TTFT bench prefills into a REAL paged cache (pages
    pre-assigned) instead of silently reporting the dense-cache number for
    the paged phase."""

    create = staticmethod(_make_paged_cache)


# Weight config → (param builder, decode batch ladder, KV cache class).
# Each phase runs in its own SUBPROCESS: the 7B-in-16GB fits are tight enough
# that a prior phase's allocator state (fragmentation + anything an OOMed
# attempt left pinned) starves the next phase even after jax.clear_caches().
# All dense phases decode through the fused K-step tail path
# (EngineConfig.decode_steps' fast path); "paged" marks the paged-kernel
# phase. NOTE: some (batch, shape) points crash the platform's remote
# compiler (e.g. batch 80 at 7B int8+kvq) — the ladder skips them.
PHASES = {
    "bf16": (_zero_params, ((8, 256), (4, 256), (2, 256), (1, 256)),
             DenseKVCache),
    "int8": (_zero_qparams, ((48, 256), (32, 256), (16, 256), (1, 256)),
             DenseKVCache),
    # int4 weights through the half-split Pallas matmul (ops/quant_matmul.py).
    "int4": (_zero_q4s_params, ((64, 256), (32, 256), (16, 256), (1, 256)),
             DenseKVCache),
    # int8 weights + int8 KV (per-token/head scales): the KV working set
    # dominates HBM traffic at large batch, so halving it moves the headline.
    "int8_kvq": (_zero_qparams,
                 ((112, 256), (96, 256), (64, 256), (32, 256), (1, 256)),
                 QuantizedDenseKVCache),
    # int4 weights (half-split STACKED Pallas matmul) + int8 KV through the
    # fused attention kernel: weight bytes halve vs int8, freeing HBM for
    # larger batches on the same chip.
    "int4_kvq": (_zero_q4s_params,
                 ((160, 256), (128, 256), (112, 256), (96, 256), (64, 256)),
                 "dense_kernel"),
    # int8 + int8KV decode through the FUSED Pallas kernel (in-kernel tail,
    # zero-copy whole-stack operands — ops/quant_attention.py).
    "int8_kvq_pallas": (_zero_qparams,
                        ((112, 256), (96, 256), (64, 256), (32, 256)),
                        "dense_kernel"),
    # int8 weights + Pallas paged-attention kernel over the page pool.
    "paged_pallas": (_zero_qparams, ((48, 256), (32, 256), (16, 256)),
                     "paged"),
    # ...and with int8 pages + scale planes. The fused window gathers the
    # pool to contiguous buffers once per K steps (cache/paged.py r3 tail):
    # b64 is the largest fit with the gather buffer (b80/88 crash the remote
    # compiler, b96 OOMs).
    "paged_kvq": (_zero_qparams, ((64, 256), (48, 256)),
                  "paged_kvq"),
    # BASELINE config 4: Mistral-7B-shape (GQA + sliding-window attention)
    # served through the ENGINE on the int8 paged pool at bs=32 continuous
    # batching — handled by _mistral_phase().
    "mistral_paged_swa": None,
    # The NORTH-STAR model: Llama-3-8B-shape, int8 weights + int8 KV. GQA
    # cuts the KV working set 4x vs the 7B MHA shape, so much larger batches
    # fit and the decode attention rides the MXU.
    "llama3_8b_int8_kvq": (_zero_qparams,
                           ((384, 256), (256, 256), (128, 256), (64, 256)),
                           "dense_kernel"),
    # Long-context decode (VERDICT r2 order 4): the ladder entries' ctx
    # makes ~half of it LIVE context, so these report tok/s where KV traffic
    # dominates (headline phases run ~128-160 live).
    "int8_kvq_1k": (_zero_qparams, ((24, 2048), (16, 2048), (8, 2048)),
                    "dense_kernel"),
    "int8_kvq_2k": (_zero_qparams, ((12, 4096), (8, 4096), (4, 4096)),
                    "dense_kernel"),
    # r4: past INPLACE_CTX the fused window reads the pool IN PLACE via the
    # whole-pool kernel (no gather, no second KV copy) — the batch that fits
    # matches dense (the r3 gather capped this phase at b8).
    "paged_kvq_1k": (_zero_qparams, ((24, 2048), (16, 2048), (12, 2048)),
                     "paged_kvq"),
    # StreamingLLM sink ring mid-stream (signature feature) — _sink_phase().
    "sink_1k": None,
    # Mixtral-per-layer-shape MoE decode through the engine (EP path's first
    # on-chip number) — _mixtral_moe_phase().
    "mixtral": None,
    # Draft+verify speculative serving (BASELINE config 5) — _speculative_phase().
    "speculative": None,
    # The SERVING number: InferenceEngine.step() end to end (scheduler,
    # admission, sampling stack, host⇄device hops) at the int8_kvq headline
    # configuration — handled by _engine_phase(), not the ladder machinery.
    "engine_int8_kvq": None,
    # Transport tier (relay microbench + 2-node pipeline), CPU-scope —
    # _distributed_phase().
    "distributed": None,
    # Disaggregated prefill/decode vs colocated (gateway TTFT split + KV
    # transfer cost), CPU-scope — _disagg_phase().
    "disagg": None,
    # Prefill compute (TFLOP/s at prompt 128/512/2048) — _prefill_phase().
    "prefill": None,
    # Mixed-phase serving: decode ITL p50/p99 while a long prompt is admitted
    # monolithically vs chunked through the ragged plan — _mixed_phase().
    "mixed": None,
}

assert tuple(PHASES) == PHASE_NAMES, "keep PHASE_NAMES (the parent's) in step"

# Phases that skip the (redundant) prompt-128 TTFT measurement to bound
# total bench wall time.
_NO_TTFT = {"int8_kvq_1k", "int8_kvq_2k", "paged_kvq_1k"}


def _engine_decode_bench(cfg, params, batch, prompt_len, ticks=4,
                         decode_steps=None, kv_quant="int8",
                         cache_kind="dense", measure_burst=False):
    """Serving-engine throughput: tokens/sec measured THROUGH
    ``InferenceEngine.step()`` — scheduler lock, admission, sampling-params
    stacking, numpy⇄device hops, and event delivery all inside the timed
    window — at the headline int8-weights + int8-KV configuration.

    The engine's auto ``decode_steps`` resolves to the fused write-behind-tail
    path (K=16), exactly what ``cli.py serve`` now runs by default.
    """
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    # Pipelined engines need extra warm steps: step 1 only admits/prefills,
    # step 2 dispatches+compiles the first tick, step 3 primes the pipeline.
    # warm=3 + ticks=4 keeps max_seq at 256 for prompt 128 — the platform's
    # remote compiler 500-crashes on the b72 engine program at T=288 while
    # the T=256 one compiles (the cliff is shape-sensitive).
    warm = 3
    k_guess = decode_steps or 16  # EngineConfig auto default on the tail path
    max_seq = prompt_len + 1 + (warm + ticks) * k_guess
    max_seq = ((max_seq + 31) // 32) * 32
    ecfg = EngineConfig(
        max_batch_size=batch,
        max_seq_len=max_seq,
        prefill_buckets=(prompt_len,),
        decode_steps=decode_steps,
        # Fixed full-size buffer: mid-measurement ladder growth would splice
        # a pad-copy + recompile into the timed ticks.
        decode_windows=(),
        # XLA:CPU lacks the bf16 dot the int8-KV attention path emits.
        dtype="bfloat16",
    )
    if cache_kind == "paged":
        ps = 64
        slots = -(-max_seq // ps)
        ccfg = CacheConfig(
            kind="paged", kv_quant=kv_quant, page_size=ps,
            num_pages=batch * slots + 1, max_pages_per_session=slots,
        )
    else:
        ccfg = CacheConfig(kind="dense", kv_quant=kv_quant)
    eng = InferenceEngine(cfg, params, ecfg, ccfg)
    opts = SamplingOptions(max_new_tokens=1_000_000, eos_token_id=-1)
    gids = [eng.submit([1] * prompt_len, opts) for _ in range(batch)]
    # Warm steps: admission + `batch` bucketed prefills, the compile of the
    # decode tick, and (pipelined engines) priming the dispatch→resolve
    # pipeline. Everything after is steady state.
    for _ in range(warm):
        eng.step()
    t0 = time.perf_counter()
    delivered = 0
    for _ in range(ticks):
        for _, tok, _fin in eng.step():
            if tok != -1:
                delivered += 1
    dt = time.perf_counter() - t0
    if delivered == 0:
        raise RuntimeError("engine delivered no tokens in the timed window")

    # Engine-level TTFT: drain the load, then time submit→first-token for one
    # fresh session on warm executables (admission + bucketed prefill + the
    # sampled first token).
    for g in gids:
        eng.cancel(g)
    eng.step()
    eng.collect_finished()
    ttfts = []
    for _ in range(3):
        t1 = time.perf_counter()
        eng.submit([1] * prompt_len,
                   SamplingOptions(max_new_tokens=1, eos_token_id=-1))
        ev = eng.step()
        ttfts.append((time.perf_counter() - t1) * 1e3)
        assert any(fin for _, _t, fin in ev)
        eng.collect_finished()
    # Concurrent-admission burst measured against a LIVE decode (r5 ask:
    # the stall matters only when it preempts serving): batch-k resident
    # sessions decode continuously; k sessions then land while a pipelined
    # tick is in flight. We time the admitting step() and compare resident
    # token delivery in a 2-step window starting at the burst against the
    # same window in steady state — with overlapped admission the prefill
    # dispatch rides the in-flight tick and the ratio stays ~1.0; the old
    # synchronous path blocked the window on k prefill fetches.
    # min/median over >= 5 reps (one noisy rep must not swing the record);
    # residents are resubmitted fresh each rep so their context growth
    # stays inside max_seq (sized for warm+ticks only — growing it would
    # cross the remote compiler's ~B x T cliff at the b112 headline).
    burst = None
    if measure_burst:
        k_burst = min(4, batch)
        n_res = max(1, batch - k_burst)
        long_opts = SamplingOptions(max_new_tokens=1_000_000, eos_token_id=-1)
        reps, admit_ms, burst_tps, steady_tps = 5, [], [], []
        for _ in range(reps):
            res = [eng.submit([3] * prompt_len, long_opts)
                   for _ in range(n_res)]
            eng.step()  # admit residents (no tick in flight yet)
            eng.step()  # first pipelined tick now in flight
            resset = set(res)
            t0 = time.perf_counter()
            n0 = 0
            for _ in range(2):
                for g, tok, _f in eng.step():
                    if tok != -1 and g in resset:
                        n0 += 1
            steady_tps.append(n0 / (time.perf_counter() - t0))
            bs = [eng.submit([2] * prompt_len, long_opts)
                  for _ in range(k_burst)]
            t1 = time.perf_counter()
            n1 = 0
            for g, tok, _f in eng.step():  # the admitting step
                if tok != -1 and g in resset:
                    n1 += 1
            admit_ms.append((time.perf_counter() - t1) * 1e3)
            for g, tok, _f in eng.step():
                if tok != -1 and g in resset:
                    n1 += 1
            burst_tps.append(n1 / (time.perf_counter() - t1))
            for g in res + bs:
                eng.cancel(g)
            while eng.has_work():
                eng.step()
            eng.collect_finished()
        steady = float(np.percentile(steady_tps, 50))
        during = float(np.percentile(burst_tps, 50))
        burst = {
            "admit_burst_ms": round(float(np.min(admit_ms)), 2),
            "admit_burst_ms_p50": round(float(np.percentile(admit_ms, 50)),
                                        2),
            "burst_sessions": k_burst,
            "resident_sessions": n_res,
            "tok_s_steady": round(steady, 2),
            "tok_s_during_burst": round(during, 2),
            "burst_vs_steady_pct": round(100 * during / steady, 1)
            if steady else None,
            "reps": reps,
            "overlap_admission": bool(eng.ecfg.overlap_admission),
        }
    return (
        delivered / dt, float(np.percentile(ttfts, 50)), eng.decode_steps,
        burst,
    )


def _cycle_len(c) -> int:
    """Transition-cycle length shared by the param builder and the
    bench's prompt sampler — prompts MUST stay on the cycle (an off-cycle
    token hits an all-zero lm_head row and degenerates the walk)."""
    return min(4096, c.hidden_size, c.vocab_size)


def _cycle_qparams(c, dt, agree_frac=None):
    """Zero-layer-weight int8 params whose lm_head encodes a DETERMINISTIC
    token-transition table: with zero layer matmuls the residual stream is
    exactly the embedding, and with one-hot embeddings the logits are
    ``lm_head[token, :]`` — so ``next = argmax_j lm_head[token, j]`` is a
    programmable map. The target walks the cycle ``i → (i+1) % cycle``; a
    draft with ``agree_frac=p`` matches the target's map on a seeded-RANDOM
    p-fraction of states (Bernoulli per state) and proposes ``(i+2) %
    cycle`` on the rest. Random placement matters: each round starts right
    after a correction, so with EVENLY-spaced disagreements the measured
    acceptance is the mean run length p/(1-p) (measured r5: 0.583/proposal
    at p=0.7 — flattering); Bernoulli placement makes the leading-agree run
    geometric, i.e. exactly the iid acceptance statistics a real draft with
    per-token agreement p produces. Acceptance is then MEASURED through the
    engine, not derived (VERDICT r4 ask 1). Decode cost is
    value-independent (same shapes/dtypes as ``_zero_qparams``).

    The cycle is as long as the one-hot embedding allows (hidden_size): the
    accept/correct dynamics are DETERMINISTIC, so a short cycle can lock
    into a periodic orbit whose agreement statistics deviate from p (a
    256-state cycle measured 0.35/proposal at dialed 0.7); 4096 states plus
    per-row random prompt starts keep visited-state statistics near the
    dialed fraction."""
    cycle = _cycle_len(c)
    ps = _zero_qparams(c, dt)
    ps["embed"] = jnp.zeros((c.vocab_size, c.hidden_size), dt).at[
        jnp.arange(cycle), jnp.arange(cycle)
    ].set(1.0)
    q = np.zeros((c.hidden_size, c.vocab_size), np.int8)
    rng = np.random.default_rng(1234)
    agree_states = (
        None if agree_frac is None else rng.random(cycle) < agree_frac
    )
    for i in range(cycle):
        if agree_states is None:
            nxt = (i + 1) % cycle
        else:
            nxt = (i + 1) % cycle if agree_states[i] else (i + 2) % cycle
        q[i, nxt] = 1
    ps["lm_head"] = QuantizedTensor(
        q=jnp.asarray(q), scale=jnp.ones((c.vocab_size,), dt)
    )
    return ps


def _spec_engine_bench_multi(cfg, dcfg, params, drafts, batch, prompt_len,
                             ticks=6, spec_k=4):
    """Speculative serving throughput through ``InferenceEngine.step()``:
    each tick runs ``speculative_rounds`` fused propose→verify→accept
    rounds in ONE dispatch (r4 — the synchronous per-round tick paid 2+
    host round trips per round).

    ``drafts`` is ``[(name, build_dparams), …]`` (LAZY builders — five
    resident 7B-class drafts at once would exhaust HBM next to the target)
    measured back to back on ONE engine: the draft weights are a traced
    ARGUMENT of the fused-rounds executable, so swapping ``eng.draft``
    between runs measures every acceptance point without a fresh ~minutes
    remote compile each; the previous draft's arrays are dropped first.
    Between drafts the live sessions are cancelled, drained, and
    resubmitted (fresh target+draft prefills). Returns
    ``{name: (tok_s, measured acceptance)}`` over the timed ticks."""
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    # 6 rounds per dispatch amortize each tick's single packed fetch (its
    # fixed cost is not measured on a directly attached chip).
    rounds = 6
    max_seq = prompt_len + 1 + (3 + ticks) * rounds * (spec_k + 1)
    max_seq = ((max_seq + 31) // 32) * 32
    ecfg = EngineConfig(
        max_batch_size=batch, max_seq_len=max_seq,
        prefill_buckets=(prompt_len,), decode_windows=(),
        speculative_k=spec_k, speculative_rounds=rounds,
        # Pin the PURE speculative path: the adaptive controller would
        # (correctly) bail to plain decode at the low-acceptance points,
        # and these measurements exist to characterize speculation itself.
        speculative_adaptive=False,
        dtype="bfloat16",
    )
    first = drafts[0][1]()
    jax.block_until_ready(first)
    eng = InferenceEngine(
        cfg, params, ecfg, CacheConfig(kind="dense", kv_quant="int8"),
        draft=(dcfg, first),
    )
    del first
    opts = SamplingOptions(max_new_tokens=1_000_000, eos_token_id=-1,
                           speculative=True)
    # Per-row random prompt starts (tokens on the transition cycle): rows
    # then sample DIFFERENT orbits of the deterministic accept/correct
    # dynamics, so the measured agreement averages out orbit bias.
    cyc = _cycle_len(cfg)
    prng = np.random.default_rng(7)
    prompts_ = [
        prng.integers(0, cyc, size=prompt_len).tolist() for _ in range(batch)
    ]
    out = {}
    for i, (name, build) in enumerate(drafts):
        if i:  # the constructor already holds drafts[0]
            eng.draft = (dcfg, None)  # drop the previous draft's arrays
            eng.draft = (dcfg, build())
        gids = [eng.submit(p, opts) for p in prompts_]
        # Admission + prefills, then TWO unmeasured ticks: the pipelined
        # spec path dispatches on the first step and pays first-tick sync
        # (and any residual compile) on the second — neither belongs in
        # the timed window.
        eng.step()
        eng.step()
        eng.step()
        s0 = dict(eng.spec_stats)
        t0 = time.perf_counter()
        delivered = 0
        for _ in range(ticks):
            for _, tok, _fin in eng.step():
                if tok != -1:
                    delivered += 1
        dt = time.perf_counter() - t0
        proposed = eng.spec_stats["proposed"] - s0["proposed"]
        accepted = eng.spec_stats["accepted"] - s0["accepted"]
        out[name] = (
            delivered / dt, accepted / proposed if proposed else 0.0
        )
        for g in gids:
            eng.cancel(g)
        drain = 0
        while eng.has_work() and drain < 100:
            eng.step()
            drain += 1
        eng.collect_finished()
    return out


def _speculative_phase() -> dict:
    """BASELINE config 5's speculative decoding in the LATENCY-BOUND regime
    it exists for (small batch, weight-traffic-dominated decode), vs the
    plain fused-decode engine at the SAME batch. Measured at its two
    acceptance bounds on the chip: zero weights make draft and target agree
    on every argmax (acceptance = 1 — the mechanism's best case), and a
    draft doctored to always propose token 1 against a target emitting 0
    gives acceptance = 0 (worst case: every round pays k draft forwards +
    the k+1-position verify for one token). A derived mid-acceptance
    number interpolates the measured per-round latency: at per-token
    agreement p, a round accepts ``E(p) = p(1-p^k)/(1-p) + 1`` tokens."""
    import dataclasses as _dc

    _require_tpu()
    cfg = LLAMA2_7B
    dcfg = _dc.replace(cfg, num_layers=4)
    dt = jnp.bfloat16
    spec_k = 4

    def _cycle_params(c, agree_frac=None):
        return _cycle_qparams(c, dt, agree_frac)

    err = None
    for batch in (8, 4):
        try:
            prompt = 128
            # The cycle-walking TARGET: decode cost identical to zero
            # weights (same shapes), but the emitted stream visits the
            # transition cycle so dialed-agreement drafts produce MEASURED
            # mid-range acceptance (VERDICT r4 ask 1 — the r4 bench had
            # only the p=1 and p=0 endpoints plus a derived midpoint).
            tparams = _cycle_params(cfg)
            jax.block_until_ready(tparams)
            drafts = [
                ("full", lambda: _cycle_params(dcfg)),   # agrees everywhere
                ("p85", lambda: _cycle_params(dcfg, 0.85)),
                ("p70", lambda: _cycle_params(dcfg, 0.70)),
                ("p50", lambda: _cycle_params(dcfg, 0.50)),
                ("zero", lambda: _cycle_params(dcfg, 0.0)),  # never agrees
            ]
            res = _spec_engine_bench_multi(
                cfg, dcfg, tparams, drafts, batch, prompt_len=prompt,
            )
            # Plain fused-decode engine at the SAME batch: the number
            # speculation must beat. Reuses the cycle target (decode cost
            # is value-independent) — a SECOND resident 7B tree alongside
            # it OOMed the 16 GB chip.
            tok_plain, *_ = _engine_decode_bench(
                cfg, tparams, batch, prompt_len=prompt, ticks=8,
            )
        except Exception as e:
            err = repr(e)
            continue
        tok_full, acc_full = res["full"]
        tok_zero, acc_zero = res["zero"]
        doc = {
            "tok_s": round(tok_full, 2), "batch": batch, "ttft_ms": None,
            "acceptance": round(acc_full, 3),
            "tok_s_zero_acceptance": round(tok_zero, 2),
            "acceptance_zero": round(acc_zero, 3),
            "tok_s_plain_same_batch": round(tok_plain, 2),
            "speedup_vs_plain": round(tok_full / tok_plain, 2),
            "spec_k": spec_k, "draft_layers": dcfg.num_layers,
            "spec_rounds_per_dispatch": 6,
            "scope": "InferenceEngine.step() end to end",
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0].device_kind),
            "model": "llama-2-7b-shape",
        }
        for name in ("p85", "p70", "p50"):
            tok_p, acc_p = res[name]
            doc[f"tok_s_{name}_measured"] = round(tok_p, 2)
            doc[f"acceptance_{name}"] = round(acc_p, 3)
            doc[f"speedup_vs_plain_{name}"] = round(tok_p / tok_plain, 2)
        return doc
    raise RuntimeError(f"speculative phase failed at every batch: {err}")


MISTRAL_7B = ModelConfig(
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=10000.0,
    max_position_embeddings=8192,
    sliding_window=128,  # < the bench context so the window masks are LIVE
    family="mistral",
)


def _mistral_phase() -> dict:
    """BASELINE config 4 on the chip: Mistral-7B-shape (GQA, sliding-window
    attention) through the ENGINE on the int8 paged pool, bs=32 continuous
    batching. The sliding window (128 < context) exercises the windowed
    validity masks in the gathered paged tail."""
    import dataclasses as _dc

    _require_tpu()
    cfg = MISTRAL_7B
    dt = jnp.bfloat16
    params = _zero_qparams(cfg, dt)
    jax.block_until_ready(params)
    err = None
    for batch in (32, 16):
        try:
            # ticks=10: the 4-tick window (~1 s) made this phase hostage to
            # single host-latency hiccups (measured 1115-2547 tok/s across
            # identical-code runs before PR 1); a longer window amortizes them.
            tok_s, ttft, k, *_ = _engine_decode_bench(
                cfg, params, batch, prompt_len=128,
                cache_kind="paged", ticks=10,
            )
        except Exception as e:
            err = repr(e)
            continue
        return {
            "tok_s": round(tok_s, 2), "batch": batch,
            "sliding_window": cfg.sliding_window, "cache": "paged+int8",
            "ttft_ms": round(ttft, 2), "decode_steps": k,
            "scope": "InferenceEngine.step() end to end",
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0].device_kind),
            "model": "mistral-7b-shape",
        }
    raise RuntimeError(f"mistral phase failed at every batch: {err}")


MIXTRAL_8L = ModelConfig(
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=8,  # the full 32-layer 8-expert stack is ~45 GB int8 — far
                   # past one v5e's HBM; 8 layers keep the EXACT per-layer
                   # Mixtral-8x7B shape (8 experts, top-2, GQA) at ~12 GB
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=1000000.0,
    max_position_embeddings=4096,
    num_experts=8,
    num_experts_per_tok=2,
    family="mixtral",
)

def _mixtral_moe_phase() -> dict:
    """Expert-parallel-capable MoE decode ON CHIP: Mixtral-8x7B per-layer
    shape (8 experts, top-2 routing, GQA) served through the ENGINE with
    int8 expert weights + int8 KV — the first on-chip number for the
    dense-combine MoE decode path (``ops/moe.py``; r3 shipped it
    mesh-tested but never timed on hardware)."""
    _require_tpu()
    cfg = MIXTRAL_8L
    params = _zero_qparams(cfg, jnp.bfloat16)
    jax.block_until_ready(params)
    err = None
    for batch in (64, 48, 32):
        try:
            tok_s, ttft, k, *_ = _engine_decode_bench(
                cfg, params, batch, prompt_len=128,
                ticks=8,
            )
        except Exception as e:
            err = repr(e)
            continue
        return {
            "tok_s": round(tok_s, 2), "batch": batch,
            "experts": cfg.num_experts,
            "experts_per_token": cfg.num_experts_per_tok,
            "layers": cfg.num_layers, "weights": "int8",
            "ttft_ms": round(ttft, 2), "decode_steps": k,
            "scope": "InferenceEngine.step() end to end",
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0].device_kind),
            "model": "mixtral-8x7b-shape-8layer",
        }
    raise RuntimeError(f"mixtral phase failed at every batch: {err}")


def _engine_phase() -> dict:
    """Serving throughput through the scheduler at int8+int8KV.

    r5: the compile cliff turned out to be the BATCHED-ADMISSION PREFILL
    program (gather-rows → prefill → scatter-rows with the full [L, B, T]
    cache in one program — crashes past b88×T256 in every form tried),
    NOT the fused decode scan (which compiles at b112×T256). The engine
    now splits admission into a standalone compact prefill + a merge-only
    dispatch (engine.py _prefill_rows_standalone), and the b112 headline
    config serves THROUGH the scheduler at raw-rate (~4276 vs raw 4305).
    The descent keeps b72 as a fallback for compiler flakiness (500s have
    been observed near the cliff under concurrent compile load)."""
    _require_tpu()
    cfg = LLAMA2_7B
    dt = jnp.bfloat16
    params = _zero_qparams(cfg, dt)
    jax.block_until_ready(params)
    err = None
    out = None
    for batch in (112, 96, 72, 64):
        try:
            tok_s, ttft, k, burst = _engine_decode_bench(
                cfg, params, batch, prompt_len=128,
                measure_burst=True,
            )
        except Exception as e:
            err = repr(e)
            continue
        out = {
            "tok_s": round(tok_s, 2), "batch": batch, "weights": "int8",
            "prompt_len": 128,
            "ttft_ms": round(ttft, 2), "decode_steps": k,
            "admit_burst_ms": burst["admit_burst_ms"] if burst else None,
            "admit_burst": burst,
            "scope": "InferenceEngine.step() end to end",
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0].device_kind),
            "model": "llama-2-7b-shape",
        }
        break
    if out is None:
        raise RuntimeError(f"engine phase failed at every config: {err}")
    # Short-prompt workload class: the compile cliff scales ~(B x T), so
    # prompt-64/T-192 admits batch 96 — where the ENGINE exceeds the raw
    # b112 headline (3218 measured vs raw 3193).
    try:
        tok_s, ttft, *_ = _engine_decode_bench(
            cfg, params, 96, prompt_len=64
        )
        out["short_ctx"] = {
            "tok_s": round(tok_s, 2), "batch": 96, "prompt_len": 64,
            "ttft_ms": round(ttft, 2),
        }
    except Exception as e:
        out["short_ctx"] = {"error": repr(e)[:150]}
    return out


# Phases measuring a model shape other than the default Llama-2-7B.
_PHASE_CFG = {"llama3_8b_int8_kvq": (LLAMA3_8B, "llama-3-8b-shape")}


def _prefill_phase() -> dict:
    """Prefill compute at prompt 128/512/2048 (b1, Llama-3-8B-shape int8,
    the north-star TTFT model): device ms + TFLOP/s (VERDICT r4 ask 2's
    missing bench coverage). Measures the SHIPPED default path — W8A8
    dynamic-activation int8 MXU matmuls for S >= 128 (ops/quant.py), flash
    attention above S >= 1024 (cache/base.py), last-position-only head."""
    _require_tpu()
    cfg = LLAMA3_8B
    params = _zero_qparams(cfg, jnp.bfloat16)
    jax.block_until_ready(params)

    def model_tflops(S):
        h, d, hq, hkv, inter, L, V = (
            cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads,
            cfg.intermediate_size, cfg.num_layers, cfg.vocab_size,
        )
        per_layer = (
            2 * h * (hq * d) + 2 * 2 * h * (hkv * d) + 2 * (hq * d) * h
            + 3 * 2 * h * inter
        )
        return (S * L * per_layer + L * S * 4 * S * hq * d + 2 * h * V) / 1e12

    out = {"model": "llama-3-8b-shape",
           "backend": jax.default_backend(),
           "scope": "b1 prefill, device time (xplane), shipped defaults"}
    out["device"] = str(jax.devices()[0].device_kind)
    peak = _peak_bf16_tflops()
    for S in (128, 512, 2048):
        T = S + 128
        cache = QuantizedDenseKVCache.create(
            cfg.num_layers, 1, T, cfg.num_kv_heads, cfg.head_dim,
            jnp.bfloat16, use_kernel=True,
        )
        num_new = jnp.full((1,), S, jnp.int32)

        @jax.jit
        def prefill(params, tokens, cache):
            logits, cache = llama.model_apply(
                cfg, params, tokens, cache, num_new, head="last"
            )
            return jnp.argmax(logits[:, 0], -1)

        jax.block_until_ready(
            prefill(params, jnp.zeros((1, S), jnp.int32), cache)
        )
        # One trace per rep (>= 5) so we can report min AND median — the
        # old single-trace mean let one noisy run swing the canonical
        # record. Inputs vary per rep (see _device_time_ms_per_call).
        devs = [
            d for r in range(5)
            if (d := _device_time_ms_per_call(
                lambda i, r=r: prefill(
                    params,
                    jnp.full((1, S), ((5 * r + i) % 17) + 1, jnp.int32),
                    cache,
                ),
                reps=1,
            )) is not None
        ]
        if devs:
            dmin, dp50 = min(devs), float(np.percentile(devs, 50))
            out[f"prompt_{S}"] = {
                "reps": len(devs),
                "device_ms_min": round(dmin, 2),
                "device_ms_p50": round(dp50, 2),
                "tflop_s_best": round(model_tflops(S) / (dmin / 1e3), 1),
                "tflop_s_p50": round(model_tflops(S) / (dp50 / 1e3), 1),
                "pct_of_bf16_peak": round(
                    100 * model_tflops(S) / (dp50 / 1e3) / peak, 1
                ),
            }
        else:
            out[f"prompt_{S}"] = {"device_ms_min": None}
    out["engine_decode_sweep"] = _ragged_engine_sweep(
        cfg, params, (128, 512, 1024, 2048),
        batch=8,
    )
    return out


def _ragged_engine_sweep(cfg, params, contexts, batch=8, ticks=4) -> dict:
    """Per-context engine decode: bucketed vs ragged dispatch (the
    AttentionPlan, engine/plan.py). Mixed prompt LENGTHS per batch so the
    legacy path pays its bucket tax — one executable per (bucket,
    row-count) pair — while ragged mode pads every prefill-family dispatch
    to one width. Reports tok/s plus attn_recompiles split into warm
    (expected: the finite executable set) and steady (expected 0 for
    ragged — the zero-recompile-after-warmup contract)."""
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    _require_tpu()
    warm, k = 3, 16
    out = {}
    for ctx in contexts:
        max_seq = ((ctx + 1 + (warm + ticks) * k + 31) // 32) * 32
        ps = 64
        slots = -(-max_seq // ps)
        buckets = tuple(sorted({max(8, ctx // 4), max(8, ctx // 2), ctx}))
        # Length spread across the buckets: this is the traffic shape the
        # bucketed path recompiles on.
        lens = [
            max(4, ctx - (i * ctx) // (2 * batch)) for i in range(batch)
        ]
        row = {}
        for label, ragged in (("bucketed", False), ("ragged", True)):
            eng = InferenceEngine(
                cfg, params,
                EngineConfig(
                    max_batch_size=batch, max_seq_len=max_seq,
                    prefill_buckets=buckets, decode_windows=(),
                    ragged_attention=ragged,
                    dtype="bfloat16",
                ),
                CacheConfig(
                    kind="paged", kv_quant="int8", page_size=ps,
                    num_pages=batch * slots + 1, max_pages_per_session=slots,
                ),
            )
            opts = SamplingOptions(max_new_tokens=1_000_000, eos_token_id=-1)
            for n in lens:
                eng.submit([1] * n, opts)
            for _ in range(warm):
                eng.step()
            seen = eng.metrics.get_counter("attn_recompiles")
            t0 = time.perf_counter()
            delivered = 0
            for _ in range(ticks):
                for _, tok, _f in eng.step():
                    if tok != -1:
                        delivered += 1
            dt = time.perf_counter() - t0
            row[label] = {
                "tok_s": round(delivered / dt, 1),
                "attn_recompiles_warm": int(seen),
                "attn_recompiles_steady": int(
                    eng.metrics.get_counter("attn_recompiles") - seen
                ),
            }
        out[f"ctx_{ctx}"] = row
    return out


def _mixed_phase() -> dict:
    """Resident ITL while a LONG prompt lands mid-decode (the chunked-
    prefill co-scheduling satellite): with the legacy monolithic path the
    admitting tick stalls every resident stream behind one full-prompt
    prefill; with ragged co-scheduling (``chunk_decode_share``) the prompt
    walks in ``prefill_chunk_tokens`` chunks beside decode. Reports the
    per-step interval p50/p99 over the admission window for both modes,
    plus the long prompt's TTFT (chunking trades its TTFT for resident
    tail latency)."""
    from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig
    from distributed_llm_inference_tpu.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    _require_tpu()
    cfg = LLAMA3_8B
    params = _zero_qparams(cfg, jnp.bfloat16)
    jax.block_until_ready(params)
    batch = 8
    short = 128
    longp = 2048
    steps = (longp // short) + 12
    ps = 64
    max_seq = ((longp + 1 + (steps + 4) * 16 + 31) // 32) * 32
    slots = -(-max_seq // ps)
    out = {
        "model": "llama-3-8b-shape",
        "backend": jax.default_backend(),
        "scope": f"{batch - 1} residents (prompt {short}) + one prompt-"
                 f"{longp} admission; per-step interval over {steps} steps",
    }
    for label, (ragged, share) in (
        ("monolithic", (False, 0.0)), ("chunked", (True, 0.5)),
    ):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(
                max_batch_size=batch, max_seq_len=max_seq,
                prefill_buckets=(short, longp), decode_windows=(),
                ragged_attention=ragged, prefill_chunk_tokens=short,
                chunk_decode_share=share,
                dtype="bfloat16",
            ),
            CacheConfig(
                kind="paged", kv_quant="int8", page_size=ps,
                num_pages=batch * slots + 1, max_pages_per_session=slots,
            ),
        )
        opts = SamplingOptions(max_new_tokens=1_000_000, eos_token_id=-1)
        for _ in range(batch - 1):
            eng.submit([1] * short, opts)
        for _ in range(4):  # admit + compile + steady state
            eng.step()
        t_submit = time.perf_counter()
        gid = eng.submit([2] * longp, opts)
        itls, ttft = [], None
        for _ in range(steps):
            t0 = time.perf_counter()
            evs = eng.step()
            itls.append((time.perf_counter() - t0) * 1e3)
            if ttft is None and any(
                g == gid and tok != -1 for g, tok, _f in evs
            ):
                ttft = (time.perf_counter() - t_submit) * 1e3
        out[label] = {
            "itl_ms_p50": round(float(np.percentile(itls, 50)), 2),
            "itl_ms_p99": round(float(np.percentile(itls, 99)), 2),
            "long_ttft_ms": round(ttft, 1) if ttft is not None else None,
            "attn_chunked_rows": int(
                eng.metrics.get_counter("attn_chunked_rows")
            ),
        }
    return out


def _distributed_phase() -> dict:
    """Transport-tier benchmark (VERDICT r4 ask 4): relay microbench +
    2-node pipeline tok/s, all on localhost and EXPLICITLY CPU-scope — the
    numbers characterize the C++ relay hub and the node/task-pool stack,
    not TPU compute (which every other phase covers). Forcing CPU also
    keeps the many in-process nodes off the chip (one process per chip)."""
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        # The update is a silent no-op once the backend is initialized:
        # running the many in-process nodes against the one chip this
        # process holds would measure dispatch, so refuse instead.
        return {"error": "backend already initialized non-cpu; run this "
                         "phase in its own process",
                "scope": "cpu-localhost"}
    import threading

    from distributed_llm_inference_tpu.config import ModelConfig
    from distributed_llm_inference_tpu.distributed import (
        DirectoryService, DistributedClient, RelayClient, RelayServer,
        ServingNode, native_available,
    )
    from distributed_llm_inference_tpu.models import llama as llama_mod

    if not native_available():
        return {"error": "native relay unavailable (no g++)",
                "scope": "cpu-localhost"}

    out = {"scope": "cpu-localhost",
           "note": "transport tier only; TPU compute is covered by the "
                   "other phases"}

    # -- relay microbench: frames/s, MB/s, GET parking latency ----------------
    with RelayServer() as relay:
        with RelayClient(port=relay.port) as tx, \
                RelayClient(port=relay.port) as rx:
            # Per-frame round trip (put → get, serial): the per-hop floor.
            buf = b"x" * 4096
            n = 2000
            t0 = time.perf_counter()
            for _ in range(n):
                tx.put("q", buf)
                rx.get("q", timeout=5)
            dt = time.perf_counter() - t0
            out["frames_per_s_4k_serial"] = round(n / dt, 1)
            out["frame_roundtrip_us_4k"] = round(1e6 * dt / n, 1)

            # Hub throughput at tensor-sized frames (pipelined: the producer
            # stays ahead, the consumer drains — how forward hops actually
            # flow through the hub).
            for mb in (1, 4, 16):
                size = mb * 1024 * 1024
                frames = max(8, 64 // mb)
                payload = b"x" * size
                t0 = time.perf_counter()
                done = []

                def _drain():
                    for _ in range(frames):
                        rx.get("big", timeout=30)
                    done.append(1)

                th = threading.Thread(target=_drain)
                th.start()
                for _ in range(frames):
                    tx.put("big", payload)
                th.join()
                dt = time.perf_counter() - t0
                if not done:  # drain died mid-transfer: no fake number
                    return {**out, "error": f"{mb}MB frame drain failed"}
                out[f"mb_per_s_{mb}mb_frames"] = round(
                    frames * size / dt / 1e6, 1
                )

            # GET parking latency: a consumer blocked on an empty queue is
            # woken by the next PUT (the decode-loop idle→wake path).
            lats = []
            for _ in range(50):
                got = []

                def _park():
                    rx.get("park", timeout=5)
                    got.append(time.perf_counter())

                th = threading.Thread(target=_park)
                th.start()
                time.sleep(0.01)  # ensure the GET is parked server-side
                t_put = time.perf_counter()
                tx.put("park", buf)
                th.join()
                if not got:  # parked GET timed out: structured error
                    return {**out, "error": "parked GET never woke"}
                lats.append((got[0] - t_put) * 1e6)
            lats.sort()
            out["get_wake_us_p50"] = round(lats[len(lats) // 2], 1)
            # 50 samples: index 47 is the p95 class statistic; the true tail
            # is reported as what it is (the max), not a mislabeled p99.
            out["get_wake_us_p95"] = round(lats[int(len(lats) * 0.95)], 1)
            out["get_wake_us_max"] = round(lats[-1], 1)

    # -- 2-node pipeline: end-to-end tok/s, task-pool batching on/off ---------
    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=256,
    )
    params = llama_mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    n_clients, new_tokens = 8, 24

    def pipeline_toks(pool_max_batch):
        with RelayServer() as relay:
            with DirectoryService(relay.port, default_ttl=5.0):
                with ServingNode(
                    relay.port, cfg,
                    {k: v[0:2] for k, v in params["layers"].items()}, 0, 1,
                    max_sessions=n_clients, max_seq_len=128,
                    dtype=jnp.float32, pool_max_batch=pool_max_batch,
                ) as n1, ServingNode(
                    relay.port, cfg,
                    {k: v[2:4] for k, v in params["layers"].items()}, 2, 3,
                    max_sessions=n_clients, max_seq_len=128,
                    dtype=jnp.float32, pool_max_batch=pool_max_batch,
                ) as n2:
                    with DistributedClient(
                        relay.port, cfg, params, prefill_buckets=(16,),
                        dtype=jnp.float32,
                    ) as client:
                        errs = []

                        def drive(i, steps):
                            try:
                                client.generate(
                                    [1, 2, 3 + i], max_new_tokens=steps,
                                )
                            except Exception as e:  # pragma: no cover
                                errs.append(repr(e))

                        def burst(steps):
                            threads = [
                                threading.Thread(target=drive,
                                                 args=(i, steps))
                                for i in range(n_clients)
                            ]
                            t0 = time.perf_counter()
                            for t in threads:
                                t.start()
                            for t in threads:
                                t.join()
                            return time.perf_counter() - t0

                        # Warm with a FULL-LENGTH concurrent burst: the
                        # batched/singleton step executables AND every
                        # cache-growth bucket shape the run will touch
                        # compile here, not in the timed window (XLA:CPU
                        # compiles of even the tiny model are ~seconds).
                        burst(new_tokens)
                        if errs:
                            raise RuntimeError(errs[0])
                        # Snapshot AFTER the warm burst: its compile-era,
                        # mostly-singleton pool calls would dilute the
                        # steady-state co-batching stat.
                        bi0, bc0 = (n1.backend.batched_items,
                                    n1.backend.batched_calls)
                        dt = burst(new_tokens)
                        if errs:
                            raise RuntimeError(errs[0])
                        batched = (
                            n1.backend.batched_items - bi0,
                            n1.backend.batched_calls - bc0,
                        )
                        occ = n1.metrics.snapshot().get(
                            "pool_batch_occupancy_mean_s"
                        )
        return n_clients * new_tokens / dt, batched, occ

    def batched_client_toks():
        """Same chain, but ONE client drives all generations in lockstep
        via generate_many: hidden states co-batch at the source into one
        stacked frame per hop, so throughput no longer depends on the
        pool window catching concurrent singles."""
        with RelayServer() as relay:
            with DirectoryService(relay.port, default_ttl=5.0):
                with ServingNode(
                    relay.port, cfg,
                    {k: v[0:2] for k, v in params["layers"].items()}, 0, 1,
                    max_sessions=n_clients, max_seq_len=128,
                    dtype=jnp.float32,
                ) as n1, ServingNode(
                    relay.port, cfg,
                    {k: v[2:4] for k, v in params["layers"].items()}, 2, 3,
                    max_sessions=n_clients, max_seq_len=128,
                    dtype=jnp.float32,
                ):
                    with DistributedClient(
                        relay.port, cfg, params, prefill_buckets=(16,),
                        dtype=jnp.float32,
                    ) as client:
                        prompts = [[1, 2, 3 + i] for i in range(n_clients)]
                        # Warm run compiles the stacked-step executables
                        # for every live-row count the run will see.
                        client.generate_many(prompts,
                                             max_new_tokens=new_tokens)
                        stamps = [[] for _ in prompts]
                        t0 = time.perf_counter()
                        client.generate_many(
                            prompts, max_new_tokens=new_tokens,
                            on_token=lambda row, tok: stamps[row].append(
                                time.perf_counter()
                            ),
                        )
                        dt = time.perf_counter() - t0
                        occ = n1.metrics.snapshot().get(
                            "pool_batch_occupancy_mean_s"
                        )
        # Per-generation inter-token latency across all rows: the tail a
        # caller of one row actually experiences inside the lockstep loop.
        gaps = sorted(
            b - a for s in stamps for a, b in zip(s, s[1:])
        )
        p50 = gaps[len(gaps) // 2] if gaps else 0.0
        p95 = gaps[int(len(gaps) * 0.95)] if gaps else 0.0
        return n_clients * new_tokens / dt, p50, p95, occ

    tok_s_on, (bi, bc), occ_on = pipeline_toks(None)
    tok_s_off, _, _ = pipeline_toks(1)
    out["pipeline_2node_tok_s"] = round(tok_s_on, 1)
    out["pipeline_2node_tok_s_no_batching"] = round(tok_s_off, 1)
    out["batching_speedup"] = round(tok_s_on / tok_s_off, 2)
    out["batched_items_per_call"] = round(bi / max(bc, 1), 2)
    if occ_on is not None:
        out["pool_batch_occupancy_mean"] = round(occ_on, 2)
    out["concurrent_generations"] = n_clients
    # Per-token chain cost through 2 hops + client head (the relay-tier
    # overhead budget a TPU deployment adds on top of device compute).
    out["ms_per_token_chain"] = round(1000.0 * n_clients / tok_s_on, 2)
    bt, p50, p95, occ_b = batched_client_toks()
    out["batched_client_tok_s"] = round(bt, 1)
    out["batched_client_speedup"] = round(bt / tok_s_off, 2)
    out["token_latency_p50_ms"] = round(1000.0 * p50, 2)
    out["token_latency_p95_ms"] = round(1000.0 * p95, 2)
    if occ_b is not None:
        # ~1.0 by design: co-batching replaces pool aggregation with one
        # stacked frame per hop.
        out["batched_client_pool_occupancy"] = round(occ_b, 2)
    return out


def _disagg_phase() -> dict:
    """Disaggregated prefill/decode vs the colocated baseline: per-request
    TTFT and decode tok/s through the SAME gateway backend machinery, with
    the disagg side paying a real relay KV transfer (PrefillWorker →
    DisaggBackend). CPU-scope like the other transport-tier phase — the
    split's value on TPU is pool isolation, but its overhead (KV shipping,
    admission import) is all host/transport and measurable here."""
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        return {"error": "backend already initialized non-cpu; run this "
                         "phase in its own process",
                "scope": "cpu-localhost"}
    import asyncio
    import threading

    from distributed_llm_inference_tpu.config import (
        CacheConfig, DisaggConfig, EngineConfig, ModelConfig,
    )
    from distributed_llm_inference_tpu.disagg import PrefillWorker
    from distributed_llm_inference_tpu.distributed import (
        DirectoryService, RelayServer, native_available,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.models import llama as llama_mod
    from distributed_llm_inference_tpu.serving import (
        DisaggBackend, EngineBackend,
    )

    if not native_available():
        return {"error": "native relay unavailable (no g++)",
                "scope": "cpu-localhost"}

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=256,
    )
    params = llama_mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)

    def make_engine():
        return InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=4, prefill_buckets=(32, 64),
                         max_seq_len=128, dtype="float32"),
            CacheConfig(kind="paged", page_size=8, num_pages=256,
                        max_pages_per_session=16),
        )

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 128, size=24).tolist() for _ in range(6)]
    opts = SamplingOptions(max_new_tokens=32)

    def measure(backend):
        """Sequential requests through the gateway backend protocol:
        per-request TTFT (submit → first token) and steady decode rate."""
        loop = asyncio.new_event_loop()
        lt = threading.Thread(target=loop.run_forever, daemon=True)
        lt.start()
        backend.start(loop)
        ttfts, rates = [], []
        try:
            for i, p in enumerate([prompts[0]] + prompts):  # [0] warms JIT
                t0 = time.perf_counter()
                h = backend.submit(p, opts, None)

                async def _drain():
                    first = last = None
                    toks = 0
                    while True:
                        ev = await asyncio.wait_for(h.queue.get(),
                                                    timeout=120)
                        if ev.token >= 0:
                            toks += 1
                            last = time.perf_counter()
                            if first is None:
                                first = last
                        if ev.finished:
                            return first, last, toks

                first, last, toks = asyncio.run_coroutine_threadsafe(
                    _drain(), loop
                ).result(timeout=180)
                if i == 0 or first is None:
                    continue
                ttfts.append((first - t0) * 1e3)
                if toks > 1 and last > first:
                    rates.append((toks - 1) / (last - first))
        finally:
            backend.stop()
            loop.call_soon_threadsafe(loop.stop)
            lt.join(timeout=5)
        ttfts.sort()
        return {
            "ttft_ms_p50": round(ttfts[len(ttfts) // 2], 2),
            "decode_tok_s": round(sum(rates) / max(len(rates), 1), 1),
        }

    out = {"scope": "cpu-localhost",
           "note": "transport/host overhead of the prefill/decode split; "
                   "TPU compute is covered by the other phases"}
    out["colocated"] = measure(EngineBackend(make_engine()))
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            with PrefillWorker(relay.port, make_engine()):
                backend = DisaggBackend(
                    make_engine(), relay.port,
                    disagg_cfg=DisaggConfig(transfer_timeout_s=30.0),
                )
                out["disagg"] = measure(backend)
    # The TTFT split + transfer cost that only exist on the disagg side.
    for key, label, scale in (
        ("engine_ttft_prefill", "prefill_side_ms_p50", 1e3),
        ("engine_ttft_decode", "decode_side_ms_p50", 1e3),
        ("kv_transfer_ms", "kv_transfer_ms_p50", 1.0),
        ("kv_transfer_bytes", "kv_transfer_bytes_p50", 1.0),
    ):
        v = backend.metrics.percentile(key, 50)
        if v == v:  # skip NaN (metric never observed)
            out["disagg"][label] = round(v * scale, 2)
    if backend.metrics.get_counter("disagg_fallback_local"):
        out["disagg"]["fallback_local"] = backend.metrics.get_counter(
            "disagg_fallback_local"
        )
    out["ttft_overhead_ms"] = round(
        out["disagg"]["ttft_ms_p50"] - out["colocated"]["ttft_ms_p50"], 2
    )
    return out


def _recovery_phase() -> dict:
    """Crash-recovery MTTR: a decode node whole-node-crashes mid-stream
    (chaos proxy kills its data AND heartbeat paths); the FleetBackend
    gateway fences the dead lease and resumes the session on the survivor
    from the last shipped checkpoint. Reports detection→first-fresh-token
    MTTR (p50/p95 over trials), tokens_lost (MUST be 0: the client-visible
    stream is checked byte-exact vs an uninterrupted run), and goodput.
    CPU-scope and opt-in (`--phase recovery`): the recovery path is all
    host/transport, like the other fleet-tier phases."""
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        return {"error": "backend already initialized non-cpu; run this "
                         "phase in its own process",
                "scope": "cpu-localhost"}
    import asyncio
    import threading

    from distributed_llm_inference_tpu.config import (
        CacheConfig, DisaggConfig, EngineConfig, ModelConfig,
    )
    from distributed_llm_inference_tpu.disagg import DecodeNode
    from distributed_llm_inference_tpu.distributed import (
        DirectoryService, RelayServer, native_available,
    )
    from distributed_llm_inference_tpu.distributed.chaos import (
        ChaosProxy, FaultPlan,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.models import llama as llama_mod
    from distributed_llm_inference_tpu.serving import FleetBackend

    if not native_available():
        return {"error": "native relay unavailable (no g++)",
                "scope": "cpu-localhost"}

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
    )
    params = llama_mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)

    def make_engine():
        return InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=2, prefill_buckets=(8, 16, 32),
                         max_seq_len=64, dtype="float32"),
            CacheConfig(kind="paged", page_size=8, num_pages=64,
                        max_pages_per_session=8),
        )

    dcfg = DisaggConfig(lease_ttl_s=1.0, checkpoint_interval_ticks=2,
                        resume_max_attempts=2)
    prompt = [3, 5, 7, 11, 13]
    opts = SamplingOptions(max_new_tokens=48)  # greedy: baseline is exact
    e = make_engine()
    gid = e.submit(list(prompt), opts)
    base = []
    while True:
        done = False
        for g, tok, fin in e.step():
            if tok >= 0:
                base.append(tok)
            done = done or fin
        if done:
            break

    trials = 5
    loop = asyncio.new_event_loop()
    lt = threading.Thread(target=loop.run_forever, daemon=True)
    lt.start()
    out = {"scope": "cpu-localhost", "trials": trials,
           "note": "decode node crashed mid-stream each trial; stream "
                   "must finish byte-exact on the survivor"}
    tokens_lost = tokens_duplicated = delivered_total = 0
    wall = 0.0
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            backend = FleetBackend(relay.port, disagg_cfg=dcfg)
            backend.start(loop)
            try:
                for t in range(trials):
                    plan = FaultPlan.from_specs(
                        ["crash:fleet.tok.*:put:after=6"], seed=7 + t)
                    with ChaosProxy("127.0.0.1", relay.port,
                                    plan=plan) as proxy:
                        # Victim first: directory insertion order breaks
                        # the min-load tie, so the proxied node serves.
                        n1 = DecodeNode(proxy.port, make_engine(),
                                        node_id=f"victim-{t}",
                                        disagg_cfg=dcfg, epoch=1)
                        n2 = DecodeNode(relay.port, make_engine(),
                                        node_id=f"survivor-{t}",
                                        disagg_cfg=dcfg, epoch=1)
                        t0 = time.perf_counter()
                        h = backend.submit(
                            list(prompt), opts,
                            deadline=time.monotonic() + 180)

                        async def _drain():
                            toks, seqs = [], []
                            while True:
                                ev = await asyncio.wait_for(
                                    h.queue.get(), timeout=180)
                                if ev.token >= 0:
                                    toks.append(ev.token)
                                    seqs.append(ev.seq)
                                if ev.finished:
                                    return toks, seqs

                        toks, seqs = asyncio.run_coroutine_threadsafe(
                            _drain(), loop).result(timeout=240)
                        wall += time.perf_counter() - t0
                        delivered_total += len(toks)
                        tokens_duplicated += len(seqs) - len(set(seqs))
                        if toks != base:
                            tokens_lost += len(base) - sum(
                                a == b for a, b in zip(toks, base))
                        if not plan.injected:
                            out["note"] = "WARNING: crash fault never fired"
                        n2.stop()
                        n1.stop()
                m = backend.metrics
                out["deaths_detected"] = m.get_counter(
                    "node_deaths_detected")
                out["resume_attempts"] = m.get_counter("resume_attempts")
                out["resume_failures"] = m.get_counter("resume_failures")
                out["mttr_ms_p50"] = round(m.percentile("mttr_ms", 50), 1)
                out["mttr_ms_p95"] = round(m.percentile("mttr_ms", 95), 1)
            finally:
                backend.stop()
                loop.call_soon_threadsafe(loop.stop)
                lt.join(timeout=5)
    out["tokens_lost"] = tokens_lost
    out["tokens_duplicated"] = tokens_duplicated
    out["goodput_tok_s"] = round(delivered_total / wall, 1) if wall else 0.0
    return out


def _kvbytes_phase() -> dict:
    """Latent (MLA) KV compression accounting (`--phase kvbytes`, opt-in):
    stored KV bytes per token, the max resident batch a fixed pool byte
    budget holds at 2k context, the disagg prefill wire bytes, and the
    migration checkpoint bytes — latent (f32 and int8 stored forms) vs
    the conventional per-head paged baselines at proportional geometry
    (Hkv=8 x D=32 per-head K/V vs one rank-64 + 16-dim rope latent; the
    ratios, not the absolute tiny-model numbers, are the measurement).
    CPU-scope: every number is a byte count, not a kernel time."""
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        return {"error": "backend already initialized non-cpu; run this "
                         "phase in its own process",
                "scope": "cpu-localhost"}
    import dataclasses as _dc

    from distributed_llm_inference_tpu.config import (
        CacheConfig, EngineConfig, LatentConfig, ModelConfig,
    )
    from distributed_llm_inference_tpu.disagg.kv_codec import (
        encode_kv, encode_session,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.models import llama as llama_mod

    base_cfg = ModelConfig(
        vocab_size=128, hidden_size=128, intermediate_size=256, num_layers=2,
        num_heads=8, num_kv_heads=8, head_dim=32,
    )
    lat_cfg = _dc.replace(
        base_cfg, family="mla", num_kv_heads=1,
        latent=LatentConfig(rank=64, rope_head_dim=16),
    )
    ecfg = EngineConfig(max_batch_size=2, prefill_buckets=(16, 64),
                        max_seq_len=128, dtype="float32")
    ccfg = CacheConfig(kind="paged", page_size=16, num_pages=32,
                       max_pages_per_session=8)
    prompt = list(range(3, 51))  # 48 tokens
    # Headroom over the export point: export_session only snapshots LIVE
    # sessions, and pipelined ticks can drain several tokens per step().
    opts = SamplingOptions(max_new_tokens=16)
    pool_budget = 256 << 20  # fixed HBM budget the resident-batch count fills
    ctx = 2048

    def measure(cfg, kv_quant):
        params = llama_mod.init_params(cfg, jax.random.PRNGKey(0),
                                       jnp.float32)
        cc = _dc.replace(ccfg, kv_quant=kv_quant)
        eng = InferenceEngine(cfg, params, ecfg, cc,
                              rng=jax.random.PRNGKey(1))
        bpt = eng.metrics.get_gauge("kv_bytes_per_token")
        planes, first, chain = eng.prefill_export(list(prompt), opts)
        quant = "ks" in planes or "cs" in planes
        wire = sum(len(f) for f in encode_kv(
            "g", planes, len(prompt), first, chain,
            page_size=cc.page_size, quant=quant,
        ))
        gid = eng.submit(list(prompt), opts)
        emitted = 0
        # Checkpoint right after the first token: tail-capable caches drain
        # the WHOLE decode budget in one step(), so any later export point
        # finds the session finished; first-token exports also put every
        # variant's n_valid at len(prompt), keeping ckpt bytes comparable.
        for _ in range(10):
            emitted += sum(1 for _, tok, _ in eng.step() if tok >= 0)
            if emitted >= 1:
                break
        snap = eng.export_session(gid)
        ckpt = (sum(len(f) for f in encode_session(
                    gid, snap, page_size=cc.page_size))
                if snap is not None else None)
        return {
            "kv_bytes_per_token": bpt,
            "batch_at_2k_ctx_256mb": int(pool_budget // (bpt * ctx)),
            "kv_transfer_bytes": wire,
            "migrate_ckpt_bytes": ckpt,
            "latent_decompress_dispatches": int(eng.metrics.get_counter(
                "latent_decompress_dispatches")),
        }

    out = {
        "scope": "cpu-localhost",
        "geometry": "L2 Hq8 Hkv8 D32 vs latent rank64+rope16",
        "prompt_tokens": len(prompt),
        "baseline_f32": measure(base_cfg, None),
        "baseline_int8": measure(base_cfg, "int8"),
        "latent_f32": measure(lat_cfg, None),
        "latent_int8": measure(lat_cfg, "int8"),
    }
    for name in ("latent_f32", "latent_int8"):
        b, l = out["baseline_f32"], out[name]
        out[f"{name}_vs_baseline_f32"] = {
            k: round(b[k] / l[k], 2)
            for k in ("kv_bytes_per_token", "kv_transfer_bytes",
                      "migrate_ckpt_bytes")
            if b.get(k) and l.get(k)
        }
    out["targets"] = {"latent_f32_kv_bytes_per_token": ">=4x baseline_f32",
                      "wire_and_ckpt": "drop proportionally"}
    return out


def _prefix_phase() -> dict:
    """Prefix/KV reuse (prefixstore/): a multi-turn workload where every
    request repeats a long shared system prompt. Cold requests (unique
    system prompt each time) pay the full prefill; warm requests attach to
    the cached prefix pages and prefill only the user suffix — the bucket
    drops from 1024 to 32 tokens, which is the whole point. Reports cold vs
    warm p50 TTFT (acceptance: warm >= 5x lower), the engine's
    token-weighted prefix hit rate, the host-spill reload p50, and an
    in-process routing demo showing the directory steering the prompt to
    the node that advertised its prefix. CPU-scope and opt-in
    (`--phase prefix`) like the other host-tier phases."""
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        return {"error": "backend already initialized non-cpu; run this "
                         "phase in its own process",
                "scope": "cpu-localhost"}
    from distributed_llm_inference_tpu.config import (
        CacheConfig, EngineConfig, ModelConfig, PrefixConfig,
    )
    from distributed_llm_inference_tpu.distributed.directory import (
        BlockDirectory,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.models import llama as llama_mod

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
    )
    params = llama_mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ps = 16
    sys_len = 960  # 60 full pages: the shared "system prompt"
    sys_prompt = [(i * 37) % 96 + 2 for i in range(sys_len)]

    def make_engine(spill=0, num_pages=256):
        return InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=4, max_seq_len=1536,
                         prefill_buckets=(32, 1024), dtype="float32"),
            CacheConfig(kind="paged", page_size=ps, num_pages=num_pages,
                        max_pages_per_session=70, prefix_caching=True),
            prefix_cfg=PrefixConfig(spill_bytes_max=spill),
        )

    opts = SamplingOptions(max_new_tokens=1, eos_token_id=-1)
    e = make_engine()
    # Untimed warm-up: compile BOTH prefill buckets (cold 576, warm 32)
    # and seed the shared system prompt into the page registry.
    e.generate([sys_prompt + [99, 98]], opts)
    e.generate([sys_prompt + [97, 96]], opts)

    trials = 7
    cold_ms, warm_ms = [], []
    for t in range(trials):
        cold = [((t + 3) * 53 + i * 7) % 96 + 2 for i in range(sys_len)]
        t0 = time.perf_counter()
        e.generate([cold + [3, 5]], opts)
        cold_ms.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        e.generate([sys_prompt + [7 + t, 11]], opts)
        warm_ms.append((time.perf_counter() - t0) * 1e3)

    def p50(vals):
        return round(sorted(vals)[len(vals) // 2], 2)

    out = {"scope": "cpu-localhost", "trials": trials,
           "sys_prompt_tokens": sys_len, "page_size": ps,
           "cold_ttft_ms_p50": p50(cold_ms),
           "warm_ttft_ms_p50": p50(warm_ms),
           "warm_speedup": round(p50(cold_ms) / max(p50(warm_ms), 1e-6), 1),
           "speedup_target": ">=5x",
           "prefix_hit_rate": round(
               e.metrics.snapshot().get("prefix_hit_rate", 0.0), 3)}

    # Host-DRAM spill tier: a pool too small for two long sessions evicts
    # the first one's pages into the arena; re-running the first prompt
    # reloads them with one host->device copy per page.
    se = make_engine(spill=1 << 22, num_pages=20)  # 19 usable pages
    pa = [(i * 11) % 96 + 2 for i in range(256)]   # 17 pages
    pb = [(i * 13) % 96 + 5 for i in range(256)]
    se.generate([pa + [3, 4]], opts)
    se.generate([pb + [5, 6]], opts)  # pressure spills pa's pages
    se.generate([pa + [7, 8]], opts)  # reloads from the arena
    snap = se.metrics.snapshot()
    out["spilled_pages"] = snap.get("prefix_spilled_pages", 0)
    out["spill_reloads"] = snap.get("prefix_spill_reloads", 0)
    rl = se.metrics.percentile("prefix_reload_ms", 50)
    out["spill_reload_ms_p50"] = round(rl, 3) if rl == rl else None

    # Prefix-aware routing, in process: the warm engine advertises its
    # chain heads; the directory must steer the shared prompt to it, not
    # to the (less loaded) empty node.
    d = BlockDirectory(default_ttl=30.0)
    d.register("node-empty", 0, 1, "q.e", role="decode")
    d.register("node-warm", 0, 1, "q.w", role="decode")
    d.heartbeat("node-warm", load=3)
    d.advertise_prefixes("node-warm", ps, e.advertised_prefix_heads())
    nid, tok = d.match_prefix(sys_prompt + [1, 2, 3])
    out["routing"] = {"picked": nid, "matched_tokens": tok,
                      "expect": "node-warm despite higher load"}
    return out


# Arrival shape for `--phase traffic`, settable via `--arrival` (see main()).
_ARRIVAL = "poisson"
# `--trace N` (traffic phase): enable gateway tracing and dump the N
# slowest requests' stitched cross-node traces with the phase record.
_TRACE_N = 0


def _rate_envelope(shape: str, t: float, window_s: float) -> float:
    """Arrival-rate multiplier at time ``t`` for the traffic phases'
    non-homogeneous Poisson processes. ``poisson`` is the flat legacy
    process; ``bursty`` alternates 1 s spikes at 3x the base rate with
    troughs at 0.6x (mean ~1.4x — the shape an elastic fleet must absorb
    without provisioning for the spike full-time); ``diurnal`` sweeps a
    full sinusoid over the window (0.2x..1.8x), the compressed
    day/night cycle."""
    import math

    if shape == "bursty":
        return 3.0 if (t % 3.0) < 1.0 else 0.6
    if shape == "diurnal":
        return 1.0 + 0.8 * math.sin(2.0 * math.pi * t / max(window_s, 1e-9))
    return 1.0


def _traffic_phase(arrival: str = "poisson") -> dict:
    """Open-loop multi-tenant traffic harness (`--phase traffic`): a
    Poisson arrival process per tenant fired at a real HTTP gateway —
    arrivals never wait for completions, so queueing shows up as TTFT
    tail growth instead of being absorbed by a closed loop's back-off.
    ``--arrival bursty|diurnal`` reshapes both tenants' processes with
    the seeded rate envelope (``_rate_envelope``) while keeping the
    schedule deterministic per seed.
    Two adversarial tenants: "chat" (interactive lane, multi-turn
    requests sharing a system prefix, modest max_tokens) and "scraper"
    (batch lane, heavy-tailed prompt lengths, higher rate). Three runs
    on identical seeds: interactive SOLO (its baseline), both tenants
    under legacy FIFO admission, and both under the sched/ scheduler
    (weighted-fair lanes + deadline shedding). Reports per-tenant
    p50/p99 TTFT and p99 inter-token latency, goodput under an SLO
    derived from the solo run, Jain's fairness index over per-tenant
    token-satisfaction ratios, and the shed/reject counter split.
    Acceptance targets: sched interactive p99 TTFT <= 2x solo, Jain
    >= 0.8, any shedding happens before prefill dispatch (gateway
    counters move, engine submission counters don't). CPU-scope and
    opt-in like the other host-tier phases."""
    import http.client
    import random
    import threading

    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        return {"error": "backend already initialized non-cpu; run this "
                         "phase in its own process",
                "scope": "cpu-localhost"}
    from distributed_llm_inference_tpu.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedConfig, ServingConfig,
        TraceConfig,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.models import llama as llama_mod
    from distributed_llm_inference_tpu.serving import ApiServer, EngineBackend

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
    )
    params = llama_mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    WINDOW_S = 8.0
    SYS_PREFIX = [(i * 37) % 96 + 2 for i in range(64)]  # shared chat prefix

    def start_server(sched_on):
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=4, max_seq_len=512,
                         prefill_buckets=(32, 64, 128, 256),
                         dtype="float32"),
            CacheConfig(kind="paged", page_size=16, num_pages=512,
                        max_pages_per_session=24, prefix_caching=True),
        )
        backend = EngineBackend(eng, idle_sleep_s=0.001)
        scfg = ServingConfig(host="127.0.0.1", port=0, max_queue_depth=256)
        server = ApiServer(
            backend, scfg,
            sched_cfg=SchedConfig() if sched_on else None,
            # `--trace N`: sample every request so the N slowest have
            # stitched traces to dump; off otherwise (the default bench
            # measures the zero-cost disabled path).
            trace_cfg=TraceConfig() if _TRACE_N > 0 else None,
        )
        server.start()
        # Untimed warm-up: compile every prefill bucket + the decode step
        # so the timed window measures queueing, not XLA compiles.
        for n in (24, 56, 120, 250):
            _do_request([3] * n, 4, "warmup", "interactive", 60.0,
                        server.port, {})
        if server.sched is not None:
            # Warm-up TTFTs carry one-off compile time; drop them so the
            # shed model learns only from steady-state samples.
            server.sched.reset_estimator()
        return server, backend

    def _do_request(prompt, max_tokens, user, lane, timeout_s, port, rec):
        """One streamed completion; fills `rec` with ttft/gaps/tokens."""
        rec.setdefault("status", 0)
        t0 = time.perf_counter()
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=timeout_s + 30.0
            )
            conn.request(
                "POST", "/v1/completions",
                json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                            "stream": True, "user": user, "lane": lane,
                            "timeout_s": timeout_s}),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            rec["status"] = resp.status
            tid = resp.getheader("x-trace-id")
            if tid:
                rec["trace_id"] = tid
            if resp.status != 200:
                rec["code"] = json.loads(resp.read()).get(
                    "error", {}).get("code")
                conn.close()
                return
            last_t = None
            for raw in resp:
                if not raw.startswith(b"data: "):
                    continue
                payload = raw[len(b"data: "):].strip()
                if payload == b"[DONE]":
                    break
                doc = json.loads(payload)
                if doc["choices"][0]["token_ids"]:
                    now = time.perf_counter()
                    if last_t is None:
                        rec["ttft"] = now - t0
                    else:
                        rec.setdefault("gaps", []).append(now - last_t)
                    last_t = now
                    rec["tokens"] = rec.get("tokens", 0) + 1
                fr = doc["choices"][0].get("finish_reason")
                if fr:
                    rec["finish"] = fr
            conn.close()
        except Exception as e:  # connection death counts as a failure
            rec["error"] = repr(e)[:80]

    def make_workload(seed, include_batch):
        """Deterministic open-loop schedule: [(arrival_s, kwargs)].
        Non-homogeneous Poisson via rate-modulated gaps: each gap is
        sampled at the envelope-scaled rate current at that moment, so
        the same seed + shape always yields the same schedule."""
        rng = random.Random(seed)
        work = []
        t = 0.0
        while True:  # interactive "chat": ~3 req/s base, shared prefix
            t += rng.expovariate(
                3.0 * max(_rate_envelope(arrival, t, WINDOW_S), 0.05))
            if t >= WINDOW_S:
                break
            turn = [rng.randrange(2, 98) for _ in range(rng.randrange(8, 25))]
            work.append((t, dict(prompt=SYS_PREFIX + turn, max_tokens=16,
                                 user="chat", lane="interactive",
                                 timeout_s=30.0)))
        if include_batch:
            t = 0.0
            while True:  # batch "scraper": ~4 req/s, heavy-tailed lengths
                t += rng.expovariate(
                    4.0 * max(_rate_envelope(arrival, t, WINDOW_S), 0.05))
                if t >= WINDOW_S:
                    break
                if rng.random() < 0.2:  # the heavy tail
                    n = rng.randrange(192, 250)
                else:
                    n = rng.randrange(16, 33)
                prompt = [rng.randrange(2, 98) for _ in range(n)]
                work.append((t, dict(prompt=prompt, max_tokens=32,
                                     user="scraper", lane="batch",
                                     timeout_s=6.0)))
        work.sort(key=lambda w: w[0])
        return work

    trace_dumps = []  # `--trace N`: stitched traces of the slowest requests

    def _dump_slow_traces(recs, port):
        """Fetch the N slowest requests' stitched traces off the still-
        running gateway (`/debug/trace/<id>`) before it shuts down."""
        slow = sorted(
            (r for r in recs if "ttft" in r and r.get("trace_id")),
            key=lambda r: r["ttft"], reverse=True,
        )[:_TRACE_N]
        for r in slow:
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=10.0)
                conn.request("GET", f"/debug/trace/{r['trace_id']}")
                resp = conn.getresponse()
                doc = json.loads(resp.read()) if resp.status == 200 else {
                    "error": resp.status}
                conn.close()
            except Exception as e:
                doc = {"error": repr(e)[:80]}
            trace_dumps.append({
                "trace_id": r["trace_id"], "user": r["user"],
                "ttft_ms": round(r["ttft"] * 1e3, 1), "trace": doc,
            })

    def run_traffic(sched_on, include_batch, seed=1234,
                    collect_traces=False):
        server, backend = start_server(sched_on)
        try:
            work = make_workload(seed, include_batch)
            recs = [dict(user=kw["user"], requested=kw["max_tokens"])
                    for _, kw in work]
            threads = []
            t0 = time.perf_counter()
            for (at, kw), rec in zip(work, recs):
                delay = at - (time.perf_counter() - t0)
                if delay > 0:
                    time.sleep(delay)  # open loop: fire on schedule
                th = threading.Thread(
                    target=_do_request, kwargs=dict(port=server.port,
                                                    rec=rec, **kw),
                    daemon=True,
                )
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=60.0)
            snap = backend.metrics.snapshot()
            if collect_traces and _TRACE_N > 0:
                _dump_slow_traces(recs, server.port)
        finally:
            server.request_shutdown()
            server.join(timeout=60.0)
        return recs, snap

    def pct(vals, q):
        if not vals:
            return None
        vals = sorted(vals)
        return round(
            vals[min(len(vals) - 1, int(q / 100.0 * len(vals)))] * 1e3, 1
        )

    def tenant_stats(recs, user, slo_s=None):
        mine = [r for r in recs if r["user"] == user]
        ttfts = [r["ttft"] for r in mine if "ttft" in r]
        gaps = [g for r in mine for g in r.get("gaps", [])]
        served = sum(r.get("tokens", 0) for r in mine)
        requested = sum(r["requested"] for r in mine)
        out = {
            "requests": len(mine),
            "ok": sum(1 for r in mine if r.get("finish") == "stop"
                      or r.get("finish") == "length"),
            "r429": sum(1 for r in mine if r["status"] == 429),
            "ttft_ms_p50": pct(ttfts, 50), "ttft_ms_p99": pct(ttfts, 99),
            "itl_ms_p99": pct(gaps, 99),
            "satisfaction": round(served / max(requested, 1), 3),
        }
        if slo_s is not None:
            good = sum(
                r.get("tokens", 0) for r in mine
                if r.get("ttft") is not None and r["ttft"] <= slo_s
            )
            out["goodput_tok_s"] = round(good / WINDOW_S, 1)
        return out

    def jain(xs):
        if not xs or all(x == 0 for x in xs):
            return 0.0
        return round(sum(xs) ** 2 / (len(xs) * sum(x * x for x in xs)), 3)

    # Run 1 — interactive alone: the no-contention baseline the SLO and
    # the "<= 2x solo" acceptance bar both come from.
    solo_recs, _ = run_traffic(sched_on=True, include_batch=False)
    solo = tenant_stats(solo_recs, "chat")
    slo_s = max(0.25, 4.0 * (solo["ttft_ms_p50"] or 0.0) / 1e3)

    # Run 2 — both tenants, legacy FIFO admission (scheduler off).
    fifo_recs, fifo_snap = run_traffic(sched_on=False, include_batch=True)
    # Run 3 — both tenants, scheduler on: weighted-fair lanes + shedding.
    sched_recs, sched_snap = run_traffic(sched_on=True, include_batch=True,
                                         collect_traces=True)

    def summarize(recs, snap):
        chat = tenant_stats(recs, "chat", slo_s)
        scraper = tenant_stats(recs, "scraper", slo_s)
        return {
            "chat": chat, "scraper": scraper,
            "jain_fairness": jain(
                [chat["satisfaction"], scraper["satisfaction"]]
            ),
            "shed_early": int(snap.get("sched_shed_early", 0)),
            "rejected_rate_limit": int(
                snap.get("sched_reject_rate_limit", 0)
            ),
            "engine_sessions_submitted": int(
                snap.get("sessions_submitted", 0)
            ),
            "gateway_http_requests": int(snap.get("http_requests", 0)),
        }

    fifo = summarize(fifo_recs, fifo_snap)
    sched = summarize(sched_recs, sched_snap)
    solo_p99 = solo["ttft_ms_p99"] or 1e-9
    sched_p99 = sched["chat"]["ttft_ms_p99"] or 0.0
    extra = {"slow_traces": trace_dumps} if _TRACE_N > 0 else {}
    return {
        **extra,
        "scope": "cpu-localhost", "window_s": WINDOW_S,
        "arrival": arrival,
        # One gateway+engine for the whole window: the node-count
        # integral a fleet run (`--phase elastic`) is compared against.
        "node_seconds": WINDOW_S,
        "slo_ttft_ms": round(slo_s * 1e3, 1),
        "solo_interactive": solo,
        "fifo": fifo, "sched": sched,
        "interactive_p99_vs_solo_x": round(sched_p99 / solo_p99, 2),
        "targets": {"interactive_p99_vs_solo_x": "<=2.0 (sched on)",
                    "jain_fairness": ">=0.8",
                    "sheds_pre_prefill": "engine submits < gateway "
                                         "requests when shed_early > 0"},
    }


def _elastic_phase() -> dict:
    """Elastic vs statically over-provisioned decode fleet under bursty
    open-loop traffic (`--phase elastic`): the same seeded bursty
    workload (shared-prefix prompts, 1 s spikes at 3x the base rate) is
    fired at a FleetBackend gateway twice — once over a static pool of
    ``N_MAX`` decode nodes up for the whole window, once starting from
    one node with the FleetController autoscaling between 1 and
    ``N_MAX`` (warm standbys spawn on sustained load, drain-then-fence
    on idle). Reports per-run goodput under an SLO derived from the
    static run's TTFT p50, the node-count integral (node-seconds, the
    provisioning cost), and the fleet/cost-model decision counters.
    Acceptance target: elastic goodput within ~10% of static at a lower
    node-count integral. Native-relay CPU phase, opt-in like traffic."""
    import http.client
    import random
    import threading

    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        return {"error": "backend already initialized non-cpu; run this "
                         "phase in its own process",
                "scope": "cpu-localhost"}
    from distributed_llm_inference_tpu.config import (
        CacheConfig, DisaggConfig, EngineConfig, FleetConfig, ModelConfig,
        PrefixConfig, ServingConfig,
    )
    from distributed_llm_inference_tpu.disagg import DecodeNode
    from distributed_llm_inference_tpu.distributed.directory import (
        DirectoryClient, DirectoryService,
    )
    from distributed_llm_inference_tpu.distributed.relay import (
        RelayServer, native_available,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.fleet import (
        FleetController, live_decode_rows,
    )
    from distributed_llm_inference_tpu.models import llama as llama_mod
    from distributed_llm_inference_tpu.serving import ApiServer, FleetBackend

    if not native_available():
        return {"error": "g++ unavailable to build the native relay",
                "scope": "cpu-localhost"}

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
    )
    params = llama_mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    WINDOW_S = 8.0
    N_MAX = 3
    SYS = [(i * 31) % 96 + 2 for i in range(24)]  # shared prompt prefix
    # Generous lease: N engines decoding + open-loop request threads on
    # one CPU starve 1 s heartbeats into false expiry, which reads as
    # node churn rather than load.
    DCFG = DisaggConfig(lease_ttl_s=3.0, checkpoint_interval_ticks=4,
                        resume_max_attempts=4)
    FCFG = FleetConfig(
        drain_timeout_s=5.0, autoscale_interval_s=0.2, scale_out_load=1.5,
        scale_in_load=0.3, scale_hold_s=0.6, min_nodes=1, max_nodes=N_MAX,
        rebalance_interval_s=2.0, hot_load_factor=1.8,
    )

    def make_engine():
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=2, prefill_buckets=(16, 32, 64),
                         max_seq_len=96, dtype="float32"),
            CacheConfig(kind="paged", page_size=8, num_pages=128,
                        max_pages_per_session=10, prefix_caching=True),
        )
        # Warm standby: compile prefill + decode BEFORE the timed window
        # for both runs (scale-out registers an already-warm engine).
        eng.submit(list(SYS) + [3] * 8,
                   SamplingOptions(max_new_tokens=2, temperature=0.0))
        while eng.has_work():
            eng.step()
        eng.collect_finished()
        return eng

    def make_workload(seed):
        rng = random.Random(seed)
        work, t = [], 0.0
        while True:  # single bursty tenant, ~1.5 req/s base rate
            t += rng.expovariate(
                1.5 * max(_rate_envelope("bursty", t, WINDOW_S), 0.05))
            if t >= WINDOW_S:
                break
            tail = [rng.randrange(2, 98) for _ in range(rng.randrange(4, 13))]
            work.append((t, SYS + tail))
        return work

    def _do_request(prompt, port, rec):
        t0 = time.perf_counter()
        rec.setdefault("status", 0)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60.0)
            conn.request(
                "POST", "/v1/completions",
                json.dumps({"prompt": prompt, "max_tokens": 12,
                            "stream": True, "timeout_s": 30.0}),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                conn.close()
                return
            for raw in resp:
                if not raw.startswith(b"data: "):
                    continue
                payload = raw[len(b"data: "):].strip()
                if payload == b"[DONE]":
                    break
                doc = json.loads(payload)
                if doc["choices"][0]["token_ids"]:
                    rec.setdefault("ttft", time.perf_counter() - t0)
                    rec["tokens"] = rec.get("tokens", 0) + 1
            conn.close()
        except Exception as e:  # noqa: BLE001 - failure = lost goodput
            rec["error"] = repr(e)[:80]

    def run_fleet(elastic, seed=4321):
        with RelayServer() as relay:
            with DirectoryService(relay.port, default_ttl=5.0):
                standby = [make_engine() for _ in range(N_MAX)]
                live, counter = {}, [0]

                def spawn():
                    if not standby:
                        return
                    nid = f"d{counter[0]}"
                    counter[0] += 1
                    live[nid] = DecodeNode(relay.port, standby.pop(),
                                           node_id=nid, disagg_cfg=DCFG,
                                           epoch=1)

                def retire(nid):
                    n = live.pop(nid, None)
                    if n is not None:
                        n.stop()

                for _ in range(1 if elastic else N_MAX):
                    spawn()
                ctl = None
                if elastic:
                    ctl = FleetController(
                        relay.port, fleet_cfg=FCFG, disagg_cfg=DCFG,
                        spawn=spawn, retire=retire,
                    )
                    ctl.start()
                backend = FleetBackend(relay.port, disagg_cfg=DCFG,
                                       prefix_cfg=PrefixConfig(),
                                       fleet_cfg=FCFG)
                server = ApiServer(backend, ServingConfig(
                    host="127.0.0.1", port=0, max_queue_depth=256))
                server.start()
                # Node-count integral: sample the routable pool at 10 Hz.
                integral = [0.0]
                stop_sampler = threading.Event()

                def sample():
                    d = DirectoryClient(relay.port)
                    try:
                        last = time.perf_counter()
                        while not stop_sampler.wait(0.1):
                            now = time.perf_counter()
                            try:
                                rows = live_decode_rows(d.alive())
                            except Exception:  # noqa: BLE001
                                rows = []
                            integral[0] += (now - last) * len(rows)
                            last = now
                    finally:
                        d.close()

                sampler = threading.Thread(target=sample, daemon=True)
                sampler.start()
                try:
                    work = make_workload(seed)
                    recs = [dict() for _ in work]
                    threads = []
                    t0 = time.perf_counter()
                    for (at, prompt), rec in zip(work, recs):
                        delay = at - (time.perf_counter() - t0)
                        if delay > 0:
                            time.sleep(delay)  # open loop
                        th = threading.Thread(target=_do_request,
                                              args=(prompt, server.port, rec),
                                              daemon=True)
                        th.start()
                        threads.append(th)
                    for th in threads:
                        th.join(timeout=60.0)
                finally:
                    stop_sampler.set()
                    sampler.join(timeout=5.0)
                    if ctl is not None:
                        ctl.close()
                    server.request_shutdown()
                    server.join(timeout=60.0)
                    for n in list(live.values()):
                        n.stop()
                snap = dict(backend.metrics.snapshot())
                if ctl is not None:
                    snap.update({f"ctl_{k}": v for k, v in
                                 ctl.metrics.snapshot().items()})
                return recs, integral[0], snap

    def pct(vals, q):
        if not vals:
            return None
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(q / 100.0 * len(vals)))]

    def summarize(recs, node_seconds, snap, slo_s):
        ttfts = [r["ttft"] for r in recs if "ttft" in r]
        good = sum(r.get("tokens", 0) for r in recs
                   if r.get("ttft") is not None and r["ttft"] <= slo_s)
        p99 = pct(ttfts, 99)
        return {
            "requests": len(recs),
            "ok": sum(1 for r in recs if r.get("tokens")),
            "ttft_ms_p50": round((pct(ttfts, 50) or 0.0) * 1e3, 1),
            "ttft_ms_p99": round(p99 * 1e3, 1) if p99 else None,
            "goodput_tok_s": round(good / WINDOW_S, 1),
            "node_seconds": round(node_seconds, 1),
            "decisions": {
                "query_moved": int(snap.get("fleet_query_moved", 0)),
                "pages_fetched": int(snap.get("fleet_pages_fetched", 0)),
                "migrated": int(snap.get("fleet_migrated", 0)),
                "routed_by_prefix": int(snap.get("routed_by_prefix", 0)),
                "drained_sessions": int(
                    snap.get("fleet_drained_sessions", 0)),
                "scale_out": int(snap.get("ctl_fleet_scale_out", 0)),
                "scale_in": int(snap.get("ctl_fleet_scale_in", 0)),
            },
        }

    static_recs, static_ns, static_snap = run_fleet(elastic=False)
    ttfts = [r["ttft"] for r in static_recs if "ttft" in r]
    slo_s = max(0.25, 4.0 * (pct(ttfts, 50) or 0.0))
    elastic_recs, elastic_ns, elastic_snap = run_fleet(elastic=True)

    static = summarize(static_recs, static_ns, static_snap, slo_s)
    elastic = summarize(elastic_recs, elastic_ns, elastic_snap, slo_s)
    ratio = (elastic["goodput_tok_s"] / static["goodput_tok_s"]
             if static["goodput_tok_s"] else None)
    return {
        "scope": "cpu-localhost", "window_s": WINDOW_S,
        "arrival": "bursty", "n_max": N_MAX,
        "slo_ttft_ms": round(slo_s * 1e3, 1),
        "static": static, "elastic": elastic,
        "goodput_vs_static": round(ratio, 3) if ratio is not None else None,
        "node_seconds_saved": round(static_ns - elastic_ns, 1),
        "targets": {"goodput_vs_static": ">=0.9",
                    "node_seconds": "elastic < static"},
    }


def run_phase(name: str) -> dict:
    if name == "distributed":
        return _distributed_phase()
    if name == "disagg":
        return _disagg_phase()
    if name == "recovery":
        return _recovery_phase()
    if name == "prefix":
        return _prefix_phase()
    if name == "kvbytes":
        return _kvbytes_phase()
    if name == "traffic":
        return _traffic_phase(_ARRIVAL)
    if name == "elastic":
        return _elastic_phase()
    if name == "prefill":
        return _prefill_phase()
    if name == "mixed":
        return _mixed_phase()
    _require_tpu()
    cfg, model_label = _PHASE_CFG.get(name, (LLAMA2_7B, "llama-2-7b-shape"))
    if name == "engine_int8_kvq":
        return _engine_phase()
    if name == "sink_1k":
        return _sink_phase()
    if name == "speculative":
        return _speculative_phase()
    if name == "mistral_paged_swa":
        return _mistral_phase()
    if name == "mixtral":
        return _mixtral_moe_phase()
    build, ladder, cache_cls = PHASES[name]
    # float32 on CPU throughout: XLA:CPU lacks several bf16 kernels the
    # quantized paths emit.
    params = build(cfg, jnp.bfloat16)
    jax.block_until_ready(params)
    if cache_cls in ("paged", "paged_kvq"):
        from distributed_llm_inference_tpu.cache.paged import (
            PagedKVCache,
            QuantizedPagedKVCache,
        )

        pcls = QuantizedPagedKVCache if cache_cls == "paged_kvq" else PagedKVCache
        # Long-context paged phases use 128-token pages: the in-place fused
        # kernel DMAs one page per grid step, and 128-wide tiles close the
        # per-page overhead gap vs dense's 256-wide sweep (b24/1k measured:
        # ps64 795, ps128 897, ps256 842 tok/s vs dense 858).
        ps = 128 if name.endswith(("_1k", "_2k")) else 64
        err = None
        best = None
        for scan_k in (16, 1):  # best of the two descents (see _decode_ladder)
            for b_, ctx in ladder:
                try:
                    t_ = _try_paged_decode_bench(
                        cfg, params, b_, ctx, scan_k=scan_k, cls=pcls,
                        page_size=ps,
                    )
                except Exception as e:
                    err = repr(e)
                    continue
                if best is None or t_ > best[0]:
                    best = (t_, b_)
                break
        if best is None:
            raise RuntimeError(f"all paged configs failed: {err}")
        tok_s, batch = best
        ttft = ttft_dev = None
        if name not in _NO_TTFT:
            ttft, ttft_dev = _ttft_bench(cfg, params, cache_cls=_PagedTTFTCache)
    else:
        use_kernel = cache_cls == "dense_kernel"
        if use_kernel:
            cache_cls = QuantizedDenseKVCache
        tok_s, batch = _decode_ladder(
            cfg, params, ladder, cache_cls, use_kernel=use_kernel
        )
        ttft = ttft_dev = None
        if name not in _NO_TTFT:
            ttft, ttft_dev = _ttft_bench(cfg, params, cache_cls=cache_cls)
    return {
        "tok_s": round(tok_s, 2), "batch": batch,
        "ttft_ms": round(ttft, 2) if ttft is not None else None,
        "ttft_device_ms": ttft_dev,
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0].device_kind),
        "model": model_label,
    }


def main():
    """The child: one phase in this process (the parent left above)."""
    global _ARRIVAL, _TRACE_N
    if "--arrival" in sys.argv:  # poisson | bursty | diurnal
        _ARRIVAL = sys.argv[sys.argv.index("--arrival") + 1]
    if "--trace" in sys.argv:  # dump the N slowest requests' traces
        _TRACE_N = int(sys.argv[sys.argv.index("--trace") + 1])
    enable_compile_cache()
    print(json.dumps(run_phase(sys.argv[sys.argv.index("--phase") + 1])))


if __name__ == "__main__":
    main()
