#!/usr/bin/env python3
"""chip_smoke.py — does the serving path still start on the chip?

One process, one chip, the entry points a user calls. Mistral-7B-v0.1 at its
published widths (32 layers, hidden 4096, FFN 14336, 32 q / 8 kv heads of
128, vocabulary 32000, sliding window 4096), int8 weights and an int8 paged
KV cache, seeded random weights, and the engine flags ``distribute api``
gives (``cli.cmd_api``): ragged prefill on, 2048-token chunks, the Pallas
kernels. In order:

1. ``load``    a 2-layer full-width checkpoint, written here from the seed,
               goes through ``checkpoint.load_model_params`` → engine
               ``quantization="int8"`` (the path ``distribute api --model
               DIR --quantize int8`` takes) and generates a few tokens.
2. ``serve``   the 32-layer engine behind ``EngineBackend`` →
               ``ApiServer.serve_forever`` on a thread, spoken to over
               localhost HTTP: ``/healthz``, two identical rounds of seven
               ``/v1/completions`` requests (one an SSE stream, two prompts
               over 2048 tokens, the last sent while the others decode so
               its chunks ride the decode cadence), then ``/metrics``.
3. ``numerics`` the logits of one prompt's prefill and of 16 teacher-forced
               decode steps through the attention path the engine's plan
               selected (ragged + fused Pallas kernels) may sit no farther
               from an all-float32 run than those of an engine built with
               ``use_pallas_attention=False, ragged_attention=False`` on
               the same params (XLA attention) do.
4. ``steps``   every jitted engine step the rounds used is lowered and
               compiled again from its recorded shapes (a compile-cache
               read) to say whether ``tpu_custom_call`` is in its text.

``--chips 4`` runs instead, and only, the tensor-parallel path: the same
widths in bf16 at the depth one chip can hold, ``tp=4`` through
``engine.generate`` — shards spread, all-reduces compiled in — against the
same weights on one chip, by the same float32 yardstick; and the tp=4
engine's fresh-row prefill program (a scratch dense cache, whole pages
installed) beside its page-table program, a pad width each, logits and
pages by that yardstick (``phase_fresh_prefill``).

Every line on stdout is one JSON object; the LAST is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Timings are smoke readings for the reader, not metrics. Without
``--rehearse-cpu`` the script refuses to run unless JAX finds a TPU; the
rehearsal walks the same control flow on the CPU at a tiny size with the
kernels interpreted, and its last line says ``"platform": "cpu"``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import http.client
import json
import math
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
NO_CHIP, CHECK_FAILED = 2, 1

# Mistral-7B-v0.1 config.json (mistralai/Mistral-7B-v0.1), as an HF dict so
# the load phase can write it out and ``ModelConfig.from_hf_config`` reads
# it back — one source for both.
MISTRAL_7B = {
    "model_type": "mistral",
    "vocab_size": 32000,
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0,
    "max_position_embeddings": 32768,
    "sliding_window": 4096,
    "tie_word_embeddings": False,
}
WEIGHT_STD = 0.02


@dataclasses.dataclass(frozen=True)
class Size:
    """Everything the rehearsal shrinks; the control flow is shared."""

    hf: dict
    dtype: str
    engine: dict          # EngineConfig fields besides dtype/quantization
    cache: dict           # CacheConfig fields besides kind/kv_quant
    prompts: tuple        # six sent together, the seventh while they decode
    new_tokens: tuple     # prompts[2] (legacy bucket < 1024) is the probe
    load_layers: int
    tp_layers: int        # --chips 4: depth one chip holds in bf16


FULL = Size(
    hf=MISTRAL_7B,
    dtype="bfloat16",
    # cli.cmd_api's defaults (--max-sessions 8 --max-seq-len 2048); every
    # other EngineConfig / CacheConfig field keeps its default.
    engine={"max_batch_size": 8, "max_seq_len": 2048},
    cache={},
    prompts=(30, 45, 200, 420, 860, 2300, 2500),
    new_tokens=(64, 32, 48, 32, 64, 32, 32),
    load_layers=2,
    # bf16 per layer: 436 MB of weights + 134 MB of the default 512-page
    # pool; 20 layers + embeddings = 11.9 GB of a 16 GB chip.
    tp_layers=20,
)
TINY = Size(
    hf={**MISTRAL_7B, "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 8, "num_key_value_heads": 4,  # tp=4 divides
        "max_position_embeddings": 512},
    dtype="float32",  # XLA:CPU lacks bf16 dots the int8 paths emit
    engine={"max_batch_size": 8, "max_seq_len": 64,
            "prefill_buckets": (8, 16, 32)},
    cache={"page_size": 8, "num_pages": 128, "max_pages_per_session": 12},
    prompts=(3, 5, 10, 14, 20, 40, 44),
    new_tokens=(20, 8, 12, 8, 20, 8, 8),
    load_layers=1,
    tp_layers=2,
)

OUT_LINES = []


def emit(obj) -> None:
    line = json.dumps(obj)
    OUT_LINES.append(line)
    print(line, flush=True)


class CheckFailed(Exception):
    pass


def check(name: str, ok, **info) -> None:
    emit({"check": name, "ok": bool(ok), **info})
    if not ok:
        raise CheckFailed(name)


# --------------------------------------------------------------------------
# compilation accounting
# --------------------------------------------------------------------------


class CompileLog:
    """Counts what JAX's own monitoring reports: every backend compile
    request (one per executable the process did not already hold — a
    persistent-cache hit is still a request) and the persistent cache's
    hits among them."""

    def __init__(self):
        import jax

        self.requests, self.seconds, self.hits = 0, 0.0, 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += seconds
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.requests, self.seconds, self.hits, len(self.names))

    def since(self, mark) -> dict:
        return {
            "compile_requests": self.requests - mark[0],
            "compile_s": round(self.seconds - mark[1], 2),
            "persistent_cache_hits": self.hits - mark[2],
            "compiled": self.names[mark[3]:][:12],
        }


# --------------------------------------------------------------------------
# seeded weights
# --------------------------------------------------------------------------


def model_config(size: Size, num_layers=None):
    from distributed_llm_inference_tpu.config import ModelConfig

    cfg = ModelConfig.from_hf_config(size.hf)
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    return cfg


def seeded_params(cfg, seed: int, dtype, stored_int8: bool):
    """The model's parameters from ``seed``, generated on the device one
    layer at a time (``lax.map``). ``stored_int8`` draws the projections
    directly in their served form — uniform int8 values with per-channel
    scales set so the dequantized weights have std ``WEIGHT_STD`` — so a
    7B tree never exists in bf16."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_inference_tpu.ops.quant import QuantizedTensor

    h, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    shapes = {
        "wq": (h, hq * d), "wk": (h, hkv * d), "wv": (h, hkv * d),
        "wo": (hq * d, h), "wg": (h, f), "wu": (h, f), "wd": (f, h),
    }

    def matrix(key, shape):
        if not stored_int8:
            return (
                jax.random.normal(key, shape, jnp.float32) * WEIGHT_STD
            ).astype(dtype)
        kq, ks = jax.random.split(key)
        q = jnp.maximum(
            jax.lax.bitcast_convert_type(
                jax.random.bits(kq, shape, jnp.uint8), jnp.int8
            ),
            -127,
        )
        # uniform[-127, 127] has std 127/sqrt(3)
        scale = (WEIGHT_STD * math.sqrt(3) / 127) * jax.random.uniform(
            ks, shape[-1:], jnp.float32, 0.5, 1.5
        )
        return QuantizedTensor(q=q, scale=scale.astype(dtype))

    def one_layer(key):
        keys = jax.random.split(key, len(shapes))
        layer = {n: matrix(k, s) for (n, s), k in zip(shapes.items(), keys)}
        layer["attn_norm"] = jnp.ones((h,), dtype)
        layer["mlp_norm"] = jnp.ones((h,), dtype)
        return layer

    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "embed": (
            jax.random.normal(k_embed, (v, h), jnp.float32) * WEIGHT_STD
        ).astype(dtype),
        "layers": jax.jit(lambda ks: jax.lax.map(one_layer, ks))(
            jax.random.split(k_layers, cfg.num_layers)
        ),
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": jax.jit(matrix, static_argnums=1)(k_head, (h, v)),
    }


def seeded_prompt(seed: int, index: int, length: int, vocab: int):
    import numpy as np

    rng = np.random.default_rng([seed, index, length])
    return [int(t) for t in rng.integers(1, vocab, size=length)]


# --------------------------------------------------------------------------
# engine construction (as cli.cmd_api does)
# --------------------------------------------------------------------------


def build_engine(size: Size, cfg, params, quantization=None, xla_attention=False,
                 mesh_cfg=None, kv_quant="int8"):
    from distributed_llm_inference_tpu.config import (
        CacheConfig, EngineConfig, TraceConfig,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine

    ekw = dict(size.engine, dtype=size.dtype, quantization=quantization)
    if xla_attention:
        ekw.update(use_pallas_attention=False, ragged_attention=False)
    return InferenceEngine(
        cfg, params, EngineConfig(**ekw),
        CacheConfig(kind="paged", kv_quant=kv_quant, **size.cache),
        mesh_cfg=mesh_cfg, trace_cfg=TraceConfig(),
    )


def device_bytes(key: str = "peak_bytes_in_use") -> list:
    """``memory_stats()[key]`` of every device (None where unreported)."""
    import jax

    return [(d.memory_stats() or {}).get(key) for d in jax.devices()]


# --------------------------------------------------------------------------
# phase 1: the load path
# --------------------------------------------------------------------------

_HF_NAMES = {
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
    "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
    "wg": "mlp.gate_proj", "wu": "mlp.up_proj", "wd": "mlp.down_proj",
}


def write_checkpoint(path: str, size: Size, seed: int):
    """A depth-cut, full-width HF checkpoint from the seed."""
    import jax
    import numpy as np

    from distributed_llm_inference_tpu.utils.checkpoint import save_safetensors

    cfg = model_config(size, size.load_layers)
    dtype = jax.numpy.dtype(size.dtype)
    p = jax.device_get(seeded_params(cfg, seed, dtype, stored_int8=False))
    state = {
        "model.embed_tokens.weight": p["embed"],
        "model.norm.weight": p["final_norm"],
        "lm_head.weight": p["lm_head"].T,
    }
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        for ours, theirs in _HF_NAMES.items():
            state[f"{pre}{theirs}.weight"] = p["layers"][ours][i].T
        state[pre + "input_layernorm.weight"] = p["layers"]["attn_norm"][i]
        state[pre + "post_attention_layernorm.weight"] = (
            p["layers"]["mlp_norm"][i]
        )
    save_safetensors(state, os.path.join(path, "model.safetensors"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({**size.hf, "num_hidden_layers": cfg.num_layers}, f)
    return sum(np.asarray(a).nbytes for a in state.values())


def phase_load(size: Size, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.ops.quant import (
        QuantizedTensor, quantize_int8,
    )
    from distributed_llm_inference_tpu.utils import checkpoint, streader

    # The reader's library is git-ignored and may have travelled with the
    # tree: rebuild what this phase loads.
    streader.build_native(force=True)
    check("native_streader_built", streader.native_available())
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        nbytes = write_checkpoint(ckpt, size, seed)
        cfg = checkpoint.load_config(ckpt)
        params = checkpoint.load_model_params(ckpt, cfg, jnp.dtype(size.dtype))
    host = params["layers"]["wq"]
    check(
        "load_keeps_layer_stacks_on_host",
        isinstance(host, np.ndarray),
        leaf_type=type(host).__name__,
    )
    engine = build_engine(size, cfg, params, quantization="int8")
    served = engine.params["layers"]["wq"]
    eager = quantize_int8(jnp.asarray(host))
    check(
        "load_quantized_leaf_equals_eager_quantize_int8",
        isinstance(served, QuantizedTensor)
        and bool(jnp.array_equal(served.q, eager.q))
        and bool(jnp.array_equal(served.scale, eager.scale)),
    )
    prompt = seeded_prompt(seed, 100, size.prompts[1], cfg.vocab_size)
    out = engine.generate([prompt], SamplingOptions(max_new_tokens=8))[0]
    check(
        "load_engine_generates",
        len(out) == 8 and all(0 <= t < cfg.vocab_size for t in out),
        tokens=out,
    )
    emit({
        "phase": "load", "layers": cfg.num_layers,
        "width": "full" if size is FULL else "tiny",
        "checkpoint_bytes": nbytes, "peak_bytes_in_use": device_bytes(),
        "smoke_reading_wall_s": round(time.monotonic() - t0, 1),
    })


# --------------------------------------------------------------------------
# phase 2: the server
# --------------------------------------------------------------------------


def _request(port, method, path, body=None, timeout=600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(
        method, path, None if body is None else json.dumps(body),
        {"Content-Type": "application/json"},
    )
    return conn, conn.getresponse()


def _complete(port, prompt, max_tokens, result, first_token=None):
    """One non-streaming or (``first_token`` given) SSE completion; fills
    ``result`` with status, token ids and finish_reason."""
    body = {"prompt": prompt, "max_tokens": max_tokens, "timeout_s": 600.0}
    stream = first_token is not None
    if stream:
        body["stream"] = True
    try:
        conn, resp = _request(port, "POST", "/v1/completions", body)
        result["status"] = resp.status
        tokens, reason = [], None
        if not stream:
            choice = json.loads(resp.read())["choices"][0]
            tokens, reason = choice["token_ids"], choice["finish_reason"]
        else:
            done = False
            for raw in iter(resp.fp.readline, b""):
                raw = raw.strip()
                if not raw.startswith(b"data: "):
                    continue
                data = raw[len(b"data: "):]
                if data == b"[DONE]":
                    done = True
                    break
                choice = json.loads(data)["choices"][0]
                tokens += choice["token_ids"]
                reason = choice["finish_reason"] or reason
                if tokens:
                    first_token.set()
            result["sse_done"] = done
        conn.close()
        result["tokens"], result["finish_reason"] = tokens, reason
    except Exception as e:  # the thread's failure is the request's result
        result["error"] = repr(e)
    finally:
        if first_token is not None:
            first_token.set()


def run_round(port, backend, size: Size, prompts) -> list:
    """Six requests sent together — the driver is held until all six wait in
    the engine's queue, so one tick admits them as a batch — and a seventh
    sent once the SSE stream has produced a token, so that its chunked
    prefill is co-scheduled with live decode rows."""
    results = [
        {"prompt_tokens": len(p), "max_tokens": n, "stream": i == 0}
        for i, (p, n) in enumerate(zip(prompts, size.new_tokens))
    ]
    first_token = threading.Event()
    threads = [
        threading.Thread(
            target=_complete,
            args=(port, p, n, r, first_token if i == 0 else None),
        )
        for i, (p, n, r) in enumerate(zip(prompts, size.new_tokens, results))
    ]
    backend.pause()
    for t in threads[:-1]:
        t.start()
    deadline = time.monotonic() + 60.0
    while backend.queue_depth() < len(threads) - 1:
        if time.monotonic() > deadline:
            break
        time.sleep(0.005)
    queued = backend.queue_depth()
    backend.resume()
    first_token.wait(timeout=900.0)
    threads[-1].start()
    for t in threads:
        t.join(timeout=900.0)
    for r in results:
        r["queued_together"] = queued
    return results


def _prom_counters(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith("dli_") and "_total " in line:
            name, value = line.split(" ")
            out[name[len("dli_"):-len("_total")]] = float(value)
    return out


@contextlib.contextmanager
def serving(engine):
    """``cmd_api``'s tail: EngineBackend → ApiServer.serve_forever, here on
    a thread of this process (signal handlers need the main thread and are
    skipped by the server itself)."""
    from distributed_llm_inference_tpu.config import ServingConfig, TraceConfig
    from distributed_llm_inference_tpu.serving import ApiServer, EngineBackend

    scfg = ServingConfig(host="127.0.0.1", port=0, default_timeout_s=600.0)
    backend = EngineBackend(engine, idle_sleep_s=scfg.idle_sleep_s)
    server = ApiServer(backend, scfg, trace_cfg=TraceConfig())
    ready = threading.Event()
    thread = threading.Thread(
        target=server.serve_forever,
        kwargs={"ready_cb": lambda _port: ready.set()},
        name="api-server", daemon=True,
    )
    thread.start()
    try:
        if not ready.wait(timeout=60.0):
            raise CheckFailed("api server did not bind")
        yield server, backend
    finally:
        server.request_shutdown()
        thread.join(timeout=120.0)


def record_steps(engine) -> dict:
    """Note the argument shapes of every jitted engine step the rounds
    dispatch (first call per signature), for ``phase_steps``. The calls go
    through untouched."""
    import jax

    recorded = {}
    steps = {
        "prefill": "_prefill", "prefill_fresh": "_prefill_fresh",
        "prefill_chunk": "_prefill_ns", "prefill_batch": "_prefill_batch",
        "decode_scan": "_decode_k", "decode": "_decode",
    }

    def spec(x):
        if isinstance(x, jax.Array):
            # an uncommitted array follows the others, as in the live call
            where = x.sharding if x.committed else None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where)
        return x

    def recorder(name, fn):
        # under a mesh the engine wraps its jitted step (functools.wraps)
        jitted = fn if hasattr(fn, "lower") else fn.__wrapped__

        @functools.wraps(fn)
        def call(*args):
            key = (name, str(jax.tree.map(
                lambda x: getattr(x, "shape", type(x).__name__), args[1:]
            )))
            if key not in recorded:
                recorded[key] = (jitted, jax.tree.map(spec, args))
            return fn(*args)

        return call

    for name, attr in steps.items():
        if getattr(engine, attr) is not None:   # no fresh program here
            setattr(engine, attr, recorder(name, getattr(engine, attr)))
    return recorded


def phase_serve(size: Size, seed: int, engine, cfg, log: CompileLog) -> dict:
    prompts = [
        seeded_prompt(seed, i, n, cfg.vocab_size)
        for i, n in enumerate(size.prompts)
    ]
    chunk = engine.plan.chunk_tokens
    check(
        "engine_takes_the_chip_default_path",
        engine.plan.enabled and engine.cache.use_ragged
        and engine.cache.use_kernel and engine._pipelined,
        ragged=engine.plan.enabled, use_ragged=engine.cache.use_ragged,
        use_kernel=engine.cache.use_kernel, chunk_tokens=chunk,
        decode_steps=engine.decode_steps, pipelined=engine._pipelined,
    )
    check(
        "round_has_prompts_over_one_chunk",
        sum(n > chunk for n in size.prompts) >= 2 and len(prompts) >= 6,
    )
    rounds = []
    with serving(engine) as (server, backend):
        conn, resp = _request(server.port, "GET", "/healthz", timeout=30.0)
        health = json.loads(resp.read())
        conn.close()
        check("healthz", resp.status == 200 and health["status"] == "ok",
              body=health)
        for n in (1, 2):
            mark, t0 = log.mark(), time.monotonic()
            results = run_round(server.port, backend, size, prompts)
            wall = time.monotonic() - t0
            for r in results:
                emit({"round": n, "request": {
                    k: v for k, v in r.items() if k != "tokens"
                }, "completion_tokens": len(r.get("tokens", []))})
            ok = all(
                r.get("status") == 200
                and len(r.get("tokens", [])) == r["max_tokens"]
                and r.get("finish_reason") == "length"
                and all(0 <= t < cfg.vocab_size for t in r["tokens"])
                for r in results
            )
            check(f"round{n}_every_reply_complete", ok,
                  driver_alive=backend._thread.is_alive())
            check(f"round{n}_six_were_admitted_together",
                  results[0]["queued_together"] == len(results) - 1)
            check(f"round{n}_sse_stream_terminated",
                  results[0].get("sse_done") is True)
            # A greedy stream may fall into a fixed point (the tiny model's
            # do for some seeds); tied logits would do it to every stream.
            distinct = [len(set(r["tokens"])) for r in results]
            check(
                f"round{n}_streams_are_not_one_repeated_token",
                sum(d > 1 for d in distinct) > len(distinct) // 2,
                distinct=distinct,
            )
            rounds.append({"results": results, "wall_s": wall,
                           **log.since(mark)})
            emit({"round": n, "smoke_reading_wall_s": round(wall, 2),
                  **log.since(mark)})
        check("round2_repeats_round1_tokens",
              [r["tokens"] for r in rounds[0]["results"]]
              == [r["tokens"] for r in rounds[1]["results"]])
        check("round2_compiles_nothing",
              rounds[1]["compile_requests"] == 0,
              compiled=rounds[1]["compiled"])
        conn, resp = _request(server.port, "GET", "/metrics", timeout=30.0)
        counters = _prom_counters(resp.read().decode())
        conn.close()
    received = sum(
        len(r["tokens"]) for rnd in rounds for r in rnd["results"]
    )
    n_req = sum(len(rnd["results"]) for rnd in rounds)
    want = {
        "http_requests": n_req,
        "sessions_submitted": n_req,
        "gateway_tokens": received,
        # the first token of every request comes out of its prefill
        "decode_tokens": received - n_req,
        "prefill_tokens": 2 * sum(size.prompts),
    }
    got = {k: counters.get(k) for k in want}
    check("prometheus_counters_match_client", got == want, got=got, want=want)
    check(
        "chunked_prefill_rode_the_decode_cadence",
        counters.get("attn_chunked_rows", 0) >= 2,
        attn_chunked_rows=counters.get("attn_chunked_rows", 0),
        attn_ragged_dispatches=counters.get("attn_ragged_dispatches"),
        cache_growths=counters.get("cache_growths"),
        admission_order_errors=counters.get("admission_order_errors", 0),
    )
    # the in-place sweep's scale rows (none while the table is under
    # INPLACE_CTX, as the rehearsal's always is): a page size of whole
    # tiles leaves the wrapper's gather of every table slot nothing
    check(
        "decode_scale_rows_came_by_the_page",
        counters.get("decode_scale_rows_gathered", 0) == 0,
        decode_scale_rows_by_page=counters.get("decode_scale_rows_by_page", 0),
        decode_pages_live=counters.get("decode_pages_live", 0),
    )
    emit({
        "phase": "serve", "layers": cfg.num_layers,
        "smoke_reading_compile_s": round(rounds[0]["compile_s"], 1),
        "smoke_reading_round1_wall_s": round(rounds[0]["wall_s"], 1),
        "smoke_reading_round2_wall_s": round(rounds[1]["wall_s"], 1),
        "peak_bytes_in_use": device_bytes(),
    })
    return {"prompt": prompts[2], "tokens": rounds[0]["results"][2]["tokens"]}


# --------------------------------------------------------------------------
# phase 3: numerics against XLA attention
# --------------------------------------------------------------------------


def probe(engine, cfg, params, prompt, forced, slots: int, dtype):
    """Logits of ``prompt``'s last position and of ``len(forced) - 1``
    teacher-forced decode steps (step i consumes ``forced[i]`` and emits
    the logits that follow it, whatever they say), through ``engine``'s own
    attention path: a one-row cache of its cache's class, page size and
    kernel flags over a ``slots``-wide table, its prefill pad width, its
    decode program (the fused write-behind scan, or one token a dispatch)
    and its mesh. ``params`` are placed like the engine's."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_inference_tpu.models import llama
    from distributed_llm_inference_tpu.parallel import (
        cache_pspecs, shard_pytree,
    )

    like = engine.cache
    pages = -(-(len(prompt) + len(forced)) // like.page_size)
    cache = type(like).create(
        cfg.num_layers, 1, pages + 1, like.page_size, slots,
        cfg.num_kv_heads, cfg.head_dim, dtype,
        use_kernel=like.use_kernel, use_ragged=like.use_ragged,
    ).assign_pages(0, list(range(1, pages + 1)))
    pad_to = engine.plan.final_shape(len(prompt), engine.plan.buckets[-1])
    tokens = jnp.zeros((1, pad_to), jnp.int32).at[0, : len(prompt)].set(
        jnp.asarray(prompt, jnp.int32)
    )
    forced = jnp.asarray(forced, jnp.int32)
    one = jnp.ones((1,), jnp.int32)

    def run(params, tokens, forced, cache):
        first, cache = llama.model_apply(
            cfg, params, tokens, cache, len(prompt) * one, head="last"
        )
        if engine.decode_steps > 1:
            steps, _ = llama.multi_decode_apply(
                cfg, params, forced[:1][None], cache, forced.shape[0] - 1,
                lambda i, logits, st: (forced[i + 1][None], one, st, logits),
                jnp.zeros(()), one,
            )
            return first[0, 0], steps[:, 0]

        def token(cache, tok):
            logits, cache = llama.model_apply(
                cfg, params, tok[None, None], cache, one
            )
            return cache, logits[0, 0]

        return first[0, 0], jax.lax.scan(token, cache, forced[:-1])[1]

    if engine.mesh is None:
        return jax.device_get(jax.jit(run)(params, tokens, forced, cache))
    cache = shard_pytree(cache, engine.mesh, cache_pspecs(cache))
    with engine.mesh:
        return jax.device_get(jax.jit(run)(params, tokens, forced, cache))


def as_close_to_float32(name, ours, theirs, gold, **info) -> None:
    """The check both comparisons share. ``ours`` may sit no farther from
    the float32 logits ``gold`` than ``theirs`` does (a quarter more, plus
    0.01 for the float32 rehearsal, where that distance is zero), and
    ``theirs`` itself must be nearer to ``gold`` than to noise.

    Why not compare the two directly, or their greedy tokens: with seeded
    random weights bf16 activations alone put XLA attention 0.07 of the
    logits' norm from float32 after one layer and 0.35 after 32, growing
    as sqrt(depth) (my chip run, PR 21); any two bf16 paths differ by as
    much, and their greedy streams part at the first near-tie (token 5 of
    33 between tp=4 and one chip). A path that computed the wrong thing
    sits farther out, toward the sqrt(2) of unrelated logits."""
    import numpy as np

    def rel(x, y):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        return float(np.linalg.norm(x - y) / np.linalg.norm(y))

    def far(side, ref):
        return {"prefill": round(rel(side[0], ref[0]), 4), "decode_max": round(
            max(rel(x, y) for x, y in zip(side[1], ref[1])), 4)}

    o, t = far(ours, gold), far(theirs, gold)
    agree = int(np.sum(np.argmax(ours[1], -1) == np.argmax(theirs[1], -1)))
    check(
        name,
        all(np.all(np.isfinite(x)) for x in ours)
        and all(o[k] <= 1.25 * t[k] + 0.01 and t[k] < 0.7 for k in o),
        ours_vs_float32=o, theirs_vs_float32=t,
        ours_vs_theirs=far(ours, theirs),
        decode_argmax_agree=f"{agree}/{len(ours[1])}", **info,
    )


def phase_fresh_prefill(cfg, engine, wide, seed: int, dtype) -> None:
    """The engine's two prefill programs for a FRESH one-piece prompt, a pad
    width each: ``_prefill_fresh`` (the model over a scratch dense cache,
    the K/V installed as whole pages) beside ``_prefill`` (the row's page
    table: what every row took before there was a fresh program), on the
    engine's own mesh, from copies of its own cache. The pages each leaves
    and the greedy token come out of the engine's executables; they return
    no logits, so those come from the two programs' bodies written out here
    (as ``probe`` writes out the table path's), and the yardstick for both
    is the table path with float32 weights over a float32 pool (``wide``):
    the fresh program may sit no farther from it than the table program
    does, in the logits and in the pages (``as_close_to_float32``'s rule;
    two valid bf16 programs are about as far from each other as each is
    from float32, so they are not compared directly)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.cache.dense import DenseKVCache
    from distributed_llm_inference_tpu.engine.sampling import SamplingParams
    from distributed_llm_inference_tpu.models import llama
    from distributed_llm_inference_tpu.parallel import (
        cache_pspecs, shard_pytree,
    )

    check("engine_has_the_fresh_row_program", engine._prefill_fresh is not None)
    ps, row = engine.cache.page_size, 0
    sp, key = SamplingParams.create(1), jax.random.PRNGKey(seed)

    def table(params, tokens, cache, n_valid):
        sub = cache.select_row(row)
        logits, sub = llama.model_apply(
            cfg, params, tokens, sub, n_valid[None], head="last"
        )
        return logits[0, 0], cache.merge_row(sub, row)

    def fresh(params, tokens, cache, n_valid):
        layers, _, kv_heads, _, head_dim = cache.k_pages.shape
        scratch = DenseKVCache.create(
            layers, 1, tokens.shape[1], kv_heads, head_dim, cache.k_pages.dtype
        )
        logits, scratch = llama.model_apply(
            cfg, params, tokens, scratch, n_valid[None], head="last"
        )
        sub = cache.select_row(row).ingest_row(scratch.k, scratch.v, n_valid)
        return logits[0, 0], cache.merge_row(sub, row)

    def rel(x, y):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        return float(np.linalg.norm(x - y) / np.linalg.norm(y))

    def held(cache, n):
        """The row's K and V as ``[2, L, n, Hkv * D]``, and whether the
        row's length and table are the prompt's and every page past its run
        is as the pool was made (zero)."""
        need = -(-n // ps)
        out = []
        ok = int(cache.lengths[row]) == n and np.array_equal(
            np.asarray(cache.page_table[row, :need]), np.arange(1, need + 1)
        )
        for plane in (cache.k_pages, cache.v_pages):
            a = np.asarray(jax.device_get(plane[:, 1:need + 1]), np.float32)
            a = np.swapaxes(a, 2, 3).reshape(a.shape[0], need * ps, -1)
            out.append(a[:, :n])
            ok = ok and not bool(jnp.any(plane[:, need + 1:]))
        return np.stack(out), ok

    engine._ensure_capacity(engine.ecfg.max_seq_len)
    like = engine.cache
    slots = like.page_table.shape[1]
    for index, bucket in enumerate(engine.plan.buckets):
        n = bucket * 3 // 4 + 1             # a ragged last page
        prompt = seeded_prompt(seed, 10 + index, n, cfg.vocab_size)
        check("the_prompt_pads_to_its_bucket",
              engine.plan.final_shape(n, engine._max_chunk()) == bucket)
        tokens = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
            jnp.asarray(prompt, jnp.int32)
        )
        need, n_valid = -(-n // ps), jnp.int32(n)

        def pool(dt, pages):
            """A zeroed pool like the engine's, the row's pages assigned."""
            made = type(like).create(
                cfg.num_layers, like.lengths.shape[0], pages, ps, slots,
                cfg.num_kv_heads, cfg.head_dim, dt,
                use_kernel=like.use_kernel, use_ragged=like.use_ragged,
            ).assign_pages(row, list(range(1, need + 1)))
            return shard_pytree(made, engine.mesh, cache_pspecs(made))

        with engine.mesh:
            # the engine's executables, over a pool of its own pool's shape
            # (they donate the cache they are given)
            own = like.k_pages.shape[1]
            tok_f, cache = engine._prefill_fresh(
                engine.params, tokens, pool(dtype, own), row, n_valid, key, sp
            )
            kv_f, ok_f = held(cache, n)
            tok_t, cache = engine._prefill(
                engine.params, tokens, pool(dtype, own), row, n_valid, key, sp
            )
            kv_t, ok_t = held(cache, n)
            small = need + 2
            log_f = jax.jit(fresh)(
                engine.params, tokens, pool(dtype, small), n_valid
            )[0]
            log_t = jax.jit(table)(
                engine.params, tokens, pool(dtype, small), n_valid
            )[0]
            with jax.default_matmul_precision("highest"):
                gold, cache = jax.jit(table)(
                    wide, tokens, pool(jnp.float32, small), n_valid
                )
            kv_gold, _ = held(cache, n)
            del cache
        far = {
            "logits": (rel(log_f, gold), rel(log_t, gold), rel(log_f, log_t)),
            "pages": (rel(kv_f, kv_gold), rel(kv_t, kv_gold), rel(kv_f, kv_t)),
        }
        check(
            f"fresh_prefill_as_close_to_float32_as_the_table_path_{bucket}",
            np.all(np.isfinite(np.asarray(log_f, np.float32)))
            and all(f <= 1.25 * t + 0.01 and t < 0.7
                    for f, t, _ in far.values())
            and ok_f and ok_t
            # layer 0's V is the same sums in both programs: value for value
            and np.array_equal(kv_f[1, 0], kv_t[1, 0]),
            prompt_tokens=n, pad_width=bucket, table_slots=slots,
            **{f"{k}_fresh_table_between": [round(x, 4) for x in v]
               for k, v in far.items()},
            pages_between_by_layer={
                int(l): round(rel(kv_f[:, l], kv_t[:, l]), 4)
                for l in sorted({0, 1, cfg.num_layers // 2, cfg.num_layers - 1})
            },
            greedy_tokens=[int(tok_f), int(tok_t), int(np.argmax(gold))],
        )


def phase_numerics(size: Size, cfg, params, engine, served: dict,
                   slots: int) -> None:
    import jax
    import jax.numpy as jnp

    prompt, forced = served["prompt"], served["tokens"][:17]
    reference = build_engine(size, cfg, params, xla_attention=True)
    check(
        "reference_engine_takes_xla_attention",
        not reference.plan.enabled and not reference.cache.use_ragged
        and not reference.cache.use_kernel,
    )
    dtype = jnp.dtype(size.dtype)
    kern = probe(engine, cfg, params, prompt, forced, slots, dtype)
    xla = probe(reference, cfg, params, prompt, forced, slots, dtype)
    # The yardstick: XLA attention again with every activation in float32
    # (same int8 weights, same int8 K/V).
    wide = jax.tree.map(
        lambda x: x.astype(jnp.float32) if x.dtype == dtype else x, params
    )
    gold = probe(reference, cfg, wide, prompt, forced, slots, jnp.float32)
    as_close_to_float32(
        "kernel_logits_as_close_to_float32_as_xla_attention",
        kern, xla, gold, ours_is="ragged + fused Pallas kernels",
        theirs_is="XLA attention (use_pallas_attention=False, "
                  "ragged_attention=False)",
        layers=cfg.num_layers, depth_cut=False, prompt_tokens=len(prompt),
        decode_steps=len(forced) - 1, table_slots=slots,
        prefill_argmax_is_served_token=int(kern[0].argmax()) == forced[0],
    )


def flash_guard_check(cfg, size: Size) -> None:
    """``flash_attention`` hands shapes its tiling refuses to XLA without a
    word. It serves the legacy int8 prefill from 1024 tokens up
    (``cache/base.py``); at this model's widths the guard must not fire."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.ops import flash_attention as fa

    delegated = []
    inner = fa.gqa_attention

    def counting(*a, **k):
        delegated.append(1)
        return inner(*a, **k)

    s, t = (2048, 4096) if size is FULL else (32, 64)
    dtype = jnp.dtype(size.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, s, cfg.num_heads, cfg.head_dim), dtype)
    k, v = (
        jax.random.normal(kk, (1, t, cfg.num_kv_heads, cfg.head_dim), dtype)
        for kk in keys[1:]
    )
    mask = (jnp.arange(t)[None, :] <= jnp.arange(s)[:, None] + (t - s))[None]
    fa.gqa_attention = counting
    try:
        out = jax.jit(fa.flash_attention)(q, k, v, mask)
    finally:
        fa.gqa_attention = inner
    ref = jax.jit(inner)(q, k, v, mask)
    err = float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - ref.astype(jnp.float32)
    )))
    check("flash_attention_kept_its_kernel", not delegated and err < 0.05,
          s=s, t=t, delegated_to_xla=len(delegated),
          max_abs_err=round(err, 5),
          finite=bool(np.all(np.isfinite(np.asarray(out, np.float32)))))


# --------------------------------------------------------------------------
# phase 4: what is in the compiled steps
# --------------------------------------------------------------------------


def phase_steps(engine, recorded: dict, on_chip: bool, want_all_reduce=False):
    for (name, shapes), (jitted, args) in recorded.items():
        with engine.mesh if engine.mesh is not None else contextlib.nullcontext():
            text = jitted.lower(*args).compile().as_text()
        kernel = "tpu_custom_call" in text
        emit({"step": name, "args": shapes[:160], "tpu_custom_call": kernel,
              "all_reduce": "all-reduce" in text})
        if on_chip and not want_all_reduce:
            check(f"{name}_holds_a_pallas_kernel", kernel)
        if want_all_reduce:
            check(f"{name}_holds_all_reduces", "all-reduce" in text)
    check("steps_recorded", len(recorded) >= 2, steps=len(recorded))


# --------------------------------------------------------------------------
# the one-chip run and the four-chip run
# --------------------------------------------------------------------------


def run_one_chip(size: Size, seed: int, log: CompileLog, on_chip: bool):
    import jax

    phase_load(size, seed)
    cfg = model_config(size)
    t0 = time.monotonic()
    params = seeded_params(cfg, seed, jax.numpy.dtype(size.dtype), True)
    jax.block_until_ready(params)
    engine = build_engine(size, cfg, params)
    emit({"phase": "build", "layers": cfg.num_layers,
          "weights": "int8 stored form, seeded", "kv": "int8 paged",
          "smoke_reading_wall_s": round(time.monotonic() - t0, 1),
          "bytes_in_use": device_bytes("bytes_in_use")})
    recorded = record_steps(engine)
    served = phase_serve(size, seed, engine, cfg, log)
    flash_guard_check(cfg, size)
    # Both probes at the widest page table the rounds decoded over (the
    # idle engine has shrunk its own back).
    slots = max(args[2].page_table.shape[1] for _, args in recorded.values())
    phase_numerics(size, cfg, params, engine, served, slots)
    phase_steps(engine, recorded, on_chip)


def run_four_chips(size: Size, seed: int, log: CompileLog, on_chip: bool):
    """tp=4 over the host's four chips against one chip, both at the depth
    one chip holds in bf16: a prefill and 32 decode steps through
    ``engine.generate``, then the mesh engine's two prefill programs for a
    fresh prompt (``phase_fresh_prefill``). No other phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.config import MeshConfig
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.parallel import (
        param_pspecs, shard_pytree,
    )

    cfg = model_config(size, size.tp_layers)
    dtype = jnp.dtype(size.dtype)
    # Host copies: each engine places them itself (tp=4 straight to shards).
    params = jax.device_get(seeded_params(cfg, seed, dtype, stored_int8=False))
    prompt = seeded_prompt(seed, 0, size.prompts[3], cfg.vocab_size)
    opts = SamplingOptions(max_new_tokens=33)  # 1 from prefill + 32 decode
    emit({"phase": "tp4", "layers": cfg.num_layers, "depth_cut": True,
          "depth_reason": "what one chip holds in bf16", "dtype": size.dtype})

    def run(mesh_cfg):
        engine = build_engine(size, cfg, params, mesh_cfg=mesh_cfg,
                              kv_quant=None)
        recorded = record_steps(engine)
        mark, t0 = log.mark(), time.monotonic()
        tokens = engine.generate([prompt], opts)[0]
        emit({"tp": mesh_cfg.tp if mesh_cfg else 1,
              "attention": {
                  "ragged": engine.plan.enabled,
                  "use_ragged": engine.cache.use_ragged,
                  "pallas_decode": engine.cache.use_kernel,
                  "overlaps_admission": engine._overlap_ok(),
              },
              "decode_steps": engine.decode_steps, "tokens": tokens,
              "smoke_reading_wall_s": round(time.monotonic() - t0, 1),
              **log.since(mark)})
        check("stream_is_whole",
              len(tokens) == 33 and len(set(tokens)) > 1
              and all(0 <= t < cfg.vocab_size for t in tokens))
        return engine, recorded, tokens

    engine, recorded, sharded = run(MeshConfig(tp=4))
    leaves = engine.params["layers"]
    held = {d.id: 0 for d in jax.devices()}
    total = 0
    for name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
        total += leaves[name].nbytes
        for shard in leaves[name].addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    in_use = device_bytes("bytes_in_use")
    live = [b for b in in_use if b]
    check(
        "every_device_holds_a_quarter_of_the_sharded_leaves",
        all(abs(b - total / 4) <= 0.02 * total for b in held.values())
        and (not on_chip or (len(live) == 4 and max(live) < 1.5 * min(live))),
        sharded_leaf_bytes=total, held_per_device=held,
        bytes_in_use_per_device=in_use,
    )
    phase_steps(engine, recorded, on_chip, want_all_reduce=True)
    slots = max(args[2].page_table.shape[1] for _, args in recorded.values())
    forced = sharded[:17]
    on_mesh = probe(engine, cfg, engine.params, prompt, forced, slots, dtype)
    # The yardstick: the same mesh program with float32 weights (which one
    # chip cannot hold at this depth).
    wide = jax.tree.map(lambda x: np.asarray(x, np.float32), params)
    wide = shard_pytree(wide, engine.mesh, param_pspecs(wide))
    gold = probe(engine, cfg, wide, prompt, forced, slots, jnp.float32)
    phase_fresh_prefill(cfg, engine, wide, seed, dtype)
    del engine, leaves, wide
    engine, _, single = run(None)
    one_chip = probe(engine, cfg, engine.params, prompt, forced, slots, dtype)
    same = next(
        (i for i, (a, b) in enumerate(zip(sharded, single)) if a != b), 33
    )
    as_close_to_float32(
        "tp4_logits_as_close_to_float32_as_one_chip",
        on_mesh, one_chip, gold, ours_is="tp=4, XLA attention",
        theirs_is="one chip, ragged + Pallas paged kernels",
        layers=cfg.num_layers, prompt_tokens=len(prompt),
        greedy_streams_common_prefix=f"{same}/33",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for smoke.jsonl (a copy of stdout)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny-size walk of the same control flow on the "
                         "CPU, kernels interpreted; never a chip result")
    args = ap.parse_args(argv)

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={args.chips}"]
        )
    try:
        import distributed_llm_inference_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the program is not here ({e})", file=sys.stderr)
        return NO_CHIP
    import jax

    from distributed_llm_inference_tpu.utils.compile_cache import (
        cache_entries, enable_compile_cache,
    )

    platform = jax.devices()[0].platform
    want = "cpu" if args.rehearse_cpu else "tpu"
    if platform != want or len(jax.devices()) < args.chips:
        print(
            f"chip_smoke: need {args.chips} {want} device(s), JAX found "
            f"{len(jax.devices())} {platform}; not carrying on"
            + ("" if args.rehearse_cpu else " (--rehearse-cpu walks the "
               "control flow on the CPU)"),
            file=sys.stderr,
        )
        return NO_CHIP
    device = {"platform": platform, "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    size = TINY if args.rehearse_cpu else FULL
    if args.rehearse_cpu:
        # The plan asks the platform which kernels to take; the rehearsal
        # answers for the chip so the same branches run, interpreted.
        from distributed_llm_inference_tpu.engine import engine as engine_mod
        from distributed_llm_inference_tpu.engine.plan import AttentionPlan

        engine_mod.AttentionPlan = functools.partial(
            AttentionPlan, backend="tpu"
        )

    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    log = CompileLog()
    try:
        import jaxlib
        import flax
        import numpy

        try:
            from importlib.metadata import version

            libtpu = version("libtpu")
        except Exception:
            libtpu = None
        emit({"chip_smoke": "rehearsal-cpu" if args.rehearse_cpu else "chip",
              "seed": args.seed, "chips": args.chips, "device": device,
              "versions": {"jax": jax.__version__,
                           "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                           "flax": flax.__version__,
                           "numpy": numpy.__version__},
              "compile_cache": {
                  "dir": cache_dir,
                  "placed_by_env": bool(
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")),
                  "entries_before": entries_before}})
        t0 = time.monotonic()
        if args.chips == 4:
            run_four_chips(size, args.seed, log, not args.rehearse_cpu)
        else:
            run_one_chip(size, args.seed, log, not args.rehearse_cpu)
        emit({"compile_cache": {"dir": cache_dir,
                                "entries_before": entries_before,
                                "entries_after": cache_entries(cache_dir)},
              "process": {"compile_requests": log.requests,
                          "persistent_cache_hits": log.hits,
                          "compiled_here": log.requests - log.hits,
                          "smoke_reading_compile_s": round(log.seconds, 1),
                          "smoke_reading_wall_s": round(
                              time.monotonic() - t0, 1)},
              "peak_bytes_in_use": device_bytes()})
    except CheckFailed as e:
        print(f"chip_smoke: FAILED at {e}", file=sys.stderr)
        return CHECK_FAILED
    finally:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "smoke.jsonl"), "w") as f:
            f.write("\n".join(OUT_LINES) + "\n")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
