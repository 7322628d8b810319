#!/usr/bin/env python3
"""benchmark/server.py — the process that holds the cell's chip or chips.

Started by ``benchmark/run.py`` and by nothing else. It makes the cell's
weights on the device from ``--seed``, builds ``InferenceEngine`` →
``EngineBackend`` → ``ApiServer.serve_forever`` as ``cli.cmd_api``'s tail
does (the configuration file's ``EngineConfig`` / ``CacheConfig`` /
``MeshConfig`` fields, every other field at its default, the flight recorder
on as ``distribute api`` has it), checks the served path's logits against the
benchmark's plain reference, and then serves. The parent speaks to the
gateway over localhost HTTP like any client, and to this process over
stdin/stdout, one JSON object a line:

    {"cmd": "open"}         the window opens: mark compilations, start
                            sampling the page pool
    {"cmd": "trace_start"}  start a jax.profiler trace (--trace 1 only)
    {"cmd": "trace_stop"}   stop it
    {"cmd": "close"}        the window closed: reply with what only this
                            process can know (compilations in the window,
                            the engine's own TTFT readings, pool and device
                            memory peaks, the reduced trace)
    {"cmd": "quit"}         stop serving and exit

Without ``--rehearse-cpu`` it refuses to run unless JAX finds a TPU with the
chips the cell asks for.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import statistics
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_CHIP = 2
#: what a configuration file carries for the harness; every other top-level
#: key is the published ``config.json``'s
HARNESS_KEYS = (
    "name", "source", "reduced", "assumed", "deployment", "serve", "correct",
    "rehearse",
)


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def load_config(path: str, rehearse: bool) -> dict:
    """The configuration as run: the file, with its ``rehearse`` block laid
    over it for the CPU rehearsal (tiny sizes, same control flow)."""
    with open(path) as f:
        conf = json.load(f)
    if rehearse:
        tiny = conf["rehearse"]
        conf.update({k: v for k, v in tiny.items() if k not in ("serve", "correct")})
        conf["serve"] = {**conf["serve"], **tiny["serve"]}
        conf["correct"] = tiny["correct"]
    return conf


def hf_block(conf: dict) -> dict:
    """The published block, whole: what ``ModelConfig.from_hf_config`` and
    the plain reference both receive. A family's own keys (latent ranks,
    windows by layer, shared experts) need no list here."""
    return {k: v for k, v in conf.items() if k not in HARNESS_KEYS}


class CompileLog:
    """What JAX's own monitoring reports: every backend compile request (one
    per executable the process did not hold yet; a persistent-cache hit is
    still a request, and still stalls the step that waits for it)."""

    def __init__(self):
        import jax

        self.events = []            # (time.monotonic(), fun_name, seconds)
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(
                (time.monotonic(), kw.get("fun_name", "?"), seconds)
            )

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def between(self, lo: float, hi: float) -> list:
        return [(n, round(s, 3)) for t, n, s in self.events if lo <= t <= hi]


def probe_cache(like, pages: int, slots: int, dtype):
    """A one-row cache as ``like``, the engine's own, says of itself: its
    class, its pool's layers, head count and stored width (``k_pages`` is
    ``[layers, pages, heads, page size, width]`` in every paged class: 8 x
    128 for Mistral's K and V, 1 x ``lat_dim`` for a latent pool), its page
    size and kernel flags, over a ``slots``-wide table with ``pages`` pages
    assigned (page 0 stays the null page)."""
    layers, _, heads, page_size, width = like.k_pages.shape
    return type(like).create(
        layers, 1, pages + 1, page_size, slots, heads, width, dtype,
        use_kernel=like.use_kernel, use_ragged=like.use_ragged,
    ).assign_pages(0, list(range(1, pages + 1)))


def probe(engine, cfg, params, prompt, forced, slots: int, dtype):
    """Logits of ``prompt``'s last position and of ``len(forced) - 1``
    teacher-forced decode steps (step i consumes ``forced[i]``), through the
    engine's own attention path: a one-row cache as its cache describes
    itself (``probe_cache``) over a ``slots``-wide table, its prefill pad
    width, its decode program (the fused write-behind scan, or one token a
    dispatch) and its mesh. As ``chip_smoke.probe`` (PR 21), copied so that
    the yardstick does not move with that file."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_inference_tpu.models import llama
    from distributed_llm_inference_tpu.parallel import cache_pspecs, shard_pytree

    pages = -(-(len(prompt) + len(forced)) // engine.cache.page_size)
    cache = probe_cache(engine.cache, pages, slots, dtype)
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


def judged_numbers(dist: list, judge: str) -> list:
    """What of the positions' distances (the prefill's, then each decode
    step's) is held to the tolerance. ``"each"``: the prefill position and
    the median of the decode steps, each. ``"third_least"``, for routed
    models: the third smallest of all positions. A token that rounding sends
    to another expert than float32 does moves that token's logits as far as
    unrelated ones and says nothing of the path; how many of the 17 do so
    goes from 2 to 9 with the seed, and the median with it (0.23 to 0.75,
    my chip runs, PR 25: no limit holds it apart from a path in int4, which
    reads 1.03). The positions least disturbed read the path's precision: a
    lower precision, a wrong mask or a dropped term moves every position
    (the decode steps read the K and V the prefill wrote), so it moves these
    too. The third and not the least, so that no single position decides."""
    if judge == "third_least":
        return [sorted(dist)[2]]
    if judge == "each":
        return [dist[0], statistics.median(dist[1:])]
    raise ValueError(f"correct.judge {judge!r}: each or third_least")


def check_numerics(conf: dict, cfg, engine, seed: int) -> dict:
    """``correct``, part (b): the served path's logits against the plain
    reference in float32 at highest matmul precision, over the same stored
    weights. Logits, never tokens: with seeded weights the largest logit
    changes on rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.ops.quant import QuantizedTensor

    want = conf["correct"]
    rng = np.random.default_rng([seed, 11])
    n, steps = int(want["probe_prompt_tokens"]), int(want["decode_steps"])
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
    forced = [int(t) for t in rng.integers(1, cfg.vocab_size, size=steps + 1)]
    ps = engine.ccfg.page_size
    slots = -(-(n + steps + 1) // ps) + 1
    t0 = time.monotonic()
    first, decoded = probe(
        engine, cfg, engine.params, prompt, forced, slots,
        jnp.dtype(conf["serve"]["dtype"]),
    )
    t1 = time.monotonic()
    reference = importlib.import_module(
        f"benchmark.reference.{conf['serve']['reference']}"
    )
    plain = jax.tree.map(
        lambda x: {"q": x.q, "scale": x.scale}
        if isinstance(x, QuantizedTensor) else x,
        engine.params, is_leaf=lambda x: isinstance(x, QuantizedTensor),
    )
    hf = hf_block(conf)
    tokens = jnp.asarray(prompt + forced[:-1], jnp.int32)
    with jax.default_matmul_precision("highest"):
        gold = np.asarray(jax.device_get(jax.jit(
            lambda p, t: reference.forward(hf, p, t)[n - 1:]
        )(plain, tokens)), np.float64)
    ours = np.concatenate([np.asarray(first)[None], np.asarray(decoded)]).astype(np.float64)

    def rel(x, y):
        return float(np.linalg.norm(x - y) / np.linalg.norm(y))

    dist = [rel(o, g) for o, g in zip(ours, gold)]
    # what unrelated logits would read: the reference against itself, one
    # position off
    unrelated = rel(gold[1], gold[0])
    decode_median = float(np.median(dist[1:]))
    judged = judged_numbers(dist, want.get("judge", "each"))
    ok = bool(np.all(np.isfinite(ours))) and max(judged) <= want["tolerance"]
    return {
        "ok": ok, "judged": judged, "prefill": dist[0],
        "decode_median": decode_median,
        "decode_max": max(dist[1:]), "decode": [round(d, 4) for d in dist[1:]],
        "tolerance": want["tolerance"], "unrelated": unrelated,
        "probe_s": t1 - t0, "reference_s": time.monotonic() - t1,
        "layers": cfg.num_layers, "prompt_tokens": n, "decode_steps": steps,
    }


class PoolSampler(threading.Thread):
    """Least ``free_count`` of the page allocator while the window is open."""

    def __init__(self, allocator):
        super().__init__(name="pool-sampler", daemon=True)
        self.allocator, self.least = allocator, None
        self._stop_evt = threading.Event()

    def run(self):
        while not self._stop_evt.wait(0.02):
            free = self.allocator.free_count
            self.least = free if self.least is None else min(self.least, free)

    def stop(self):
        self._stop_evt.set()
        self.join(timeout=5.0)


def device_memory() -> dict:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return {
        "peak_bytes": max((s.get("peak_bytes_in_use", 0) for s in stats), default=0),
        "in_use_bytes": [s.get("bytes_in_use") for s in stats],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, required=True, choices=(1, 4))
    ap.add_argument("--out", required=True, help="the run's output directory")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    started = time.monotonic()

    sys.path.insert(0, REPO)
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
        print(f"benchmark: the program is not here ({e})", file=sys.stderr)
        return NO_CHIP
    import jax
    import jax.numpy as jnp

    from distributed_llm_inference_tpu.config import (
        CacheConfig, EngineConfig, MeshConfig, ModelConfig, ServingConfig,
        TraceConfig,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.serving import ApiServer, EngineBackend
    from distributed_llm_inference_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    devices = jax.devices()
    want = "cpu" if args.rehearse_cpu else "tpu"
    if devices[0].platform != want or len(devices) < args.chips:
        print(
            f"benchmark: need {args.chips} {want} device(s), JAX found "
            f"{len(devices)} {devices[0].platform}; not carrying on",
            file=sys.stderr,
        )
        return NO_CHIP
    if args.rehearse_cpu:
        # The plan asks the platform which kernels to take; the rehearsal
        # answers for the chip so the same branches run, interpreted.
        import functools

        from distributed_llm_inference_tpu.engine import engine as engine_mod
        from distributed_llm_inference_tpu.engine.plan import AttentionPlan

        engine_mod.AttentionPlan = functools.partial(AttentionPlan, backend="tpu")
    cache_dir = enable_compile_cache()
    log = CompileLog()
    conf = load_config(args.config, args.rehearse_cpu)
    serve = conf["serve"]
    cfg = ModelConfig.from_hf_config(hf_block(conf))
    dtype = jnp.dtype(serve["dtype"])
    mesh_cfg = MeshConfig(**serve["mesh"]) if serve.get("mesh") else None
    mesh = None
    if mesh_cfg is not None:
        from distributed_llm_inference_tpu.parallel import build_mesh

        mesh = build_mesh(mesh_cfg)

    t_imports = time.monotonic() - started
    t0 = time.monotonic()
    maker = importlib.import_module(f"benchmark.weights.{serve['weight_maker']}")
    params = maker.make(cfg, args.seed, dtype, serve["weights"], mesh=mesh)
    jax.block_until_ready(params)
    t_weights = time.monotonic() - t0

    t0 = time.monotonic()
    ekw = dict(serve["engine"])
    if "prefill_buckets" in ekw:
        ekw["prefill_buckets"] = tuple(ekw["prefill_buckets"])
    engine = InferenceEngine(
        cfg, params, EngineConfig(dtype=serve["dtype"], **ekw),
        CacheConfig(**serve["cache"]), mesh_cfg=mesh_cfg,
        trace_cfg=TraceConfig(),
    )
    del params
    t_engine = time.monotonic() - t0
    numerics = check_numerics(conf, cfg, engine, args.seed)

    scfg = ServingConfig(host="127.0.0.1", port=0, model_name=conf["name"])
    backend = EngineBackend(engine, idle_sleep_s=scfg.idle_sleep_s)
    server = ApiServer(backend, scfg, trace_cfg=TraceConfig())
    bound = threading.Event()
    port = []
    thread = threading.Thread(
        target=server.serve_forever, name="api-server", daemon=True,
        kwargs={"ready_cb": lambda p: (port.append(p), bound.set())},
    )
    thread.start()
    if not bound.wait(timeout=60.0):
        print("benchmark: the api server did not bind", file=sys.stderr)
        return 1

    from distributed_llm_inference_tpu.cache.base import window_ladder

    cc = engine.ccfg
    ladder = window_ladder(
        min(engine.ecfg.max_seq_len, cc.max_pages_per_session * cc.page_size),
        custom=engine.ecfg.decode_windows, strict=False,
    )
    emit({
        "event": "ready", "port": port[0],
        "device": {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "shapes": {
            "page_size": cc.page_size, "num_pages": cc.num_pages,
            "table_slots": sorted({-(-w // cc.page_size) for w in ladder}),
            "pad_widths": (
                [engine.plan.chunk_tokens] if engine.plan.enabled
                else list(engine.plan.buckets)
            ),
            "chunk_tokens": engine.plan.chunk_tokens,
            "max_batch_size": engine.batch, "max_seq_len": engine.ecfg.max_seq_len,
            "decode_steps": engine.decode_steps, "vocab_size": cfg.vocab_size,
            "ragged": engine.plan.enabled, "pallas": engine.cache.use_kernel,
            "tp": mesh_cfg.tp if mesh_cfg else 1,
            "group_admission": mesh_cfg is None,
        },
        "numerics": numerics,
        "setup": {
            "imports_s": t_imports, "weights_s": t_weights,
            "engine_s": t_engine, "compile_cache": cache_dir,
            "compile_requests": len(log.events), "cache_hits": log.hits,
            "compile_s": sum(s for _, _, s in log.events),
        },
    })

    sampler, opened, trace_dir, engine_ttft0 = None, None, None, 0
    traced = []                 # epoch seconds: the trace's start and stop

    def ttft_readings():
        # the summary's own list: /metrics gives its quantiles only over
        # the whole life of the process, warm-up included
        with engine.metrics._lock:
            return list(engine.metrics._timings.get("engine_ttft", ()))

    for line in sys.stdin:
        cmd = json.loads(line).get("cmd")
        if cmd == "open":
            opened = time.monotonic()
            engine_ttft0 = len(ttft_readings())
            sampler = PoolSampler(engine.allocator)
            sampler.start()
            emit({"reply": "open"})
        elif cmd == "trace_start":
            trace_dir = os.path.join(args.out, "trace")
            # a second trace of one window replaces an empty first one
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            traced[:] = [time.time()]
            emit({"reply": "trace_start"})
        elif cmd == "trace_stop":
            traced.append(time.time())
            jax.profiler.stop_trace()
            from benchmark.reduce import xplane

            # which part of the trace is missing, if any: the parent takes
            # the trace once more while the window still runs, and prints
            # no result line for a traced run that has no trace
            emit({
                "reply": "trace_stop",
                "missing": xplane.trace_missing(trace_dir),
            })
        elif cmd == "close":
            closed = time.monotonic()
            sampler.stop()
            reply = {
                "reply": "close",
                "compiles_in_window": log.between(opened, closed),
                "compile_requests": len(log.events), "cache_hits": log.hits,
                "compile_s": sum(s for _, _, s in log.events),
                "engine_ttft_s": ttft_readings()[engine_ttft0:],
                "pool_free_least": sampler.least,
                "num_pages": cc.num_pages,
                "memory": device_memory(),
            }
            if trace_dir is not None:
                from benchmark.reduce import xplane

                files = xplane.trace_files(trace_dir)
                planes = xplane.read_xplane(files[0]) if files else []
                reply["trace"] = xplane.reduce_trace(planes)
                # on the flight recorder's clock, for a reader that takes
                # tick records and kernel events over the same span
                reply["trace_epoch_s"] = traced
                if planes and reply["trace"]:
                    first = min(
                        e[1] for p in planes for ln in p["lines"]
                        for e in ln["events"]
                    )
                    xplane.write_sample(
                        planes, os.path.join(args.out, "trace_sample.json"),
                        first + int(reply["trace"]["window_s"] * 5e8),
                        int(2e7),
                    )
                # the raw trace is tens of MB a chip; its reduction and a
                # 20 ms cut stay
                shutil.rmtree(trace_dir, ignore_errors=True)
            emit(reply)
        elif cmd == "quit":
            break
    server.request_shutdown()
    thread.join(timeout=60.0)
    emit({"event": "exit", "thread_alive": thread.is_alive()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
