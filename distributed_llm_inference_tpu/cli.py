"""``distribute`` — the CLI the reference shipped as a 0-byte placeholder.

(``/root/reference/distribute`` is empty; SURVEY §2.1 row "Launcher".)

Subcommands map onto the deployment roles:

* ``relay``     run the native relay hub + block directory (control plane)
* ``serve``     load a layer block from a checkpoint and serve it as a node
* ``generate``  client: route a prompt through the registered nodes
* ``local``     single-host serving: load a checkpoint into the continuous-
                batching engine and generate (no relay needed)
* ``api``       HTTP gateway: OpenAI-compatible ``/v1/completions`` (JSON +
                SSE streaming) over the local engine, or over the relay
                chain with ``--relay``; ``/metrics`` + ``/healthz`` included
* ``prefill``   disaggregated serving: prefill-pool worker — full model,
                prefill + first token only, ships KV planes to
                ``api --disagg`` gateways over the relay
* ``chaos``     fault-injecting TCP proxy in front of a relay hub: point
                endpoints at its port and replay a seeded failure schedule
* ``trace``     fetch one request's stitched cross-node trace (Chrome
                trace-event JSON) from a gateway's ``/debug/trace/<id>``,
                or the engine flight-recorder ring from ``/debug/ticks``
* ``info``      inspect a checkpoint (config, layer count, shard files)
* ``check``     run the ``tools.distcheck`` static analyzer over the
                package (lock discipline, event-loop lints, PRNG/host-sync
                hygiene, metrics registry, relay-frame schema)

Examples::

    distribute relay --port 18900
    distribute serve --model /ckpt/llama --layers 0:16 --relay :18900
    distribute serve --model /ckpt/llama --layers 16:32 --relay :18900
    distribute generate --model /ckpt/llama --relay :18900 --prompt-ids 1,2,3
    distribute local --model /ckpt/llama --prompt-ids 1,2,3 --max-new 32
    distribute api --model /ckpt/llama --port 8000
    distribute api --model /ckpt/llama --port 8000 --relay :18900
    distribute prefill --model /ckpt/llama --relay :18900
    distribute api --model /ckpt/llama --port 8000 --relay :18900 --disagg
    distribute chaos --upstream :18900 --port 18901 --seed 7 \\
        --fault 'drop:block.*:put:after=5,count=2' --fault 'sever:*:any'
    distribute trace --url http://127.0.0.1:8000 4f2a9c1d3b5e7a90
    distribute trace --url http://127.0.0.1:8000 --ticks
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
from typing import List, Optional, Tuple


def _model_source(args):
    """``(resolve, cache_note)`` for ``--model``: a local snapshot dir uses
    direct path lookup; an ``http(s)://`` URL streams files on demand into
    a local content cache (``utils/hub.py`` — the reference's
    ``cached_file`` hub route, ``utils/model.py:27-34``), so a node
    cold-starts on a fresh host with nothing pre-populated on disk."""
    model = args.model
    if model.startswith(("http://", "https://")):
        import hashlib
        import os

        from .utils.hub import HttpResolver

        root = getattr(args, "weights_cache", None) or os.path.expanduser(
            "~/.cache/distribute"
        )
        slug = hashlib.sha1(model.encode()).hexdigest()[:12]
        cache = os.path.join(root, f"remote-{slug}")
        return HttpResolver(model, cache), cache
    return None, None


def _parse_relay(addr: str) -> Tuple[str, int]:
    host, _, port = addr.rpartition(":")
    if not port.isdigit():
        raise SystemExit(f"--relay {addr!r}: expected host:port (e.g. :18900)")
    return host or "127.0.0.1", int(port)


def _parse_layers(spec: str) -> Tuple[int, int]:
    """``a:b`` half-open (HF style) → inclusive (first, last)."""
    a, _, b = spec.partition(":")
    first, end = int(a), int(b)
    if end <= first:
        raise SystemExit(f"--layers {spec}: end must exceed start")
    return first, end - 1


def _parse_ids(spec: str) -> List[int]:
    return [int(t) for t in spec.replace(" ", "").split(",") if t]


def _resolve_prompt(args) -> Tuple[List[int], Optional[object]]:
    """``(prompt_ids, tokenizer)`` from ``--prompt-ids`` or ``--prompt``
    (the latter tokenizes with the checkpoint's tokenizer via transformers
    and enables text detokenization of the output). Call BEFORE loading
    weights so argument errors are instant. The parser enforces exactly one
    of the two flags."""
    if getattr(args, "prompt", None) is not None:
        try:
            from transformers import AutoTokenizer

            tok = AutoTokenizer.from_pretrained(args.model)
        except Exception as e:
            raise SystemExit(
                f"--prompt needs a loadable tokenizer in {args.model!r}: {e}"
            )
        return tok(args.prompt)["input_ids"], tok
    if getattr(args, "prompt_ids", None) is None:
        raise SystemExit("one of --prompt / --prompt-ids is required")
    return _parse_ids(args.prompt_ids), None


def cmd_relay(args) -> int:
    from .distributed.directory import DirectoryService
    from .distributed.relay import RelayServer

    server = RelayServer(args.port)
    service = DirectoryService(server.port, default_ttl=args.lease_ttl)
    print(json.dumps({"event": "relay_up", "port": server.port}), flush=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        service.stop()
        server.stop()
    return 0


def cmd_serve(args) -> int:
    import jax.numpy as jnp

    from .distributed.worker import ServingNode
    from .utils import checkpoint

    host, port = _parse_relay(args.relay)
    resolve, _ = _model_source(args)
    cfg = checkpoint.load_config(args.model, resolve=resolve)
    if args.layers is not None:
        first, last = _parse_layers(args.layers)
    else:
        # Directory-driven self-selection (the reference's "choose optimal
        # block ids" intent, server/server.py:8): ask which layers the
        # deployment needs most — a dead node's lapsed lease re-opens its
        # range, so a spare started with NO --layers auto-adopts the hole.
        # Resolved BEFORE loading weights: the node then streams only its
        # assigned block.
        from .distributed.directory import DirectoryClient

        with DirectoryClient(port, host) as d:
            # Reserve the range while the (possibly minutes-long) weight
            # load runs, so concurrent spares spread across holes.
            first, last = d.assign(
                cfg.num_layers, args.max_layers, reserve_ttl=600.0
            )
        print(json.dumps({
            "event": "layers_assigned", "first_layer": first,
            "last_layer": last,
        }), flush=True)
    params = checkpoint.load_block_params(
        args.model, cfg, list(range(first, last + 1)),
        jnp.dtype(args.dtype), resolve=resolve, cache_dir=args.weights_cache,
    )
    from .config import CacheConfig, MeshConfig

    mesh_cfg = MeshConfig(tp=args.tp) if args.tp > 1 else None
    cache_cfg = CacheConfig(
        kind=args.cache, kv_quant=args.kv_quant,
        window_length=args.sink_window, num_sink_tokens=args.sink_tokens,
        page_size=args.page_size, num_pages=args.num_pages,
    )
    node = ServingNode(
        port, cfg, params["layers"], first, last, host=host,
        node_id=args.node_id, max_sessions=args.max_sessions,
        max_seq_len=args.max_seq_len, dtype=jnp.dtype(args.dtype),
        quantize=args.quantize, cache_cfg=cache_cfg, mesh_cfg=mesh_cfg,
    )
    print(json.dumps({
        "event": "node_up", "node_id": node.node_id, "queue": node.queue,
        "layers": [first, last],
    }), flush=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    try:
        while not stop and node.is_healthy():
            time.sleep(0.2)
    finally:
        node.stop()
    return 0


def cmd_prefill(args) -> int:
    """Run a prefill-pool worker for disaggregated serving: a full-model
    engine that only ever prefills prompts (+ samples the first token) and
    ships the resulting KV planes to ``api --disagg`` gateways."""
    import jax.numpy as jnp

    from .config import CacheConfig, DisaggConfig, EngineConfig
    from .disagg.prefill_worker import PrefillWorker
    from .engine.engine import InferenceEngine
    from .utils import checkpoint

    host, port = _parse_relay(args.relay)
    resolve, _ = _model_source(args)
    cfg = checkpoint.load_config(args.model, resolve=resolve)
    params = checkpoint.load_model_params(
        args.model, cfg, jnp.dtype(args.dtype), resolve=resolve,
        cache_dir=args.weights_cache,
    )
    engine = InferenceEngine(
        cfg, params,
        EngineConfig(
            max_batch_size=args.max_sessions, max_seq_len=args.max_seq_len,
            dtype=args.dtype, quantization=args.quantize,
        ),
        # The cache config MUST match the decode pool's (quantized KV ships
        # as stored int8+scales; the gateway rejects a quantization
        # mismatch at admission).
        CacheConfig(kind=args.cache, kv_quant=args.kv_quant,
                    page_size=args.page_size, num_pages=args.num_pages),
    )
    worker = PrefillWorker(
        port, engine, host=host, node_id=args.node_id,
        disagg_cfg=DisaggConfig(kv_frame_bytes=args.kv_frame_bytes),
        lease_ttl=args.lease_ttl,
    )
    print(json.dumps({
        "event": "prefill_up", "node_id": worker.node_id,
        "queue": worker.queue,
    }), flush=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    try:
        while not stop and worker.is_healthy():
            time.sleep(0.2)
    finally:
        worker.stop()
    return 0


def cmd_generate(args) -> int:
    import jax.numpy as jnp

    from .distributed.client import DistributedClient
    from .utils import checkpoint

    host, port = _parse_relay(args.relay)
    prompt, tok = _resolve_prompt(args)
    resolve, _ = _model_source(args)
    cfg = checkpoint.load_config(args.model, resolve=resolve)
    params = checkpoint.load_client_params(
        args.model, cfg, jnp.dtype(args.dtype), resolve=resolve
    )
    with DistributedClient(
        port, cfg, params, host=host, dtype=jnp.dtype(args.dtype)
    ) as client:
        deadline = time.monotonic() + args.route_wait
        while True:
            try:
                client.plan_route()
                break
            except LookupError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.3)
        out = client.generate(
            prompt, max_new_tokens=args.max_new, eos_token_id=args.eos
        )
    doc = {"event": "generated", "prompt": prompt, "tokens": out}
    if tok is not None:
        doc["text"] = tok.decode(out)
    print(json.dumps(doc), flush=True)
    return 0


def cmd_local(args) -> int:
    import jax.numpy as jnp

    from .config import CacheConfig, EngineConfig, TraceConfig
    from .engine.engine import InferenceEngine
    from .engine.sampling import SamplingOptions
    from .utils import checkpoint

    prompt, tok = _resolve_prompt(args)
    if args.speculative_draft and args.temperature:
        raise SystemExit("--speculative-draft is greedy-only "
                         "(remove --temperature)")
    resolve, _ = _model_source(args)
    cfg = checkpoint.load_config(args.model, resolve=resolve)
    params = checkpoint.load_model_params(
        args.model, cfg, jnp.dtype(args.dtype), resolve=resolve,
        cache_dir=args.weights_cache,
    )
    from .utils.tracing import profile_trace

    extra = {}
    t0 = time.monotonic()
    draft = None
    if args.speculative_draft:
        dcfg = checkpoint.load_config(args.speculative_draft)
        dparams = checkpoint.load_model_params(
            args.speculative_draft, dcfg, jnp.dtype(args.dtype),
            cache_dir=args.weights_cache,
        )
        draft = (dcfg, dparams)
    engine = InferenceEngine(
        cfg, params,
        EngineConfig(
            max_batch_size=args.max_sessions, max_seq_len=args.max_seq_len,
            max_new_tokens=args.max_new, dtype=args.dtype,
            quantization=args.quantize or ("int8" if args.int8 else None),
            speculative_k=args.speculative_k if draft else 0,
            decode_steps=args.decode_steps,
        ),
        CacheConfig(kind=args.cache, kv_quant=args.kv_quant),
        draft=draft,
        # with a profile asked for, the flight recorder is on: the trace's
        # host plane then carries every tick (``engine_tick``, step_num =
        # the tick's id) and its phases, and the ticks are written beside it
        trace_cfg=TraceConfig(ticks_capacity=100_000)
        if args.profile_dir else None,
    )
    if engine.flight is not None:
        # whoever asks for a profile is watching: the ticks written beside
        # it carry the dispatch clock, for ``xplane_profile.py --ticks``
        engine.flight.clock.lease(float("inf"))
    with profile_trace(args.profile_dir):
        out = engine.generate(
            [prompt],
            SamplingOptions(
                temperature=args.temperature, max_new_tokens=args.max_new,
                eos_token_id=args.eos if args.eos is not None else -1,
                speculative=draft is not None,
            ),
        )[0]
    if args.profile_dir:
        with open(os.path.join(args.profile_dir, "ticks.json"), "w") as f:
            json.dump({"ticks": engine.flight.snapshot()}, f)
    extra["metrics"] = engine.metrics.snapshot()
    if draft is not None:
        st = engine.spec_stats
        extra["speculative"] = {
            **st,
            "acceptance_rate": round(
                st["accepted"] / max(st["proposed"], 1), 4
            ),
        }
    doc = {
        "event": "generated", "prompt": prompt, "tokens": out,
        "seconds": round(time.monotonic() - t0, 3), **extra,
    }
    if tok is not None:
        doc["text"] = tok.decode(out)
    print(json.dumps(doc), flush=True)
    return 0


def cmd_api(args) -> int:
    import jax.numpy as jnp

    from .config import (
        CacheConfig,
        DisaggConfig,
        EngineConfig,
        SchedConfig,
        ServingConfig,
        TraceConfig,
    )
    from .serving import ApiServer, ClientBackend, DisaggBackend, EngineBackend
    from .utils import checkpoint

    if args.disagg and not args.relay:
        raise SystemExit("--disagg needs --relay (the prefill pool and the "
                         "KV transfer both ride the relay hub)")

    tokenizer = None
    if args.tokenizer:
        try:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(args.tokenizer)
        except Exception as e:
            raise SystemExit(
                f"--tokenizer {args.tokenizer!r} failed to load: {e}"
            )
    resolve, _ = _model_source(args)
    cfg = checkpoint.load_config(args.model, resolve=resolve)
    scfg = ServingConfig(
        host=args.host, port=args.port,
        max_queue_depth=args.max_queue_depth,
        default_timeout_s=args.timeout,
        drain_timeout_s=args.drain_timeout,
        model_name=args.model,
        breaker_failure_threshold=args.breaker_failures,
        breaker_recovery_s=args.breaker_recovery,
        breaker_probe_interval_s=args.breaker_probe_interval,
    )
    sched_cfg = None
    if args.sched:
        weights = []
        for spec in args.sched_weight or []:
            tenant, _, w = spec.partition("=")
            try:
                weights.append((tenant, float(w)))
            except ValueError:
                raise SystemExit(
                    f"--sched-weight {spec!r}: expected TENANT=WEIGHT"
                )
        sched_cfg = SchedConfig(
            rate_tokens_per_s=args.sched_rate,
            burst_tokens=args.sched_burst,
            weights=tuple(weights),
            batch_share=args.sched_batch_share,
            shed_headroom=args.sched_shed_headroom,
            max_lane_depth=args.sched_max_lane_depth,
        )
    trace_cfg = None if args.no_trace else TraceConfig(
        trace_sample_rate=args.trace_sample_rate,
    )
    if args.disagg:
        # Disaggregated serving: the local engine is the DECODE pool
        # member; prompt prefill routes to role="prefill" workers (the
        # ``prefill`` subcommand) through the relay, with local-prefill
        # fallback when the pool is empty or a transfer fails.
        from .engine.engine import InferenceEngine

        host, port = _parse_relay(args.relay)
        params = checkpoint.load_model_params(
            args.model, cfg, jnp.dtype(args.dtype), resolve=resolve,
            cache_dir=args.weights_cache,
        )
        engine = InferenceEngine(
            cfg, params,
            EngineConfig(
                max_batch_size=args.max_sessions,
                max_seq_len=args.max_seq_len, dtype=args.dtype,
                quantization=args.quantize,
            ),
            CacheConfig(kind=args.cache, kv_quant=args.kv_quant),
            trace_cfg=trace_cfg,
        )
        backend = DisaggBackend(
            engine, port, relay_host=host,
            disagg_cfg=DisaggConfig(
                kv_frame_bytes=args.kv_frame_bytes,
                transfer_timeout_s=args.transfer_timeout,
            ),
            idle_sleep_s=scfg.idle_sleep_s,
            sched_cfg=sched_cfg,
        )
    elif args.relay:
        from .distributed.client import DistributedClient

        host, port = _parse_relay(args.relay)
        params = checkpoint.load_client_params(
            args.model, cfg, jnp.dtype(args.dtype), resolve=resolve
        )
        client = DistributedClient(
            port, cfg, params, host=host, dtype=jnp.dtype(args.dtype)
        )
        backend = ClientBackend(
            client, request_timeout_s=args.timeout,
            batch_max=args.client_batch,
            batch_window_s=args.client_batch_window,
        )
    else:
        from .engine.engine import InferenceEngine

        params = checkpoint.load_model_params(
            args.model, cfg, jnp.dtype(args.dtype), resolve=resolve,
            cache_dir=args.weights_cache,
        )
        engine = InferenceEngine(
            cfg, params,
            EngineConfig(
                max_batch_size=args.max_sessions,
                max_seq_len=args.max_seq_len, dtype=args.dtype,
                quantization=args.quantize,
            ),
            CacheConfig(kind=args.cache, kv_quant=args.kv_quant),
            trace_cfg=trace_cfg,
        )
        backend = EngineBackend(engine, idle_sleep_s=scfg.idle_sleep_s)
    server = ApiServer(backend, scfg, tokenizer=tokenizer,
                       sched_cfg=sched_cfg, trace_cfg=trace_cfg)
    server.serve_forever(ready_cb=lambda port: print(
        json.dumps({"event": "api_up", "port": port}), flush=True
    ))
    return 0


def cmd_chaos(args) -> int:
    """Stand a fault-injecting proxy in front of a relay hub. Point the
    endpoints under test (``serve``/``generate``/``api --relay``) at the
    proxy's port; the seeded plan makes the failure sequence replayable —
    same seed + same faults + same traffic = same injections (reported as
    JSON events and in a final summary on shutdown)."""
    from .distributed.chaos import ChaosProxy, FaultPlan

    host, port = _parse_relay(args.upstream)
    plan = FaultPlan.from_specs(args.fault or [], seed=args.seed)
    proxy = ChaosProxy(host, port, port=args.port, plan=plan)
    print(json.dumps({
        "event": "chaos_up", "port": proxy.port,
        "upstream": f"{host}:{port}", "seed": args.seed,
        "faults": args.fault or [],
    }), flush=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    seen = 0
    try:
        while not stop:
            time.sleep(0.2)
            injected = plan.injected[seen:]
            seen += len(injected)
            for kind, queue, op in injected:
                print(json.dumps({
                    "event": "fault_injected", "kind": kind,
                    "queue": queue, "op": op,
                }), flush=True)
    finally:
        proxy.stop()
        print(json.dumps({
            "event": "chaos_down", "injected": len(plan.injected),
        }), flush=True)
    return 0


def cmd_fleet(args) -> int:
    """One-shot elastic-fleet operations against a running relay +
    decode pool: inspect the pool, drain-then-fence one node (its
    in-flight streams live-migrate off with zero token loss), or run a
    single hot-node rebalance pass."""
    from .config import FleetConfig
    from .fleet import FleetController

    host, port = _parse_relay(args.relay)
    ctl = FleetController(
        port, host,
        fleet_cfg=FleetConfig(drain_timeout_s=args.drain_timeout),
    )
    try:
        if args.action == "status":
            print(json.dumps(ctl.status(), indent=2))
        elif args.action == "drain":
            if not args.node:
                print("fleet drain: a node id is required", file=sys.stderr)
                return 2
            print(json.dumps(ctl.drain(args.node)))
        else:  # rebalance
            print(json.dumps({"migrations": ctl.rebalance_once()}))
    except LookupError as e:
        print(f"fleet {args.action}: {e}", file=sys.stderr)
        return 1
    finally:
        ctl.close()
    return 0


def cmd_trace(args) -> int:
    """Fetch a stitched cross-node trace (``/debug/trace/<id>``, Chrome
    trace-event JSON — load it in ``chrome://tracing`` or Perfetto) or the
    engine flight-recorder ring (``/debug/ticks``) from a running
    gateway. The trace id is the ``X-Trace-Id`` header every sampled
    completion response carries."""
    import urllib.error
    import urllib.request

    base = args.url.rstrip("/")
    if not args.ticks and not args.trace_id:
        print("trace: a trace id is required (or pass --ticks)",
              file=sys.stderr)
        return 2
    path = "/debug/ticks" if args.ticks else f"/debug/trace/{args.trace_id}"
    try:
        with urllib.request.urlopen(base + path, timeout=args.timeout) as r:
            body = r.read().decode()
    except urllib.error.HTTPError as e:
        print(f"trace: {base + path} -> {e.code} {e.reason}",
              file=sys.stderr)
        return 1
    except (urllib.error.URLError, OSError) as e:
        print(f"trace: {base + path} unreachable: {e}", file=sys.stderr)
        return 1
    if args.out:
        with open(args.out, "w") as f:
            f.write(body)
        print(json.dumps({"event": "trace_written", "path": args.out,
                          "bytes": len(body)}), flush=True)
    elif args.ticks:
        for line in _ticks_lines(json.loads(body)):
            print(line, flush=True)
    else:
        print(body, flush=True)
    return 0


def _ticks_lines(doc: dict) -> List[str]:
    """A ``/debug/ticks`` body as ``trace --ticks`` prints it, one JSON
    object a line: the ticks, then the programs the process loaded with the
    most load seconds first, then the boot marks."""
    programs = sorted(
        doc.get("programs", {}).items(),
        key=lambda kv: -sum(v for k, v in kv[1].items() if k.endswith("_s")),
    )
    return [
        json.dumps({"ticks": doc["ticks"]}),
        json.dumps({"programs": dict(programs)}),
        json.dumps({"boot": doc.get("boot")}),
    ]


def cmd_info(args) -> int:
    from .models import registry
    from .utils import checkpoint

    resolve, _ = _model_source(args)
    cfg = checkpoint.load_config(args.model, validate=False, resolve=resolve)
    try:
        registry.validate_config(cfg)
        supported = True
    except (KeyError, ValueError):
        supported = False
    resolve = resolve or checkpoint._default_resolve(args.model)
    entry = checkpoint.find_index(resolve)
    print(json.dumps({
        "model": args.model, "entry": entry, "family": cfg.family,
        "num_layers": cfg.num_layers, "hidden_size": cfg.hidden_size,
        "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
        "vocab_size": cfg.vocab_size, "num_experts": cfg.num_experts,
        "sliding_window": cfg.sliding_window, "supported": supported,
    }, indent=2))
    return 0


def cmd_check(args) -> int:
    # tools/ lives at the repo root, one level above this package; when
    # running from an installed copy without tools/ the gate cannot run,
    # so say so instead of crashing.
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.path.isfile(os.path.join(
            repo_root, "tools", "distcheck", "core.py")):
        print("distribute check: tools/distcheck not found "
              f"(looked under {repo_root}); run from a source checkout",
              file=sys.stderr)
        return 2
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from tools.distcheck.__main__ import main as distcheck_main

    argv = list(args.paths)
    if args.no_baseline:
        argv.append("--no-baseline")
    if args.json:
        argv.append("--json")
    if args.changed is not None:
        argv.extend(["--changed", args.changed])
    return distcheck_main(argv)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="distribute",
        description="TPU-native distributed LLM inference launcher",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    r = sub.add_parser("relay", help="run the relay hub + block directory")
    r.add_argument("--port", type=int, default=0)
    r.add_argument("--lease-ttl", type=float, default=10.0)
    r.set_defaults(fn=cmd_relay)

    s = sub.add_parser("serve", help="serve a layer block from a checkpoint")
    s.add_argument("--model", required=True)
    s.add_argument("--layers", default=None,
                   help="half-open range, e.g. 0:16; omit to let the "
                        "DIRECTORY assign the most-needed range (gap fill "
                        "first, thinnest replication otherwise)")
    s.add_argument("--max-layers", type=int, default=None,
                   help="cap on a directory-assigned range (default: the "
                        "whole model)")
    s.add_argument("--relay", required=True, help="host:port of the relay")
    s.add_argument("--node-id", default=None)
    s.add_argument("--max-sessions", type=int, default=8)
    s.add_argument("--max-seq-len", type=int, default=512)
    s.add_argument("--dtype", default="bfloat16")
    s.add_argument("--weights-cache", default=None,
                   help="directory for pre-converted weight caching "
                        "(skips HF-layout conversion on repeat bring-up)")
    s.add_argument("--quantize", default=None, choices=("int8", "int4"),
                   help="serve this block with quantized weights")
    s.add_argument("--kv-quant", default=None, choices=("int8",),
                   help="store this node's KV cache int8")
    s.add_argument("--cache", default="dense",
                   choices=("dense", "sink", "paged"),
                   help="this node's KV storage: dense growth-ladder, "
                        "StreamingLLM sink ring (unbounded streams, fixed "
                        "memory), or vLLM-style paged pool")
    s.add_argument("--sink-window", type=int, default=1024,
                   help="sink ring length (--cache sink)")
    s.add_argument("--sink-tokens", type=int, default=4,
                   help="always-kept sink tokens (--cache sink)")
    s.add_argument("--page-size", type=int, default=64,
                   help="tokens per page (--cache paged)")
    s.add_argument("--num-pages", type=int, default=512,
                   help="page pool size (--cache paged)")
    s.add_argument("--tp", type=int, default=1,
                   help="shard this node's block over N local chips "
                        "(tensor parallel within the node; the relay "
                        "protocol is unchanged)")
    s.set_defaults(fn=cmd_serve)

    pf = sub.add_parser(
        "prefill",
        help="disaggregated serving: prefill-pool worker (full model, "
             "prefill + first token only; ships KV to api --disagg)",
    )
    pf.add_argument("--model", required=True)
    pf.add_argument("--relay", required=True, help="host:port of the relay")
    pf.add_argument("--node-id", default=None)
    pf.add_argument("--lease-ttl", type=float, default=10.0)
    pf.add_argument("--max-sessions", type=int, default=8)
    pf.add_argument("--max-seq-len", type=int, default=2048)
    pf.add_argument("--dtype", default="bfloat16")
    pf.add_argument("--quantize", default=None,
                    choices=("int8", "int4", "int8_outlier"))
    pf.add_argument("--cache", default="paged", choices=("paged", "dense"),
                    help="must match the decode pool (sink caches can't "
                         "export whole-prompt KV)")
    pf.add_argument("--kv-quant", default=None, choices=("int8",),
                    help="must match the decode pool's KV quantization")
    pf.add_argument("--page-size", type=int, default=64)
    pf.add_argument("--num-pages", type=int, default=512)
    pf.add_argument("--kv-frame-bytes", type=int, default=4 * 1024 * 1024,
                    help="max relay frame payload for shipped KV planes")
    pf.add_argument("--weights-cache", default=None,
                    help="directory for pre-converted weight caching")
    pf.set_defaults(fn=cmd_prefill)

    g = sub.add_parser("generate", help="generate through registered nodes")
    g.add_argument("--model", required=True)
    g.add_argument("--relay", required=True)
    gp = g.add_mutually_exclusive_group(required=True)
    gp.add_argument("--prompt-ids", default=None, help="comma-separated ids")
    gp.add_argument("--prompt", default=None,
                    help="text prompt (tokenized with the model's tokenizer)")
    g.add_argument("--max-new", type=int, default=16)
    g.add_argument("--eos", type=int, default=None)
    g.add_argument("--dtype", default="bfloat16")
    g.add_argument("--route-wait", type=float, default=15.0,
                   help="seconds to wait for full layer coverage")
    g.set_defaults(fn=cmd_generate)

    l = sub.add_parser("local", help="single-host engine generate")
    l.add_argument("--model", required=True)
    lp = l.add_mutually_exclusive_group(required=True)
    lp.add_argument("--prompt-ids", default=None)
    lp.add_argument("--prompt", default=None,
                    help="text prompt (tokenized with the model's tokenizer)")
    l.add_argument("--max-new", type=int, default=16)
    l.add_argument("--eos", type=int, default=None)
    l.add_argument("--temperature", type=float, default=0.0)
    l.add_argument("--cache", default="paged",
                   choices=("paged", "dense", "sink"))
    l.add_argument("--int8", action="store_true")
    l.add_argument("--quantize", default=None,
                   choices=("int8", "int4", "int8_outlier"))
    l.add_argument("--kv-quant", default=None, choices=("int8",),
                   help="int8 KV cache (dense/paged): halves KV HBM "
                        "traffic; on TPU the dense kind also unlocks the "
                        "fused Pallas decode kernel (the headline path)")
    l.add_argument("--max-sessions", type=int, default=8)
    l.add_argument("--max-seq-len", type=int, default=2048)
    l.add_argument("--dtype", default="bfloat16")
    l.add_argument("--weights-cache", default=None,
                   help="directory for pre-converted weight caching")
    l.add_argument("--decode-steps", type=int, default=None,
                   help="fused decode steps per dispatch (tokens stream "
                        "every K steps; big throughput win on TPU). Default: "
                        "auto — 16 where the fused tail path composes, else 1")
    l.add_argument("--speculative-draft", default=None,
                   help="draft model checkpoint dir: greedy speculative "
                        "decoding (same tokenizer/vocab as --model)")
    l.add_argument("--speculative-k", type=int, default=4)
    l.add_argument("--profile-dir", default=None,
                   help="dump a jax.profiler trace (device planes, and the "
                        "engine's ticks and phases on the host plane) and "
                        "the flight recorder's tick records (ticks.json; "
                        "tick id = the trace's step_num) into this directory")
    l.set_defaults(fn=cmd_local)

    a = sub.add_parser(
        "api",
        help="HTTP gateway: OpenAI-compatible /v1/completions (+SSE), "
             "/metrics, /healthz",
    )
    a.add_argument("--model", required=True)
    a.add_argument("--host", default="0.0.0.0")
    a.add_argument("--port", type=int, default=8000,
                   help="0 = ephemeral (bound port printed in api_up)")
    a.add_argument("--relay", default=None,
                   help="host:port of a relay: serve through the "
                        "distributed chain instead of a local engine")
    a.add_argument("--disagg", action="store_true",
                   help="with --relay: disaggregated prefill/decode — the "
                        "local engine decodes, admission routes prompts to "
                        "``prefill`` workers and imports their shipped KV "
                        "(falls back to local prefill on any failure)")
    a.add_argument("--transfer-timeout", type=float, default=30.0,
                   help="with --disagg: seconds to wait for a prefill "
                        "worker's KV frames before falling back locally")
    a.add_argument("--kv-frame-bytes", type=int, default=4 * 1024 * 1024,
                   help="with --disagg: max relay frame payload requested "
                        "for shipped KV planes")
    a.add_argument("--client-batch", type=int, default=0,
                   help="with --relay: group up to N admitted requests "
                        "into one batched decode loop (generate_many) so "
                        "they share stacked frames and device calls; 0 = "
                        "one generation per thread")
    a.add_argument("--client-batch-window", type=float, default=0.01,
                   help="seconds the request collector lingers from the "
                        "first admitted request of a group")
    a.add_argument("--tokenizer", default=None,
                   help="tokenizer checkpoint dir: enables string prompts "
                        "and decoded text in responses")
    a.add_argument("--max-queue-depth", type=int, default=64,
                   help="gateway-in-flight bound; beyond it requests get "
                        "429 + Retry-After")
    a.add_argument("--timeout", type=float, default=120.0,
                   help="default per-request deadline seconds (body "
                        "timeout_s overrides)")
    a.add_argument("--drain-timeout", type=float, default=30.0,
                   help="SIGTERM drain budget before in-flight requests "
                        "are cancelled")
    a.add_argument("--breaker-failures", type=int, default=5,
                   help="consecutive backend failures that open the "
                        "circuit breaker (503 + Retry-After while open)")
    a.add_argument("--breaker-recovery", type=float, default=5.0,
                   help="seconds the breaker stays open before admitting "
                        "half-open trial traffic")
    a.add_argument("--breaker-probe-interval", type=float, default=1.0,
                   help="backend health-probe period seconds (0 disables)")
    a.add_argument("--max-sessions", type=int, default=8)
    a.add_argument("--max-seq-len", type=int, default=2048)
    a.add_argument("--dtype", default="bfloat16")
    a.add_argument("--cache", default="paged",
                   choices=("paged", "dense", "sink"))
    a.add_argument("--kv-quant", default=None, choices=("int8",))
    a.add_argument("--quantize", default=None,
                   choices=("int8", "int4", "int8_outlier"))
    a.add_argument("--weights-cache", default=None,
                   help="directory for pre-converted weight caching")
    a.add_argument("--sched", action="store_true",
                   help="enable the multi-tenant admission scheduler "
                        "(sched/): tenant identity + rate limits, "
                        "weighted-fair interactive/batch lanes, "
                        "deadline-aware shedding")
    a.add_argument("--sched-rate", type=float, default=0.0,
                   help="per-tenant token budget refill rate "
                        "(prompt+max_tokens per second; 0 = no rate limit)")
    a.add_argument("--sched-burst", type=float, default=0.0,
                   help="per-tenant token-bucket burst capacity "
                        "(0 = 2 seconds of --sched-rate)")
    a.add_argument("--sched-weight", action="append", default=None,
                   metavar="TENANT=W",
                   help="per-tenant fair-share weight (repeatable); "
                        "unlisted tenants get weight 1.0")
    a.add_argument("--sched-batch-share", type=float, default=0.125,
                   help="fraction of admissions reserved for the batch "
                        "lane under interactive pressure (0 = strict "
                        "priority, batch may starve)")
    a.add_argument("--sched-shed-headroom", type=float, default=1.0,
                   help="shed a request at admission when its estimated "
                        "TTFT exceeds headroom * remaining deadline "
                        "(0 disables shedding)")
    a.add_argument("--sched-max-lane-depth", type=int, default=256,
                   help="pending tickets allowed per lane before "
                        "queue-full 429s")
    a.add_argument("--trace-sample-rate", type=float, default=1.0,
                   help="fraction of requests minted a distributed-trace "
                        "context (X-Trace-Id response header; stitched "
                        "trace at /debug/trace/<id>)")
    a.add_argument("--no-trace", action="store_true",
                   help="disable distributed tracing AND the engine "
                        "flight recorder entirely (no recorder "
                        "allocation, /debug routes 404)")
    a.set_defaults(fn=cmd_api)

    c = sub.add_parser(
        "chaos",
        help="fault-injecting TCP proxy in front of a relay hub "
             "(replayable seeded failure schedules)",
    )
    c.add_argument("--upstream", required=True,
                   help="host:port of the real relay hub")
    c.add_argument("--port", type=int, default=0,
                   help="port to listen on (0 = ephemeral, printed in "
                        "chaos_up)")
    c.add_argument("--seed", type=int, default=0,
                   help="seeds probabilistic rules and corrupt-byte choice")
    c.add_argument("--fault", action="append", default=None,
                   metavar="KIND:QUEUE:OP[:K=V,...]",
                   help="repeatable fault spec, e.g. "
                        "'drop:block.*:put:after=5,count=2', "
                        "'corrupt:client.*:reply', 'delay:*:any:"
                        "delay_s=0.2,prob=0.3,count=none'; kinds: drop, "
                        "delay, duplicate, truncate, corrupt, sever, crash "
                        "(crash = whole-node death after N matched frames: "
                        "severs every connection through the proxy and "
                        "refuses reconnects, so heartbeats stop too and "
                        "the node's directory lease expires)")
    c.set_defaults(fn=cmd_chaos)

    fl = sub.add_parser(
        "fleet",
        help="elastic decode-pool control: status / drain (live-migrate "
             "a node's sessions off, then fence it) / rebalance "
             "(migrate sessions off hot nodes)",
    )
    fl.add_argument("action", choices=("status", "drain", "rebalance"))
    fl.add_argument("node", nargs="?", default=None,
                    help="node id to drain (drain action only)")
    fl.add_argument("--relay", required=True, help="host:port of the relay")
    fl.add_argument("--drain-timeout", type=float, default=15.0,
                    help="seconds to wait for the drained node's load to "
                         "reach zero before fencing anyway (stragglers "
                         "re-home via crash recovery, still exactly-once)")
    fl.set_defaults(fn=cmd_fleet)

    tr = sub.add_parser(
        "trace",
        help="fetch a stitched cross-node request trace (Chrome "
             "trace-event JSON) or the flight-recorder tick ring from a "
             "gateway's debug endpoints",
    )
    tr.add_argument("trace_id", nargs="?", default=None,
                    help="trace id (the X-Trace-Id response header)")
    tr.add_argument("--url", required=True,
                    help="gateway base URL, e.g. http://127.0.0.1:8000")
    tr.add_argument("--ticks", action="store_true",
                    help="fetch /debug/ticks (per-tick engine flight "
                         "recorder) instead of a trace")
    tr.add_argument("--out", default=None,
                    help="write the JSON here instead of stdout")
    tr.add_argument("--timeout", type=float, default=10.0)
    tr.set_defaults(fn=cmd_trace)

    i = sub.add_parser("info", help="inspect a checkpoint")
    i.add_argument("--model", required=True)
    i.set_defaults(fn=cmd_info)

    k = sub.add_parser(
        "check",
        help="run the distcheck static analyzer (lock discipline, "
             "event-loop lints, PRNG/host-sync hygiene, metrics registry, "
             "frame schema)")
    k.add_argument("paths", nargs="*", default=[],
                   help="files or directories to analyze (default: the "
                        "installed package)")
    k.add_argument("--no-baseline", action="store_true",
                   help="report baselined findings too")
    k.add_argument("--json", action="store_true",
                   help="machine-readable findings (JSON array)")
    k.add_argument("--changed", nargs="?", const="HEAD", default=None,
                   metavar="REF",
                   help="analyze only .py files changed vs a git ref "
                        "(default HEAD)")
    k.set_defaults(fn=cmd_check)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    # Before any command traces: where compiled executables are kept.
    from .utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
