"""Cross-node distributed-tracing e2e suite.

The acceptance surface for the tracing tentpole:

* a disaggregated request (gateway -> prefill worker -> local decode)
  yields ONE stitched trace whose gateway segments (route / kv_transfer /
  admit / decode_wait) account for the measured TTFT;
* a fleet-drain re-homed request yields ONE trace joining the gateway's
  rehome/handoff markers to the node-side decode/handoff spans;
* with tracing disabled (or unsampled) the token stream is byte-exact vs
  the traced run — sampling must never perturb generation;
* ``trace.pull`` against a dead or corrupting node degrades to a partial
  trace within the collect budget — collection never wedges a request
  post-mortem;
* the HTTP surface: ``X-Trace-Id`` on sampled responses,
  ``/debug/trace/<id>`` stitching, ``/debug/ticks`` flight-recorder
  snapshots, recorder depth in ``/healthz`` — and 404/absent-header when
  tracing is off.
"""

import contextlib
import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_inference_tpu.config import (
    CacheConfig,
    DisaggConfig,
    EngineConfig,
    ModelConfig,
    ServingConfig,
    TraceConfig,
)
from distributed_llm_inference_tpu.disagg import DecodeNode, PrefillWorker
from distributed_llm_inference_tpu.distributed.directory import (
    DirectoryService,
)
from distributed_llm_inference_tpu.distributed.relay import (
    RelayServer,
    native_available,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.fleet import FleetController
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.serving import (
    ApiServer,
    DisaggBackend,
    EngineBackend,
    FleetBackend,
)
from distributed_llm_inference_tpu.utils import tracing
from distributed_llm_inference_tpu.utils.tracing import (
    SpanRecorder,
    TraceContext,
    stitch_chrome_trace,
)

needs_native = pytest.mark.skipif(
    not native_available(), reason="g++ unavailable to build the native relay"
)

CFG = ModelConfig(
    vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=16,
)
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

RECOVERY_DCFG = DisaggConfig(
    lease_ttl_s=1.0, checkpoint_interval_ticks=2, resume_max_attempts=2,
)


def make_engine(kind="paged", batch=2, trace_cfg=None):
    return InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=batch, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32"),
        CacheConfig(kind=kind, page_size=8, num_pages=64,
                    max_pages_per_session=8),
        trace_cfg=trace_cfg,
    )


def drain_engine(engine, gid, budget_s=60.0):
    toks = []
    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline:
        for g, tok, fin in engine.step():
            if g != gid:
                continue
            if tok >= 0:
                toks.append(tok)
            if fin:
                engine.collect_finished()
                return toks
        engine.collect_finished()
    raise AssertionError(f"{gid} did not finish within {budget_s}s")


@pytest.fixture
def loop():
    import asyncio

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    yield loop
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=5)


def _traced_stream(backend, loop, prompt, opts, trace=None, timeout=60.0):
    """Stream one request; return (toks, seqs, reason, resumed, ttft_s)
    where ttft is measured wall-clock submit -> first token event."""
    import asyncio

    t0 = time.monotonic()
    h = backend.submit(prompt, opts, deadline=time.monotonic() + timeout,
                       trace=trace)

    async def _drain():
        toks, seqs, resumed, ttft = [], [], 0, None
        while True:
            ev = await asyncio.wait_for(h.queue.get(), timeout=timeout)
            resumed = max(resumed, getattr(ev, "resumed", 0) or 0)
            if ev.token >= 0:
                if ttft is None:
                    ttft = time.monotonic() - t0
                toks.append(ev.token)
                seqs.append(getattr(ev, "seq", len(seqs)))
            if ev.finished:
                return toks, seqs, ev.finish_reason, resumed, ttft

    return asyncio.run_coroutine_threadsafe(_drain(), loop).result(
        timeout=timeout + 30
    )


# -- cross-node stitch: disaggregated prefill ---------------------------------


@needs_native
@pytest.mark.disagg
def test_disagg_request_stitches_single_cross_node_trace(loop):
    """One disagg request = ONE trace: a gateway lane whose segment
    durations account for the measured TTFT, plus the prefill worker's
    ``prefill.export`` lane pulled over the relay."""
    prompt = [1, 2, 3, 4, 5]
    opts = SamplingOptions(max_new_tokens=6)
    base = make_engine().generate([prompt], opts)[0]
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            worker = PrefillWorker(relay.port, make_engine(), node_id="pw1")
            backend = DisaggBackend(
                make_engine(), relay.port,
                disagg_cfg=DisaggConfig(transfer_timeout_s=10.0),
            )
            backend.attach_tracer(SpanRecorder(),
                                  TraceConfig(collect_timeout_s=5.0))
            backend.start(loop)
            try:
                ctx = TraceContext.mint(1.0)
                toks, _, reason, _, ttft = _traced_stream(
                    backend, loop, prompt, opts, trace=ctx)
                assert toks == base and reason == "length"
                assert ttft is not None and ttft > 0
                assert backend.metrics.get_counter(
                    "disagg_fallback_local") == 0  # genuinely cross-node
                node_spans = backend.collect_trace(ctx.trace_id)
                assert set(node_spans) == {"gateway", "pw1"}
                gw = {s["name"]: s for s in node_spans["gateway"]}
                assert {"gateway.route", "gateway.kv_transfer",
                        "gateway.admit",
                        "gateway.decode_wait"} <= set(gw)
                assert any(s["name"] == "prefill.export"
                           for s in node_spans["pw1"])
                for lane in node_spans.values():
                    for s in lane:
                        assert s["trace_id"] == ctx.trace_id
                        assert s["duration_s"] >= 0
                # The gateway segments are sequential and span submit ->
                # first token: their sum must account for the measured
                # TTFT (generous slack: CI jitter, thread handoff).
                total = sum(gw[n]["duration_s"] for n in (
                    "gateway.route", "gateway.kv_transfer",
                    "gateway.admit", "gateway.decode_wait"))
                assert total <= ttft + 0.5, (total, ttft)
                assert total >= 0.3 * ttft, (total, ttft)
                # The worker's export segment nests inside the gateway's
                # kv_transfer window.
                exp = next(s for s in node_spans["pw1"]
                           if s["name"] == "prefill.export")
                assert exp["duration_s"] <= gw[
                    "gateway.kv_transfer"]["duration_s"] + 0.5
                doc = stitch_chrome_trace(ctx.trace_id, node_spans)
                pids = {e["pid"] for e in doc["traceEvents"]}
                assert pids == {"gateway", "pw1"}
                ts = [e["ts"] for e in doc["traceEvents"]]
                assert ts == sorted(ts)
                assert doc["otherData"]["trace_id"] == ctx.trace_id
            finally:
                backend.stop()
                if worker.is_healthy():
                    worker.stop()


# -- cross-node stitch: fleet drain re-home -----------------------------------


def _drain_when_partway(ctl, node, min_tokens, out):
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        done = sum(len(s.generated)
                   for s in list(node.engine.sessions.values()))
        if done >= min_tokens:
            break
        time.sleep(0.01)
    try:
        out.update(ctl.drain(node.node_id))
    except Exception as e:  # noqa: BLE001 - surfaced by the assertions
        out["error"] = repr(e)


@needs_native
@pytest.mark.fleet
@pytest.mark.disagg
def test_fleet_drain_rehomed_request_stitches_single_trace(loop):
    """A drain mid-stream re-homes the session; the request still forms
    ONE trace: the gateway lane records the rehome + the handoff marker
    (linking to the drained node's ``drain.handoff`` span), the survivor
    lane records ``decode.resume``, and the drained node recorded its
    admit / first-token / handoff spans under the same trace id."""
    prompt = [3, 5, 7, 11, 13]
    opts = SamplingOptions(max_new_tokens=48)
    e = make_engine()
    base = drain_engine(e, e.submit(list(prompt), opts))
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            n1 = DecodeNode(relay.port, make_engine(), node_id="n1",
                            disagg_cfg=RECOVERY_DCFG, epoch=1)
            n2 = DecodeNode(relay.port, make_engine(), node_id="n2",
                            disagg_cfg=RECOVERY_DCFG, epoch=1)
            backend = FleetBackend(relay.port, disagg_cfg=RECOVERY_DCFG)
            backend.attach_tracer(SpanRecorder(),
                                  TraceConfig(collect_timeout_s=5.0))
            backend.start(loop)
            ctl = FleetController(relay.port, disagg_cfg=RECOVERY_DCFG)
            summary = {}
            drainer = threading.Thread(
                target=_drain_when_partway, args=(ctl, n1, 4, summary),
                daemon=True)
            try:
                ctx = TraceContext.mint(1.0)
                drainer.start()
                toks, seqs, reason, resumed, _ = _traced_stream(
                    backend, loop, prompt, opts, trace=ctx)
                drainer.join(timeout=30.0)
                assert "error" not in summary, summary
                assert toks == base and reason == "length"
                assert seqs == list(range(len(toks)))
                assert resumed == 1
                # The drained node recorded this request's spans under
                # the SAME trace id (asserted in-process: its directory
                # row is fenced, so trace.pull may no longer reach it).
                n1_names = {s.name
                            for s in n1.tracer.spans_for(ctx.trace_id)}
                assert {"decode.admit", "decode.first_token",
                        "drain.handoff"} <= n1_names, n1_names
                node_spans = backend.collect_trace(ctx.trace_id)
                assert "gateway" in node_spans and "n2" in node_spans
                gw_names = {s["name"] for s in node_spans["gateway"]}
                assert {"gateway.rehome",
                        "gateway.handoff_marker"} <= gw_names, gw_names
                marker = next(s for s in node_spans["gateway"]
                              if s["name"] == "gateway.handoff_marker")
                # The marker links the re-home to the node-side handoff.
                assert marker["args"]["node_trace"] == ctx.trace_id
                # The survivor's lane: the re-homed session landed there
                # under the SAME trace — warm (decode.resume, checkpoint
                # replay) or cold (decode.admit, prompt resubmission),
                # and it streamed (decode.first_token).
                n2_names = {s["name"] for s in node_spans["n2"]}
                assert n2_names & {"decode.resume", "decode.admit"}, n2_names
                assert "decode.first_token" in n2_names, n2_names
                doc = stitch_chrome_trace(ctx.trace_id, node_spans)
                assert {"gateway", "n2"} <= set(doc["otherData"]["nodes"])
                # The controller's drain op minted its own control-plane
                # trace, distinct from the request's.
                assert summary.get("trace") not in (None, ctx.trace_id)
            finally:
                ctl.close()
                backend.stop()
                n2.stop()
                n1.stop()


# -- sampling parity ----------------------------------------------------------


def test_sampling_on_off_token_streams_byte_exact(loop):
    """Tracing must be an observer: traced, unsampled, and
    tracer-less runs of the same greedy prompt produce byte-identical
    token streams."""
    prompt = [7, 8, 9, 10]
    opts = SamplingOptions(max_new_tokens=8)
    base = make_engine(kind="dense").generate([prompt], opts)[0]

    def run(attach, trace):
        backend = EngineBackend(make_engine(kind="dense"),
                                idle_sleep_s=0.001)
        if attach:
            backend.attach_tracer(SpanRecorder(), TraceConfig())
        backend.start(loop)
        try:
            toks, _, reason, _, _ = _traced_stream(
                backend, loop, prompt, opts, trace=trace)
            assert reason == "length"
            return toks
        finally:
            backend.stop()

    traced = run(True, TraceContext.mint(1.0))
    unsampled = run(True, TraceContext.mint(0.0))  # mint -> None
    bare = run(False, None)
    assert traced == unsampled == bare == base


# -- trace.pull degradation ---------------------------------------------------


@needs_native
def test_trace_pull_dead_node_partial_trace_within_budget(loop):
    """A trace.pull target that never answers costs at most the shared
    collect budget and leaves its lane out — never a wedged collect."""
    with RelayServer() as relay:
        backend = EngineBackend(make_engine(kind="dense"),
                                idle_sleep_s=0.001)
        backend.attach_tracer(SpanRecorder(),
                              TraceConfig(collect_timeout_s=1.0))
        backend.relay_port = relay.port  # collector wiring, no directory
        backend._trace_targets = lambda: [
            {"node_id": "ghost", "queue": "decode.ghost"},
            {"node_id": "ghost2", "queue": "decode.ghost2"},
        ]
        ctx = TraceContext.mint(1.0)
        with tracing.trace_span(backend.tracer, "gateway.request", ctx,
                                node="gateway"):
            pass
        t0 = time.monotonic()
        out = backend.collect_trace(ctx.trace_id)
        elapsed = time.monotonic() - t0
        assert set(out) == {"gateway"}  # partial: local lane survives
        assert elapsed < 5.0  # one shared budget, not per-node
        assert backend.metrics.get_counter("trace_pull_failures") == 2


@needs_native
@pytest.mark.chaos
@pytest.mark.disagg
def test_trace_pull_corrupt_answer_partial_trace(loop):
    """Chaos-corrupted ``trace.spans`` answers are dropped as malformed;
    collection still returns the gateway lane within the budget."""
    from distributed_llm_inference_tpu.distributed.chaos import (
        ChaosProxy,
        FaultPlan,
    )

    prompt = [1, 2, 3, 4, 5]
    opts = SamplingOptions(max_new_tokens=4)
    plan = FaultPlan.from_specs(["corrupt:trace.spans.*:put"], seed=7)
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            with ChaosProxy("127.0.0.1", relay.port, plan=plan) as proxy:
                # The worker answers trace.pull through the chaos proxy;
                # its KV path is untouched (spec matches only the
                # trace.spans reply queue).
                worker = PrefillWorker(proxy.port, make_engine(),
                                       node_id="pw1")
                backend = DisaggBackend(
                    make_engine(), relay.port,
                    disagg_cfg=DisaggConfig(transfer_timeout_s=10.0),
                )
                backend.attach_tracer(SpanRecorder(),
                                      TraceConfig(collect_timeout_s=2.0))
                backend.start(loop)
                try:
                    ctx = TraceContext.mint(1.0)
                    toks, _, reason, _, _ = _traced_stream(
                        backend, loop, prompt, opts, trace=ctx)
                    assert reason == "length" and toks
                    t0 = time.monotonic()
                    out = backend.collect_trace(ctx.trace_id)
                    elapsed = time.monotonic() - t0
                    assert "gateway" in out
                    assert "pw1" not in out  # its answer was corrupted
                    assert elapsed < 10.0
                    assert plan.injected, "corrupt fault never fired"
                    # The fault surfaces either as a CRC-rejected frame
                    # (malformed) or as a lost answer (pull timeout) —
                    # both leave a partial trace, never a wedge.
                    m = backend.metrics
                    assert (m.get_counter("malformed_frames")
                            + m.get_counter("trace_pull_failures")) >= 1
                finally:
                    backend.stop()
                    if worker.is_healthy():
                        worker.stop()


# -- HTTP surface -------------------------------------------------------------


@contextlib.contextmanager
def serving(trace_cfg=None, **scfg_kw):
    eng = make_engine(kind="dense", trace_cfg=trace_cfg)
    backend = EngineBackend(eng, idle_sleep_s=0.001)
    scfg = ServingConfig(host="127.0.0.1", port=0, **scfg_kw)
    server = ApiServer(backend, scfg, trace_cfg=trace_cfg)
    server.start()
    try:
        yield server, backend
    finally:
        server.request_shutdown()
        server.join(timeout=60.0)


def _post(port, body, timeout=60.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(
        "POST", "/v1/completions", json.dumps(body),
        {"Content-Type": "application/json"},
    )
    return conn, conn.getresponse()


def _get(port, path, timeout=10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    return conn, conn.getresponse()


@pytest.mark.http
def test_http_trace_id_debug_trace_ticks_and_healthz():
    with serving(trace_cfg=TraceConfig(trace_sample_rate=1.0,
                                       ticks_capacity=64)) as (server, _b):
        conn, resp = _post(server.port, {"prompt": [1, 2, 3],
                                         "max_tokens": 4})
        assert resp.status == 200
        tid = resp.getheader("X-Trace-Id")
        resp.read()
        conn.close()
        assert tid  # sampled at 1.0: every response carries its trace id
        c2, r2 = _get(server.port, f"/debug/trace/{tid}")
        assert r2.status == 200
        doc = json.loads(r2.read())
        c2.close()
        names = {e["name"] for e in doc["traceEvents"]}
        assert "gateway.request" in names
        assert "gateway.decode_wait" in names, names
        assert all(e["pid"] == "gateway" for e in doc["traceEvents"])
        assert doc["otherData"]["trace_id"] == tid
        # The request span covers the whole measured request: it must be
        # the longest gateway segment.
        req = next(e for e in doc["traceEvents"]
                   if e["name"] == "gateway.request")
        assert req["dur"] >= max(e["dur"] for e in doc["traceEvents"])
        c3, r3 = _get(server.port, "/debug/ticks")
        assert r3.status == 200
        body = json.loads(r3.read())
        c3.close()
        ticks = body["ticks"]
        assert ticks and len(ticks) <= 64
        assert any(t["occupancy"] > 0 for t in ticks)
        # beside the ticks: what the process loaded, by program and stage,
        # and its boot marks, all passed by now
        assert set(body) == {"ticks", "programs", "boot"}
        rows = body["programs"].values()
        assert rows and all(set(r) == {
            "loads", "cache_hits", "trace_s", "lower_s", "compile_s",
            "cache_read_s",
        } for r in rows)
        assert sum(r["loads"] for r in rows) >= len(rows)
        boot = body["boot"]
        assert set(boot) == {"start", *tracing.BOOT_MARKS}
        assert 0 < boot["engine_build"] <= boot["engine_built"] <= boot["first_request"]
        assert boot["start"] + boot["first_request"] <= time.time()
        c5, r5 = _get(server.port, "/metrics")
        text = r5.read().decode()
        c5.close()
        for name in ("process_start_time_seconds", "boot_engine_build_seconds",
                     "boot_engine_built_seconds", "boot_first_request_seconds",
                     *(f"engine_program_load_{s}_seconds_total"
                       for s in tracing.LOAD_STAGES)):
            assert f"dli_{name} " in text, name
        c4, r4 = _get(server.port, "/healthz")
        health = json.loads(r4.read())
        c4.close()
        assert health["trace"]["depth"] >= 1
        assert health["trace"]["dropped"] == 0


@pytest.mark.http
def test_trace_ticks_prints_the_programs_most_seconds_first_then_the_boot(capsys):
    """``distribute trace --ticks``: three JSON lines, the ticks as they
    were, the programs' rows ordered by their load seconds, the boot marks."""
    import argparse

    from distributed_llm_inference_tpu import cli

    with serving(trace_cfg=TraceConfig(ticks_capacity=64)) as (server, _b):
        conn, resp = _post(server.port, {"prompt": [1, 2, 3], "max_tokens": 4})
        resp.read()
        conn.close()
        assert cli.cmd_trace(argparse.Namespace(
            url=f"http://127.0.0.1:{server.port}/", ticks=True, trace_id=None,
            out=None, timeout=10.0,
        )) == 0
    ticks, programs, boot = (
        json.loads(l) for l in capsys.readouterr().out.strip().splitlines()[-3:]
    )
    assert list(ticks) == ["ticks"] and ticks["ticks"]
    rows = list(programs["programs"].values())
    seconds = [
        sum(r[f"{s}_s"] for s in tracing.LOAD_STAGES) for r in rows
    ]
    assert len(rows) > 1 and seconds == sorted(seconds, reverse=True)
    assert set(boot["boot"]) == {"start", *tracing.BOOT_MARKS}
    # a body without the two keys (an older gateway) prints what it has
    assert [json.loads(l) for l in cli._ticks_lines({"ticks": []})] == [
        {"ticks": []}, {"programs": {}}, {"boot": None},
    ]


@pytest.mark.http
def test_http_debug_trace_holds_the_engine_spans_under_the_request():
    """``engine.queue`` and ``engine.first_token`` of one request: recorded
    by the engine into the gateway's recorder, descendants of
    ``gateway.request``, each naming the tick of its event, and inside
    ``gateway.decode_wait`` on the shared epoch clock."""
    with serving(trace_cfg=TraceConfig(trace_sample_rate=1.0,
                                       ticks_capacity=64)) as (server, b):
        conn, resp = _post(server.port, {"prompt": [1, 2, 3],
                                         "max_tokens": 4})
        tid = resp.getheader("X-Trace-Id")
        resp.read()
        conn.close()
        c2, r2 = _get(server.port, f"/debug/trace/{tid}")
        events = json.loads(r2.read())["traceEvents"]
        c2.close()
        by_name = {e["name"]: e for e in events}
        assert {"gateway.request", "gateway.decode_wait", "engine.queue",
                "engine.first_token"} <= set(by_name), sorted(by_name)
        parent = {e["args"]["span_id"]: e["args"].get("parent_id")
                  for e in events}
        request = by_name["gateway.request"]["args"]["span_id"]
        ticks = {t["tick"] for t in b.flight_snapshot()}
        wait = by_name["gateway.decode_wait"]
        for name in ("engine.queue", "engine.first_token"):
            e = by_name[name]
            node = e["args"]["span_id"]
            while parent.get(node) is not None and node != request:
                node = parent[node]
            assert node == request, name
            assert e["args"]["tick"] in ticks, e["args"]
            assert e["args"]["gen_id"] == wait["args"]["gen_id"]
            assert e["dur"] >= 0
            # inside the gateway's decode wait, to the clocks' resolution
            assert e["ts"] >= wait["ts"] - 5e3
            assert e["ts"] + e["dur"] <= wait["ts"] + wait["dur"] + 5e3
        q, f = by_name["engine.queue"], by_name["engine.first_token"]
        assert q["args"]["tick"] <= f["args"]["tick"]
        assert abs(q["ts"] + q["dur"] - f["ts"]) < 5e3  # one instant, in us
        snap = b.metrics.snapshot()
        assert snap["engine_queue_wait_count"] == 1
        assert snap["engine_first_token_wait_count"] == 1


@pytest.mark.http
def test_http_tracing_disabled_no_header_404_and_parity():
    with serving(trace_cfg=TraceConfig(trace_sample_rate=1.0)) as (s_on, _b):
        conn, resp = _post(s_on.port, {"prompt": [1, 2, 3], "max_tokens": 4})
        traced = json.loads(resp.read())["choices"][0]["token_ids"]
        conn.close()
    with serving() as (server, backend):
        conn, resp = _post(server.port, {"prompt": [1, 2, 3],
                                         "max_tokens": 4})
        assert resp.status == 200
        assert resp.getheader("X-Trace-Id") is None
        plain = json.loads(resp.read())["choices"][0]["token_ids"]
        conn.close()
        assert plain == traced  # byte-exact with tracing off
        c2, r2 = _get(server.port, "/debug/trace/deadbeef")
        assert r2.status == 404
        r2.read()
        c2.close()
        c3, r3 = _get(server.port, "/debug/ticks")
        assert r3.status == 200
        assert json.loads(r3.read())["ticks"] == []  # no flight ring
        c3.close()
        assert backend.engine.flight is None  # zero-cost disabled path


# -- the dispatch clock -------------------------------------------------------


KINDS = ("prefill", "chunk", "decode")  # of dispatch (``plan.note_dispatch``)


def _clock_of(engine):
    """Every noted dispatch of the engine's ticks beside its clock entry."""
    out = []
    for t in engine.flight.snapshot():
        assert len(t["dispatch_clock"]) == len(t["dispatches"]), t
        out.extend(zip(t["dispatches"], t["dispatch_clock"]))
    return out


def test_dispatch_clock_stamps_every_dispatch_and_cuts_the_first_token():
    """A short and a chunked prompt through a tiny armed engine: every
    noted dispatch has its three stamps in order once the engine drains,
    the kinds' device seconds and the idle seconds are the span from the
    first enqueue to the last ready stamp, each request's three pieces sum
    to its first-token wait, and a traced request has them as spans under
    ``engine.first_token``."""
    eng = make_engine(trace_cfg=TraceConfig())
    eng.tracer = SpanRecorder()
    eng.flight.clock.lease(600.0)  # watched to the end, however slow the host
    ctxs = [TraceContext.mint(), TraceContext.mint()]
    opts = SamplingOptions(max_new_tokens=6)
    eng.submit([1, 2, 3], opts, trace=ctxs[0])
    eng.submit(list(range(1, 41)), opts, trace=ctxs[1])  # 32 + 8: a chunk
    while eng.has_work():
        eng.step()
    noted = _clock_of(eng)
    assert {d[0] for d, _ in noted} == {"prefill", "chunk", "decode"}
    for _, c in noted:
        assert c["enq_ns"] <= c["ret_ns"] <= c["ready_ns"], c
        assert c["device_ms"] >= 0.0 and c["idle_ms"] >= 0.0
        assert sum(c.get("idle_phase_ms", {}).values()) == pytest.approx(
            c["idle_ms"], abs=1e-6
        )
    m = eng.metrics
    kinds = {k: m.get_counter(f"engine_device_seconds_{k}") for k in KINDS}
    idle = m.get_counter("engine_device_idle_seconds")
    span = (noted[-1][1]["ready_ns"] - noted[0][1]["enq_ns"]) / 1e9
    assert sum(kinds.values()) + idle == pytest.approx(span, abs=1e-3)
    assert sum(
        m.get_counter(f"engine_device_idle_{p}_seconds") for p in tracing.PHASES
    ) == pytest.approx(idle, abs=1e-6)
    for k in KINDS:
        assert m.get_counter(f"engine_dispatches_{k}") == sum(
            1 for d, _ in noted if d[0] == k
        )
    assert m.get_counter("engine_decode_steps") == sum(
        d[1][1] for d, _ in noted if d[0] == "decode"
    )
    assert 0.0 <= m.get_counter("engine_enqueue_seconds") <= sum(
        (c["ret_ns"] - c["enq_ns"]) / 1e9 for _, c in noted
    ) + 1e-9
    # a load inside a call is taken out of the enqueue seconds, and named
    assert any("compile_ms" in c for _, c in noted)
    assert any(t.get("compiled") for t in eng.flight.snapshot())
    with m._lock:
        waits = list(m._timings["engine_first_token_wait"])
        pieces = [
            list(m._timings[f"engine_first_token_{p}"])
            for p in ("prefill_wait", "prefill_own", "deliver")
        ]
    assert len(waits) == 2
    for i, wait in enumerate(waits):
        assert sum(p[i] for p in pieces) == pytest.approx(wait, abs=1e-3)
        assert pieces[1][i] > 0.0 and pieces[2][i] >= 0.0
    for ctx in ctxs:
        spans = {s.name: s for s in eng.tracer.spans_for(ctx.trace_id)}
        first = spans["engine.first_token"]
        for name in ("engine.prefill_wait", "engine.prefill_own",
                     "engine.first_token_deliver"):
            assert spans[name].parent_id == first.span_id, name
            assert spans[name].args["tick"] == first.args["tick"]
            assert spans[name].start_s >= first.start_s - 1e-3
        assert sum(
            spans[n].duration_s for n in spans if n not in
            ("engine.queue", "engine.first_token")
        ) == pytest.approx(first.duration_s, abs=2e-3)


def test_without_a_trace_config_there_is_no_clock():
    """``trace_cfg=None``: no watcher thread, no stamp, and ``/metrics`` has
    none of the clock's names."""
    before = {t.ident for t in threading.enumerate()}
    eng = make_engine()
    eng.generate([[1, 2, 3], list(range(1, 41))], SamplingOptions(max_new_tokens=4))
    assert eng.flight is None and eng._clock is None
    assert not [
        t for t in threading.enumerate()
        if t.ident not in before and t.name == "dispatch-clock"
    ]
    text = eng.metrics.prometheus()
    for name in ("engine_device_", "engine_dispatches_", "engine_decode_steps",
                 "engine_enqueue_seconds", "engine_first_token_prefill",
                 "engine_first_token_deliver", "engine_program_load",
                 "engine_compile_cache_hits", "process_start_time", "boot_"):
        assert name not in text, name
