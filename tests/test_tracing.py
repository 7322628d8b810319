"""Tracing/profiling subsystem (SURVEY §5.1).

The flight recorder splits every engine tick's wall time into host phases
and counts them (counts and structure are checked here, never times); the
jax.profiler wrapper must produce a trace dump and be idempotent/no-op-safe.
"""

import threading
import time

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inference_tpu.config import CacheConfig, EngineConfig, ModelConfig
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.utils import tracing


PHASE_MS = [f"{p}_ms" for p in tracing.PHASES]


def small_engine(trace_cfg=None, batch=2, **ekw):
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ecfg = EngineConfig(max_batch_size=batch, prefill_buckets=(8, 16),
                        max_seq_len=32, dtype="float32", **ekw)
    return InferenceEngine(cfg, params, ecfg, CacheConfig(kind="dense"),
                           trace_cfg=trace_cfg)


def test_regions_charge_exclusive_time_to_the_tick():
    """A region inside another suspends the outer one: the five phases of a
    tick sum to its wall time, and the counters get the same seconds."""
    from distributed_llm_inference_tpu.utils.metrics import Metrics

    m = Metrics()
    fr = tracing.FlightRecorder(capacity=4, metrics=m)
    assert fr.begin() == 0
    with fr.region("dispatch"):
        with fr.region("blocked"):
            time.sleep(0.002)
        with fr.region("deliver"):
            pass
    fr.end(kind="plain")
    assert fr.begin() == 1  # what passed since end() is this tick's outside
    fr.end(kind="plain")
    first, second = fr.snapshot()
    assert [first["tick"], second["tick"]] == [0, 1]
    assert first["outside_ms"] == 0.0 and second["outside_ms"] > 0.0
    assert first["blocked_ms"] >= 2.0 > first["dispatch_ms"]  # suspended
    for t in (first, second):
        assert all(t[k] >= 0.0 for k in PHASE_MS)
        wall = t["host_ms"] + t["outside_ms"]
        assert sum(t[k] for k in PHASE_MS) == pytest.approx(wall, abs=1e-6)
        assert t["t0_ns"] <= t["t"] * 1e9
    assert m.get_counter("engine_ticks") == 2.0
    for p in tracing.PHASES:
        assert m.get_counter(f"engine_tick_{p}_seconds") * 1e3 == (
            pytest.approx(first[f"{p}_ms"] + second[f"{p}_ms"])
        )
    assert m.get_counter("engine_tick_seconds") == pytest.approx(sum(
        m.get_counter(f"engine_tick_{p}_seconds") for p in tracing.PHASES
    ))


def test_span_recorder_bounded_and_thread_safe():
    rec = tracing.SpanRecorder(capacity=64)

    def worker(i):
        for j in range(50):
            rec.record(tracing.Span(f"t{i}.{j}", 0.0, 0.001))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec.spans()) == 64  # bounded, no crash


def test_profile_trace_writes_device_trace(tmp_path):
    d = str(tmp_path / "prof")
    with tracing.profile_trace(d):
        jnp.dot(jnp.ones((8, 8)), jnp.ones((8, 8))).block_until_ready()
    files = [str(p) for p in (tmp_path / "prof").rglob("*")]
    assert any("trace" in f or f.endswith(".pb") or f.endswith(".json.gz")
               for f in files), files
    # No-op and double-stop safety.
    with tracing.profile_trace(None):
        pass
    assert tracing.stop_profile() is None


def test_engine_tick_records_split_the_host_time():
    """The tick record in place of the old prefill / decode_step spans: one
    tick that admitted two rows and decoded lists all three dispatches, its
    five phases are non-negative and sum to its wall time, and it was
    blocked in its fetches."""
    from distributed_llm_inference_tpu.config import TraceConfig

    eng = small_engine(TraceConfig(ticks_capacity=64), decode_steps=1)
    opts = SamplingOptions(max_new_tokens=4)
    eng.submit([1, 2, 3], opts), eng.submit(list(range(1, 11)), opts)
    eng.step()
    (tick,) = eng.flight.snapshot()
    assert tick["admitted"] == 2 and tick["occupancy"] == 2
    assert tick["dispatches"] == [
        ("prefill", (1, 8), 3), ("prefill", (1, 16), 10),
        ("decode", (2, 1, eng.cache.max_len), 4 + 11),
    ]
    assert tick["dispatch"] == tick["dispatches"][-1]
    assert tick["free_pages"] is None  # a dense cache has no page pool
    assert all(tick[k] >= 0.0 for k in PHASE_MS)
    wall = tick["host_ms"] + tick["outside_ms"]
    assert abs(sum(tick[k] for k in PHASE_MS) - wall) < 1.0  # ms
    # two synchronous admissions and the decode fetch each waited
    assert tick["blocked_ms"] > 0.0 and tick["deliver_ms"] > 0.0
    assert tick["admit_ms"] > 0.0 and tick["dispatch_ms"] > 0.0
    while eng.has_work():
        eng.step()
    ticks = eng.flight.snapshot()
    assert [t["tick"] for t in ticks] == list(range(len(ticks)))
    assert all(t["dispatches"] == [t["dispatch"]] for t in ticks[1:])
    assert all(t["outside_ms"] > 0.0 for t in ticks[1:])


def test_tick_counters_equal_the_sum_of_the_tick_fields():
    from distributed_llm_inference_tpu.config import TraceConfig

    eng = small_engine(TraceConfig(ticks_capacity=256))
    eng.generate([[1, 2, 3], [4, 5, 6, 7]], SamplingOptions(max_new_tokens=9))
    ticks = eng.flight.snapshot()
    m = eng.metrics
    assert m.get_counter("engine_ticks") == len(ticks) > 0
    for p in tracing.PHASES:
        assert m.get_counter(f"engine_tick_{p}_seconds") * 1e3 == (
            pytest.approx(sum(t[f"{p}_ms"] for t in ticks))
        )
    assert m.get_counter("engine_tick_seconds") * 1e3 == pytest.approx(
        sum(t["host_ms"] + t["outside_ms"] for t in ticks)
    )
    # the request-side pair: observed once a session, at the event
    snap = m.snapshot()
    assert snap["engine_queue_wait_count"] == 2
    assert snap["engine_first_token_wait_count"] == 2


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_without_a_trace_config_step_touches_no_recorder(
    monkeypatch, decode_steps
):
    """``trace_cfg=None``: the same tokens, and not one call into the flight
    recorder, the profiler's annotations or a span recorder."""
    from distributed_llm_inference_tpu.config import TraceConfig

    prompts = [[1, 2, 3], list(range(1, 11)), [5, 6]]
    opts = SamplingOptions(max_new_tokens=7)
    on = small_engine(TraceConfig(), decode_steps=decode_steps)
    traced = on.generate(prompts, opts)
    assert on.flight.snapshot()

    def boom(*a, **k):
        raise AssertionError("tracing work on the disabled path")

    for name in ("begin", "end", "region", "record"):
        monkeypatch.setattr(tracing.FlightRecorder, name, boom)
    monkeypatch.setattr(tracing.SpanRecorder, "record", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", boom)
    off = small_engine(None, decode_steps=decode_steps)
    assert off.flight is None and off.tracer is None
    assert off.plan.dispatches is None
    assert off.generate(prompts, opts) == traced


def test_a_raising_region_leaves_the_tick_clock_whole():
    fr = tracing.FlightRecorder()
    fr.begin()
    with pytest.raises(RuntimeError):
        with fr.region("dispatch"):
            with fr.region("blocked"):
                raise RuntimeError("x")
    with fr.region("deliver"):  # the stack unwound: regions still nest
        pass
    fr.end(kind="plain")
    (t,) = fr.snapshot()
    assert sum(t[k] for k in PHASE_MS) == pytest.approx(t["host_ms"], abs=1e-6)


def test_nested_profile_trace_keeps_outer(tmp_path):
    outer = str(tmp_path / "outer")
    assert tracing.start_profile(outer) is True
    with tracing.profile_trace(str(tmp_path / "inner")):
        pass  # must NOT stop the outer trace
    assert tracing.stop_profile() == outer  # outer still owned + running


# ---------------------------------------------------------------------------
# distributed request tracing (TraceContext / trace_span / stitch) + the
# engine flight recorder
# ---------------------------------------------------------------------------


def test_trace_context_mint_child_and_header_round_trip():
    ctx = tracing.TraceContext.mint(1.0)
    assert ctx is not None and len(ctx.trace_id) == 16
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_id == ctx.span_id
    assert child.span_id != ctx.span_id
    hdr = {"op": "x", **ctx.to_header()}
    back = tracing.TraceContext.from_header(hdr)
    assert back is not None
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)


def test_trace_context_sampling_off_and_none_keys():
    assert tracing.TraceContext.mint(0.0) is None
    # Unsampled requests still ship the keys, valued None — the reader
    # must treat that exactly like an absent context.
    assert tracing.TraceContext.from_header({"trace": None}) is None
    assert tracing.TraceContext.from_header({}) is None


def test_trace_span_noop_and_recording():
    rec = tracing.SpanRecorder()
    with tracing.trace_span(None, "x", tracing.TraceContext.mint(1.0)) as c:
        assert c is None  # disabled recorder: no-op
    with tracing.trace_span(rec, "x", None) as c:
        assert c is None  # unsampled request: no-op
    assert rec.depth() == 0
    ctx = tracing.TraceContext.mint(1.0)
    with tracing.trace_span(rec, "kv_transfer", ctx, node="gw", n=2) as c:
        assert c is not None and c.parent_id == ctx.span_id
    (s,) = rec.spans()
    assert s.name == "kv_transfer" and s.node == "gw"
    assert s.trace_id == ctx.trace_id and s.parent_id == ctx.span_id
    assert s.args == {"n": 2}
    # Spans survive a raising region (failed transfers are the point).
    try:
        with tracing.trace_span(rec, "boom", ctx):
            raise RuntimeError("x")
    except RuntimeError:
        pass
    assert [x.name for x in rec.spans()] == ["kv_transfer", "boom"]


def test_span_recorder_counts_evictions():
    class _Sink:
        def __init__(self):
            self.n = 0

        def counter(self, name, inc=1):
            assert name == "trace_spans_dropped"
            self.n += inc

    sink = _Sink()
    rec = tracing.SpanRecorder(capacity=4, metrics=sink)
    for i in range(10):
        rec.record(tracing.Span(f"s{i}", 0.0, 0.0))
    assert rec.depth() == 4
    assert rec.dropped == 6
    assert sink.n == 6


def test_span_recorder_spans_for_filters_by_trace():
    rec = tracing.SpanRecorder()
    rec.record(tracing.Span("a", 0.0, 0.0, trace_id="t1", span_id="s1"))
    rec.record(tracing.Span("b", 0.0, 0.0, trace_id="t2", span_id="s2"))
    rec.record(tracing.Span("local", 0.0, 0.0))
    assert [s.name for s in rec.spans_for("t1")] == ["a"]


def test_stitch_chrome_trace_lanes_and_filtering():
    doc = tracing.stitch_chrome_trace("tid", {
        "gateway": [
            {"name": "gateway.request", "start_s": 10.0, "duration_s": 0.5,
             "trace_id": "tid", "span_id": "a", "parent_id": None},
            {"name": "other", "start_s": 10.1, "duration_s": 0.1,
             "trace_id": "OTHER", "span_id": "z"},
        ],
        "node-1": [
            {"name": "decode.first_token", "start_s": 10.2,
             "duration_s": 0.2, "trace_id": "tid", "span_id": "b",
             "parent_id": "a", "args": {"gen": "g"}},
        ],
    })
    names = [(e["pid"], e["name"]) for e in doc["traceEvents"]]
    assert names == [("gateway", "gateway.request"),
                     ("node-1", "decode.first_token")]  # sorted, filtered
    assert doc["otherData"]["trace_id"] == "tid"
    assert doc["otherData"]["nodes"] == ["gateway", "node-1"]
    ev = doc["traceEvents"][1]
    assert ev["args"]["parent_id"] == "a" and ev["args"]["gen"] == "g"


def test_flight_recorder_ring_is_bounded_with_monotonic_ticks():
    fr = tracing.FlightRecorder(capacity=8)
    for i in range(30):
        fr.record(kind="decode", batch=i)
    snap = fr.snapshot()
    assert len(snap) == 8  # bounded
    assert [r["tick"] for r in snap] == list(range(22, 30))  # no resets
    assert all("t" in r for r in snap)
    assert [r["batch"] for r in fr.snapshot(last=2)] == [28, 29]


def test_engine_flight_recorder_gated_on_trace_config():
    from distributed_llm_inference_tpu.config import TraceConfig

    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ecfg = EngineConfig(max_batch_size=2, prefill_buckets=(8,),
                        max_seq_len=32, dtype="float32")
    off = InferenceEngine(cfg, params, ecfg, CacheConfig(kind="dense"))
    assert off.flight is None  # disabled path: no ring, no per-tick work
    on = InferenceEngine(cfg, params, ecfg, CacheConfig(kind="dense"),
                         trace_cfg=TraceConfig(ticks_capacity=16))
    on.generate([[1, 2, 3]], SamplingOptions(max_new_tokens=4))
    ticks = on.flight.snapshot()
    assert ticks and len(ticks) <= 16
    assert {t["kind"] for t in ticks} <= {"plain", "pipelined"}, ticks[:3]
    for t in ticks:
        assert "occupancy" in t and "admitted" in t and "host_ms" in t
    assert any(t["occupancy"] > 0 for t in ticks)  # the session decoded
