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
from distributed_llm_inference_tpu.engine import engine as eng_mod
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


# -- the dispatch clock's arithmetic, on made-up stamps ------------------------

MS = 1_000_000  # the scripts below are in milliseconds
KINDS = ("prefill", "chunk", "decode")  # of dispatch (``plan.note_dispatch``)

# (at ms, action): "begin" / "end" a tick; "+phase" / "-" a region; "enter" /
# "leave kind steps" a noted dispatch's call; "ready i" the i-th dispatch's
# result; "load seconds" a program load reported inside the open call
CLOCK_CASES = {
    # the second call is entered while the first still runs: no idle, and
    # the device seconds are the differences of the ready stamps
    "back_to_back": dict(
        script=[(0, "begin"), (0, "+dispatch"), (10, "enter"),
                (20, "leave decode 4"), (30, "enter"), (40, "leave decode 4"),
                (100, "ready 0"), (250, "ready 1"), (260, "-"), (300, "end")],
        device={"decode": 0.240}, idle={}, enqueue=0.020,
        dispatches={"decode": 2}, steps=8,
    ),
    # the device waited from 50 to 100: 20 under admit, 30 under dispatch
    "a_gap_falls_to_the_phases_that_held_it": dict(
        script=[(0, "begin"), (10, "enter"), (20, "leave prefill 1"),
                (50, "ready 0"), (70, "+dispatch"), (100, "enter"),
                (110, "leave decode 1"), (200, "ready 1"), (210, "-"),
                (220, "end")],
        device={"prefill": 0.040, "decode": 0.100},
        idle={"admit": 0.020, "dispatch": 0.030}, enqueue=0.020,
        dispatches={"prefill": 1, "decode": 1}, steps=1,
    ),
    # a gap over two ticks: the time between them is ``outside``
    "a_gap_between_two_ticks_is_outside": dict(
        script=[(0, "begin"), (10, "enter"), (20, "leave chunk 1"),
                (50, "ready 0"), (60, "end"), (90, "begin"),
                (95, "+dispatch"), (100, "enter"), (130, "leave decode 16"),
                (400, "ready 1"), (410, "-"), (420, "end")],
        device={"chunk": 0.040, "decode": 0.300},
        idle={"admit": 0.015, "outside": 0.030, "dispatch": 0.005},
        enqueue=0.040, dispatches={"chunk": 1, "decode": 1}, steps=16,
    ),
    # a program load inside the call is not the enqueue's time
    "a_compile_inside_a_call_leaves_the_enqueue_seconds": dict(
        script=[(0, "begin"), (10, "enter"), (1500, "load 1.4"),
                (2010, "leave prefill 1"), (2100, "ready 0"), (2200, "end")],
        device={"prefill": 2.090}, idle={}, enqueue=0.600,
        dispatches={"prefill": 1}, steps=0, compile_ms=1400.0,
    ),
    # one ready event for two dispatches (the watcher was late): the time
    # falls to the first, and nothing is lost
    "a_late_ready_settles_what_was_enqueued_before_it": dict(
        script=[(0, "begin"), (10, "enter"), (20, "leave chunk 1"),
                (30, "enter"), (40, "leave prefill 1"), (300, "ready 1"),
                (310, "end")],
        device={"chunk": 0.290, "prefill": 0.0}, idle={}, enqueue=0.020,
        dispatches={"chunk": 1, "prefill": 1}, steps=0,
    ),
}


@pytest.mark.parametrize("case", sorted(CLOCK_CASES))
def test_dispatch_clock_arithmetic(monkeypatch, case):
    from distributed_llm_inference_tpu.utils.metrics import Metrics

    want = CLOCK_CASES[case]
    now = [0]
    monkeypatch.setattr(tracing.time, "time_ns", lambda: now[0])
    m = Metrics()
    fr = tracing.FlightRecorder(capacity=8, metrics=m)
    fr.snapshot()     # somebody watches: the first tick arms the clock
    clock = fr.clock  # a ``leave`` without a result wakes no watcher
    entries, regions = [], []
    for at, action in want["script"]:
        now[0] = at * MS
        verb, *args = action.split()
        if verb == "begin":
            fr.begin()
        elif verb == "end":
            fr.end(kind="plain", dispatches=[])
        elif verb.startswith("+"):
            regions.append(fr.region(verb[1:]))
            regions[-1].__enter__()
        elif verb == "-":
            regions.pop().__exit__(None, None, None)
        elif verb == "enter":
            entries.append(clock.enter())
        elif verb == "leave":
            clock.leave(entries[-1], None, args[0], int(args[1]))
        elif verb == "ready":
            clock.settle(entries[int(args[0])])
        elif verb == "load":
            fr._loaded("jit_step", float(args[0]))
    for kind in KINDS:
        assert m.get_counter(f"engine_device_seconds_{kind}") == pytest.approx(
            want["device"].get(kind, 0.0)
        ), kind
        assert m.get_counter(f"engine_dispatches_{kind}") == (
            want["dispatches"].get(kind, 0)
        )
    assert m.get_counter("engine_decode_steps") == want["steps"]
    idle = m.get_counter("engine_device_idle_seconds")
    assert idle == pytest.approx(sum(want["idle"].values()))
    for phase in tracing.PHASES:
        assert m.get_counter(
            f"engine_device_idle_{phase}_seconds"
        ) == pytest.approx(want["idle"].get(phase, 0.0)), phase
    assert m.get_counter("engine_enqueue_seconds") == pytest.approx(
        want["enqueue"]
    )
    # what the device ran and what it waited is the whole span
    span = (entries[-1]["ready_ns"] - entries[0]["enq_ns"]) / 1e9
    assert sum(want["device"].values()) + idle == pytest.approx(span)
    assert all(e["enq_ns"] <= e["ret_ns"] <= e["ready_ns"] for e in entries)
    # the stamps ride the tick's record, a load with its program's name
    recorded = [c for t in fr.snapshot() for c in t["dispatch_clock"]]
    assert recorded == entries
    assert [c.get("compile_ms") for c in recorded if "compile_ms" in c] == (
        [want["compile_ms"]] if "compile_ms" in want else []
    )
    if "compile_ms" in want:
        assert fr.snapshot()[0]["compiled"] == [("jit_step", want["compile_ms"])]


def test_program_loads_count_nested_reports_once(monkeypatch):
    """JAX reports a trace inside a trace inside it: the seconds are the
    union's, a load is a ``backend_compile_duration`` event, and the watcher
    of the thread hears the program's whole load as its compile ends."""
    loads = tracing.ProgramLoads()
    now = [0.0]
    monkeypatch.setattr(tracing.time, "time", lambda: now[0])
    heard = []
    loads.watch(lambda name, seconds: heard.append((name, seconds)))
    trace = "/jax/core/compile/jaxpr_trace_duration"
    for at, event, seconds in [
        (100.0, trace, 2.0),            # 98..100, inside the next
        (101.0, trace, 5.0),            # 96..101: 3 s of its own
        (102.5, "/jax/core/compile/jaxpr_to_mlir_module_duration", 1.0),
        (106.0, "/jax/compilation_cache/cache_retrieval_time_sec", 0.5),
        (107.0, "/jax/core/compile/backend_compile_duration", 4.0),
        (108.0, "/jax/some/other_duration", 9.0),
    ]:
        now[0] = at
        loads._duration(event, seconds, fun_name="jit_step")
    assert (loads.loads, loads.cache_hits) == (1, 1)
    assert loads.seconds == pytest.approx(10.0)
    assert heard == [("jit_step", pytest.approx(10.0))]
    loads.unwatch()
    now[0] = 120.0
    loads._duration("/jax/core/compile/backend_compile_duration", 1.0)
    assert loads.loads == 2 and len(heard) == 1


_EVENTS = {
    "trace": "/jax/core/compile/jaxpr_trace_duration",
    "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "compile": "/jax/core/compile/backend_compile_duration",
    "read": "/jax/compilation_cache/cache_retrieval_time_sec",
}


def _feed(loads, monkeypatch, reports, fun_name="jit_step"):
    """``(end, kind, seconds)`` reports through the listener, each at its
    end on a clock the test holds."""
    now = [0.0]
    monkeypatch.setattr(tracing.time, "time", lambda: now[0])
    for at, kind, seconds in reports:
        now[0] = at
        loads._duration(_EVENTS[kind], seconds, fun_name=fun_name)


@pytest.mark.parametrize("reports,want", [
    # the reports of ``..._count_nested_reports_once``: the inner trace's
    # 2 s and the outer's own 3 s are tracing, the cache answered the compile
    ([(100.0, "trace", 2.0), (101.0, "trace", 5.0), (102.5, "lower", 1.0),
      (106.0, "read", 0.5), (107.0, "compile", 4.0)],
     {"trace": 5.0, "lower": 1.0, "compile": 0.0, "cache_read": 4.0}),
    # a jitted function traced, lowered and compiled INSIDE another's
    # lowering: each second under the innermost report that holds it
    ([(10.0, "trace", 1.0), (13.0, "trace", 0.5), (14.0, "lower", 0.75),
      (16.0, "compile", 2.0), (17.0, "lower", 6.0), (20.0, "compile", 3.0)],
     {"trace": 1.5, "lower": 0.75 + (6.0 - 0.5 - 0.75 - 2.0),
      "compile": 5.0, "cache_read": 0.0}),
    # reports that only touch (one ends as the next starts) nest nothing
    ([(5.0, "trace", 1.0), (6.0, "lower", 1.0), (7.0, "compile", 1.0)],
     {"trace": 1.0, "lower": 1.0, "compile": 1.0, "cache_read": 0.0}),
], ids=["nested-traces-and-a-cache-read", "a-load-inside-a-lowering", "end-to-start"])
def test_program_loads_keep_each_second_under_the_stage_that_owns_it(
    monkeypatch, reports, want
):
    loads = tracing.ProgramLoads()
    _feed(loads, monkeypatch, reports)
    got = dict(zip(tracing.LOAD_STAGES, loads.stages))
    assert got == pytest.approx(want)
    assert sum(loads.stages) == pytest.approx(loads.seconds)
    # and by program: the rows hold every second and every load
    rows = loads.snapshot().values()
    for stage in tracing.LOAD_STAGES:
        assert sum(r[f"{stage}_s"] for r in rows) == pytest.approx(want[stage])
    assert sum(r["loads"] for r in rows) == loads.loads
    assert sum(r["cache_hits"] for r in rows) == loads.cache_hits


def test_a_trace_with_hundreds_of_reports_directly_inside_it_counts_each_once(
    monkeypatch,
):
    """An unrolled stack: 48 layers of 20 jitted calls each, every one a
    report of its own inside ONE outer trace (PR 60: with the 64 newest kept,
    the outer's own seconds held the 896 dropped ones a second time)."""
    loads = tracing.ProgramLoads()
    inner = [(10.0 + 0.01 * (i + 1), "trace", 0.005) for i in range(960)]
    _feed(loads, monkeypatch, inner + [
        (20.0, "trace", 10.5),          # 9.5..20: the 960 and 5.7 s of its own
        (21.0, "lower", 1.0), (23.0, "compile", 2.0),
    ])
    got = dict(zip(tracing.LOAD_STAGES, loads.stages))
    assert got == pytest.approx(
        {"trace": 10.5, "lower": 1.0, "compile": 2.0, "cache_read": 0.0})
    assert loads.seconds == pytest.approx(13.5)
    assert loads.snapshot()["jit_step"]["trace_s"] == pytest.approx(10.5)


def test_only_a_retrieval_inside_it_makes_a_compile_a_cache_read(monkeypatch):
    """The retrieval is reported inside the compile event it answers, on its
    thread: that compile is ``cache_read``; the next one, with no retrieval
    of its own, is ``compile`` again, and so is one on another thread while
    this thread's retrieval waits for its compile event."""
    loads = tracing.ProgramLoads()
    _feed(loads, monkeypatch, [
        (10.0, "read", 0.25), (10.5, "compile", 1.0),      # a hit: 1 s
        (20.0, "compile", 3.0),                             # a miss: 3 s
        (30.0, "read", 0.5),                                # pending here
    ])
    other = threading.Thread(target=lambda: loads._duration(
        _EVENTS["compile"], 2.0, fun_name="jit_other"))
    other.start()
    other.join()
    _feed(loads, monkeypatch, [(31.0, "compile", 0.75)])
    got = dict(zip(tracing.LOAD_STAGES, loads.stages))
    assert got == pytest.approx(
        {"trace": 0.0, "lower": 0.0, "compile": 5.0, "cache_read": 1.75})
    assert (loads.loads, loads.cache_hits) == (4, 2)
    rows = loads.snapshot()
    assert rows["jit_other"] == {
        "loads": 1, "cache_hits": 0, "trace_s": 0.0, "lower_s": 0.0,
        "compile_s": 2.0, "cache_read_s": 0.0,
    }
    assert rows["jit_step"]["loads"] == 3 and rows["jit_step"]["cache_hits"] == 2
    assert rows["jit_step"]["cache_read_s"] == pytest.approx(1.75)
    assert rows["jit_step"]["compile_s"] == pytest.approx(3.0)


def test_two_programs_on_two_threads_keep_separate_rows(monkeypatch):
    """What a thread gathered since its last load is its own: two threads
    tracing at once, each row gets its thread's seconds and no other's."""
    loads = tracing.ProgramLoads()
    clock = threading.local()           # each thread's reports end to start
    monkeypatch.setattr(tracing.time, "time", lambda: clock.now)
    traced = threading.Barrier(2)

    def load(name, trace_s, compile_s):
        clock.now = 100.0
        loads._duration(_EVENTS["trace"], trace_s, fun_name=name)
        traced.wait(timeout=10.0)       # both pending before either compiles
        clock.now = 101.0
        loads._duration(_EVENTS["lower"], 0.25, fun_name=name)
        clock.now = 110.0
        loads._duration(_EVENTS["compile"], compile_s, fun_name=name)

    threads = [
        threading.Thread(target=load, args=("jit_a", 1.0, 4.0)),
        threading.Thread(target=load, args=("jit_b", 2.0, 8.0)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rows = loads.snapshot()
    assert set(rows) == {"jit_a", "jit_b"}
    for name, trace_s, compile_s in (("jit_a", 1.0, 4.0), ("jit_b", 2.0, 8.0)):
        assert rows[name] == {
            "loads": 1, "cache_hits": 0, "trace_s": trace_s, "lower_s": 0.25,
            "compile_s": compile_s, "cache_read_s": 0.0,
        }
    assert loads.seconds == pytest.approx(15.5) == pytest.approx(sum(loads.stages))


def test_boot_marks_are_written_once_and_in_order(monkeypatch):
    """A fresh process's marks: the first recorder writes ``engine_build``,
    the constructor's end ``engine_built``, the first ``submit``
    ``first_request``; a second engine moves none of them and shows the same
    gauges; an engine without a recorder marks nothing."""
    from distributed_llm_inference_tpu.config import TraceConfig

    boot = tracing.BootMarks()
    monkeypatch.setattr(tracing, "BOOT", boot)
    monkeypatch.setattr(eng_mod, "BOOT", boot)
    assert 0.0 < time.time() - boot.start < 24 * 3600.0     # the OS's, not ours
    assert boot.snapshot() == {
        "start": boot.start, "engine_build": None, "engine_built": None,
        "first_request": None,
    }
    bare = small_engine()
    bare.submit([1, 2, 3], SamplingOptions(max_new_tokens=2))
    assert boot.engine_build is None and boot.first_request is None
    assert "boot_" not in bare.metrics.prometheus()
    eng = small_engine(trace_cfg=TraceConfig())
    assert boot.first_request is None
    assert eng.metrics.get_gauge("boot_engine_built_seconds") == boot.engine_built
    assert "boot_first_request" not in eng.metrics.prometheus()
    _serve(eng)
    marks = boot.snapshot()
    assert 0.0 < marks["engine_build"] <= marks["engine_built"] <= marks["first_request"]
    assert marks["first_request"] <= time.time() - boot.start
    second = small_engine(trace_cfg=TraceConfig())
    _serve(second)
    assert boot.snapshot() == marks
    for m in (eng.metrics, second.metrics):
        assert m.get_gauge("process_start_time_seconds") == boot.start
        for name in tracing.BOOT_MARKS:
            assert m.get_gauge(f"boot_{name}_seconds") == marks[name]
    # /metrics keeps the start to the millisecond, epoch seconds as it is
    line = next(
        l for l in eng.metrics.prometheus().splitlines()
        if l.startswith("dli_process_start_time_seconds ")
    )
    assert float(line.split()[1]) == pytest.approx(boot.start, abs=1e-3)


def test_an_engine_that_served_a_request_shows_its_loads_by_stage(
    compiles_cold, monkeypatch
):
    """The four stage counters are on ``/metrics`` beside the sum, and sum to
    it; with the persistent cache off (the session's is on, ``conftest``:
    this test compiles cold, and counts from nothing, as a process that has
    just started does) nothing is a cache read; the programs' rows hold what
    the counters hold."""
    from distributed_llm_inference_tpu.config import TraceConfig

    for name, value in vars(tracing.ProgramLoads()).items():
        if name not in ("_lock", "_installed"):  # the listener stays as it is
            monkeypatch.setattr(tracing.PROGRAM_LOADS, name, value)
    with compiles_cold():
        eng = small_engine(trace_cfg=TraceConfig())
        _serve(eng)
    m = eng.metrics
    stages = {
        s: m.get_counter(f"engine_program_load_{s}_seconds")
        for s in tracing.LOAD_STAGES
    }
    text = m.prometheus()
    for s in tracing.LOAD_STAGES:
        assert f"dli_engine_program_load_{s}_seconds_total" in text, s
    assert stages["trace"] > 0 and stages["lower"] > 0 and stages["compile"] > 0
    assert stages["cache_read"] == 0.0
    assert sum(stages.values()) == pytest.approx(
        m.get_counter("engine_program_load_seconds"))
    rows = tracing.PROGRAM_LOADS.snapshot()
    assert sum(r["loads"] for r in rows.values()) == tracing.PROGRAM_LOADS.loads
    assert all(r["cache_hits"] == 0 and r["loads"] >= 1 for r in rows.values())
    assert sum(
        r[f"{s}_s"] for r in rows.values() for s in tracing.LOAD_STAGES
    ) == pytest.approx(tracing.PROGRAM_LOADS.seconds, rel=1e-6)


def test_a_process_that_exits_with_the_watcher_busy_exits_cleanly():
    """The watcher is a daemon thread that waits inside JAX: left there
    while the interpreter finalizes it aborts the process (exit 134), which
    a server would report as a failed run. The recorder's finalizer ends
    and joins it at exit."""
    import subprocess
    import sys

    code = (
        "import jax.numpy as jnp\n"
        "from distributed_llm_inference_tpu.utils import tracing\n"
        "fr = tracing.FlightRecorder()\n"
        "fr.snapshot()\n"
        "fr.begin()\n"
        "assert fr.clock.armed\n"
        "x = jnp.zeros(())\n"
        "for _ in range(200):\n"
        "    fr.clock.leave(fr.clock.enter(), x + 1, 'decode', 1)\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True,
    )
    assert done.returncode == 0, done.stderr[-2000:]


# -- the lease: the clock runs while somebody reads the ticks ------------------

CLOCK_NAMES = (
    "engine_clocked_ticks", "engine_device_", "engine_dispatches_",
    "engine_decode_steps", "engine_enqueue_seconds",
    "engine_first_token_prefill", "engine_first_token_deliver",
)


def _watchers():
    return [t for t in threading.enumerate() if t.name == "dispatch-clock"]


def _clock_lines(metrics):
    """What ``/metrics`` says under the clock's names."""
    return [
        line for line in metrics.prometheus().splitlines()
        if any(name in line for name in CLOCK_NAMES)
    ]


def _serve(engine, prompt=(1, 2, 3), new=5):
    """One request to its end; the ids of the ticks that served it."""
    first = engine.flight.tick
    engine.submit(list(prompt), SamplingOptions(max_new_tokens=new))
    while engine.has_work():
        engine.step()
    return range(first, engine.flight.tick)


def _records(engine, ids):
    """The ring's records of the ticks ``ids``, read without watching."""
    with engine.flight._lock:
        return [t for t in engine.flight._ring if t["tick"] in ids]


def _programs(engine):
    """The step programs this engine has (``_prefill_fresh`` is None where
    every row prefills through its table), by attribute name."""
    return {
        name: getattr(engine, name) for name in eng_mod._CLOCKED_PROGRAMS
        if getattr(engine, name) is not None
    }


def _wrapped(engine):
    """The step programs that stand behind a ``_clocked`` wrapper."""
    return [
        name for name, fn in _programs(engine).items()
        if not hasattr(fn, "lower")
    ]


def test_an_engine_nobody_watches_runs_no_dispatch_clock():
    """``TraceConfig()`` alone arms nothing: ticks run and are recorded with
    no wrapper around a step program (the call is the jitted program's
    own), no watcher thread, no ``dispatch_clock`` field, none of the
    clock's names on ``/metrics`` and no entry remembered by a session."""
    from distributed_llm_inference_tpu.config import TraceConfig

    assert not _watchers()
    eng = small_engine(trace_cfg=TraceConfig())
    ids = _serve(eng)
    clock = eng.flight.clock
    assert not clock.armed and not _watchers() and clock._thread is None
    assert not _wrapped(eng) and not eng._unclocked
    records = _records(eng, ids)
    assert any(t["dispatches"] for t in records)
    assert not any("dispatch_clock" in t for t in records)
    assert not clock.entries and not clock._pending and not eng.flight._marks
    text = eng.metrics.prometheus()
    assert "engine_ticks" in text and "engine_first_token_wait" in text
    assert not _clock_lines(eng.metrics)
    # the count of the programs the process loads is not the clock's
    assert eng.metrics.get_counter("engine_program_loads") > 0


def test_a_read_of_the_ticks_arms_the_clock_for_a_lease():
    """One ``snapshot()`` arms it: the next ticks carry the field and the
    counters run; the lease's end stops the thread and the counters; a
    second lease starts clean, with no idle gap across the stretch."""
    from distributed_llm_inference_tpu.config import TraceConfig

    eng = small_engine(trace_cfg=TraceConfig())
    clock, m = eng.flight.clock, eng.metrics
    _serve(eng)                                 # unarmed: loads the programs
    before = time.time_ns()
    eng.flight.snapshot()
    assert before + 1e9 < clock.lease_ns <= time.time_ns() + (
        tracing.CLOCK_LEASE_S * 1e9
    )
    assert not clock.armed                      # the drive thread's to do
    clock.lease(600.0)                          # the test's own: no race
    programs = _programs(eng)
    assert not _wrapped(eng)
    ids = _serve(eng)
    assert clock.armed
    # while armed every step program stands behind the clock's wrapper
    assert _wrapped(eng) == list(programs)
    assert all(
        getattr(eng, name).__wrapped__ is fn for name, fn in programs.items()
    )
    first = _records(eng, ids)
    assert all("dispatch_clock" in t for t in first)
    assert all(
        len(t["dispatch_clock"]) == len(t["dispatches"]) for t in first
    )
    assert m.get_counter("engine_clocked_ticks") == len(first)
    assert m.get_counter("engine_dispatches_decode") > 0
    with m._lock:
        assert len(m._timings["engine_first_token_prefill_own"]) == 1
    # the lease runs out: the next tick disarms, the watcher ends
    clock.lease_ns = 0.0
    eng.step()
    assert not clock.armed
    assert all(getattr(eng, name) is fn for name, fn in programs.items())
    deadline = time.monotonic() + 5.0
    while _watchers() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _watchers()
    counted = _clock_lines(m)
    ids = _serve(eng)
    assert not any("dispatch_clock" in t for t in _records(eng, ids))
    assert counted and _clock_lines(m) == counted
    # a second lease, two tenths of a second later: the device's idle time
    # starts with it
    time.sleep(0.2)
    idle = m.get_counter("engine_device_idle_seconds")
    clock.lease(600.0)
    ids = _serve(eng)
    second = [c for t in _records(eng, ids) for c in t["dispatch_clock"]]
    assert second and second[0]["idle_ms"] == 0.0
    assert m.get_counter("engine_device_idle_seconds") - idle < 0.2
    assert len(_watchers()) <= 1
    with m._lock:
        assert len(m._timings["engine_first_token_prefill_own"]) == 2
        assert len(m._timings["engine_first_token_wait"]) == 4
    eng.flight.clock.stop()
    assert not _watchers() and not clock.armed


def test_a_session_admitted_before_the_arming_has_no_pieces():
    """The clock saw the end of its prompt and not its admission: a wait is
    observed and the pieces are not, and so for a session whose first token
    comes after the lease's end."""
    from distributed_llm_inference_tpu.config import TraceConfig
    from distributed_llm_inference_tpu.engine.session import Session

    eng = small_engine(trace_cfg=TraceConfig())
    clock, m = eng.flight.clock, eng.metrics
    clock.lease(600.0)
    _serve(eng)
    entry = next(
        c for t in _records(eng, range(eng.flight.tick))
        for c in t["dispatch_clock"]
    )

    def first_token(admitted_at):
        s = Session([1, 2, 3], SamplingOptions(max_new_tokens=1))
        s.admit_time, s.first_token_time = admitted_at, time.monotonic()
        s.prompt_clock = [entry]
        eng._note_first_token(s)
        assert s.prompt_clock == []
        with m._lock:
            return (len(m._timings["engine_first_token_wait"]),
                    len(m._timings["engine_first_token_prefill_own"]))

    assert first_token(clock.armed_at + 0.001) == (2, 2)
    assert first_token(clock.armed_at - 0.001) == (3, 2)
    clock.lease_ns = 0.0
    eng.step()
    assert first_token(clock.armed_at + 0.001) == (4, 2)
    clock.stop()
