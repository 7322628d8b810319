"""``utils/xplane.py``: from a profiler trace to the device's busy and idle
time and the host phase each idle gap belongs to. The arithmetic on made-up
planes; the reading of a real trace on the CPU, where the host plane carries
the engine's ticks and phases (a CPU trace has no device plane: every device
number below is made up, none is measured)."""

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, ModelConfig, TraceConfig,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.utils import tracing, xplane


def ev(name, start, dur, **stats):
    return (name, start, dur, stats)


def host(*events):
    return {"name": xplane.HOST_PLANE,
            "lines": [{"name": "engine-driver", "events": list(events)}]}


def device(n, ops, modules=()):
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": xplane.OPS_LINE, "events": list(ops)},
        {"name": xplane.MODULES_LINE, "events": list(modules)},
    ]}


# one tick 100..200: admit 100..130 (blocked 110..120 inside it), dispatch
# 130..190 (blocked 150..180, then deliver 180..188 inside it); a second
# tick 260..300 with no region at all
HOST = host(
    ev("engine_tick", 100, 100, step_num=7),
    ev("engine.admit", 100, 30), ev("engine.blocked", 110, 10),
    ev("engine.dispatch", 130, 60), ev("engine.blocked", 150, 30),
    ev("engine.deliver", 180, 8),
    ev("engine_tick", 260, 40, step_num=8),
)


def test_host_segments_take_the_innermost_region():
    assert xplane.host_segments([HOST]) == [
        (100, 110, "admit"), (110, 120, "blocked"), (120, 130, "admit"),
        (130, 150, "dispatch"), (150, 180, "blocked"), (180, 188, "deliver"),
        (188, 190, "dispatch"), (190, 200, "admit"), (260, 300, "admit"),
    ]


def test_busy_is_a_union_and_idle_goes_to_the_enclosing_phase():
    ops = [
        ev("%fusion.1 = f32[2]{0} fusion(%p)", 90, 30),             # 90..120
        ev("%fusion.2 = f32[2]{0} fusion(%p)", 100, 15),            # nested
        ev("%while.3 = (s32[]) while(%t)", 140, 50),                # 140..190
        ev("%closed_call.4 = f32[2]{0} custom-call(%q)", 150, 20),  # its body
        ev("%fusion.1 = f32[2]{0} fusion(%p)", 250, 20),            # 250..270
    ]
    out = xplane.reduce_planes([
        HOST, device(1, [ev("%copy.9 = f32[] copy(%a)", 90, 90)]),
        device(0, ops, [ev("jit__decode_step(123)", 140, 50)]),
    ])
    d0, d1 = out["devices"]
    assert d0["plane"] == "/device:TPU:0"  # by number, not by file order
    assert (d0["first_ns"], d0["last_ns"]) == (90, 270)
    assert d0["busy_ns"] == 30 + 50 + 20 and d0["idle_ns"] == 80
    assert d1["busy_ns"] == 90 and d1["idle_ns"] == 0
    # containers are left out, their bodies listed; durations sum by name
    assert out["ops_ns"] == {
        "fusion:fusion.1": 50, "fusion:fusion.2": 15,
        "custom-call:closed_call.4": 20,
    }
    assert out["op_counts"]["fusion:fusion.1"] == 2
    assert out["modules_ns"] == {"jit__decode_step": 50}
    # gap 120..140: admit to 130, dispatch after; gap 190..250: the tick's
    # last 10 ns (admit), then between two ticks
    assert out["idle_by_phase_ns"] == {
        "admit": 10 + 10, "dispatch": 10, "blocked": 0, "deliver": 0,
        "outside": 50,
    }
    assert sum(out["idle_by_phase_ns"].values()) == d0["idle_ns"]
    assert out["ticks"] == [7, 8]
    assert out["host_by_phase_ns"] == {
        "admit": 10 + 10 + 10 + 40, "dispatch": 22, "blocked": 40,
        "deliver": 8, "outside": 60,  # 200..260, between the two ticks
    }


def test_a_trace_without_a_device_plane_reduces_to_the_host_alone():
    out = xplane.reduce_planes([HOST])
    assert out["devices"] == [] and out["idle_by_phase_ns"] == {}
    assert out["ticks"] == [7, 8] and not out["ops_ns"]


def test_ops_inside_lists_a_programs_operations_a_compiled_shape_at_a_time():
    """``ops_inside`` (``tools/xplane_profile.py --inside``): device 0's
    operations inside the executions of the programs whose name holds the
    word, by module fingerprint (a prefill's pad widths come apart), the
    containers left out and the other programs' operations with them."""
    wide = "%fusion.1 = f32[1,2048,2432]{2,1,0} fusion(%p)"
    copy = "%copy.2 = bf16[640,2,64,128]{3,1,2,0} copy(%pool)"
    ops = [
        ev("%while.3 = (s32[]) while(%t)", 100, 60),        # a container
        ev(wide, 100, 40), ev(copy, 140, 20),               # wide run 1
        ev("%fusion.1 = f32[1,512,2432]{2,1,0} fusion(%p)", 200, 10),
        ev(wide, 300, 44), ev(copy, 344, 16),               # wide run 2
        ev("%fusion.9 = f32[2]{0} fusion(%z)", 400, 30),    # the decode scan's
    ]
    modules = [
        ev("jit__prefill_row(11)", 100, 62), ev("jit__prefill_row(22)", 200, 12),
        ev("jit__prefill_row(11)", 300, 61), ev("jit__decode_scan(5)", 400, 30),
    ]
    from tools.xplane_profile import ops_inside

    out = ops_inside(
        [HOST, device(1, ops, modules), device(0, ops, modules)], "_prefill_row"
    )
    assert sorted(out) == ["jit__prefill_row(11)", "jit__prefill_row(22)"]
    wide_runs, narrow = out["jit__prefill_row(11)"], out["jit__prefill_row(22)"]
    assert (wide_runs["runs"], wide_runs["ns"]) == (2, 123)
    assert wide_runs["ops_ns"] == {"fusion:fusion.1": 84, "copy:copy.2": 36}
    assert wide_runs["op_counts"] == {"fusion:fusion.1": 2, "copy:copy.2": 2}
    assert wide_runs["text"]["copy:copy.2"] == copy     # the shapes say what
    assert (narrow["runs"], narrow["ns"]) == (1, 12)
    assert narrow["ops_ns"] == {"fusion:fusion.1": 10}
    assert "[1,512,2432]" in narrow["text"]["fusion:fusion.1"]
    assert ops_inside([HOST], "_prefill_row") == {}


def test_short_op_name():
    text = "%closed_call.41 = bf16[32,8,4,128]{3,2,1,0} custom-call(%a, %b)"
    assert xplane.short_op_name(text) == "custom-call:closed_call.41"
    assert xplane.short_op_name("jit__decode_step") == "jit__decode_step"


def test_a_cpu_trace_carries_the_ticks_and_phases_on_the_host_plane(tmp_path):
    """The benchmark's profiler options (host tracer 1, python tracer 0):
    every ``step()`` is an ``engine_tick`` step whose ``step_num`` is its tick
    record's id, with the ``engine.<phase>`` regions nested in it."""
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(max_batch_size=2, prefill_buckets=(8,), max_seq_len=32,
                     dtype="float32", decode_steps=1),
        CacheConfig(kind="dense"), trace_cfg=TraceConfig(),
    )
    opts = SamplingOptions(max_new_tokens=3)
    eng.generate([[1, 2, 3]], opts)  # compile outside the trace
    before = eng.flight.tick
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.generate([[4, 5, 6]], opts)
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    out = xplane.aggregate(path)
    assert out["ticks"] == list(range(before, eng.flight.tick))
    assert out["devices"] == []
    host_ms = out["host_by_phase_ns"]
    assert set(host_ms) == set(tracing.PHASES)
    for phase in ("admit", "dispatch", "blocked", "deliver"):
        assert host_ms[phase] > 0, phase
    # the tick records time the same regions on the host's own clock
    ticks = [t for t in eng.flight.snapshot() if t["tick"] >= before]
    traced = (sum(host_ms.values()) - host_ms["outside"]) / 1e6
    recorded = sum(t["host_ms"] for t in ticks)
    assert traced == pytest.approx(recorded, rel=0.2)
    text = "\n".join(xplane.describe(path, like=["engine."]))
    assert "'engine_tick'" in text and "step_num" in text
    assert "'engine.blocked'" in text


def test_the_dispatch_clock_joins_the_trace_dispatch_by_dispatch():
    """Made-up planes and ticks: the trace's clock starts 1000 ns before the
    epoch's here (tick 7's ``t0_ns`` 1100 is its event's 100). Four noted
    dispatches, the first of which the trace's start cut and the last of
    which its end (both are left out): the join anchors the first whole
    module event on the ready stamp nearest its end and takes the rest in
    turn; a gap's idle is what no operation covers, and falls to the phases
    that hold it on both sides."""
    modules = [
        ev("jit__decode_scan(12)", 100, 3),             # cut: it ran 145
        ev("jit__prefill_row(11)", 105, 20),            # 105..125
        ev("jit_convert_element_type(5)", 126, 2),      # not a noted program
        ev("jit__decode_scan(12)", 140, 50),            # 140..190
        ev("jit__prefill_row_nosample(13)", 262, 30),   # 262..292
        ev("jit__prefill_row(11)", 293, 2),             # cut by the end
    ]
    ops = [
        ev("%fusion.1 = f32[2]{0} fusion(%p)", 105, 20),
        ev("%convert.2 = f32[2]{0} convert(%p)", 126, 2),
        ev("%while.3 = (s32[]) while(%t)", 140, 50),
        ev("%fusion.4 = f32[2]{0} fusion(%p)", 262, 30),
    ]

    def clock(enq, ready, device_ms, idle_ms=0.0, **by_phase):
        entry = {"enq_ns": enq, "ret_ns": enq + 1, "ready_ns": ready,
                 "device_ms": device_ms, "idle_ms": idle_ms}
        if by_phase:
            entry["idle_phase_ms"] = by_phase
        return entry

    ticks = [
        {"tick": 6, "t0_ns": 900, "dispatches": [("decode", (2, 16, 4), 9)],
         "dispatch_clock": [clock(905, 1050, 145e-6)]},
        {"tick": 7, "t0_ns": 1100, "dispatches": [
            ("prefill", (1, 8), 3), ("decode", (2, 16, 4), 9)],
         "dispatch_clock": [
             clock(1102, 1126, 24e-6),
             clock(1138, 1191, 53e-6, 12e-6, admit=4e-6, dispatch=8e-6)]},
        {"tick": 8, "t0_ns": 1260, "dispatches": [
            ("chunk", (1, 8), 8), ("prefill", (1, 8), 2)],
         "dispatch_clock": [
             clock(1261, 1293, 32e-6, 70e-6, admit=11e-6, outside=59e-6),
             clock(1270, None, None)]},     # not ready when polled
    ]
    out = xplane.join_dispatches([HOST, device(0, ops, modules)], ticks)
    assert out["offset_ns"] == 1000 and out["unmatched"] == 0
    assert [(p["tick"], p["index"], p["kind"]) for p in out["pairs"]] == [
        (7, 0, "prefill"), (7, 1, "decode"), (8, 0, "chunk"),
    ]
    assert [p["trace_ms"] for p in out["pairs"]] == [20e-6, 50e-6, 30e-6]
    assert [p["clock_ms"] for p in out["pairs"]] == [24e-6, 53e-6, 32e-6]
    # gap 125..140 holds a 2 ns operation; gap 190..262 none
    assert [p.get("trace_idle_ms") for p in out["pairs"]] == [
        None, pytest.approx(13e-6), pytest.approx(72e-6)]
    assert [p.get("clock_idle_ms") for p in out["pairs"]] == [None, 12e-6, 70e-6]
    assert out["kinds"]["decode"] == {
        "n": 1, "trace_ms": 50e-6, "clock_ms": 53e-6, "worst": out["pairs"][1],
    }
    assert out["kinds"]["prefill"]["worst"]["tick"] == 7
    idle = out["idle"]
    assert idle["trace_ms"] == pytest.approx(85e-6)
    assert idle["clock_ms"] == pytest.approx(82e-6)
    # 125..126 and 128..130 admit, 130..140 dispatch; 190..200 admit,
    # 200..260 between two ticks, 260..262 admit
    assert idle["by_phase"] == {
        "admit": [pytest.approx(15e-6), pytest.approx(15e-6)],
        "dispatch": [pytest.approx(10e-6), pytest.approx(8e-6)],
        "blocked": [0.0, 0.0], "deliver": [0.0, 0.0],
        "outside": [pytest.approx(60e-6), pytest.approx(59e-6)],
    }
    assert sum(t for t, _ in idle["by_phase"].values()) == pytest.approx(
        idle["trace_ms"])
    # no device plane, or ticks the trace does not hold: nothing to join
    assert xplane.join_dispatches([HOST], ticks)["pairs"] == []
    assert xplane.join_dispatches(
        [HOST, device(0, ops, modules)], [{**ticks[0], "tick": 99}]
    )["pairs"] == []
    # what ``tools/xplane_profile.py --ticks`` prints of it
    from tools import xplane_profile

    lines = xplane_profile.clock_report(out, every=True)
    assert lines[0].startswith("3 dispatches matched, 0 module events unmatched")
    assert any(ln.startswith("decode") and "7.1" in ln for ln in lines)
    assert lines[-1].startswith("8.0 chunk:")
    assert xplane_profile.clock_report(
        xplane.join_dispatches([HOST], ticks)
    ) == ["no noted dispatch of these ticks ran in this trace"]
