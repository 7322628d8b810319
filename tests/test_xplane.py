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
    assert out["devices"] == [] and xplane.device_time_ps(str(tmp_path)) == 0
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
