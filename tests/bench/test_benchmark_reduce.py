"""The reduction from a profiler trace to numbers: busy time as a union of
intervals, module durations by name, custom-call and all-reduce shares,
several device planes — on made-up planes where the answer is known, and on
a cut of a trace recorded on the v5e (``benchmark/reduce/sample_trace.json``,
my chip run, PR 22)."""

import os

import pytest

from benchmark.reduce import xplane

SAMPLE = os.path.join(
    os.path.dirname(os.path.abspath(xplane.__file__)), "sample_trace.json"
)


def plane(n, ops, modules=()):
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Ops", "events": list(ops)},
        {"name": "XLA Modules", "events": list(modules)},
    ]}


def test_union_counts_overlaps_once():
    assert xplane.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert xplane.union_ns([]) == 0
    assert xplane.merged([(5, 15), (0, 10), (20, 30)]) == [(0, 15), (20, 30)]


def test_busy_is_a_union_not_a_sum_and_shares_are_of_busy():
    ops = [
        ("fusion:fusion.1", 0, 100), ("fusion:fusion.2", 50, 100),   # 150 busy
        ("custom-call:closed_call.3", 200, 50),
        ("all-reduce:all-reduce.4", 300, 100),
        ("all-reduce-start:all-reduce-start.5", 350, 100),           # overlaps .4
        ("while:while.6", 300, 150),      # holds the two above: not an op
    ]
    modules = [("jit__decode_scan(123)", 0, 250), ("jit__prefill_row(9)", 300, 150),
               ("jit__decode_scan(123)", 500, 10)]
    out = xplane.reduce_trace([plane(0, ops, modules)])
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(350e-9)
    assert out["window_s"] == pytest.approx(450e-9)
    assert out["custom_call_s"] == pytest.approx(50e-9)
    assert out["all_reduce_device0_s"] == pytest.approx(150e-9)
    assert out["modules_device0_s"]["jit__decode_scan"] == [pytest.approx(250e-9), pytest.approx(10e-9)]
    assert [n for n, _ in out["device_ops"]] == [
        "fusion:fusion.1", "fusion:fusion.2", "all-reduce:all-reduce.4",
        "all-reduce-start:all-reduce-start.5", "custom-call:closed_call.3",
    ]
    gaps = dict(out["idle_gaps"])
    assert gaps["inside jit__decode_scan"] == pytest.approx(50e-9)
    assert gaps["before jit__prefill_row"] == pytest.approx(50e-9)


def test_several_device_planes_share_one_span_and_average_busy():
    a = plane(0, [("fusion:fusion.1", 0, 100)])
    b = plane(1, [("fusion:fusion.1", 100, 300)])
    host = {"name": "/host:CPU", "lines": [{"name": "XLA Ops", "events": [("x", 0, 10**9)]}]}
    out = xplane.reduce_trace([a, b] + [host][:0])
    assert out["devices"] == 2
    assert out["window_s"] == pytest.approx(400e-9)
    assert out["busy_s"] == pytest.approx(200e-9)
    assert out["busy_device0_s"] == pytest.approx(100e-9)


def test_a_trace_with_no_device_operation_reduces_to_nothing():
    assert xplane.reduce_trace([]) is None
    assert xplane.reduce_trace([plane(0, [])]) is None


def test_an_operation_is_named_by_its_opcode_and_result():
    text = ("%closed_call.17 = (bf16[1,2048,8,4,128]{4,3,2,1,0:T(4,128)(2,1)S(1)}, "
            "s8[2]{0}) custom-call(s32[1,47]{1,0:T(1,128)} %copy-done.15), "
            'custom_call_target="tpu_custom_call"')
    assert xplane.short_op_name(text) == "custom-call:closed_call.17"
    assert xplane.is_custom_call(xplane.short_op_name(text))
    assert xplane.short_op_name("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)") == "all-reduce:all-reduce.3"
    assert xplane.is_all_reduce("all-reduce-start:ars.1")
    assert not xplane.is_all_reduce("fusion:all-reduce-like.2")
    assert xplane.short_op_name("not hlo") == "not hlo"


def test_module_names_lose_their_fingerprint():
    assert xplane.module_name("jit__decode_scan(1234567890)") == "jit__decode_scan"
    assert xplane.module_name("jit_f") == "jit_f"


def test_recorded_v5e_sample():
    planes = xplane.read_sample(SAMPLE)
    out = xplane.reduce_trace(planes)
    assert out["devices"] >= 1
    assert 0 < out["busy_s"] <= out["window_s"]
    summed = sum(d for p in planes[:1] for ln in p["lines"]
                 if ln["name"] == "XLA Ops" for _, _, d in ln["events"]) / 1e9
    # the sum of durations counts nested events twice; the union cannot
    assert out["busy_device0_s"] <= summed + 1e-12
    assert any("decode" in k or "prefill" in k for k in out["modules_device0_s"])
    assert out["device_ops"] and len(out["device_ops"]) <= 10
