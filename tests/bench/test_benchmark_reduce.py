"""The reduction from a profiler trace to numbers: busy time as a union of
intervals, module durations by name, custom-call and all-reduce shares,
several device planes — on made-up planes where the answer is known, and on
a cut of a trace recorded on the v5e (``benchmark/reduce/sample_trace.json``,
my chip run, PR 22)."""

import json
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


def test_a_kernel_is_named_without_xlas_instance_number():
    assert xplane.kernel_name("custom-call:quantized_paged_fused_attention.5") == "quantized_paged_fused_attention"
    assert xplane.kernel_name("custom-call:%closed_call.17") == "closed_call"
    assert xplane.kernel_name("custom-call:flash_attention") == "flash_attention"
    text = ("%quantized_paged_fused_attention.5 = (bf16[32,1,32,128]{3,2,1,0}) "
            'custom-call(s32[32]{0} %x), custom_call_target="tpu_custom_call"')
    assert xplane.kernel_name(xplane.short_op_name(text)) == "quantized_paged_fused_attention"


def test_two_instances_of_one_kernel_fold_into_one_name():
    ops = [
        ("custom-call:quantized_paged_fused_attention.3", 0, 40),
        ("custom-call:quantized_paged_fused_attention.5", 100, 60),
        ("custom-call:quantized_ragged_paged_attention.2", 200, 500),
        ("fusion:fusion.7", 40, 60),
    ]
    out = xplane.reduce_trace([plane(0, ops)])
    assert out["kernels_device0"] == {
        "quantized_paged_fused_attention": {"count": 2, "sum_s": pytest.approx(100e-9)},
        "quantized_ragged_paged_attention": {"count": 1, "sum_s": pytest.approx(500e-9)},
    }
    # the instances stay apart where the driver's breakdown lists them
    assert dict(out["device_ops"])["custom-call:quantized_paged_fused_attention.5"] == pytest.approx(60e-9)
    assert out["custom_call_s"] == pytest.approx(600e-9)


def test_what_is_no_custom_call_is_no_kernel_and_device_0_alone_is_listed():
    ops = [
        ("fusion:quantized_paged_fused_attention.3", 0, 40),      # a name is not enough
        ("all-reduce:all-reduce.4", 50, 10),
        ("while:while.6", 0, 100),
    ]
    other = plane(1, [("custom-call:flash_attention.2", 0, 30)])
    out = xplane.reduce_trace([plane(0, ops), other])
    assert out["kernels_device0"] == {}
    # the smallest kernel is listed, where ``device_ops`` keeps ten
    many = [(f"fusion:fusion.{i}", 100 * i, 90) for i in range(12)]
    out = xplane.reduce_trace([plane(0, many + [("custom-call:tiny_kernel.1", 5000, 1)])])
    assert "custom-call:tiny_kernel.1" not in dict(out["device_ops"])
    assert out["kernels_device0"] == {"tiny_kernel": {"count": 1, "sum_s": pytest.approx(1e-9)}}


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


#: the reduction of ``sample_trace.json`` as PR 24's ``reduce_trace`` gave it
#: (computed at ``e62b8c4`` on the CPU: a sum over a recorded file, no time
#: of this machine's). ``kernels_device0`` is added beside these, and none
#: of them moves.
SAMPLE_AT_PR24 = {'devices': 1,
 'window_s': 0.34520555,
 'busy_s': 0.345183732,
 'custom_call_s': 0.005600318,
 'all_reduce_device0_s': 0.0,
 'busy_device0_s': 0.345183732,
 'modules_device0_s': {'jit__carry_scatter': [8.39e-07, 4.107e-06],
                       'jit_convert_element_type': [5.92e-07, 5.93e-07, 5.93e-07, 5.92e-07, 5.93e-07,
                                                    5.92e-07, 5.92e-07, 5.92e-07],
                       'jit__threefry_split': [3.461e-06, 3.447e-06],
                       'jit__unstack': [8.5e-07, 9.18e-07],
                       'jit__prefill_row': [0.342276313],
                       'jit_squeeze': [5.43e-07, 5.45e-07, 5.42e-07],
                       'jit__carry_combine': [1.001e-06],
                       'jit__table_write_batch': [6.889e-06],
                       'jit__decode_scan': [0.731470859]},
 'device_ops': [['custom-call:closed_call.17', 0.005600315], ['fusion:fusion.204', 0.001954536],
                ['fusion:abs_reduce_fusion.14', 0.001471734], ['fusion:abs_reduce_fusion.17', 0.000683993],
                ['fusion:fusion.203', 0.000625062], ['fusion:constant_dynamic-slice_fusion.8', 0.00051409],
                ['fusion:convolution_convert_fusion.5', 0.000362796],
                ['fusion:clamp_convert_fusion.17', 0.000258697],
                ['fusion:bitcast_dynamic-update-slice_fusion.5', 0.000257804],
                ['fusion:constant_dynamic-slice_fusion.7', 0.000255288]],
 'idle_gaps': [['before jit_convert_element_type', 1.2769e-05], ['before jit__prefill_row', 4.247e-06],
               ['before jit__unstack', 1.805e-06], ['before jit__carry_scatter', 1.72e-06],
               ['inside jit__threefry_split', 8.94e-07], ['inside jit__prefill_row', 3.72e-07],
               ['inside jit__carry_scatter', 1.1e-08]],
 'longest_gap_s': 1.2769e-05}


def test_recorded_v5e_sample_every_other_key_is_what_it_was():
    out = xplane.reduce_trace(xplane.read_sample(SAMPLE))
    kernels = out.pop("kernels_device0")
    assert json.loads(json.dumps(out)) == SAMPLE_AT_PR24
    # recorded before PR 23 named the kernels: the one custom call of the cut
    # is the ragged prefill kernel under Pallas's default name; XLA's own
    # custom calls (``custom-call.14``, 0 to 1 ns each) have no other name
    assert set(kernels) == {"closed_call", "custom-call"}
    assert kernels["closed_call"] == {"count": 1, "sum_s": pytest.approx(0.005600315)}
    assert kernels["custom-call"]["sum_s"] < 1e-7
    assert kernels and all(k["count"] > 0 and k["sum_s"] > 0 for k in kernels.values())
    listed = {}
    for name, seconds in out["device_ops"]:
        if xplane.is_custom_call(name):
            k = xplane.kernel_name(name)
            listed[k] = listed.get(k, 0.0) + seconds
    for name, seconds in listed.items():
        assert kernels[name]["sum_s"] >= seconds - 1e-12
    assert sum(k["sum_s"] for k in kernels.values()) >= out["custom_call_s"] - 1e-12
