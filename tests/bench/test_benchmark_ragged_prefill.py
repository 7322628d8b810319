"""The ragged prefill kernel's two readers (PR 27), on made-up records: no
server, no JAX. ``ragged_prefill_attn_roofline_pct`` counts what the VALID
tokens of each prefill dispatch of the traced span need, by
``benchmark/kernels/quantized_ragged_paged_attention.py``;
``ragged_live_tile_pct`` is a ratio of two ``/metrics`` counters over the
window, and nothing where the program has none (the parent)."""

import importlib
import json
import os
import types

import pytest

from benchmark import peaks
from benchmark.kernels import quantized_ragged_paged_attention as kernel

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_the_ragged_prefill_kernels_bytes_and_operations_come_from_shapes():
    dense = config("mistral-7b")
    # a valid position of one layer: int8 K and V of 8 heads of 128 and a
    # float32 scale a head for each, as the decode kernel's count has it,
    # and 32 query heads of 128 in and out, two bytes a value
    assert kernel.bytes_read(dense, 1, 1) == 2112 + 2 * 32 * 128 * 2
    assert kernel.bytes_read(dense, 1, 1, query_bytes=4.0) == 2112 + 2 * 32 * 128 * 4
    # one token attends to itself: QK^T and PV of 32 heads of 128
    assert kernel.operations(dense, 1, 1) == 4 * 32 * 128
    # causal pairs: n (n + 1) / 2 a prompt; two equal rows of 128 are fewer
    # pairs than one of 256, and the pad is not in it at all
    assert kernel.causal_pairs(1, 256) == 256 * 257 / 2
    assert kernel.causal_pairs(2, 256) == 2 * 128 * 129 / 2
    assert kernel.operations(dense, 1, 256) == 256 * 257 / 2 * 4 * 32 * 128
    # a 256-token prompt is bound by its bytes (5.8 us against 2.7), a
    # 2048-token one by its operations (175 us against 46)
    v5e = peaks.peaks_for("TPU v5 lite")
    for valid, by_bytes in ((256, True), (2048, False)):
        bytes_s = kernel.bytes_read(dense, 1, valid) / v5e["hbm_bytes_per_s"]
        ops_s = kernel.operations(dense, 1, valid) / v5e["bf16_flops"]
        assert (bytes_s > ops_s) == by_bytes
    assert kernel.operations(dense, 1, 2048) / v5e["bf16_flops"] == pytest.approx(
        175e-6, rel=0.01
    )


def test_the_prefill_kernels_roofline_share_is_taken_over_the_traced_span_alone():
    reader = importlib.import_module(
        "benchmark.layer_metrics.ragged_prefill_attn_roofline_pct"
    )
    dense = config("mistral-7b")
    v5e = peaks.peaks_for("TPU v5 lite")
    prefill = lambda valid, rows=1: ["prefill", [rows, 2048], valid]   # noqa: E731
    decode = ["decode", [32, 16, 38], 20000]
    ticks = {
        1: {"t0_ns": 999.5e9, "dispatches": [prefill(2000)]},        # before it
        2: {"t0_ns": 1000.5e9, "dispatches": [prefill(256), decode]},
        3: {"t0_ns": 1001.5e9, "dispatches": [decode, prefill(1024)]},
        4: {"t0_ns": 1002.5e9, "dispatches": [prefill(2000)]},       # after it
        5: {"t0_ns": 1001.7e9, "dispatches": []},                    # an idle tick
    }

    def least(valid, rows=1):
        return max(
            kernel.bytes_read(dense, rows, valid) / v5e["hbm_bytes_per_s"],
            kernel.operations(dense, rows, valid) / v5e["bf16_flops"],
        )

    def run(ticks=ticks, **closed):
        return types.SimpleNamespace(
            closed=closed, ticks=ticks, conf=dense, device={"kind": "TPU v5 lite"},
        )

    def traced(calls, least_s, share=0.02):
        return {"trace_epoch_s": [1000.0, 1002.0], "trace": {"kernels_device0": {
            "quantized_ragged_paged_attention": {"count": calls, "sum_s": least_s / share},
            "quantized_paged_fused_attention": {"count": 1024, "sum_s": 0.3},
        }}}

    # two prefill dispatches over 32 layers: the kernel took 50 times what
    # their valid tokens need
    need = 32 * (least(256) + least(1024))
    assert reader.read(run(**traced(64, need))) == pytest.approx(2.0)
    assert reader.LAYER == "kernels" and reader.DEVICE_METRIC
    # the device lags the host: a dispatch more in the trace than in the
    # records moves the count, not the mean a call
    assert reader.read(run(**traced(96, need * 96 / 64))) == pytest.approx(2.0)
    # a two-row dispatch: its record sums the rows' tokens, taken as equal
    two = {2: {"t0_ns": 1000.5e9, "dispatches": [prefill(600, rows=2)]}}
    assert reader.read(run(two, **traced(32, 32 * least(600, 2)))) == pytest.approx(2.0)
    # a chunked dispatch in the span: its queries start past 0 and the
    # record does not say where, so nothing is said (rag is not listed)
    chunked = dict(ticks)
    chunked[3] = {"t0_ns": 1001.5e9, "dispatches": [["chunk", [1, 2048], 2048]]}
    assert reader.read(run(chunked, **traced(64, need))) is None
    # records of seven dispatches against the events of two
    many = {i: {"t0_ns": (1000.1 + i / 10) * 1e9, "dispatches": [prefill(256)]}
            for i in range(7)}
    assert reader.read(run(many, **traced(64, need))) is None
    # nothing to read: no trace, no such kernel in it, no span, no tick in it
    assert reader.read(run()) is None
    assert reader.read(run(trace={"kernels_device0": {}}, trace_epoch_s=[1000.0, 1002.0])) is None
    assert reader.read(run(trace=traced(64, need)["trace"])) is None
    assert reader.read(run({1: ticks[1]}, **traced(64, need))) is None


def test_the_live_tile_share_is_the_windows_and_nothing_on_a_program_without_it():
    reader = importlib.import_module("benchmark.layer_metrics.ragged_live_tile_pct")

    def run(opened, closed):
        return types.SimpleNamespace(metrics_open=opened, metrics_close=closed)

    # 1504 tiles a one-row dispatch at width 47; 10 of them live before the
    # window, 40 of four dispatches inside it
    opened = {"ragged_attn_tiles_live": 10.0, "ragged_attn_tiles_grid": 1504.0}
    closed = {"ragged_attn_tiles_live": 50.0, "ragged_attn_tiles_grid": 5 * 1504.0}
    assert reader.read(run(opened, closed)) == pytest.approx(100 * 40 / (4 * 1504))
    assert reader.LAYER == "kernels" and not reader.DEVICE_METRIC
    # counters first seen inside the window count from zero
    assert reader.read(run({}, closed)) == pytest.approx(100 * 50 / (5 * 1504))
    # the parent has no such counter; an idle window moved none
    assert reader.read(run({"prefill_valid_tokens": 1.0}, {"prefill_valid_tokens": 9.0})) is None
    assert reader.read(run(opened, opened)) is None
    assert reader.read(run(None, None)) is None


def test_both_readers_are_entries_of_the_kernels_layer_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    one_chip = [w["name"] for w in bench["workloads"] if w["chips"] == 1]
    roof, live = entries["ragged_prefill_attn_roofline_pct"], entries["ragged_live_tile_pct"]
    assert roof["layer"] == live["layer"] == "kernels"
    assert roof["moves"] == live["moves"] == "tpot_ms_p50"
    assert roof["source"] == "device_trace" and live["source"] == "program_counter"
    # rag's prefills are chunked (nothing to read); the mesh runs no kernel
    assert roof["workloads"] == ["mistral-7b.chat", "mistral-7b.reason"]
    assert set(one_chip) >= set(live["workloads"]) >= set(roof["workloads"])
