"""The eight per-layer metrics of the engine's host split (ISSUE 23): each
reader's arithmetic on a stub run whose ``/metrics`` readings are given
dictionaries (no server, no JAX), and the traced CPU rehearsal of
``mistral-7b.chat``, which may print the two censuses (counts) and none of the
six times."""

import importlib
import json
import os
import types

import pytest

from test_benchmark_rehearsal import bench, last_line, run_cell

TIMES = [
    "queue_wait_ms_mean", "first_token_wait_ms_mean",
    "host_unblocked_share_pct", "tick_host_admit_ms",
    "tick_host_dispatch_ms", "tick_host_deliver_ms",
]
CENSUSES = ["prefill_pad_census_pct", "decode_grid_occupancy_pct"]

# what /metrics read when the window opened and when it closed, in the names
# benchmark/prom.py gives: 40 ticks of 50 ms in between, 30 ms of each blocked
OPEN = {
    "engine_ticks": 100.0, "engine_tick_seconds": 10.0,
    "engine_tick_admit_seconds": 1.0, "engine_tick_dispatch_seconds": 2.0,
    "engine_tick_blocked_seconds": 5.0, "engine_tick_deliver_seconds": 1.5,
    "engine_tick_outside_seconds": 0.5,
    "engine_queue_wait_seconds_sum": 3.0, "engine_queue_wait_seconds_count": 6.0,
    "engine_first_token_wait_seconds_sum": 1.0,
    "engine_first_token_wait_seconds_count": 6.0,
    "prefill_valid_tokens": 1000.0, "prefill_padded_tokens": 8192.0,
    "decode_live_positions": 5000.0, "decode_grid_positions": 100000.0,
}
CLOSE = {
    "engine_ticks": 140.0, "engine_tick_seconds": 12.0,
    "engine_tick_admit_seconds": 1.2, "engine_tick_dispatch_seconds": 2.4,
    "engine_tick_blocked_seconds": 6.2, "engine_tick_deliver_seconds": 1.58,
    "engine_tick_outside_seconds": 0.62,
    "engine_queue_wait_seconds_sum": 5.0, "engine_queue_wait_seconds_count": 10.0,
    "engine_first_token_wait_seconds_sum": 2.6,
    "engine_first_token_wait_seconds_count": 10.0,
    "prefill_valid_tokens": 1700.0, "prefill_padded_tokens": 12288.0,
    "decode_live_positions": 9000.0, "decode_grid_positions": 150000.0,
}
BY_HAND = {
    "queue_wait_ms_mean": (1e3 * 2.0 / 4, ["engine_queue_wait_seconds_count"]),
    "first_token_wait_ms_mean": (
        1e3 * 1.6 / 4, ["engine_first_token_wait_seconds_count"]),
    "host_unblocked_share_pct": (
        100.0 * (2.0 - 1.2) / 2.0, ["engine_tick_seconds"]),
    "tick_host_admit_ms": (1e3 * 0.2 / 40, ["engine_ticks"]),
    "tick_host_dispatch_ms": (1e3 * 0.4 / 40, ["engine_ticks"]),
    "tick_host_deliver_ms": (1e3 * (0.08 + 0.12) / 40, ["engine_ticks"]),
    "prefill_pad_census_pct": (100.0 * 700 / 4096, ["prefill_padded_tokens"]),
    "decode_grid_occupancy_pct": (
        100.0 * 4000 / 50000, ["decode_grid_positions"]),
}


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def stub(opened, closed):
    return types.SimpleNamespace(metrics_open=opened, metrics_close=closed)


@pytest.mark.parametrize("name", TIMES + CENSUSES)
def test_reader_gives_the_hand_computed_value(name):
    want, _ = BY_HAND[name]
    assert reader(name).read(stub(OPEN, CLOSE)) == pytest.approx(want)
    # a counter the window's first reading did not have yet started at zero
    fresh = stub({}, {k: CLOSE[k] - OPEN[k] for k in CLOSE})
    assert reader(name).read(fresh) == pytest.approx(want)


@pytest.mark.parametrize("name", TIMES + CENSUSES)
def test_reader_gives_none_without_its_counters(name):
    """The parent commit's program has none of these counters, and a window
    with no tick, admission or dispatch moves no count: the line then leaves
    the metric out, and nothing raises."""
    _, counts = BY_HAND[name]
    assert reader(name).read(stub({}, {})) is None
    assert reader(name).read(stub(None, None)) is None
    for count in counts:
        still = dict(CLOSE, **{count: OPEN[count]})
        assert reader(name).read(stub(OPEN, still)) is None
        gone = {k: v for k, v in CLOSE.items() if k != count}
        assert reader(name).read(stub(OPEN, gone)) is None


def test_entries_name_the_layer_the_source_and_the_cells():
    per_layer = {m["name"]: m for m in bench()["per_layer"]}
    cells = {c["name"] for c in bench()["workloads"]}
    for name in TIMES:
        m = per_layer[name]
        assert m["source"] == "program_span" and reader(name).DEVICE_METRIC
        assert m["layer"] == reader(name).LAYER == "engine host loop"
    for name in CENSUSES:
        m = per_layer[name]
        assert m["source"] == "program_counter"
        assert not reader(name).DEVICE_METRIC
        assert set(m["workloads"]) < cells and m["moves"] == "tpot_ms_p50"
    assert per_layer["queue_wait_ms_mean"]["workloads"] == ["mistral-7b.chat"]
    for name in TIMES[2:]:
        assert "workloads" not in per_layer[name]  # all four cells


def test_traced_rehearsal_of_chat_prints_the_censuses_and_no_time(tmp_path):
    line = last_line(run_cell(tmp_path, "mistral-7b.chat", trace=1))
    assert line["correct"] is True
    for name in CENSUSES:
        assert 0 < line["metrics"][name]["value"] <= 100, line["metrics"]
        assert line["metrics"][name]["unit"] == "%"
    assert not set(TIMES) & set(line["metrics"])
    # the census counts every dispatch; the sample it stands beside may see
    # fewer, never more valid tokens than were padded
    sample = line["metrics"].get("prefill_pad_occupancy_pct")
    assert sample is None or 0 < sample["value"] <= 100
