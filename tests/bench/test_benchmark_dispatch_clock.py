"""The thirteen readers of the engine's dispatch-clock counters
(``benchmark/layer_metrics/``, through ``benchmark/clock_counters.py``): their
arithmetic on a made-up run, ``None`` where the program has no such counter
(the parent tree, or a window in which nothing moved), and their entries in
``BENCHMARK.json``. Counts only: no number here is a device's."""

import importlib
import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

OPEN = {
    "engine_ticks": 100.0, "engine_clocked_ticks": 60.0,
    "engine_enqueue_seconds": 1.0,
    "engine_device_seconds_prefill": 2.0, "engine_device_seconds_decode": 10.0,
    "engine_dispatches_prefill": 10.0, "engine_dispatches_decode": 50.0,
    "engine_decode_steps": 800.0,
    "engine_device_idle_seconds": 0.5,
    "engine_device_idle_admit_seconds": 0.1,
    "engine_device_idle_dispatch_seconds": 0.2,
    "engine_device_idle_blocked_seconds": 0.05,
    "engine_device_idle_outside_seconds": 0.15,
    "engine_first_token_prefill_wait_seconds_sum": 3.0,
    "engine_first_token_prefill_wait_seconds_count": 10.0,
    "engine_first_token_prefill_own_seconds_sum": 1.0,
    "engine_first_token_prefill_own_seconds_count": 10.0,
    "engine_first_token_deliver_seconds_sum": 0.5,
    "engine_first_token_deliver_seconds_count": 10.0,
    "engine_program_loads": 120.0, "engine_program_load_seconds": 33.5,
}
# the window: 44 ticks, 40 of them with the clock armed; 4 prefills of 0.25 s, 2 chunks of 0.5 s (the first the
# process ran), 30 decode dispatches of 16 steps in 9 s; the device waited
# 1.5 s: 0.3 admit, 0.6 dispatch, 0.1 blocked, 0.2 deliver (the first), 0.3
# outside; the calls held the thread 2.4 s; 5 first tokens
CLOSE = dict(OPEN, **{
    "engine_ticks": 144.0, "engine_clocked_ticks": 100.0,
    "engine_enqueue_seconds": 3.4,
    "engine_device_seconds_prefill": 3.0, "engine_device_seconds_chunk": 1.0,
    "engine_device_seconds_decode": 19.0,
    "engine_dispatches_prefill": 14.0, "engine_dispatches_chunk": 2.0,
    "engine_dispatches_decode": 80.0, "engine_decode_steps": 1280.0,
    "engine_device_idle_seconds": 2.0,
    "engine_device_idle_admit_seconds": 0.4,
    "engine_device_idle_dispatch_seconds": 0.8,
    "engine_device_idle_blocked_seconds": 0.15,
    "engine_device_idle_deliver_seconds": 0.2,
    "engine_device_idle_outside_seconds": 0.45,
    "engine_first_token_prefill_wait_seconds_sum": 4.7,
    "engine_first_token_prefill_wait_seconds_count": 15.0,
    "engine_first_token_prefill_own_seconds_sum": 1.9,
    "engine_first_token_prefill_own_seconds_count": 15.0,
    "engine_first_token_deliver_seconds_sum": 0.55,
    "engine_first_token_deliver_seconds_count": 15.0,
    "engine_program_loads": 121.0, "engine_program_load_seconds": 34.0,
})
# metric -> (by hand, a counter without whose movement it has no value)
BY_HAND = {
    "engine_device_idle_pct": (100.0 * 1.5 / (1.5 + 1.0 + 1.0 + 9.0), None),
    "device_idle_admit_ms": (1e3 * 0.3 / 40, "engine_clocked_ticks"),
    "device_idle_dispatch_ms": (1e3 * 0.6 / 40, "engine_clocked_ticks"),
    "device_idle_deliver_ms": (1e3 * (0.1 + 0.2 + 0.3) / 40, "engine_clocked_ticks"),
    "decode_step_device_ms": (1e3 * 9.0 / 480, "engine_decode_steps"),
    "prefill_dispatch_device_ms": (1e3 * 2.0 / 6, None),
    "device_prefill_share_pct": (100.0 * 2.0 / 11.0, None),
    "enqueue_wait_ms": (1e3 * 2.4 / 40, "engine_clocked_ticks"),
    "first_token_prefill_wait_ms_mean": (
        1e3 * 1.7 / 5, "engine_first_token_prefill_wait_seconds_count"),
    "first_token_prefill_own_ms_mean": (
        1e3 * 0.9 / 5, "engine_first_token_prefill_own_seconds_count"),
    "first_token_deliver_ms_mean": (
        1e3 * 0.05 / 5, "engine_first_token_deliver_seconds_count"),
}
AT_OPEN = {"setup_program_load_s": 33.5, "setup_programs_loaded": 120.0}
NAMES = sorted(BY_HAND) + sorted(AT_OPEN)


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def stub(opened, closed):
    return types.SimpleNamespace(metrics_open=opened, metrics_close=closed)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_the_hand_computed_value(name):
    want, _ = BY_HAND[name]
    assert reader(name).read(stub(OPEN, CLOSE)) == pytest.approx(want)
    # a counter the window's first reading did not have yet started at zero
    fresh = stub({}, {k: CLOSE[k] - OPEN.get(k, 0.0) for k in CLOSE})
    assert reader(name).read(fresh) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(AT_OPEN))
def test_the_set_up_readers_take_the_opening_reading_not_a_delta(name):
    assert reader(name).read(stub(OPEN, CLOSE)) == AT_OPEN[name]
    assert reader(name).read(stub(OPEN, None)) == AT_OPEN[name]


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_without_its_counters(name):
    """The parent commit's program has none of these counters (its readings
    hold the tick clock's and the censuses' alone): the line then leaves the
    metric out, and nothing raises. Nor has a window in which the counter
    under the ratio did not move."""
    parent = {"engine_ticks": 140.0, "engine_tick_seconds": 9.0}
    assert reader(name).read(stub(dict(parent, engine_ticks=100.0), parent)) is None
    assert reader(name).read(stub({}, {})) is None
    assert reader(name).read(stub(None, None)) is None
    still = BY_HAND.get(name, (None, None))[1]
    if still is not None:
        assert reader(name).read(stub(OPEN, dict(CLOSE, **{still: OPEN[still]}))) is None
        gone = {k: v for k, v in CLOSE.items() if k != still}
        assert reader(name).read(stub(OPEN, gone)) is None


def test_a_kind_the_window_never_dispatched_counts_nothing():
    """tp4 chunks nothing and a decode-only window prefills nothing: the
    kind's counter does not exist, and the sums go on without it."""
    closed = {k: v for k, v in CLOSE.items() if "chunk" not in k}
    run = stub(OPEN, closed)
    assert reader("prefill_dispatch_device_ms").read(run) == pytest.approx(1e3 * 1.0 / 4)
    assert reader("device_prefill_share_pct").read(run) == pytest.approx(100.0 / 10.0)
    only_decode = {k: v for k, v in closed.items() if "prefill" not in k}
    opened = {k: v for k, v in OPEN.items() if "prefill" not in k}
    run = stub(opened, only_decode)
    assert reader("prefill_dispatch_device_ms").read(run) is None
    assert reader("device_prefill_share_pct").read(run) == 0.0
    assert reader("engine_device_idle_pct").read(run) == pytest.approx(100.0 * 1.5 / 10.5)


def test_entries_name_the_layer_the_source_and_the_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-13:]] == [
        "engine_device_idle_pct", "device_idle_admit_ms",
        "device_idle_dispatch_ms", "device_idle_deliver_ms",
        "decode_step_device_ms", "prefill_dispatch_device_ms",
        "device_prefill_share_pct", "enqueue_wait_ms",
        "first_token_prefill_wait_ms_mean", "first_token_prefill_own_ms_mean",
        "first_token_deliver_ms_mean", "setup_program_load_s",
        "setup_programs_loaded",
    ]
    for name in NAMES:
        m = per_layer[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["layer"] == reader(name).LAYER and reader(name).DEVICE_METRIC
        if name.startswith("first_token_"):
            assert m["workloads"] == ["mistral-7b.chat"]
            assert m["moves"] == "ttft_ms_p50"
        else:
            assert "workloads" not in m  # every cell
            assert m["moves"] == ("setup_s" if name in AT_OPEN else "tpot_ms_p50")
    # the pieces are cut from the wait that ``first_token_wait_ms_mean`` reads
    assert per_layer["first_token_wait_ms_mean"]["workloads"] == ["mistral-7b.chat"]
