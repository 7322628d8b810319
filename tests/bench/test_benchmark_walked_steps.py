"""``latent_sweep_steps_walked_pct`` (ISSUE 48): the reader's arithmetic on a
stub run whose ``/metrics`` readings are given dictionaries (no server, no
JAX). The share of rows x table blocks the latent pool's decode sweep walks;
a program without the counter pair (the parent of PR 48, and every commit
before it) gives nothing and does not raise."""

import importlib
import json
import os
import types

import pytest

READER = "benchmark.layer_metrics.latent_sweep_steps_walked_pct"
OPEN = {"decode_sweep_steps_walked": 1000.0, "decode_sweep_steps_grid": 2000.0}
CLOSE = {"decode_sweep_steps_walked": 5400.0, "decode_sweep_steps_grid": 10000.0}


def read(opened, closed):
    run = types.SimpleNamespace(metrics_open=opened, metrics_close=closed)
    return importlib.import_module(READER).read(run)


def test_the_share_is_the_windows_and_not_the_runs():
    assert read(OPEN, CLOSE) == pytest.approx(100.0 * 4400 / 8000)
    # a counter the window's first reading did not have yet started at zero
    assert read({}, {k: CLOSE[k] - OPEN[k] for k in CLOSE}) == pytest.approx(55.0)


@pytest.mark.parametrize(
    "missing", ["decode_sweep_steps_walked", "decode_sweep_steps_grid", "both"]
)
def test_a_program_without_the_counters_gives_nothing(missing):
    gone = set(CLOSE) if missing == "both" else {missing}
    closed = {k: v for k, v in CLOSE.items() if k not in gone}
    assert read({}, closed) is None
    assert read(None, None) is None


def test_no_decode_dispatch_in_the_window_gives_nothing():
    assert read(CLOSE, CLOSE) is None


def test_the_entry_names_the_kernels_layer_and_the_cells_that_walk():
    reader = importlib.import_module(READER)
    assert reader.LAYER == "kernels" and reader.DEVICE_METRIC is False
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "latent_sweep_steps_walked_pct"]
    assert entry == bench["per_layer"][-1]
    assert (entry["layer"], entry["source"], entry["moves"], entry["better"]) == (
        reader.LAYER, "program_counter", "tpot_ms_p50", "lower")
    # (glm's sweep under a selection walks no list yet: nothing to read)
    assert entry["workloads"] == [
        "moonlight-16b-a3b.reason1k", "xing4.0-29b-a4b.reason1k",
    ]
