"""``decode_joint_tile_pages_pct`` (ISSUE 42): the reader's arithmetic on a
stub run whose ``/metrics`` readings are given dictionaries (no server, no
JAX). The share of a decode dispatch's live pages that lie in full blocks of
the in-place sweep (its tiles, unpadded); a program without the counter pair (the
parent of PR 42, and every commit before it) gives nothing and does not
raise."""

import importlib
import types

import pytest

READER = "benchmark.layer_metrics.decode_joint_tile_pages_pct"
OPEN = {"decode_pages_live": 1000.0, "decode_pages_joint": 600.0}
CLOSE = {"decode_pages_live": 9000.0, "decode_pages_joint": 6200.0}


def stub(opened, closed):
    return types.SimpleNamespace(metrics_open=opened, metrics_close=closed)


def read(opened, closed):
    return importlib.import_module(READER).read(stub(opened, closed))


def test_the_share_is_the_windows_and_not_the_runs():
    assert read(OPEN, CLOSE) == pytest.approx(100.0 * 5600 / 8000)
    # a counter the window's first reading did not have yet started at zero
    assert read({}, {k: CLOSE[k] - OPEN[k] for k in CLOSE}) == pytest.approx(70.0)


@pytest.mark.parametrize("cell", ["a mesh", "a latent pool", "a window pool"])
def test_where_another_path_decodes_it_reads_zero_and_not_nothing(cell):
    """tp4 (no Pallas under a mesh) and reason1k (the one-plane form by the
    grid) count the live pages and no joint one; a window layer's three
    pages never fill a block."""
    closed = {"decode_pages_live": 4000.0, "decode_pages_joint": 0.0}
    assert read({"decode_pages_live": 1000.0, "decode_pages_joint": 0.0},
                closed) == 0.0


@pytest.mark.parametrize("missing", ["decode_pages_live", "decode_pages_joint", "both"])
def test_a_program_without_the_counters_gives_nothing(missing):
    gone = set(CLOSE) if missing == "both" else {missing}
    closed = {k: v for k, v in CLOSE.items() if k not in gone}
    assert read({}, closed) is None
    assert read(None, None) is None


def test_no_decode_dispatch_in_the_window_gives_nothing():
    assert read(CLOSE, CLOSE) is None


def test_the_reader_names_its_layer_and_is_a_count():
    reader = importlib.import_module(READER)
    assert reader.LAYER == "kernels" and reader.DEVICE_METRIC is False
