"""``prefill_fresh_share_pct`` (ISSUE 55): the reader's arithmetic on a stub
run whose ``/metrics`` readings are given dictionaries (no server, no JAX),
and its entry in ``BENCHMARK.json`` looked up by name (wherever in the list
it stands), whose one cell is a cell the benchmark has and reports the
end-to-end metric the share moves."""

import importlib
import types

import pytest

from test_benchmark_rehearsal import bench

NAME = "prefill_fresh_share_pct"
CELLS = ["mistral-7b-bf16-tp4.chat32"]
OPEN = {"prefill_fresh_rows": 30.0, "prefill_table_rows": 10.0}
CLOSE = {"prefill_fresh_rows": 57.0, "prefill_table_rows": 13.0}


def reader():
    return importlib.import_module(f"benchmark.layer_metrics.{NAME}")


def read(opened, closed):
    return reader().read(
        types.SimpleNamespace(metrics_open=opened, metrics_close=closed))


def test_the_share_is_the_windows_and_not_the_runs():
    assert read(OPEN, CLOSE) == pytest.approx(100.0 * 27 / 30)     # mixed
    # a counter the window's first reading did not have yet started at zero
    assert read({}, {"prefill_fresh_rows": 27.0,
                     "prefill_table_rows": 3.0}) == pytest.approx(90.0)


@pytest.mark.parametrize("closed,want", [
    ({"prefill_fresh_rows": 66.0}, 100.0),  # every admission a fresh piece
    ({"prefill_table_rows": 66.0}, 0.0),    # a kernel reads the pages in place
], ids=["every-row-fresh", "every-row-through-its-table"])
def test_a_counter_the_program_never_moved_counts_as_zero(closed, want):
    assert read({}, closed) == pytest.approx(want)
    assert read(None, closed) == pytest.approx(want)


@pytest.mark.parametrize("opened,closed", [
    ({}, {}), (None, None), (OPEN, OPEN), (CLOSE, CLOSE),
    ({"engine_ticks": 3.0, "admit_sync_sessions": 2.0},
     {"engine_ticks": 9.0, "admit_sync_sessions": 8.0}),
], ids=["empty", "no-readings", "still-open", "still-close", "the-parent"])
def test_where_neither_counter_moved_there_is_nothing(opened, closed):
    assert read(opened, closed) is None


def test_the_entry_names_the_cache_the_counter_and_a_cell_that_is_there():
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == NAME]
    assert reader().LAYER == "cache" and not reader().DEVICE_METRIC
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": reader().LAYER,
        "moves": "out_tok_s", "workloads": CELLS,
    }
    (judged,) = [m for m in bench()["end_to_end"] if m["name"] == "out_tok_s"]
    assert set(CELLS) <= set(judged["workloads"])
    assert set(CELLS) <= {c["name"] for c in bench()["workloads"]}
    assert any(m["layer"] == "cache" and m["name"] != NAME
               for m in bench()["per_layer"])   # a layer the file already names
