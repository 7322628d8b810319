"""``admit_overlap_share_pct`` (ISSUE 54): the reader's arithmetic on a stub
run whose ``/metrics`` readings are given dictionaries (no server, no JAX),
its entry in ``BENCHMARK.json`` looked up by name (wherever in the list it
stands), and one traced rehearsal of ``mistral-7b-bf16-tp4.chat32`` on the
CPU's four virtual devices, whose ``tp``-only mesh engine defers an admission
that meets a tick in flight (a count, so the rehearsal may print it)."""

import importlib
import types

import pytest

from test_benchmark_rehearsal import bench, last_line, run_cell

NAME = "admit_overlap_share_pct"
CELLS = ["mistral-7b.reason", "mistral-7b-bf16-tp4.chat32"]
OPEN = {"admit_overlap_sessions": 40.0, "admit_sync_sessions": 12.0}
CLOSE = {"admit_overlap_sessions": 76.0, "admit_sync_sessions": 16.0}


def reader():
    return importlib.import_module(f"benchmark.layer_metrics.{NAME}")


def read(opened, closed):
    return reader().read(
        types.SimpleNamespace(metrics_open=opened, metrics_close=closed))


def test_the_share_is_the_windows_and_not_the_runs():
    assert read(OPEN, CLOSE) == pytest.approx(100.0 * 36 / 40)
    # a counter the window's first reading did not have yet started at zero
    assert read({}, {"admit_overlap_sessions": 36.0,
                     "admit_sync_sessions": 4.0}) == pytest.approx(90.0)


@pytest.mark.parametrize("closed,want", [
    ({"admit_sync_sessions": 66.0}, 0.0),       # the parent's mesh engine
    ({"admit_overlap_sessions": 66.0}, 100.0),  # never once synchronous
], ids=["no-overlap-counter", "no-sync-counter"])
def test_a_counter_the_program_never_moved_counts_as_zero(closed, want):
    assert read({}, closed) == pytest.approx(want)
    assert read(None, closed) == pytest.approx(want)


@pytest.mark.parametrize("opened,closed", [
    ({}, {}), (None, None), (OPEN, OPEN), (CLOSE, CLOSE),
    ({"engine_ticks": 3.0}, {"engine_ticks": 9.0}),
], ids=["empty", "no-readings", "still-open", "still-close", "other-counters"])
def test_a_window_without_an_admission_gives_nothing(opened, closed):
    assert read(opened, closed) is None


def test_the_entry_names_the_host_loop_the_counter_and_its_two_cells():
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == NAME]
    assert reader().LAYER == "engine host loop" and not reader().DEVICE_METRIC
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": reader().LAYER,
        "moves": "out_tok_s", "workloads": CELLS,
    }
    (judged,) = [m for m in bench()["end_to_end"] if m["name"] == "out_tok_s"]
    assert set(CELLS) <= set(judged["workloads"])
    assert set(CELLS) <= {c["name"] for c in bench()["workloads"]}


def test_the_traced_rehearsal_of_the_mesh_cell_overlaps_its_admissions(tmp_path):
    """32 closed-loop clients keep a tick in flight, so all but the first
    admissions of the window ride behind one; warm-up took the same path, so
    nothing compiles in the window."""
    line = last_line(run_cell(tmp_path, CELLS[1], trace=1))
    assert line["correct"] is True and line["failed"] == 0
    share = line["metrics"][NAME]
    assert share["unit"] == "%" and 50.0 < share["value"] <= 100.0, share
    assert line["metrics"]["compiles_in_window"]["value"] == 0
