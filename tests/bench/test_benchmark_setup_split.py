"""The eight readers that cut ``setup_s`` from inside the program (ISSUE 59):
four stages of the program loads and four pieces of the server process's life
up to the window. Their arithmetic on a stub run whose ``/metrics`` reading
at the window's open is a given dictionary (no server, no JAX), nothing where
the program has no such counter or gauge (the parent), their entries in
``BENCHMARK.json`` looked up by name, and one rehearsal on the CPU that prints
all eight from a real server: times of a CPU, read here for their arithmetic
and never written under a device metric's name by the benchmark itself (the
readers are device metrics, and this test alone tells them otherwise)."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from test_benchmark_rehearsal import REPO, bench, detail, last_line

STAGES = ["setup_trace_s", "setup_lower_s", "setup_compile_s", "setup_cache_read_s"]
PIECES = [
    "setup_boot_s", "setup_engine_build_s", "setup_build_to_first_request_s",
    "setup_first_request_to_window_s",
]
LAYER = {**{n: "device programs" for n in STAGES},
         **{n: "engine host loop" for n in PIECES}}
# a warm set-up: the process started at epoch 1000.5, met the engine's
# constructor 14.25 s later, left it at 16.0, took its first request at 21.5;
# 33.5 s of program loads, none of them a compile
OPEN = {
    "engine_program_loads": 120.0, "engine_program_load_seconds": 33.5,
    "engine_program_load_trace_seconds": 17.0,
    "engine_program_load_lower_seconds": 9.25,
    "engine_program_load_compile_seconds": 0.0,
    "engine_program_load_cache_read_seconds": 7.25,
    "process_start_time_seconds": 1000.5,
    "boot_engine_build_seconds": 14.25, "boot_engine_built_seconds": 16.0,
    "boot_first_request_seconds": 21.5,
}
# the window opened at 150.0 on the run's monotonic clock, which stands
# 900.0 behind the epoch: 1050.0, 28.0 s after the first request
T0, EPOCH_OFFSET = 150.0, 900.0
BY_HAND = {
    "setup_trace_s": 17.0, "setup_lower_s": 9.25, "setup_compile_s": 0.0,
    "setup_cache_read_s": 7.25, "setup_boot_s": 14.25,
    "setup_engine_build_s": 1.75, "setup_build_to_first_request_s": 5.5,
    "setup_first_request_to_window_s": 28.0,
}
# what each reader cannot do without
NEEDS = {
    "setup_trace_s": ["engine_program_load_trace_seconds"],
    "setup_lower_s": ["engine_program_load_lower_seconds"],
    "setup_compile_s": ["engine_program_load_compile_seconds"],
    "setup_cache_read_s": ["engine_program_load_cache_read_seconds"],
    "setup_boot_s": ["boot_engine_build_seconds"],
    "setup_engine_build_s": ["boot_engine_build_seconds", "boot_engine_built_seconds"],
    "setup_build_to_first_request_s": [
        "boot_engine_built_seconds", "boot_first_request_seconds"],
    "setup_first_request_to_window_s": [
        "process_start_time_seconds", "boot_first_request_seconds"],
}


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def stub(opened, closed=None, t0=T0):
    return types.SimpleNamespace(
        metrics_open=opened, metrics_close=closed, t0=t0,
        epoch_offset=EPOCH_OFFSET,
    )


@pytest.mark.parametrize("name", STAGES + PIECES)
def test_reader_gives_the_hand_computed_value_at_the_windows_open(name):
    assert reader(name).read(stub(OPEN)) == pytest.approx(BY_HAND[name])
    # not a delta: what the counters read at the window's close is not asked
    later = {k: v + 3.0 for k, v in OPEN.items()}
    assert reader(name).read(stub(OPEN, later)) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", STAGES + PIECES)
def test_reader_gives_nothing_where_the_program_has_no_such_record(name):
    """The parent commit's program has the sum and the count of the loads
    and none of these: the line then leaves the metric out, nothing raises."""
    parent = {"engine_ticks": 140.0, "engine_program_loads": 120.0,
              "engine_program_load_seconds": 33.5}
    for opened in (parent, {}, None):
        assert reader(name).read(stub(opened)) is None
    for gone in NEEDS[name]:
        assert reader(name).read(
            stub({k: v for k, v in OPEN.items() if k != gone})) is None


def test_the_pieces_tile_the_childs_life_and_the_stages_the_loads():
    run = stub(OPEN)
    assert sum(reader(n).read(run) for n in STAGES) == pytest.approx(
        reader("setup_program_load_s").read(run))
    # from the process's start to the window's open, each second once
    opened = T0 + EPOCH_OFFSET
    assert sum(reader(n).read(run) for n in PIECES) == pytest.approx(
        opened - OPEN["process_start_time_seconds"])
    # a window that never opened has no last piece, and the others stand
    assert reader("setup_first_request_to_window_s").read(stub(OPEN, t0=None)) is None
    assert reader("setup_boot_s").read(stub(OPEN, t0=None)) == 14.25


@pytest.mark.parametrize("name", STAGES + PIECES)
def test_the_entry_is_found_by_name_behind_the_ones_that_were_there(name):
    names = [m["name"] for m in bench()["per_layer"]]
    assert names.count(name) == 1
    assert names.index(name) > names.index("prefill_pool_inplace_share_pct")
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == name]
    # every cell has a set-up: no list of cells
    assert entry == {
        "name": name, "unit": "s", "better": "lower", "source": "program_span",
        "layer": LAYER[name], "moves": "setup_s",
    }
    assert reader(name).LAYER == LAYER[name] and reader(name).DEVICE_METRIC
    (judged,) = [m for m in bench()["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in judged
    assert any(m["layer"] == LAYER[name] and m["name"] not in STAGES + PIECES
               for m in bench()["per_layer"])   # a layer the file already names


def test_the_entries_are_the_only_change_and_stand_at_the_end_in_order():
    assert [m["name"] for m in bench()["per_layer"]][-8:] == STAGES + PIECES


def test_the_program_declares_the_counters_and_gauges():
    from distributed_llm_inference_tpu.utils import metrics

    assert metrics.METRICS["engine_program_load_*_seconds"][0] == "counter"
    assert metrics.METRICS["boot_*_seconds"][0] == "gauge"
    assert metrics.METRICS["process_start_time_seconds"][0] == "gauge"


def test_a_rehearsal_prints_all_eight_and_they_tile_its_set_up(tmp_path):
    """One traced rehearsal of a cell, the eight readers (and the sum they
    cut) told for this process alone that a CPU's seconds may be printed:
    a real server's counters and gauges through the real ``Run``."""
    names = STAGES + PIECES + ["setup_program_load_s"]
    code = (
        "import importlib, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from benchmark import run\n"
        f"for name in {names!r}:\n"
        "    importlib.import_module(\n"
        "        'benchmark.layer_metrics.' + name).DEVICE_METRIC = False\n"
        "sys.exit(run.main(sys.argv[1:]))\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", "mistral-7b.reason",
         "--seed", "5", "--seconds", "3", "--trace", "1",
         "--out", str(tmp_path / "out"), "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240,
    )
    line = last_line(proc)
    assert line["correct"] is True
    got = {n: line["metrics"][n]["value"] for n in names}     # all are there
    assert all(line["metrics"][n]["unit"] == "s" for n in names)
    # a cache of this run's own: every program compiled, none read
    assert got["setup_compile_s"] > 0 and got["setup_cache_read_s"] == 0.0
    assert all(got[n] > 0 for n in names if n != "setup_cache_read_s")
    assert sum(got[n] for n in STAGES) == pytest.approx(
        got["setup_program_load_s"], rel=0.01)
    # the four pieces are under the run's own set-up, by the parent's start
    more = detail(proc)
    tiled = sum(got[n] for n in PIECES)
    assert 0.0 <= more["setup_s"] - tiled < 3.0
    # and the first of them holds what the child timed from its own main()
    child = more["child_setup"]
    assert 0.0 <= got["setup_boot_s"] - (child["imports_s"] + child["weights_s"]) < 3.0
