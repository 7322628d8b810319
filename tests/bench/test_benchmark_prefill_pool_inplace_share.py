"""``prefill_pool_inplace_share_pct`` (ISSUE 58): the reader's arithmetic on a
stub run whose ``/metrics`` readings are given dictionaries (no server, no
JAX), and its entry in ``BENCHMARK.json`` looked up by name (wherever in the
list it stands), whose cells are cells the benchmark has and report the
end-to-end metric the share moves."""

import importlib
import types

import pytest

from test_benchmark_rehearsal import bench

NAME = "prefill_pool_inplace_share_pct"
CELLS = [
    "mistral-7b.chat", "mistral-7b.reason", "mixtral-8x7b-8l.rag",
    "k-exaone-236b-a23b.mixedlen", "ouro-2.6b.mathchat",
]
OPEN = {"prefill_pool_inplace_rows": 30.0, "prefill_pool_scatter_rows": 10.0}
CLOSE = {"prefill_pool_inplace_rows": 57.0, "prefill_pool_scatter_rows": 13.0}


def reader():
    return importlib.import_module(f"benchmark.layer_metrics.{NAME}")


def read(opened, closed):
    return reader().read(
        types.SimpleNamespace(metrics_open=opened, metrics_close=closed))


def test_the_share_is_the_windows_and_not_the_runs():
    assert read(OPEN, CLOSE) == pytest.approx(100.0 * 27 / 30)     # mixed
    # a counter the window's first reading did not have yet started at zero
    assert read({}, {"prefill_pool_inplace_rows": 27.0,
                     "prefill_pool_scatter_rows": 3.0}) == pytest.approx(90.0)


@pytest.mark.parametrize("closed,want", [
    ({"prefill_pool_inplace_rows": 66.0}, 100.0),   # the int8 pool, in place
    ({"prefill_pool_scatter_rows": 66.0}, 0.0),     # a layer's planes handed out
], ids=["every-row-in-place", "every-row-through-a-layers-planes"])
def test_a_counter_the_program_never_moved_counts_as_zero(closed, want):
    assert read({}, closed) == pytest.approx(want)
    assert read(None, closed) == pytest.approx(want)


@pytest.mark.parametrize("opened,closed", [
    ({}, {}), (None, None), (OPEN, OPEN), (CLOSE, CLOSE),
    ({"engine_ticks": 3.0, "prefill_table_rows": 2.0},
     {"engine_ticks": 9.0, "prefill_table_rows": 8.0}),
], ids=["empty", "no-readings", "still-open", "still-close", "the-parent"])
def test_where_neither_counter_moved_there_is_nothing(opened, closed):
    assert read(opened, closed) is None


def test_the_entry_names_the_cache_the_counter_and_cells_that_are_there():
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == NAME]
    assert reader().LAYER == "cache" and not reader().DEVICE_METRIC
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": reader().LAYER,
        "moves": "tpot_ms_p50", "workloads": CELLS,
    }
    (judged,) = [m for m in bench()["end_to_end"] if m["name"] == "tpot_ms_p50"]
    assert set(CELLS) <= set(
        judged.get("workloads", [c["name"] for c in bench()["workloads"]]))
    assert set(CELLS) <= {c["name"] for c in bench()["workloads"]}
    assert any(m["layer"] == "cache" and m["name"] != NAME
               for m in bench()["per_layer"])   # a layer the file already names


def test_the_engine_has_both_counters():
    from distributed_llm_inference_tpu.utils import metrics

    for name in OPEN:
        assert metrics.METRICS[name][0] == "counter"
