"""``decode_steps_per_dispatch`` (ISSUE 49): the reader's arithmetic on a stub
run whose ``/metrics`` readings are given dictionaries (no server, no JAX),
its entry, and one rehearsal of the cell it is read in,
``mistral-7b-bf16-tp4.chat32``, on the CPU's four virtual devices: the mesh
engine decodes through the fused write-behind scan (the value-dtype paged
cache's gathered tail, ``cache/paged.py``), and the probe's fused branch is
that path."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "mistral-7b-bf16-tp4.chat32"
READER = "benchmark.layer_metrics.decode_steps_per_dispatch"
OPEN = {"engine_enqueue_seconds": 1.0, "engine_dispatches_decode": 50.0,
        "engine_decode_steps": 800.0}
CLOSE = {"engine_enqueue_seconds": 3.4, "engine_dispatches_decode": 80.0,
         "engine_decode_steps": 1280.0}


def read(opened, closed):
    sys.path.insert(0, REPO)
    run = types.SimpleNamespace(metrics_open=opened, metrics_close=closed)
    return importlib.import_module(READER).read(run)


@pytest.mark.parametrize("steps,want", [(480.0, 16.0), (30.0, 1.0)],
                         ids=["fused", "a-token-a-dispatch"])
def test_the_count_is_the_windows_and_not_the_runs(steps, want):
    closed = dict(CLOSE, engine_decode_steps=OPEN["engine_decode_steps"] + steps)
    assert read(OPEN, closed) == pytest.approx(want)
    # the clock is armed after the open reading: its counters start at zero
    assert read({}, {k: closed[k] - OPEN[k] for k in closed}) == pytest.approx(want)


@pytest.mark.parametrize("missing", sorted(CLOSE))
def test_a_program_without_the_clock_gives_nothing(missing):
    closed = {k: v for k, v in CLOSE.items() if k != missing}
    assert read({}, closed) is None
    assert read(None, None) is None


def test_no_decode_dispatch_in_the_window_gives_nothing():
    assert read(CLOSE, CLOSE) is None


def test_the_entry_names_the_host_loop_and_the_mesh_cell():
    sys.path.insert(0, REPO)
    reader = importlib.import_module(READER)
    assert reader.LAYER == "engine host loop" and reader.DEVICE_METRIC is True
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "decode_steps_per_dispatch"]
    assert (entry["layer"], entry["source"], entry["moves"], entry["better"],
            entry["unit"]) == (
        reader.LAYER, "program_span", "tpot_ms_p50", "higher", "count")
    assert entry["workloads"] == [CELL]


def test_the_mesh_cell_rehearses_on_the_fused_path(tmp_path):
    """One ``--rehearse-cpu`` run of the four-chip cell (``benchmark/
    server.py`` makes the four virtual devices from ``--chips``): every
    request served, and ``probe``'s fused branch (``engine.decode_steps >
    1``: ``multi_decode_apply`` under the mesh) within the rehearsal's 0.01
    of the float32 reference over its 16 steps. That the engine built from
    the cell's rehearsal configuration resolves 16 steps a dispatch,
    pipelined, is asserted on an engine built here: the run prints the
    probe's steps, not the engine's."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "5", "--seconds", "3", "--trace", "0",
         "--out", str(tmp_path / "out"), "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    detail, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    numerics = detail["numerics"]
    assert numerics["ok"] and numerics["decode_steps"] == 16
    assert numerics["tolerance"] == 0.01 and max(numerics["judged"]) < 0.01

    import jax

    from benchmark import server
    from distributed_llm_inference_tpu.config import (
        CacheConfig, EngineConfig, MeshConfig, ModelConfig,
    )
    from distributed_llm_inference_tpu.engine.engine import InferenceEngine
    from distributed_llm_inference_tpu.models import llama

    conf = server.load_config(os.path.join(
        REPO, "benchmark", "configs", "mistral-7b-bf16-tp4.json"), True)
    serve = conf["serve"]
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    ekw = dict(serve["engine"], prefill_buckets=tuple(
        serve["engine"]["prefill_buckets"]))
    engine = InferenceEngine(
        cfg, llama.init_params(cfg, jax.random.PRNGKey(0), dtype=serve["dtype"]),
        EngineConfig(dtype=serve["dtype"], **ekw), CacheConfig(**serve["cache"]),
        mesh_cfg=MeshConfig(**serve["mesh"]),
    )
    assert engine.mesh is not None and not engine.cache.use_kernel
    assert engine.decode_steps == 16 and engine._pipelined
