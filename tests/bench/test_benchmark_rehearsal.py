"""``benchmark/run.py --rehearse-cpu`` end to end, at the tiny size the
configuration and traffic files carry: the same two processes, gateway,
warm-up, window and reports as on the chip. Each run is a child process with
a time limit of its own and a compile cache of its own; none waits on a chip.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(tmp_path, workload, trace, seconds="3", rehearse=True, cwd=REPO, timeout=240):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["JAX_PLATFORMS"] = "cpu"
    argv = [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
            "--workload", workload, "--seed", "5", "--seconds", seconds,
            "--trace", str(trace), "--out", str(tmp_path / "out")]
    if rehearse:
        argv.append("--rehearse-cpu")
    return subprocess.run(argv, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def detail(proc):
    return json.loads(proc.stdout.strip().splitlines()[-2])


def one_chip_configurations():
    """``(configuration, its cells)`` for every configuration of
    ``BENCHMARK.json`` that serves on one chip. The tests take their cases
    from here, so a configuration a later PR adds is tested with no edit."""
    b = bench()
    out = []
    for config in b["configs"]:
        cells = [w for w in b["workloads"] if w["config"] == config["name"]]
        if cells and all(w["chips"] == 1 for w in cells):
            out.append((config["name"], cells))
    return out


def first_cells_on_one_chip():
    """``(cell, loop)`` of each such configuration's first cell; the loop is
    its generator's."""
    sys.path.insert(0, REPO)
    out = []
    for _, cells in one_chip_configurations():
        with open(os.path.join(REPO, "benchmark", "traffic",
                               cells[0]["traffic"] + ".json")) as f:
            generator = json.load(f)["generator"]
        loop = importlib.import_module(f"benchmark.generators.{generator}").LOOP
        out.append((cells[0]["name"], loop))
    return out


@pytest.mark.parametrize("workload,loop", first_cells_on_one_chip())
def test_rehearsal_end_to_end(tmp_path, workload, loop):
    proc = run_cell(tmp_path, workload, trace=0)
    line = last_line(proc)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # a CPU run reports counts only: no time, rate or utilisation under a
    # device metric's name
    assert line["metrics"] == {}
    # every number compared beside its limit: last in the line, and the last
    # lines of standard error
    assert list(line)[-1] == "compared"
    assert all(value <= limit for value, limit in line["compared"].values())
    assert "logit_distance_0" in line["compared"] and "bad_replies" in line["compared"]
    said = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [l.split()[1] for l in said] == list(line["compared"])
    assert all(l.startswith("compared ") and " limit " in l for l in said)
    more = detail(proc)
    assert more["loop"] == loop and more["numerics"]["ok"]
    served = more["served_path"]
    assert served["counters_ok"] and served["replies_ok"]
    assert served["client"] == {k: int(v) for k, v in served["gateway"].items()}
    assert served["client"]["gateway_tokens"] > 0
    table = json.load(open(tmp_path / "out" / "requests.json"))
    assert len(table) == more["requests"]["all"]
    assert all(r["got"] == r["asked"] and r["status"] == 200 for r in table)
    if loop == "closed":
        # rag-replay starts each client on the first token of the one before
        # (``start_on``), not at an instant of the clock
        firsts = {}
        for r in table:
            if r["phase"] == "traffic":
                firsts.setdefault(r["client"], r)
        assert len(firsts) > 1
        for c in sorted(firsts)[1:]:
            assert firsts[c]["sent"] >= firsts[c - 1]["first"]


def test_traced_rehearsal_reports_counts_only(tmp_path):
    line = last_line(run_cell(tmp_path, "mistral-7b.reason", trace=1))
    assert set(line) == KEYS and line["correct"] is True
    per_layer = {m["name"]: m for m in bench()["per_layer"]}
    assert set(line["metrics"]) <= set(per_layer)
    for name in line["metrics"]:
        assert per_layer[name]["source"] == "program_counter", name
    assert "compiles_in_window" in line["metrics"]
    assert 0 < line["metrics"]["batch_occupancy_pct"]["value"] <= 100
    assert "busy_s" not in line["device"] and "memory_peak_bytes" not in line["device"]


def test_without_a_tpu_the_run_fails_and_prints_no_result(tmp_path):
    proc = run_cell(tmp_path, "mistral-7b.chat", trace=0, rehearse=False)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "need 1 tpu device" in proc.stderr


def test_the_load_generator_imports_no_jax():
    code = ("import sys; sys.path.insert(0, %r); import benchmark.run, "
            "benchmark.warmup, benchmark.samples, benchmark.generators.open_poisson, "
            "benchmark.generators.closed_loop; "
            "assert 'jax' not in sys.modules, 'jax imported'" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_benchmark_json_names_only_files_that_exist():
    b = bench()
    root = os.path.join(REPO, "benchmark")
    for cell in b["workloads"]:
        assert os.path.exists(os.path.join(root, "traffic", cell["traffic"] + ".json"))
        assert any(c["name"] == cell["config"] for c in b["configs"])
    for kind, sub in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in b[kind]:
            assert os.path.exists(os.path.join(root, sub, m["name"] + ".py")), m["name"]
    sys.path.insert(0, REPO)
    for m in b["per_layer"]:
        reader = importlib.import_module(f"benchmark.layer_metrics.{m['name']}")
        assert reader.LAYER == m["layer"], m["name"]
        # a count may be printed from a CPU run; nothing else may
        assert reader.DEVICE_METRIC or m["source"] == "program_counter", m["name"]
    cells = {c["name"] for c in b["workloads"]}
    by_name = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        moved = by_name[m["moves"]]
        mine = set(m.get("workloads", cells))
        assert mine <= set(moved.get("workloads", cells)), m["name"]
    assert sum(c["chips"] == 4 for c in b["workloads"]) <= max(1, len(cells) // 4)
