"""Two things later PRs lean on: the four-chip configuration runs on four
virtual CPU devices, and a new configuration, traffic mix, per-layer metric
and cell are FILES AND ENTRIES ADDED — no file that is there is edited."""

import json
import os
import shutil

from test_benchmark_rehearsal import KEYS, REPO, detail, last_line, run_cell


def test_four_chip_configuration_on_four_virtual_devices(tmp_path):
    proc = run_cell(tmp_path, "mistral-7b-bf16-tp4.chat32", trace=0)
    line = last_line(proc)
    assert set(line) == KEYS and line["correct"] is True and line["failed"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    more = detail(proc)
    # float32 on every virtual device: the mesh path against the reference
    assert more["numerics"]["decode_max"] < 1e-4
    assert more["served_path"]["counters_ok"]


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    tree = tmp_path / "tree"
    shutil.copytree(os.path.join(REPO, "benchmark"), tree / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, "distributed_llm_inference_tpu"),
                    tree / "distributed_llm_inference_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tree / "BENCHMARK.json")
    before = {
        os.path.relpath(os.path.join(d, f), tree): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(tree / "benchmark") for f in fs
    }
    # a configuration: the dense family with other (tiny) sizes
    conf = json.load(open(tree / "benchmark/configs/mistral-7b.json"))
    conf["name"] = "other-7b"
    conf["rehearse"]["num_attention_heads"] = 4
    conf["rehearse"]["num_key_value_heads"] = 2
    json.dump(conf, open(tree / "benchmark/configs/other-7b.json", "w"))
    # a traffic mix: data for the generator that is there
    mix = json.load(open(tree / "benchmark/traffic/chat-closed32.json"))
    mix["rehearse"]["clients"] = 3
    json.dump(mix, open(tree / "benchmark/traffic/trio.json", "w"))
    # a per-layer metric: a reader of its own
    (tree / "benchmark/layer_metrics/requests_ended.py").write_text(
        '"""Requests that ended in the window."""\n'
        'LAYER = "gateway"\nDEVICE_METRIC = False\n\n\n'
        "def read(run):\n"
        "    lo, hi = run.t0, run.t0 + run.seconds\n"
        "    return sum(1 for r in run.records\n"
        "               if r.phase == 'traffic' and r.ended and lo <= r.ended < hi)\n"
    )
    b = json.load(open(tree / "BENCHMARK.json"))
    b["configs"].append({"name": "other-7b", "source": "made up for the test",
                         "file": "benchmark/configs/other-7b.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "other-7b.trio", "config": "other-7b",
                           "traffic": "trio", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "requests_ended", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "gateway", "moves": "tpot_ms_p50",
                           "workloads": ["other-7b.trio"]})
    json.dump(b, open(tree / "BENCHMARK.json", "w"))

    line = last_line(run_cell(tmp_path, "other-7b.trio", trace=1, cwd=str(tree)))
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["requests_ended"]["value"] == line["attempted"] > 0
    assert "batch_occupancy_pct" not in line["metrics"]      # not this cell's
    for path, mtime in before.items():
        assert os.path.getmtime(tree / path) == mtime, f"{path} was edited"


def test_without_the_program_the_run_fails_and_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has no system to measure."""
    tree = tmp_path / "bare"
    shutil.copytree(os.path.join(REPO, "benchmark"), tree / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tree / "BENCHMARK.json")
    proc = run_cell(tmp_path, "mistral-7b.chat", trace=0, cwd=str(tree), timeout=120)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    assert "the program is not here" in proc.stderr
