"""``chip_smoke.py``'s CPU rehearsal: the chip check's control flow at a
tiny size, kernels interpreted, in a process of its own (it names the
platform before jax is imported). What it says about the chip: nothing."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, **env):
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path), *args],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, **env},
    )


def test_refuses_without_a_chip(tmp_path):
    """No rehearsal option, no accelerator: non-zero, and no result line."""
    proc = _run([], tmp_path, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not carrying on" in proc.stderr


def test_rehearsal_lines(tmp_path):
    proc = _run(["--rehearse-cpu", "--seed", "3"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    head = lines[0]
    assert head["chip_smoke"] == "rehearsal-cpu" and head["seed"] == 3
    assert set(head["versions"]) == {"jax", "jaxlib", "libtpu", "flax", "numpy"}
    assert {"dir", "entries_before"} <= set(head["compile_cache"])
    checks = {d["check"]: d for d in lines if "check" in d}
    assert all(c["ok"] for c in checks.values())
    for name in (
        "load_quantized_leaf_equals_eager_quantize_int8",
        "engine_takes_the_chip_default_path",
        "round1_every_reply_complete", "round2_compiles_nothing",
        "prometheus_counters_match_client",
        "chunked_prefill_rode_the_decode_cadence",
        "kernel_logits_as_close_to_float32_as_xla_attention",
    ):
        assert name in checks, name
    rounds = [d for d in lines if "request" in d]
    assert len(rounds) == 14 and all(
        d["request"]["status"] == 200 for d in rounds
    )
    steps = {d["step"] for d in lines if "step" in d}
    assert {"prefill", "prefill_chunk", "prefill_batch", "decode_scan"} <= steps
    # Readings of time are labelled as such, never as a device metric.
    for d in lines:
        for key in d:
            if key.endswith("_s") or "wall" in key:
                assert key.startswith("smoke_reading_") or key == "compile_s"
    with open(tmp_path / "smoke.jsonl") as f:
        assert len(f.read().splitlines()) == len(lines) - 1


def test_four_chip_rehearsal_holds_the_fresh_prefill_to_float32(tmp_path):
    """``--chips 4``: the tp=4 engine's fresh-row prefill program beside its
    page-table program and the float32 table path, a pad width each (on the
    CPU in float32 the three agree; on the chip this is the bf16 check), and
    the fresh program among the recorded steps with its all-reduces."""
    proc = _run(["--rehearse-cpu", "--chips", "4", "--seed", "4"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert lines[-1]["ok"] is True and lines[-1]["device"]["count"] == 4
    checks = {d["check"]: d for d in lines if "check" in d}
    assert all(c["ok"] for c in checks.values())
    assert "prefill_fresh_holds_all_reduces" in checks
    assert "tp4_logits_as_close_to_float32_as_one_chip" in checks
    for width in (8, 16, 32):
        c = checks[f"fresh_prefill_as_close_to_float32_as_the_table_path_{width}"]
        assert c["pad_width"] == width and c["prompt_tokens"] > width // 2
        assert max(c["logits_fresh_table_between"]) < 1e-3
        assert max(c["pages_fresh_table_between"]) < 1e-3
        assert len(set(c["greedy_tokens"])) == 1
