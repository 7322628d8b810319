"""Two things later PRs lean on: the four-chip configuration runs on four
virtual CPU devices, and a new configuration (of a family that is there, or
of one the benchmark has never seen), traffic mix, per-layer metric and cell
are FILES AND ENTRIES ADDED — no file that is there is edited, the tests
under ``tests/bench`` among them."""

import json
import os
import re
import shutil
import subprocess
import sys

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


def copy_of_the_tree(tmp_path):
    """The benchmark, the program and ``BENCHMARK.json`` in a directory of
    their own, and every benchmark file's modification time."""
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
    return tree, before


def assert_no_file_was_edited(tree, before):
    for path, mtime in before.items():
        assert os.path.getmtime(tree / path) == mtime, f"{path} was edited"


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    tree, before = copy_of_the_tree(tmp_path)
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
    assert_no_file_was_edited(tree, before)


LATENT_WEIGHTS = '''"""Seeded weights of a latent-attention (MLA) decoder in the program's
parameter layout: ``dense_gqa``'s one jitted call over this family's
matrices."""

import jax.numpy as jnp

from benchmark.weights import dense_gqa


def make(cfg, seed, dtype, stored, mesh=None):
    lat = cfg.latent
    h, f, hq, d = cfg.hidden_size, cfg.intermediate_size, cfg.num_heads, cfg.head_dim
    dn, dr = lat.nope_head_dim or d, lat.rope_head_dim
    shapes = {
        "wq": (h, hq * (dn + dr)), "wkv_a": (h, lat.rank + dr),
        "wk_b": (lat.rank, hq, dn), "wv_b": (lat.rank, hq, d),
        "wo": (hq * d, h), "wg": (h, f), "wu": (h, f), "wd": (f, h),
    }
    return dense_gqa.build(
        cfg, seed, dtype, stored, shapes, mesh=mesh,
        extra_layer=lambda key: {"kv_norm": jnp.ones((lat.rank,), dtype)},
    )
'''

LATENT_REFERENCE = '''"""Plain reference: multi-head latent attention as the DeepSeek-V2 paper
(arXiv:2405.04434, section 2.1) and ``models/llama.py`` ``_latent_attention``'s
docstring state it, in the PLAIN order: every position's K and V are
decompressed per head from the shared latent, the decoupled rotary key is
shared by the heads, rope touches the rope slices only, and the scale is
``(dn + dr) ** -0.5``. Not the absorbed form the program runs. Float32, one
sequence, no cache; imports nothing of the program."""

import sys

import jax
import jax.numpy as jnp

from benchmark.reference.dense_gqa import F32, mlp, rms_norm, rope, weight


def attention(cfg, lp, x):
    s, hq = x.shape[0], cfg["num_attention_heads"]
    rank, dn, dr = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    pos = jnp.arange(s)
    q = (x @ weight(lp["wq"])).reshape(s, hq, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])], -1)
    ckv = x @ weight(lp["wkv_a"])
    c = rms_norm(ckv[:, :rank], lp["kv_norm"], cfg["rms_norm_eps"])
    k_rope = rope(ckv[:, None, rank:], pos, cfg["rope_theta"])
    k_nope = jnp.einsum("sr,rhd->shd", c, lp["wk_b"].astype(F32))
    v = jnp.einsum("sr,rhd->shd", c, lp["wv_b"].astype(F32))
    assert v.shape[-1] == cfg["v_head_dim"]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (s, hq, dr))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * F32(dn + dr) ** -0.5
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, -1) @ weight(lp["wo"])


def forward(cfg, params, tokens):
    print("the reference received:", " ".join(sorted(cfg)), file=sys.stderr)
    x = params["embed"].astype(F32)[tokens]

    def layer(x, lp):
        x = x + attention(cfg, lp, rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"]))
        return x + mlp(cfg, lp, rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]) @ weight(params["lm_head"])
'''


def test_a_configuration_of_a_new_family_needs_only_new_files_and_entries(tmp_path):
    """A family the benchmark does not have: the program's own ``mla``
    (``model_type`` ``deepseek_v2``; ``kv_lora_rank``, ``qk_rope_head_dim``
    and ``qk_nope_head_dim`` are keys no configuration here carries), a
    paged latent cache, its weight maker and its plain float32 reference,
    all written into a copy of the tree as NEW files, one cell over a
    traffic file that is there. What each assertion guards, by the line of
    the harness at ``e62b8c4`` (PR 24) that stopped it:

    * ``correct`` at all: ``benchmark/server.py:42-48`` ``HF_KEYS`` and
      ``:68-69`` ``hf_block`` passed fifteen Mistral/Mixtral keys on, so
      ``ModelConfig.from_hf_config`` (``:312``) never saw ``kv_lora_rank``,
      ``cfg.latent`` was ``None`` and the weight maker had nothing to size;
    * ``the reference received … kv_lora_rank``: the same list stood before
      ``reference.forward`` (``check_numerics``, ``:186``);
    * ``numerics`` under 1e-4: ``:115-119`` ``probe`` built its cache as
      ``type(like).create(…, cfg.num_kv_heads, cfg.head_dim, …)``, which
      ``LatentPagedKVCache.create`` refuses (one head of ``lat_dim``:
      ``cache/latent.py:81-85``), so ``correct`` (b) could not be computed;
    * no modification time moved: the promise itself;
    * the copy's own tests pass: ``tests/bench/test_benchmark_reference.py:133-147``
      held every entry of ``configs`` to Mistral's four widths, and any test
      there that takes every entry and asserts one family's facts would stop
      the PR as surely, since it may not edit that file either."""
    tree, before = copy_of_the_tree(tmp_path)
    (tree / "benchmark/weights/latent_mla.py").write_text(LATENT_WEIGHTS)
    (tree / "benchmark/reference/latent_mla.py").write_text(LATENT_REFERENCE)
    correct = {"probe_prompt_tokens": 20, "decode_steps": 16, "tolerance": 1e-4,
               "reason": "float32 weights, activations and latent pool: only "
                         "the order of sums differs"}
    conf = {
        "name": "tiny-mla", "source": "made up for the test",
        "model_type": "deepseek_v2", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "q_lora_rank": None, "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "max_position_embeddings": 512, "tie_word_embeddings": False,
        "reduced": {}, "assumed": {}, "deployment": "a test",
        "serve": {
            "chips": 1, "mesh": None, "dtype": "float32", "weights": "float32",
            "weight_maker": "latent_mla", "reference": "latent_mla",
            "engine": {"max_batch_size": 4, "max_seq_len": 128,
                       "prefill_buckets": [8, 16, 32]},
            "cache": {"kind": "paged", "kv_quant": None, "page_size": 8,
                      "num_pages": 96, "max_pages_per_session": 16},
        },
        "correct": correct,
        "rehearse": {"serve": {}, "correct": correct},    # tiny as it is
    }
    json.dump(conf, open(tree / "benchmark/configs/tiny-mla.json", "w"))
    b = json.load(open(tree / "BENCHMARK.json"))
    b["configs"].append({"name": "tiny-mla", "source": "made up for the test",
                         "file": "benchmark/configs/tiny-mla.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-mla.chat32", "config": "tiny-mla",
                           "traffic": "chat-closed32", "chips": 1, "why": "test"})
    json.dump(b, open(tree / "BENCHMARK.json", "w"))

    proc = run_cell(tmp_path, "tiny-mla.chat32", trace=0, cwd=str(tree))
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    received = [ln for ln in proc.stderr.splitlines()
                if ln.startswith("the reference received:")]
    assert received and {"kv_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
                         "model_type"} <= set(received[0].split())
    assert not {"serve", "correct", "name"} & set(received[0].split())
    more = detail(proc)
    assert more["numerics"]["ok"] and more["numerics"]["layers"] == 2
    assert max(more["numerics"]["prefill"], more["numerics"]["decode_max"]) < 1e-4
    assert more["numerics"]["unrelated"] > 0.5
    assert more["served_path"]["counters_ok"]
    assert_no_file_was_edited(tree, before)
    assert_the_copys_own_tests_pass(tree, "tiny-mla")


def assert_the_copys_own_tests_pass(tree, name):
    """The tests that take their cases from ``BENCHMARK.json`` are files
    under ``paths`` too: the PR that adds a configuration may not edit them,
    so they must pass AS THEY ARE with its entry there. Run in the copy: the
    whole of the in-process files (a case of another configuration must not
    mind the new one either) and the test that walks ``BENCHMARK.json``; the
    rehearsed cell itself has just run above. ``name``'s own cases must be
    among them: the reference, the import and the ``reduced`` rule."""
    bench = os.path.join("tests", "bench")
    shutil.copytree(os.path.join(REPO, bench), tree / bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "tests", "conftest.py"), tree / "tests")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider", "-p", "no:xdist",
         os.path.join(bench, "test_benchmark_reference.py"),
         os.path.join(bench, "test_benchmark_arithmetic.py"),
         os.path.join(bench, "test_benchmark_reduce.py"),
         os.path.join(bench, "test_benchmark_rehearsal.py")
         + "::test_benchmark_json_names_only_files_that_exist"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    mine = re.findall(rf"::(\w+)\[{re.escape(name)}\] (\w+)", proc.stdout)
    assert {"test_prefill_then_decode_through_the_paged_cache_agrees_with_the_reference",
            "test_the_reference_imports_nothing_of_the_program",
            "test_every_configuration_file_names_what_exists_and_cuts_no_width",
            } <= {test for test, _ in mine}
    assert {result for _, result in mine} == {"PASSED"}, mine


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
