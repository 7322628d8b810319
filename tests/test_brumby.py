"""The ``brumby`` family (Brumby-14B-Base): power-retention layers whose
fixed-size gated state lives in the cache manager beside a short paged K/V
tail (``cache/retention.py``, ``ops/power_retention.py``), against the
benchmark's plain reference (the quadratic form; no state, no cache) on
seeded weights, on the CPU at small sizes.
"""

import dataclasses
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_gqa_retention as model_count
from benchmark import peaks
from benchmark import server as bench_server
from benchmark.kernels import power_retention_decode as decode_count
from benchmark.kernels import power_retention_prefill as prefill_count
from benchmark.layer_metrics import retention_prefill_roofline_pct
from benchmark.reference import brumby_gqa_retention as reference
from distributed_llm_inference_tpu.cache.retention import (
    ROW_FIELDS, retention_cache_class,
)
from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, MeshConfig, ModelConfig, PrefixConfig,
    RetentionConfig, TraceConfig,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import (
    get_family, validate_config,
)
from distributed_llm_inference_tpu.ops import power_retention as pr

CATALOG = {  # the catalog's ``config`` block, whole
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def tiny_hf(layers=3):
    return {
        **CATALOG, "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": layers, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "max_position_embeddings": 512, "max_window_layers": layers,
    }


def tiny_model(layers=3, seed=0):
    hf = tiny_hf(layers)
    cfg = ModelConfig.from_hf_config(hf)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    stack = params["layers"]
    # gains that are no ones, and gates that move from position to position
    for name, key in (("q_norm", 5), ("k_norm", 6)):
        stack[name] = 1 + 0.1 * jax.random.normal(
            jax.random.PRNGKey(key), stack[name].shape
        )
    stack["w_gate"] = stack["w_gate"] * 20
    return hf, cfg, params


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(autouse=True)
def _sub_chunk():
    """Every test leaves the chunk form's step as it found it."""
    before = pr.SUB_CHUNK
    yield
    pr.SUB_CHUNK = before


N, STEPS = 20, 16


def probe_tokens(seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 256, size=N), rng.integers(1, 256, size=STEPS + 1)


def gold_logits(hf, params, prompt, forced):
    tokens = jnp.asarray(np.concatenate([prompt, forced[:-1]]), jnp.int32)
    return reference.forward(hf, params, tokens)[N - 1:]


def fresh_cache(cfg, ps, positions=N + STEPS + 1, batch=1):
    pages = -(-positions // ps)
    cache = retention_cache_class(cfg.head_dim, cfg.retention.eps).create(
        cfg.num_layers, batch, batch * pages + 1, ps, pages + 1,
        cfg.num_kv_heads, cfg.head_dim, jnp.float32,
    )
    for row in range(batch):
        cache = cache.assign_pages(
            row, list(range(1 + row * pages, 1 + (row + 1) * pages))
        )
    return cache


def prefill(cfg, params, cache, tokens, width):
    padded = jnp.zeros((1, width), jnp.int32).at[0, : len(tokens)].set(
        jnp.asarray(tokens, jnp.int32)
    )
    return llama.model_apply(
        cfg, params, padded, cache, jnp.full((1,), len(tokens), jnp.int32),
        head="last",
    )


def fused_decode(cfg, params, cache, forced):
    f, one = jnp.asarray(forced, jnp.int32), jnp.ones((1,), jnp.int32)
    steps, cache = llama.multi_decode_apply(
        cfg, params, f[:1][None], cache, len(forced) - 1,
        lambda i, logits, st: (f[i + 1][None], one, st, logits),
        jnp.zeros(()), one,
    )
    return steps[:, 0], cache


# -- the configuration and the family --------------------------------------------


def test_the_catalogs_block_reads_whole_into_the_family():
    cfg = ModelConfig.from_hf_config(CATALOG)
    fam = validate_config(cfg)
    assert fam is get_family("brumby") and fam.retention and fam.qk_norm
    assert cfg.use_retention and cfg.qk_norm and cfg.sliding_window is None
    assert cfg.retention == RetentionConfig(eps=1e-6) and cfg.num_kv_heads == 8
    assert pr.feature_dim(cfg.head_dim) == 8320
    assert set(cfg.attention_kinds) == {"retention"} and not cfg.mixed_attention
    (seg,) = cfg.segments
    assert (seg.key, seg.attention, seg.count, seg.pool) == (
        "layers", "retention", 40, None
    )


def test_what_the_block_does_not_compute_is_refused_by_name():
    with pytest.raises(ValueError, match="use_sliding_window"):
        ModelConfig.from_hf_config({**CATALOG, "use_sliding_window": True})
    cfg = ModelConfig.from_hf_config(tiny_hf())
    with pytest.raises(ValueError, match="retention"):
        validate_config(dataclasses.replace(cfg, family="llama", qk_norm=False))
    with pytest.raises(ValueError, match="retention"):
        validate_config(dataclasses.replace(cfg, retention=None))
    with pytest.raises(ValueError, match="even head_dim"):
        validate_config(dataclasses.replace(cfg, head_dim=cfg.head_dim + 1))


@pytest.mark.parametrize("d, width, distinct", [(16, 144, 136), (128, 8320, 8256)])
def test_the_feature_maps_dot_product_is_the_squared_dot_product(d, width, distinct):
    x = jax.random.normal(jax.random.PRNGKey(1), (5, d))
    y = jax.random.normal(jax.random.PRNGKey(2), (5, d))
    phi_x, phi_y = pr.power_features(x), pr.power_features(y)
    assert phi_x.shape == (5, width) == (5, pr.feature_dim(d))
    np.testing.assert_allclose(
        jnp.sum(phi_x * phi_y, -1), jnp.sum(x * y, -1) ** 2, rtol=2e-5
    )
    # the distinct products: d (d + 1) / 2; the last rotation repeats d / 2
    assert width - d // 2 == distinct == d * (d + 1) // 2


def test_the_yardsticks_count_the_mathematics_and_no_tiling():
    conf = json.loads(
        (pathlib.Path(bench_server.__file__).parent / "configs/brumby-14b.json")
        .read_text()
    )
    hq, hkv, d, width = 40, 8, 128, 128 * 129 // 2
    assert decode_count.feature_dim(conf) == width == 8256
    # ISSUE 52's sizing: 16 rows x 10 layers of state and summed keys
    assert round(16 * model_count.state_bytes_per_token(conf) / 1e9, 2) == 5.45
    by_state = 2.0 * width * (d + 1)
    # a chunk of one pad width is cheapest pair by pair throughout ...
    assert prefill_count.operations(conf, 1, 4096) == pytest.approx(
        4096 * hkv * by_state + hq * 4.0 * d * 4096 * 4097 / 2
    )
    # ... past 4160 places a query reads the state; rows share a dispatch
    assert prefill_count.operations(conf, 1, 6000) == pytest.approx(
        6000 * hkv * by_state
        + hq * (4.0 * d * 4160.25 * 4161.25 / 2 + (6000 - 4160.25) * by_state)
    )
    assert prefill_count.operations(conf, 2, 8192) == pytest.approx(
        2 * prefill_count.operations(conf, 1, 4096)
    )


def test_the_prefill_kernels_share_is_the_windows_mean_call_over_the_traces_time():
    """One event is one layer of one dispatch; the calls' least is the mean
    over the WINDOW's prefill dispatches, whichever of them the trace saw."""
    conf = json.loads(
        (pathlib.Path(bench_server.__file__).parent / "configs/brumby-14b.json")
        .read_text()
    )
    run = types.SimpleNamespace(
        closed={"trace": {"kernels_device0": {
            "power_retention_prefill": {"count": 25, "sum_s": 0.25},
        }}},
        ticks={
            1: {"t": 10.0, "dispatches": [("prefill", (1, 4096), 4096)]},
            2: {"t": 11.0, "dispatches": [("decode", (16, 1), 16)]},
            3: {"t": 12.0, "dispatches": [("prefill", (2, 4096), 5000)]},
            4: {"t": 99.0, "dispatches": [("prefill", (1, 4096), 2048)]},
        },
        t0=5.0, seconds=45.0, epoch_offset=0.0, conf=conf,
        device={"kind": "TPU v5 lite"},
    )
    flops = peaks.peaks_for("TPU v5 lite")["bf16_flops"]
    mean_call_s = (
        prefill_count.operations(conf, 1, 4096)
        + prefill_count.operations(conf, 2, 5000)
    ) / 2 / flops
    assert retention_prefill_roofline_pct.read(run) == pytest.approx(
        100.0 * 25 * mean_call_s / 0.25
    )
    assert 0 < retention_prefill_roofline_pct.read(run) < 100
    # a program without the kernel (the parent) gives nothing and raises nothing
    run.closed = {"trace": {"kernels_device0": {}}}
    assert retention_prefill_roofline_pct.read(run) is None


# -- the recurrence against the quadratic reference ---------------------------------


@pytest.mark.parametrize("layers", [1, 3])
def test_prefill_then_decoding_through_the_cache_is_the_references_forward(layers):
    hf, cfg, params = tiny_model(layers)
    prompt, forced = probe_tokens()
    gold = gold_logits(hf, params, prompt, forced)
    first, cache = prefill(cfg, params, fresh_cache(cfg, 8), prompt, 32)
    steps, cache = fused_decode(cfg, params, cache, forced)
    np.testing.assert_allclose(first[0, 0], gold[0], atol=2e-5)
    np.testing.assert_allclose(steps, gold[1:], atol=2e-5)
    assert int(cache.lengths[0]) == N + STEPS
    # and a token a dispatch (the one-token path) reads the same
    _, cache = prefill(cfg, params, fresh_cache(cfg, 8), prompt, 32)
    one = jnp.ones((1,), jnp.int32)
    for i in range(3):
        logits, cache = llama.model_apply(
            cfg, params, jnp.asarray(forced[i])[None, None], cache, one
        )
        np.testing.assert_allclose(logits[0, 0], gold[1 + i], atol=2e-5)


def test_a_prompt_in_three_chunks_is_the_prompt_in_one(model):
    hf, cfg, params = model
    prompt, forced = probe_tokens(1)
    gold = gold_logits(hf, params, prompt, forced)
    cache = fresh_cache(cfg, 8)
    for lo, hi in ((0, 7), (7, 15), (15, 20)):   # no chunk ends on a page
        logits, cache = prefill(cfg, params, cache, prompt[lo:hi], 8)
    whole, cache_1 = prefill(cfg, params, fresh_cache(cfg, 8), prompt, 32)
    np.testing.assert_allclose(logits[0, 0], gold[0], atol=2e-5)
    np.testing.assert_allclose(logits[0, 0], whole[0, 0], atol=2e-5)
    for f in ROW_FIELDS:
        np.testing.assert_allclose(
            getattr(cache, f), getattr(cache_1, f), rtol=2e-4, atol=1e-5
        )
    steps, _ = fused_decode(cfg, params, cache, forced)
    np.testing.assert_allclose(steps, gold[1:], atol=2e-5)


@pytest.mark.parametrize("page, sub", [
    (4, 4),     # a fold after every page, inside the chunk too
    (4, 256),   # a fold once a chunk
    (8, 16),
    (64, 256),  # never folding: every position stays in the open page
])
def test_where_the_fold_lies_changes_nothing(model, page, sub):
    hf, cfg, params = model
    pr.SUB_CHUNK = sub
    prompt, forced = probe_tokens(2)
    gold = gold_logits(hf, params, prompt, forced)
    first, cache = prefill(cfg, params, fresh_cache(cfg, page), prompt, 32)
    folded = N // page * page
    assert bool(jnp.any(cache.state != 0)) == (folded > 0)
    steps, cache = fused_decode(cfg, params, cache, forced)
    np.testing.assert_allclose(first[0, 0], gold[0], atol=2e-5)
    np.testing.assert_allclose(steps, gold[1:], atol=2e-5)
    assert bool(jnp.any(cache.state != 0)) == ((N + STEPS) // page > 0)


def test_the_references_own_split_forms_are_its_quadratic_form(model):
    """The controls of ``correct`` are the reference with ONE thing changed:
    with nothing changed they are the reference."""
    hf, _, params = model
    prompt, forced = probe_tokens(3)
    tokens = jnp.asarray(np.concatenate([prompt, forced[:-1]]), jnp.int32)
    gold = reference.forward(hf, params, tokens)
    split = reference.forward(
        hf, params, tokens, mixer=reference.paged_retention(8)
    )
    np.testing.assert_allclose(split, gold, atol=2e-5)
    tail_only = reference.forward(
        hf, params, tokens, mixer=reference.paged_retention(8, folded=False)
    )
    far = jnp.linalg.norm(tail_only[N:] - gold[N:]) / jnp.linalg.norm(gold[N:])
    assert far > 0.1            # a state that is not read is told apart
    rounded = reference.forward(
        hf, params, tokens,
        mixer=reference.paged_retention(8, state_dtype=jnp.bfloat16),
    )
    near = jnp.linalg.norm(rounded[N:] - gold[N:]) / jnp.linalg.norm(gold[N:])
    assert 1e-5 < near < far


def wide_head_hf():
    """One layer of the published head (128 wide: the state's 8320 features,
    65 rotations of a key against itself) under tiny other widths."""
    return {
        **CATALOG, "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": 1, "num_attention_heads": 2,
        "num_key_value_heads": 1, "vocab_size": 256,
        "max_position_embeddings": 512, "max_window_layers": 1,
    }


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_the_served_state_is_the_references_entry_by_entry(use_kernel):
    """What the logits cannot tell (``correct``'s control (b): a bfloat16
    state reads UNDER the served path's own distance) the state's planes do:
    after a prefill over three pages, ``state`` and ``zsum`` are the
    reference's split form's in float32 to 1e-4, where the same form rounded
    to bfloat16 at every fold is 1e-3 and more away."""
    hf = wide_head_hf()
    cfg = ModelConfig.from_hf_config(hf)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    params["layers"]["w_gate"] = params["layers"]["w_gate"] * 20
    page, d = 16, cfg.head_dim
    tokens = np.random.default_rng(7).integers(1, 256, size=3 * page + 5)
    cache = fresh_cache(cfg, page, positions=64)
    cache = cache.replace(use_kernel=use_kernel)
    _, cache = prefill(cfg, params, cache, tokens, 64)
    assert cache.state.dtype == cache.zsum.dtype == jnp.float32
    assert cache.state.shape == (1, 1, 1, 8320, d)

    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = reference.rms_norm(
        params["embed"][jnp.asarray(tokens)], lp["attn_norm"], hf["rms_norm_eps"]
    )

    def planes(state_dtype):
        """The reference's ``[d, d, dv]`` state of the one head in the served
        layout: rotation ``s``'s row ``i`` is the product of key dims ``i``
        and ``i + s``, times ``sqrt 2`` where the pair comes once."""
        st, zs, ref = reference.folded_state(hf, lp, x, page, state_dtype)
        i = np.arange(d)[None, :]
        shift = np.arange(d // 2 + 1)[:, None]
        c = np.where((shift == 0) | (shift == d // 2), 1.0, np.sqrt(2.0))
        j = (i + shift) % d
        return (
            (c[..., None] * np.asarray(st)[0, i, j]).reshape(-1, d),
            (c * np.asarray(zs)[0, i, j]).reshape(-1), np.asarray(ref),
        )

    def far(got, want):
        return np.linalg.norm(got - want) / np.linalg.norm(want)

    want_st, want_zs, want_ref = planes(None)
    np.testing.assert_allclose(cache.g_fold[0, 0], want_ref, rtol=1e-5)
    got_st, got_zs = np.asarray(cache.state[0, 0, 0]), np.asarray(cache.zsum[0, 0, 0])
    assert far(got_st, want_st) < 1e-4 and far(got_zs, want_zs) < 1e-4
    np.testing.assert_allclose(got_st, want_st, atol=1e-4 * np.abs(want_st).max())
    half_st, half_zs, _ = planes(jnp.bfloat16)
    assert far(half_st, want_st) > 1e-3 and far(half_zs, want_zs) > 1e-3


def test_pad_positions_fold_nothing(model):
    _, cfg, params = model
    prompt, _ = probe_tokens(4)
    _, narrow = prefill(cfg, params, fresh_cache(cfg, 4), prompt, 24)
    _, wide = prefill(cfg, params, fresh_cache(cfg, 4), prompt, 64)
    for f in ROW_FIELDS:
        np.testing.assert_allclose(
            getattr(narrow, f), getattr(wide, f), rtol=2e-4, atol=1e-5
        )
    # a row that writes nothing (a pad row of a group) keeps its state
    padded = jnp.zeros((1, 8), jnp.int32)
    _, after = llama.model_apply(
        cfg, params, padded, wide, jnp.zeros((1,), jnp.int32), head="last"
    )
    for f in ROW_FIELDS:
        np.testing.assert_array_equal(getattr(after, f), getattr(wide, f))
    assert int(after.lengths[0]) == N


# -- the kernel against its XLA twin ---------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_decode_kernel_is_its_xla_twin(dtype):
    b, hkv, g, d, n, layers = 4, 2, 2, 16, 24, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    width = pr.feature_dim(d)
    q = jax.random.normal(ks[0], (b, hkv, g, d)).astype(dtype)
    state = jax.random.normal(ks[1], (layers, b, hkv, width, d))
    zsum = jnp.abs(jax.random.normal(ks[2], (layers, b, hkv, width))) + 5
    dec = jax.random.uniform(ks[3], (b, hkv))
    k_pairs = jax.random.normal(ks[4], (b, hkv, n, d)).astype(dtype)
    v_pairs = jax.random.normal(ks[5], (b, hkv, n, d)).astype(dtype)
    w_pairs = jax.random.uniform(ks[6], (b, hkv, n)) * (
        jax.random.uniform(ks[7], (b, hkv, n)) > 0.3
    )
    num_new = jnp.asarray([1, 0, 1, 1])
    count, rows = pr.live_rows(num_new)
    assert int(count) == 3 and rows.tolist() == [0, 2, 3, 3]
    live = np.asarray(num_new > 0)
    for layer in range(layers):
        want = pr.power_retention_decode_xla(
            q, state[layer], zsum[layer], dec, k_pairs, v_pairs, w_pairs, 1e-6
        )
        got = pr.power_retention_decode(
            q, state, zsum, dec, k_pairs, v_pairs, w_pairs, 1e-6,
            layer=jnp.asarray([layer]), walk=(count, rows), interpret=True,
        )
        np.testing.assert_allclose(got[live], want[live], rtol=2e-4, atol=2e-5)
        assert not np.any(np.asarray(got[~live]))   # a dead row is not walked


def test_the_fold_kernel_is_its_xla_twin_and_leaves_other_rows_alone():
    layers, b, hkv, d, n = 2, 4, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    width = pr.feature_dim(d)
    state = jax.random.normal(ks[0], (layers, b, hkv, width, d))
    zsum = jnp.abs(jax.random.normal(ks[1], (layers, b, hkv, width))) + 5
    ref = -jnp.abs(jax.random.normal(ks[2], (layers, b, hkv)))
    k = jax.random.normal(ks[3], (layers, b, n, hkv, d))
    v = jax.random.normal(ks[4], (layers, b, n, hkv, d))
    gsum = ref[:, :, None, :] - jnp.cumsum(
        jnp.abs(jax.random.normal(ks[5], (layers, b, n, hkv))) * 0.1, axis=2
    )
    fold = jnp.asarray(
        [[1] * 8 + [0] * 8, [0] * 16, [1] * 16, [0] * 16], bool
    )
    got = pr.power_retention_fold(
        state, zsum, ref, k, v, gsum, fold, interpret=True
    )
    for layer in range(layers):
        want = pr.retention_fold(
            state[layer], zsum[layer], ref[layer], k[layer], v[layer],
            gsum[layer], fold,
        )
        for ours, theirs in zip(got, want):
            np.testing.assert_allclose(
                ours[layer], theirs, rtol=2e-4, atol=2e-4
            )
    for ours, before in zip(got, (state, zsum, ref)):   # rows 1 and 3
        np.testing.assert_array_equal(ours[:, 1], before[:, 1])
        np.testing.assert_array_equal(ours[:, 3], before[:, 3])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_prefill_kernel_is_its_xla_twin(dtype):
    """Two rows: one with 3 unfolded positions before its 37 (folds up to
    position 40's page), one that holds nothing (a group's pad row)."""
    b, e, hkv, g, d, sub, page = 2, 64, 2, 2, 16, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    width = pr.feature_dim(d)
    q = jax.random.normal(ks[0], (b, e, hkv, g, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, e, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, e, hkv, d)).astype(dtype)
    state = jax.random.normal(ks[3], (b, hkv, width, d))
    zsum = jnp.abs(jax.random.normal(ks[4], (b, hkv, width))) + 5
    ref = -jnp.abs(jax.random.normal(ks[5], (b, hkv)))
    gsum = ref[:, None, :] - jnp.cumsum(
        jnp.abs(jax.random.normal(ks[6], (b, e, hkv))) * 0.05, axis=1
    )
    place = jnp.arange(e)[None, :]
    prior, new = jnp.asarray([[3], [0]]), jnp.asarray([[37], [0]])
    valid_q = (place >= prior) & (place < prior + new)
    valid_k = place < prior + new
    fold = valid_k & (place < (prior + new) // page * page)
    args = (q, k, v, gsum, valid_q, valid_k, fold, state, zsum, ref, 1e-6, sub)
    want = pr.retention_chunk(*args)
    got = pr.power_retention_prefill(*args, interpret=True)
    loose = 1 if dtype == jnp.bfloat16 else 0.02
    for ours, theirs in zip(got, want):
        np.testing.assert_allclose(
            ours, theirs, rtol=2e-3 * loose, atol=2e-2 * loose
        )
    np.testing.assert_array_equal(got[1][1], state[1])    # the pad row's


def test_a_prefill_through_the_kernel_is_the_prefill_without_it(model):
    hf, cfg, params = model
    pr.SUB_CHUNK = 16
    prompt, forced = probe_tokens(9)
    gold = gold_logits(hf, params, prompt, forced)
    cache = fresh_cache(cfg, 8).replace(use_kernel=True)
    for lo, hi, width in ((0, 7, 8), (7, 20, 24)):  # 7 unfolded, then 2 steps
        logits, cache = prefill(cfg, params, cache, prompt[lo:hi], width)
    np.testing.assert_allclose(logits[0, 0], gold[0], atol=5e-5)
    steps, _ = fused_decode(cfg, params, cache, forced[:3])
    np.testing.assert_allclose(steps, gold[1:3], atol=5e-5)


def test_the_fused_scan_through_the_kernel_is_the_scan_without_it(model):
    hf, cfg, params = model
    prompt, forced = probe_tokens(5)
    gold = gold_logits(hf, params, prompt, forced)
    _, cache = prefill(cfg, params, fresh_cache(cfg, 8), prompt, 32)
    steps, _ = fused_decode(
        cfg, params, cache.replace(use_kernel=True), forced[:5]
    )
    np.testing.assert_allclose(steps, gold[1:5], atol=5e-5)


# -- the engine ------------------------------------------------------------------------


WIDTH = 160


@jax.jit
def _padded_argmax(params, tokens):
    return jnp.argmax(reference.forward(tiny_hf(), params, tokens), -1)


def greedy_agrees(params, prompt, generated) -> bool:
    """Whether ``generated`` is what greedy decoding of the reference gives
    behind ``prompt``: ONE forward pass of the two together (causal, so
    padded to one width for one program), each position's largest logit
    against the token that follows it."""
    tokens = list(prompt) + list(generated)
    padded = jnp.zeros((WIDTH,), jnp.int32).at[: len(tokens)].set(
        jnp.asarray(tokens, jnp.int32)
    )
    best = np.asarray(_padded_argmax(params, padded))
    at = len(prompt) - 1
    return best[at: at + len(generated)].tolist() == list(generated)


def make_engine(cfg, params, rows=3, pages=64, **kw):
    return InferenceEngine(
        cfg, params,
        EngineConfig(
            max_batch_size=rows, prefill_buckets=(8, 16, 32), max_seq_len=256,
            dtype="float32", ragged_attention=True, prefill_chunk_tokens=32,
        ),
        CacheConfig(kind="paged", page_size=4, num_pages=pages,
                    max_pages_per_session=64, **kw.pop("cache", {})),
        trace_cfg=TraceConfig(), **kw,
    )


def run_all(engine, prompts, new_tokens):
    gids = [
        engine.submit(p, SamplingOptions(
            max_new_tokens=new_tokens, temperature=0.0, eos_token_id=-1
        )) for p in prompts
    ]
    most = 0
    while engine.has_work():
        engine.step()
        most = max([most] + [
            len(s.window_pages) for s in engine.sessions.values()
            if s.slot is not None
        ])
    done = engine.collect_finished()
    return [done[g].generated for g in gids], most


def test_the_engine_serves_it_and_pages_leave_as_they_fold(model):
    hf, cfg, params = model
    rng = np.random.default_rng(4)
    prompts = [[int(t) for t in rng.integers(1, 256, size=n)]
               for n in (70, 9, 100, 21, 45)]
    engine = make_engine(cfg, params)
    assert isinstance(engine.cache, retention_cache_class(16, 1e-6))
    assert engine.window_allocator is engine.allocator
    assert engine.decode_steps == 16 and engine._pipelined
    tokens, most = run_all(engine, prompts, 20)
    for prompt, got in zip(prompts, tokens):
        assert len(got) == 20 and greedy_agrees(params, prompt, got)
    # chunked prompts of 70 and 100 in a 63-page pool: a row never held more
    # than a chunk's pages and its open one, and every page came back
    assert most <= 32 // 4 + 1
    assert engine.allocator.free_count == 63
    seen = engine.metrics.snapshot()
    assert seen["window_pages_released"] > 40
    for name in (
        "retention_state_rows_live", "retention_state_rows_held",
        "retention_tail_positions", "retention_decode_row_steps",
        "retention_tokens_folded", "retention_folds",
        "retention_state_bytes_read",
    ):
        assert seen[name] > 0, name
    assert seen["retention_tokens_folded"] % 4 == 0
    row = cfg.num_layers * cfg.num_kv_heads * 144 * (16 + 1) * 4
    assert seen["retention_state_bytes_read"] == (
        seen["retention_state_rows_live"] * row
    )
    assert 1 <= (
        seen["retention_tail_positions"] / seen["retention_decode_row_steps"]
    ) <= 4 + 16
    exposition = engine.metrics.prometheus()
    assert "retention_state_bytes_read_total" in exposition


def test_int8_pages_are_a_lower_precision_and_the_state_stays_float32(model):
    """``kv_quant="int8"`` stores the unfolded keys and values int8: the
    control ``tests/bench`` holds the float32 pages apart from. What is
    folded from them carries their rounding; the state's dtype does not
    change."""
    hf, cfg, params = model
    engine = make_engine(cfg, params, cache={"kv_quant": "int8"})
    assert engine.cache.k_pages.dtype == jnp.int8
    assert engine.cache.ks_pages.shape == engine.cache.g_pages.shape
    assert engine.cache.state.dtype == jnp.float32
    assert type(engine.cache) is retention_cache_class(16, 1e-6, True)
    prompt, forced = probe_tokens(11)
    prompt = prompt[:-1]        # 19: the window reads 3 positions from a page
    tokens = jnp.asarray(np.concatenate([prompt, forced[:-1]]), jnp.int32)
    gold = reference.forward(hf, params, tokens)[len(prompt):]
    slots = -(-(N + STEPS + 1) // 4) + 1
    _, decoded = bench_server.probe(
        engine, cfg, engine.params, [int(t) for t in prompt],
        [int(t) for t in forced], slots, jnp.float32,
    )
    off = np.linalg.norm(decoded - gold, axis=-1) / np.linalg.norm(
        gold, axis=-1
    )
    assert 5e-4 < np.median(off) and off.max() < 0.05


def test_two_sessions_through_one_row_read_as_each_alone(model):
    hf, cfg, params = model
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(1, 256, size=n)] for n in (37, 26)]
    engine = make_engine(cfg, params, rows=1)
    tokens, _ = run_all(engine, prompts, 12)    # the second waits for the row
    for prompt, got in zip(prompts, tokens):
        assert len(got) == 12 and greedy_agrees(params, prompt, got)


def test_a_preempted_and_readmitted_session_reads_as_an_undisturbed_one(model):
    hf, cfg, params = model
    rng = np.random.default_rng(8)
    prompt = [int(t) for t in rng.integers(1, 256, size=41)]
    engine = make_engine(cfg, params, rows=2)
    (want,), _ = run_all(engine, [prompt], 24)  # undisturbed
    assert greedy_agrees(params, prompt, want)
    gid = engine.submit(prompt, SamplingOptions(
        max_new_tokens=24, temperature=0.0, eos_token_id=-1
    ))
    while len(engine.sessions[gid].generated) < 5:
        engine.step()
    engine.cancel(gid)
    while engine.has_work():
        engine.step()
    had = engine.collect_finished()[gid].generated
    assert 5 <= len(had) < 24 and had == want[: len(had)]
    # admitted again from its tokens: the row's state is rebuilt from them
    (rest,), _ = run_all(engine, [prompt + had], 24 - len(had))
    assert had + rest == want
    assert engine.allocator.free_count == 63


@pytest.mark.parametrize("what, kwargs", [
    ("prefix_caching", {"cache": {"prefix_caching": True}}),
    ("spill", {"prefix_cfg": PrefixConfig(spill_bytes_max=1 << 20)}),
    ("a draft model", {"draft": "draft"}),
    ("tp", {"mesh_cfg": MeshConfig(tp=2)}),
    ("ep", {"mesh_cfg": MeshConfig(ep=2)}),
    ("pp", {"mesh_cfg": MeshConfig(pp=2)}),
    ("sp", {"mesh_cfg": MeshConfig(sp=2)}),
    ("dp", {"mesh_cfg": MeshConfig(dp=2)}),
])
def test_what_carries_kv_planes_between_places_is_refused_by_name(model, what, kwargs):
    _, cfg, params = model
    if kwargs.get("draft") == "draft":
        kwargs = {"draft": (cfg, params)}
    with pytest.raises(ValueError, match=r"'brumby'.*retention"):
        make_engine(cfg, params, **kwargs)


def test_export_resume_disagg_and_block_workers_are_refused_by_name(model):
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    _, cfg, params = model
    engine = make_engine(cfg, params)
    gid = engine.submit([1, 2, 3, 4, 5], SamplingOptions(max_new_tokens=40))
    for _ in range(3):
        engine.step()
    for call in (
        lambda: engine.export_session(gid),
        lambda: engine.resume_session({"prompt": [1], "generated": [2]}),
        lambda: engine.prefill_export([1, 2, 3]),
        lambda: engine.admit_prefilled([1, 2, 3], {}, 4),
        lambda: engine.export_kv_row(engine.sessions[gid]),
    ):
        with pytest.raises(ValueError, match=r"'brumby'.*retention"):
            call()
    with pytest.raises(ValueError, match=r"'brumby'.*retention"):
        BlockBackend(cfg, params["layers"], 0, 2)
    with pytest.raises(NotImplementedError, match="power retention"):
        engine.cache.read_page(1)
    with pytest.raises(ValueError, match="under what"):
        make_engine(cfg, params, pages=20)


# -- the checkpoint ---------------------------------------------------------------------


def test_the_converter_round_trips_g_proj_and_the_per_head_norms():
    _, cfg, params = tiny_model(2)
    stack = {k: np.asarray(v) for k, v in params["layers"].items()}
    names = {
        "attn_norm": "input_layernorm.weight",
        "mlp_norm": "post_attention_layernorm.weight",
        "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
        "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
        "w_gate": "self_attn.g_proj.weight", "b_gate": "self_attn.g_proj.bias",
        "wg": "mlp.gate_proj.weight", "wu": "mlp.up_proj.weight",
        "wd": "mlp.down_proj.weight",
    }
    assert set(names) == set(stack)
    state = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    for i in range(cfg.num_layers):
        for ours, theirs in names.items():
            leaf = stack[ours][i]
            state[f"model.layers.{i}.{theirs}"] = leaf.T if leaf.ndim == 2 else leaf
    back = llama.convert_hf_state_dict(cfg, state, dtype=jnp.float32)
    for ours in names:
        np.testing.assert_array_equal(back["layers"][ours], stack[ours])
    assert back["layers"]["w_gate"].shape == (2, 64, 2)
    np.testing.assert_array_equal(back["lm_head"], params["lm_head"])


# -- the harness's face of the cache -------------------------------------------------------


def test_the_harness_builds_its_probes_cache_from_the_engines_own(model):
    """``benchmark/server.py`` reads ``k_pages``' shape and calls
    ``type(cache).create(layers, 1, pages + 1, ...)``: the tail pool over
    ALL the layers, a one-row state from the class alone; and its probe
    through that cache agrees with the reference."""
    hf, cfg, params = model
    engine = make_engine(cfg, params)
    one_row = bench_server.probe_cache(engine.cache, 5, 6, jnp.float32)
    assert type(one_row) is type(engine.cache)
    assert one_row.k_pages.shape == (3, 6, 2, 4, 16)
    assert one_row.g_pages.shape == (3, 6, 2, 4)
    assert one_row.state.shape == (3, 1, 2, 144, 16)
    assert one_row.page_table.tolist() == [[1, 2, 3, 4, 5, 0]]
    assert engine.cache.state.shape == (3, 3, 2, 144, 16)
    prompt, forced = probe_tokens(6)
    slots = -(-(N + STEPS + 1) // 4) + 1
    first, decoded = bench_server.probe(
        engine, cfg, engine.params, [int(t) for t in prompt],
        [int(t) for t in forced], slots, jnp.float32,
    )
    gold = gold_logits(hf, params, prompt, forced)
    np.testing.assert_allclose(first, gold[0], atol=2e-5)
    np.testing.assert_allclose(decoded, gold[1:], atol=2e-5)
