"""Xing4.0-29B-A4B's block (``xing4_0``: a residual stream ``hc_mult`` rows
wide mixed by manifold-constrained hyper-connections around every attention
and MLP sublayer, over DeepSeek-V3's latent attention with compressed queries
under YaRN and sigmoid-routed experts beside a shared one) on the engine's
normal path, at a small size on the CPU, against the benchmark's plain
reference ``benchmark/reference/xing_mla_mhc_moe.py``, which shares no code
with the program.

Size: the configuration file's rehearsal overlay, 1 dense + 2 expert layers,
8 experts of which 3 a token and 1 shared, ``q_lora_rank`` 32, rank 32 / nope
16 / rope 8 / v 16, 4 heads, ``hc_mult`` 4 with all 20 Sinkhorn rounds, the
published ``rope_scaling`` block.

Tolerances, with their reasons:

* float32 weights, activations and latent pool: only the order of sums
  differs (the absorbed against the un-absorbed attention, the maps' entries
  against their matrices); the logits' relative distance reads 2e-7
  (``TOLERANCE`` 1e-4, as ``tests/bench/test_benchmark_reference.py``).
* the int8 latent pool, one precision below: 2.5e-3, twenty times over.
* a broken hyper-connection in the SERVED path reads, at its least position,
  0.009 (``H_res`` transposed), 0.08 (Sinkhorn cut to one row normalisation)
  and 0.11 (``H_post`` dropped): asserted at 50 times the tolerance. What no
  tolerance sees (a 20th Sinkhorn round, YaRN's factor on the softmax scale
  at these widths) is held by value.
"""

import dataclasses
import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import server
from benchmark.reference import xing_mla_mhc_moe as reference
from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, HyperConnectionConfig, LatentConfig, MeshConfig,
    ModelConfig, RopeScaling,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import validate_config
from distributed_llm_inference_tpu.ops import hyper_connections as mhc
from distributed_llm_inference_tpu.ops.rotary import rope_inv_freq

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "xing4.0-29b-a4b.json")
TOLERANCE = 1e-4


def tiny(**over):
    conf = server.load_config(CONFIG, rehearse=True)
    conf.update(over)
    return conf


def published_block():
    """The catalog's ``config`` block: the file's, its three cuts undone."""
    with open(CONFIG) as f:
        conf = json.load(f)
    block = server.hf_block(conf)
    for key, cut in conf["reduced"].items():
        block[key] = cut["from"]
    return block


def engine_for(conf, kv_quant=None, **engine_kw):
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    maker = importlib.import_module(
        f"benchmark.weights.{conf['serve']['weight_maker']}"
    )
    params = maker.make(cfg, 5, jnp.float32, "float32")
    ekw = dict(conf["serve"]["engine"])
    ekw["prefill_buckets"] = tuple(ekw["prefill_buckets"])
    cache = {**conf["serve"]["cache"], "kv_quant": kv_quant}
    return cfg, InferenceEngine(
        cfg, params, EngineConfig(dtype="float32", **ekw), CacheConfig(**cache),
        **engine_kw,
    )


def distances(conf, kv_quant=None):
    """Prefill of 30 tokens, then 16 decode steps through the paged latent
    cache (``server.probe``: the engine's cache class, pad width and decode
    program), against the reference's one full forward; logits, not tokens."""
    cfg, engine = engine_for(conf, kv_quant)
    conf = {**conf, "correct": {"probe_prompt_tokens": 30, "decode_steps": 16,
                                "tolerance": TOLERANCE}}
    return server.check_numerics(conf, cfg, engine, seed=3)


def kernel_conf():
    """The rehearsal's configuration with the cache's Pallas kernels on (the
    chip's default; here interpreted): the int8 latent cache then has the
    tail protocol and the engine the fused 16-step scan."""
    conf = tiny()
    serve = conf["serve"]
    conf["serve"] = {**serve, "engine": {**serve["engine"], "use_pallas_attention": True}}
    return conf


# -- the system against the reference ---------------------------------------


@pytest.mark.parametrize("pool", [None, "int8"], ids=["float32-pool", "int8-pool"])
def test_prefill_then_decode_through_the_latent_pool_agrees_with_the_reference(pool):
    """One token a dispatch through the float latent pool at the float32
    tolerance; through the int8 pool at ITS existing tolerance (a precision
    below: over the float32 tolerance, under 0.05)."""
    out = distances(tiny(), kv_quant=pool)
    assert out["unrelated"] > 0.5 and out["layers"] == 3
    if pool is None:
        assert out["ok"], out
        assert out["prefill"] < TOLERANCE and out["decode_max"] < TOLERANCE
    else:
        assert not out["ok"]
        assert TOLERANCE * 5 < out["decode_median"] and out["decode_max"] < 0.05, out


def test_the_fused_sixteen_step_scan_equals_sixteen_one_token_steps():
    """The int8 latent engine with its kernel decodes 16 steps a dispatch
    over the write-behind tail (``multi_decode_apply``), without it one token
    a dispatch (``model_apply``): the same logits at every step, to what the
    tail leaves (a window's newest latents stay unrounded in it until the
    flush, where the one-token path rounds each to int8 at once: 6e-4,
    asserted at 2e-3), and both the reference's within the int8 pool's
    tolerance."""
    conf = {**kernel_conf(), "correct": {
        "probe_prompt_tokens": 30, "decode_steps": 16, "tolerance": 0.05}}
    cfg, fused = engine_for(conf, kv_quant="int8")
    _, single = engine_for({**conf, "serve": tiny()["serve"]}, kv_quant="int8")
    assert fused.cache.has_tail and fused.decode_steps == 16
    assert single.decode_steps == 1
    rng = np.random.default_rng(7)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=30)]
    forced = [int(t) for t in rng.integers(1, cfg.vocab_size, size=17)]
    got = [
        server.probe(e, cfg, e.params, prompt, forced, 7, jnp.float32)
        for e in (fused, single)
    ]
    for a, b in zip(*got):
        assert a.shape == b.shape
        assert np.linalg.norm(a - b) / np.linalg.norm(b) < 2e-3
    for engine in (fused, single):
        out = server.check_numerics(conf, cfg, engine, seed=3)
        assert out["ok"] and TOLERANCE * 5 < out["decode_max"] < 0.05, out


def break_served(monkeypatch, how):
    """One wrong term in the SERVED path's hyper-connection."""
    entries = mhc.sinkhorn_entries
    if how == "res_transposed":
        monkeypatch.setattr(mhc, "sinkhorn_entries", lambda m, iters, eps: [
            list(col) for col in zip(*entries(m, iters, eps))
        ])
    elif how == "one_row_norm":
        def rows_once(m, iters, eps):
            sums = [mhc._add(row) + eps for row in m]
            return [[e / s for e in row] for row, s in zip(m, sums)]

        monkeypatch.setattr(mhc, "sinkhorn_entries", rows_once)
    elif how == "no_post":
        post = mhc.post_mix
        monkeypatch.setattr(mhc, "post_mix", lambda x, y, mix: post(
            x, y, ([jnp.ones_like(h) for h in mix[0]], mix[1])
        ))
    else:
        raise AssertionError(how)


@pytest.mark.parametrize("how", ["res_transposed", "no_post", "one_row_norm"])
def test_a_broken_hyper_connection_in_the_served_path_fails_the_tolerance(
    how, monkeypatch,
):
    """``H_res`` transposed, ``H_post`` dropped (taken as 1) or Sinkhorn cut
    to one row normalisation, in the program: the comparison that passes at
    2e-7 fails, by 50 times the tolerance at its LEAST position. The
    reference's own switch for the same term gives the same distance: the
    seeded maps are far from the points where the term would not matter."""
    break_served(monkeypatch, how)
    out = distances(tiny())
    assert not out["ok"], out
    assert min(out["prefill"], out["decode_median"]) > 50 * TOLERANCE, out
    monkeypatch.undo()
    monkeypatch.setattr(
        reference, "forward",
        lambda cfg, p, t, f=reference.forward: f(cfg, p, t, broken=how),
    )
    mirrored = distances(tiny())
    assert not mirrored["ok"]
    assert 0.5 < mirrored["prefill"] / out["prefill"] < 2.0


# -- the maps, by value ------------------------------------------------------


def numpy_sinkhorn(m, k, eps):
    m = np.asarray(m, np.float64)
    for _ in range(k):
        m = m / (m.sum(-2, keepdims=True) + eps)     # columns: over the rows
        m = m / (m.sum(-1, keepdims=True) + eps)     # rows: over the columns
    return m


def seeded_sublayer(n=4, c=16, tokens=(2, 5), seed=0, spread=1.0):
    hc = HyperConnectionConfig(mult=n, sinkhorn_iters=20, eps=1e-6)
    rng = np.random.default_rng(seed)
    width = 2 * n + n * n
    p = {
        "hc_attn_phi": jnp.asarray(rng.normal(size=(width, n * c)) * (n * c) ** -0.5, jnp.float32),
        "hc_attn_alpha": spread * jnp.asarray([0.9, 1.1, 1.3], jnp.float32),
        "hc_attn_bias": jnp.asarray(spread * rng.normal(size=(width,)), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(*tokens, n, c)), jnp.float32)
    return hc, p, x


@pytest.mark.parametrize("iters", [1, 20])
def test_the_maps_equal_a_numpy_loop_of_as_many_sinkhorn_rounds(iters):
    """``sinkhorn_iters`` k is k rounds, for k = 1 and 20, to 1e-6: the
    step count that no logit tolerance can hold. And the three maps are the
    written equations: sigmoid, twice sigmoid, exp of the clamped logits."""
    hc, p, x = seeded_sublayer()
    hc = dataclasses.replace(hc, sinkhorn_iters=iters, res_clamp=(-1.0, 1.5))
    h_pre, h_post, h_res = mhc.maps(hc, p, "hc_attn", x, 1e-6)
    n, eps = hc.mult, 1e-6
    flat = np.asarray(x, np.float64).reshape(2, 5, -1)
    xt = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + eps)
    proj = xt @ np.asarray(p["hc_attn_phi"], np.float64).T
    a, b = np.asarray(p["hc_attn_alpha"], np.float64), np.asarray(p["hc_attn_bias"], np.float64)
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    np.testing.assert_allclose(h_pre, sig(a[0] * proj[..., :n] + b[:n]), atol=1e-6)
    np.testing.assert_allclose(h_post, 2 * sig(a[1] * proj[..., n:2 * n] + b[n:2 * n]), atol=1e-6)
    logits = np.clip((a[2] * proj[..., 2 * n:] + b[2 * n:]).reshape(2, 5, n, n), -1.0, 1.5)
    assert (logits == 1.5).any() and (logits == -1.0).any()    # the clamp bites
    want = numpy_sinkhorn(np.exp(logits), iters, hc.eps)
    np.testing.assert_allclose(h_res, want, atol=1e-6)
    if iters == 1:
        assert np.abs(numpy_sinkhorn(np.exp(logits), 2, hc.eps) - want).max() > 1e-3


def test_h_res_after_twenty_rounds_is_doubly_stochastic_and_not_the_identity():
    """Logits of a spread of 0.7 (a sharper map converges slower: at the
    weight maker's spread of 2.2 twenty rounds leave column sums within 0.04
    of 1, which is what the published count gives and not a fault)."""
    hc, p, x = seeded_sublayer(tokens=(8, 16), seed=3, spread=0.5)
    _, _, h_res = mhc.maps(hc, p, "hc_attn", x, 1e-6)
    h_res = np.asarray(h_res)
    assert np.abs(h_res.sum(-1) - 1).max() < 1e-3
    assert np.abs(h_res.sum(-2) - 1).max() < 1e-3
    assert np.abs(h_res - np.eye(4)).max(axis=(-1, -2)).min() > 0.2
    assert np.abs(h_res - np.swapaxes(h_res, -1, -2)).max() > 0.05


def test_the_mixes_are_the_written_sums():
    hc, p, x = seeded_sublayer()
    h_pre, h_post, h_res = (np.asarray(m, np.float64) for m in mhc.maps(hc, p, "hc_attn", x, 1e-6))
    h, mix = mhc.pre_mix(hc, p, "hc_attn", x, 1e-6)
    xs = np.asarray(x, np.float64)
    np.testing.assert_allclose(h, np.einsum("bsi,bsic->bsc", h_pre, xs), atol=1e-5)
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(1), h.shape), np.float64)
    want = np.einsum("bsij,bsjc->bsic", h_res, xs) + h_post[..., None] * y[..., None, :]
    np.testing.assert_allclose(mhc.post_mix(x, jnp.asarray(y, jnp.float32), mix), want, atol=1e-5)


# -- YaRN ---------------------------------------------------------------------


def numpy_yarn_inv_freq(dim, theta, factor, orig, beta_fast, beta_slow):
    def correction(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(correction(beta_fast)), 0), min(math.ceil(correction(beta_slow)), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp), (low, high)


def test_yarn_frequencies_and_softmax_factor_for_xings_keys():
    cfg = ModelConfig.from_hf_config(published_block() | {
        "num_nextn_predict_layers": 0})
    rs = cfg.rope_scaling
    assert rs == RopeScaling(
        rope_type="yarn", factor=64.0, original_max_position_embeddings=4096,
        beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    want, (low, high) = numpy_yarn_inv_freq(64, 10000, 64, 4096, 32, 1)
    assert (low, high) == (10, 23)
    got = np.asarray(rope_inv_freq(64, cfg.rope_theta, rs))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(rope_inv_freq(64, cfg.rope_theta, None))
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)          # fast dims stay
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)     # slow dims / factor
    assert rs.softmax_factor == pytest.approx((0.1 * math.log(64) + 1) ** 2)
    assert rs.softmax_factor == pytest.approx(2.0047, abs=5e-5)


def test_the_scale_handed_to_the_cache_is_yarns():
    """``_latent_attention`` is the one place the scale is made: what
    ``cache.attend`` receives for Xing's keys is ``192^-0.5 x 2.0047``, and
    for the same block without ``rope_scaling`` ``192^-0.5``."""
    block = server.hf_block(tiny())
    seen = []

    class Spy:
        def attend(self, state, q, k, v, rope, q_pos, num_new, window, fn, scale, **more):
            seen.append(scale)
            return jnp.zeros(q.shape, q.dtype), state

    for hf in (block, {k: v for k, v in block.items() if k != "rope_scaling"}):
        cfg = ModelConfig.from_hf_config(hf)
        p = jax.tree.map(lambda x: x[0], llama.init_layer_params(
            cfg, jax.random.PRNGKey(0), 1, jnp.float32, kind="dense"))
        h = jnp.ones((1, 3, cfg.hidden_size), jnp.float32)
        rope = llama._rope_angles(
            rope_inv_freq(8, cfg.rope_theta, cfg.rope_scaling), jnp.arange(3)[None])
        llama._latent_attention(cfg, p, h, (), Spy(), rope, None, None)
    width = (16 + 8) ** -0.5
    assert seen == [pytest.approx(width * (0.1 * math.log(64) + 1) ** 2), pytest.approx(width)]
    full = ModelConfig.from_hf_config(published_block() | {"num_nextn_predict_layers": 0})
    assert llama._latent_softmax_scale(full) == pytest.approx(192 ** -0.5 * 2.0047, rel=2e-5)


def test_deepseek_v3_with_a_yarn_block_no_longer_raises():
    with open(os.path.join(REPO, "benchmark", "configs", "moonlight-16b-a3b.json")) as f:
        block = server.hf_block(json.load(f))
    # DeepSeek-V3's own published block
    block["rope_scaling"] = {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1.0,
        "mscale_all_dim": 1.0, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    cfg = ModelConfig.from_hf_config(block)
    assert cfg.family == "mla" and cfg.hyper is None
    assert cfg.rope_scaling.rope_type == "yarn"
    assert llama._latent_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)


# -- the configuration, and what is refused -----------------------------------


def test_from_hf_config_reads_the_catalogs_block_as_cut():
    block = published_block()
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        ModelConfig.from_hf_config(block)
    with open(CONFIG) as f:
        conf = json.load(f)
    cut = server.hf_block(conf)
    assert {k for k in block if block[k] != cut[k]} == set(conf["reduced"])
    cfg = ModelConfig.from_hf_config(cut)
    assert validate_config(cfg).name == "xing4_0"
    assert (cfg.family, cfg.num_layers, cfg.hidden_size, cfg.num_heads) == (
        "xing4_0", 13, 3584, 32)
    assert cfg.hyper == HyperConnectionConfig(
        mult=4, sinkhorn_iters=20, eps=1e-6, res_clamp=(-30.0, 30.0))
    assert cfg.latent == LatentConfig(
        rank=512, rope_head_dim=64, nope_head_dim=128, v_head_dim=128,
        q_lora_rank=768)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts) == (64, 4, 1)
    assert (cfg.moe_scoring, cfg.moe_select_bias, cfg.moe_norm_topk,
            cfg.moe_routed_scale) == ("sigmoid", True, True, 2.0)
    assert [(s.kind, s.start, s.count) for s in cfg.segments] == [
        ("dense", 0, 1), ("moe", 1, 12)]
    # an expert layer's parameters, as the configuration file's arithmetic has them
    shapes = jax.eval_shape(
        lambda: llama.init_layer_params(cfg, jax.random.PRNGKey(0), 1, kind="moe"))
    count = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert count["we_g"] + count["we_u"] + count["we_d"] == 64 * 3 * 3584 * 1024
    assert count["hc_attn_phi"] + count["hc_mlp_phi"] == 2 * 14336 * 24
    assert {k: v.dtype for k, v in shapes.items() if k.startswith("hc_")} == {
        f"hc_{s}_{leaf}": jnp.float32 for s in ("attn", "mlp")
        for leaf in ("phi", "alpha", "bias")}


@pytest.mark.parametrize("missing", [
    "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max"])
def test_hc_mult_without_its_other_keys_is_refused_by_the_keys_name(missing):
    block = server.hf_block(tiny())
    del block[missing]
    with pytest.raises(ValueError, match=missing):
        ModelConfig.from_hf_config(block)


@pytest.mark.parametrize("mesh", [
    MeshConfig(tp=2), MeshConfig(ep=2), MeshConfig(pp=3)], ids=["tp", "ep", "pp"])
def test_a_mesh_is_refused_for_a_widened_stream_by_the_keys_name(mesh):
    cfg = ModelConfig.from_hf_config(server.hf_block(tiny()))
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="hc_mult"):
        InferenceEngine(
            cfg, params, EngineConfig(dtype="float32", max_batch_size=6),
            CacheConfig(kind="paged"), mesh_cfg=mesh)


def test_block_workers_refuse_a_widened_stream_by_the_keys_name():
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    cfg = ModelConfig.from_hf_config(server.hf_block(tiny()))
    layers = llama.init_layer_params(cfg, jax.random.PRNGKey(0), 1, jnp.float32)
    with pytest.raises(ValueError, match="hc_mult"):
        BlockBackend(cfg, layers, 1, 1)


def test_the_family_and_the_converter_say_what_they_lack():
    cfg = ModelConfig.from_hf_config(server.hf_block(tiny()))
    with pytest.raises(ValueError, match="hyper"):
        validate_config(dataclasses.replace(cfg, family="mla"))
    with pytest.raises(ValueError, match="latent"):
        validate_config(dataclasses.replace(cfg, latent=None))
    with pytest.raises(ValueError, match="xing4_0.*no checkpoint converter"):
        llama.convert_hf_state_dict(cfg, {})
    block = server.hf_block(tiny())
    block["rope_scaling"] = {**block["rope_scaling"], "mscale_all_dim": 0.5}
    with pytest.raises(ValueError, match="rope_scaling"):
        ModelConfig.from_hf_config(block)


# -- what a model without a widened stream runs --------------------------------


def wide_shapes(jaxpr, n):
    """Every rank-4 value of a jaxpr (its scans' and calls' bodies too) whose
    third axis is ``n``: a widened stream's ``[B, S, n, C]``."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if len(shape) == 4 and shape[2] == n:
                    found.append(shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("program", ["prefill", "fused_decode"])
def test_a_model_without_hyper_traces_no_widened_carry(program):
    """The same block with ``hc_mult`` taken out is Moonlight's class of
    model: its programs carry ``[B, S, C]`` and nothing ``[B, S, 5, C]``
    (5 rows here, so that no head count or page size reads as the stream);
    with the key in, the same trace finds the stream."""
    def trace(hf):
        cfg = ModelConfig.from_hf_config(hf)
        params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        from distributed_llm_inference_tpu.cache.latent import QuantizedLatentPagedKVCache

        cache = QuantizedLatentPagedKVCache.create(
            cfg.num_layers, 2, 9, 8, 3, 1, cfg.latent.lat_dim, use_kernel=True,
        ).assign_pages(0, [1, 2]).assign_pages(1, [3, 4])
        two = jnp.full((2,), 2, jnp.int32)
        if program == "prefill":
            fn = lambda p, c: llama.model_apply(cfg, p, jnp.ones((2, 6), jnp.int32), c, two)
        else:
            one = jnp.ones((2,), jnp.int32)
            fn = lambda p, c: llama.multi_decode_apply(
                cfg, p, jnp.ones((2, 1), jnp.int32), c, 16,
                lambda i, logits, st: (jnp.argmax(logits, -1).astype(jnp.int32), one, st, logits[:, 0]),
                jnp.zeros(()), one)
        return wide_shapes(jax.make_jaxpr(fn)(params, cache), 5)

    block = {**server.hf_block(tiny()), "hc_mult": 5}
    plain = {k: v for k, v in block.items() if not k.startswith(("hc_", "mhc_"))}
    plain["model_type"] = "deepseek_v3"
    assert trace(plain) == []
    assert (2, 6 if program == "prefill" else 1, 5, 64) in trace(block)


# -- the scopes of a device trace --------------------------------------------------


@pytest.mark.parametrize("hyper", [True, False], ids=["hyper", "plain"])
def test_a_lowered_forward_carries_the_mixes_scopes_inside_its_sublayers(hyper):
    """``mhc_pre`` / ``mhc_post`` inside ``attention`` and inside ``mlp``:
    the stable part of the mixes' operations' names in a device trace. The
    stream is widened by the key alone, over any attention (here plain GQA on
    a dense cache); without it no operation carries the scopes."""
    from distributed_llm_inference_tpu.cache.dense import DenseKVCache

    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
        hyper=HyperConnectionConfig(mult=3) if hyper else None,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = DenseKVCache.create(2, 1, 16, 2, 16, jnp.float32)
    text = jax.jit(lambda p, c: llama.model_apply(
        cfg, p, jnp.zeros((1, 4), jnp.int32), c, jnp.full((1,), 4, jnp.int32),
        head="last",
    )).lower(params, cache).as_text(debug_info=True)
    for scope in ("attention/mhc_pre", "attention/mhc_post", "mlp/mhc_pre", "mlp/mhc_post"):
        assert (f"{scope}/" in text) is hyper, scope
    assert ("x3x32" in text) is hyper      # the carry [1, 4, 3, 32]


# -- the census -----------------------------------------------------------------


def test_the_census_counts_the_mixes_of_valid_and_of_padded_tokens():
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    cfg, engine = engine_for(kernel_conf(), kv_quant="int8")
    assert engine.plan.mhc_mixes_per_token == 2 * 3
    engine.generate([list(range(1, 12))], SamplingOptions(max_new_tokens=20))
    m = engine.metrics
    mixes = 2 * cfg.num_layers
    prefill = m.get_counter("prefill_valid_tokens"), m.get_counter("prefill_padded_tokens")
    assert prefill[0] == 11 and prefill[1] >= 11
    needed, run = m.get_counter("mhc_mixes_needed"), m.get_counter("mhc_mixes_run")
    # one prompt: its valid and padded prompt tokens, then one active row of
    # the batch's four slots a decode step
    dispatches = [d for d in engine.plan._shapes if d[0] == "decode"]
    assert dispatches and all(d[2] == 16 for d in dispatches)
    decode_needed = (needed - prefill[0] * mixes) / mixes
    decode_run = (run - prefill[1] * mixes) / mixes
    assert decode_needed > 0 and decode_needed % 16 == 0
    assert decode_run == 4 * decode_needed
