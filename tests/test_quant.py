"""Weight-only int8 quantization: roundtrip accuracy, model fidelity,
sharding composition, engine integration.

Replaces the reference's bitsandbytes ``Linear8bitLt`` capability
(``/root/reference/distributed_llm_inference/utils/model.py:93-123``) —
no CUDA-only guard: int8 weights work on every backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import (
    CacheConfig,
    EngineConfig,
    MeshConfig,
    ModelConfig,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.ops.quant import (
    QuantizedTensor,
    matmul,
    quantize_int8,
    quantize_params,
)
from distributed_llm_inference_tpu.parallel import (
    build_mesh,
    cache_pspecs,
    param_pspecs,
    shard_pytree,
)

CFG = ModelConfig(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=8,
    max_position_embeddings=64,
)


def test_quantize_roundtrip_error():
    w = np.random.RandomState(0).randn(64, 32).astype(np.float32)
    qt = quantize_int8(jnp.asarray(w), scale_dtype=jnp.float32)
    deq = np.asarray(qt.q, np.float32) * np.asarray(qt.scale)[None, :]
    # Per-channel symmetric int8: max error ≤ scale/2 per element.
    err = np.abs(deq - w)
    bound = np.asarray(qt.scale)[None, :] * 0.5 + 1e-6
    assert (err <= bound).all()


def test_quantized_matmul_close():
    r = np.random.RandomState(1)
    x = r.randn(4, 64).astype(np.float32)
    w = r.randn(64, 32).astype(np.float32)
    qt = quantize_int8(jnp.asarray(w), scale_dtype=jnp.float32)
    out = np.asarray(matmul(jnp.asarray(x), qt))
    ref = x @ w
    rel = np.abs(out - ref) / (np.abs(ref) + 1.0)
    assert rel.mean() < 0.01


def test_quantized_model_logits_close_and_structure():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params(params, scale_dtype=jnp.float32)
    assert isinstance(qparams["layers"]["wq"], QuantizedTensor)
    assert qparams["layers"]["wq"].q.dtype == jnp.int8
    assert isinstance(qparams["lm_head"], QuantizedTensor)
    assert not isinstance(qparams["layers"]["attn_norm"], QuantizedTensor)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, CFG.vocab_size)
    n = jnp.full((2,), 8, jnp.int32)
    mk = lambda: DenseKVCache.create(
        CFG.num_layers, 2, 16, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    ref, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
        params, tokens, mk()
    )
    out, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
        qparams, tokens, mk()
    )
    ref, out = np.asarray(ref), np.asarray(out)
    # int8 noise: logits stay well-correlated with the fp32 model's.
    cos = (ref * out).sum() / (np.linalg.norm(ref) * np.linalg.norm(out))
    assert cos > 0.999, cos


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(tp=2),
    MeshConfig(dp=2, tp=2),
])
def test_quantized_sharded_matches_single_device(mesh_cfg):
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params(params, scale_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, CFG.vocab_size)
    n = jnp.full((2,), 8, jnp.int32)
    mk = lambda: DenseKVCache.create(
        CFG.num_layers, 2, 16, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    ref, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
        qparams, tokens, mk()
    )
    mesh = build_mesh(mesh_cfg)
    sp = shard_pytree(qparams, mesh, param_pspecs(qparams))
    sc = shard_pytree(mk(), mesh, cache_pspecs(mk()))
    with mesh:
        out, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
            sp, tokens, sc
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_quantized_moe_runs():
    mcfg = ModelConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, max_position_embeddings=64,
        num_experts=4, num_experts_per_tok=2, family="mixtral",
    )
    params = llama.init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params(params, scale_dtype=jnp.float32)
    assert isinstance(qparams["layers"]["we_g"], QuantizedTensor)
    tokens = jnp.ones((1, 4), jnp.int32)
    n = jnp.full((1,), 4, jnp.int32)
    cache = DenseKVCache.create(2, 1, 8, mcfg.num_kv_heads, mcfg.head_dim, jnp.float32)
    ref, _ = jax.jit(lambda p, t, c: llama.model_apply(mcfg, p, t, c, n))(
        params, tokens, cache
    )
    cache = DenseKVCache.create(2, 1, 8, mcfg.num_kv_heads, mcfg.head_dim, jnp.float32)
    out, _ = jax.jit(lambda p, t, c: llama.model_apply(mcfg, p, t, c, n))(
        qparams, tokens, cache
    )
    ref, out = np.asarray(ref), np.asarray(out)
    cos = (ref * out).sum() / (np.linalg.norm(ref) * np.linalg.norm(out))
    assert cos > 0.995, cos


def test_engine_int8_generates():
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        CFG, params,
        EngineConfig(
            max_batch_size=2, prefill_buckets=(16,), max_seq_len=32,
            max_new_tokens=5, quantization="int8",
        ),
        CacheConfig(kind="dense"),
    )
    assert isinstance(eng.params["layers"]["wq"], QuantizedTensor)
    outs = eng.generate([[1, 2, 3]], SamplingOptions(temperature=0.0, max_new_tokens=5))
    assert len(outs[0]) == 5


def test_engine_rejects_unknown_quantization():
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        InferenceEngine(
            CFG, params, EngineConfig(quantization="fp4"), CacheConfig(kind="dense")
        )


# ---------------------------------------------------------------------------
# int4 (group-wise) quantization
# ---------------------------------------------------------------------------

from distributed_llm_inference_tpu.ops.quant import (  # noqa: E402
    QuantizedTensor4,
    quantize_int4,
)


def test_int4_roundtrip_error():
    w = np.random.RandomState(0).randn(64, 32).astype(np.float32)
    qt = quantize_int4(jnp.asarray(w), group_size=16, scale_dtype=jnp.float32)
    assert qt.q.dtype == jnp.int8 and qt.q.shape == (4, 16, 16)  # packed
    assert qt.scale.shape == (4, 32)
    assert qt.shape == (64, 32)
    unpacked = np.asarray(jax.jit(lambda t: t.unpack())(qt), np.float32)
    assert unpacked.shape == (4, 16, 32)
    deq = unpacked * np.asarray(qt.scale)[:, None, :]
    err = np.abs(deq.reshape(64, 32) - w)
    bound = np.repeat(np.asarray(qt.scale), 16, axis=0) * 0.5 + 1e-6
    assert (err <= bound).all()


def test_int4_matmul_close():
    r = np.random.RandomState(1)
    x = r.randn(4, 64).astype(np.float32)
    w = r.randn(64, 32).astype(np.float32)
    qt = quantize_int4(jnp.asarray(w), group_size=16, scale_dtype=jnp.float32)
    out = np.asarray(matmul(jnp.asarray(x), qt))
    # Exact vs the dequantized weights (the matmul itself adds no error) …
    deq = np.asarray(jax.jit(lambda t: t.unpack())(qt), np.float32) * np.asarray(qt.scale)[:, None, :]
    np.testing.assert_allclose(out, x @ deq.reshape(64, 32), atol=1e-4, rtol=1e-4)
    # … and within int4 noise of the fp32 product (random N(0,1) weights are
    # the worst case; real LLM weights fare much better).
    ref = x @ w
    rel = np.abs(out - ref) / (np.abs(ref) + 1.0)
    assert rel.mean() < 0.2, rel.mean()


def test_int4_model_logits_close_and_structure():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params(params, scale_dtype=jnp.float32, bits=4, group_size=16)
    assert isinstance(qparams["layers"]["wq"], QuantizedTensor4)
    assert isinstance(qparams["lm_head"], QuantizedTensor4)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, CFG.vocab_size)
    n = jnp.full((2,), 8, jnp.int32)
    mk = lambda: DenseKVCache.create(
        CFG.num_layers, 2, 16, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    ref, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
        params, tokens, mk()
    )
    out, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
        qparams, tokens, mk()
    )
    ref, out = np.asarray(ref), np.asarray(out)
    cos = (ref * out).sum() / (np.linalg.norm(ref) * np.linalg.norm(out))
    assert cos > 0.99, cos


def test_int4_moe_experts_fall_back_to_int8():
    mcfg = ModelConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, max_position_embeddings=64,
        num_experts=4, num_experts_per_tok=2, family="mixtral",
    )
    params = llama.init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params(params, scale_dtype=jnp.float32, bits=4, group_size=16)
    assert isinstance(qparams["layers"]["we_g"], QuantizedTensor)
    assert isinstance(qparams["layers"]["wq"], QuantizedTensor4)


def test_int4_sharded_matches_single_device():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params(params, scale_dtype=jnp.float32, bits=4, group_size=16)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, CFG.vocab_size)
    n = jnp.full((2,), 8, jnp.int32)
    mk = lambda: DenseKVCache.create(
        CFG.num_layers, 2, 16, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    ref, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
        qparams, tokens, mk()
    )
    mesh = build_mesh(MeshConfig(tp=2))
    sp = shard_pytree(qparams, mesh, param_pspecs(qparams))
    sc = shard_pytree(mk(), mesh, cache_pspecs(mk()))
    with mesh:
        out, _ = jax.jit(lambda p, t, c: llama.model_apply(CFG, p, t, c, n))(
            sp, tokens, sc
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_engine_int4_generates():
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        CFG, params,
        EngineConfig(
            max_batch_size=2, prefill_buckets=(16,), max_seq_len=32,
            max_new_tokens=5, quantization="int4",
        ),
        CacheConfig(kind="dense"),
    )
    # Unsharded serving quantizes into the half-split Pallas-kernel layout.
    from distributed_llm_inference_tpu.ops.quant import QuantizedTensor4Split

    assert isinstance(eng.params["layers"]["wq"], QuantizedTensor4Split)
    outs = eng.generate([[1, 2, 3]], SamplingOptions(temperature=0.0, max_new_tokens=5))
    assert len(outs[0]) == 5


# -- int4 half-split Pallas layout (ops/quant_matmul.py) ----------------------


def test_int4_split_pack_unpack_roundtrip():
    from distributed_llm_inference_tpu.ops.quant_matmul import (
        pack_int4_split,
        unpack_int4_split,
    )

    rng = np.random.RandomState(3)
    q = rng.randint(-7, 8, size=(48, 96)).astype(np.int8)
    packed = pack_int4_split(jnp.asarray(q))
    unpacked = np.asarray(unpack_int4_split(packed))
    in_pad, out_pad = unpacked.shape
    assert in_pad >= 48 and out_pad >= 96 and out_pad == packed.shape[-1] * 2
    # logical channels live in the first `out` columns, padding is zero
    np.testing.assert_array_equal(unpacked[:48, :96], q)
    assert not unpacked[48:].any() and not unpacked[:, 96:].any()


def test_int4_split_matmul_matches_dequant_oracle():
    from distributed_llm_inference_tpu.ops.quant import quantize_int4_split

    rng = np.random.RandomState(4)
    w = rng.randn(64, 96).astype(np.float32)
    x = rng.randn(5, 64).astype(np.float32)
    qt = quantize_int4_split(jnp.asarray(w))
    # oracle: dequantized int4 weights, plain matmul
    from distributed_llm_inference_tpu.ops.quant_matmul import (
        unpack_int4_split,
    )

    w4 = np.asarray(unpack_int4_split(qt.q)).astype(np.float32)
    ref = x @ (w4[:64] * np.asarray(qt.full_scale(), np.float32))[:, :96]
    out = matmul(jnp.asarray(x), qt)
    assert out.shape == (5, 96)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_int4_split_matmul_many_rows_fallback_matches_kernel():
    from distributed_llm_inference_tpu.ops.quant import quantize_int4_split

    rng = np.random.RandomState(5)
    w = rng.randn(32, 64).astype(np.float32)
    qt = quantize_int4_split(jnp.asarray(w))
    x_big = rng.randn(300, 32).astype(np.float32)      # XLA fallback path
    out_big = np.asarray(matmul(jnp.asarray(x_big), qt))
    # the same rows through the kernel path (<=256 rows) must agree
    np.testing.assert_allclose(
        np.asarray(matmul(jnp.asarray(x_big[:8]), qt)), out_big[:8],
        rtol=1e-5, atol=1e-5,
    )


def test_int4_split_quantize_roundtrip_error():
    from distributed_llm_inference_tpu.ops.quant import quantize_int4_split

    rng = np.random.RandomState(6)
    w = rng.randn(64, 64).astype(np.float32)
    qt = quantize_int4_split(jnp.asarray(w))
    from distributed_llm_inference_tpu.ops.quant_matmul import (
        unpack_int4_split,
    )

    deq = (
        np.asarray(unpack_int4_split(qt.q)).astype(np.float32)
        * np.asarray(qt.full_scale(), np.float32)
    )[:64, :64]
    err = np.abs(deq - w).max() / np.abs(w).max()
    assert err < 0.2  # 4-bit per-channel: coarse but bounded


def test_engine_int4_split_on_dp_mesh():
    """dp/ep-only meshes keep the split (Pallas) layout — the spec node's
    static in/out dims must match the param's or shard_pytree raises."""
    from distributed_llm_inference_tpu.ops.quant import QuantizedTensor4Split

    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        CFG, params,
        EngineConfig(
            max_batch_size=2, prefill_buckets=(16,), max_seq_len=32,
            quantization="int4",
        ),
        CacheConfig(kind="dense"),
        mesh_cfg=MeshConfig(dp=2),
    )
    assert isinstance(eng.params["layers"]["wq"], QuantizedTensor4Split)
    outs = eng.generate([[1, 2, 3], [4, 5]], SamplingOptions(max_new_tokens=4))
    assert all(len(o) == 4 for o in outs)


def test_engine_int4_tp_mesh_uses_grouped_layout():
    """tp>1 serving falls back to the grouped XLA layout (the packed
    half-split channel order does not column-shard)."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        CFG, params,
        EngineConfig(
            max_batch_size=2, prefill_buckets=(16,), max_seq_len=32,
            quantization="int4",
        ),
        CacheConfig(kind="dense"),
        mesh_cfg=MeshConfig(tp=2),
    )
    assert isinstance(eng.params["layers"]["wq"], QuantizedTensor4)
    outs = eng.generate([[1, 2, 3]], SamplingOptions(max_new_tokens=4))
    assert len(outs[0]) == 4


def test_int4_stacked_view_matches_per_layer_kernel():
    """The stacked int4 dispatch (QuantizedTensor4SplitView →
    int4_matmul_stacked) is numerically exact against the per-layer kernel
    and the dequant oracle on BOTH branches (decode-shaped batch-1-seq and
    many-row prefill) for every layer index — locks in the block index
    maps' layer resolution and the lo/hi scale pairing."""
    import numpy as np

    from distributed_llm_inference_tpu.ops.quant import (
        QuantizedTensor4Split,
        QuantizedTensor4SplitView,
        matmul,
        quantize_int4_split,
    )
    from distributed_llm_inference_tpu.ops.quant_matmul import (
        unpack_int4_split,
    )

    L, IN, OUT = 3, 64, 96
    w = (
        jax.random.normal(jax.random.PRNGKey(2), (L, IN, OUT), jnp.float32)
        * 0.05
    )
    q = quantize_int4_split(w)

    def oracle(x2, layer):
        w4 = np.asarray(unpack_int4_split(q.q[layer]))[:IN].astype(np.float32)
        sc = np.concatenate(
            [np.asarray(q.scale_lo[layer]), np.asarray(q.scale_hi[layer])],
            -1,
        ).reshape(-1)
        return (np.asarray(x2, np.float32) @ w4) * sc

    for layer in range(L):
        view = QuantizedTensor4SplitView(
            q.q, q.scale_lo, q.scale_hi, jnp.int32(layer), q.in_dim, q.out_dim
        )
        per_layer = QuantizedTensor4Split(
            q.q[layer], q.scale_lo[layer], q.scale_hi[layer],
            q.in_dim, q.out_dim,
        )
        # Decode shape [B, 1, IN] with B past the prefill row threshold:
        # must STILL take the stacked kernel (slice path would re-copy).
        xd = jax.random.normal(
            jax.random.PRNGKey(layer), (300, 1, IN), jnp.float32
        )
        out_v = matmul(xd, view)
        ref = oracle(xd.reshape(300, IN), layer)[:, :OUT].reshape(300, 1, OUT)
        np.testing.assert_allclose(
            np.asarray(out_v), ref, rtol=2e-2, atol=8e-3
        )
        out_p = matmul(xd[:200].reshape(200, IN), per_layer)
        np.testing.assert_allclose(
            np.asarray(out_v[:200, 0]), np.asarray(out_p),
            rtol=1e-5, atol=1e-5,
        )
        # Many-row prefill [400, IN]: the XLA unpack branch of the view.
        xp = jax.random.normal(
            jax.random.PRNGKey(10 + layer), (400, IN), jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(matmul(xp, view)), oracle(xp, layer)[:, :OUT],
            rtol=2e-2, atol=8e-3,
        )


# -- outlier-aware int8 (LLM.int8()-style decomposition) ---------------------


def test_outlier_int8_rescues_planted_outlier_rows():
    """Weights with a few huge input rows (the regime bitsandbytes'
    threshold=5.0 exists for, reference utils/model.py:102-108): plain
    per-channel int8 loses most of its resolution to the outliers; the
    decomposition carries them in fp and recovers near-int8-clean error."""
    from distributed_llm_inference_tpu.ops.quant import quantize_int8_outlier

    rng = np.random.default_rng(0)
    w = rng.standard_normal((256, 64)).astype(np.float32)
    hot = rng.choice(256, size=8, replace=False)
    w[hot] *= 100.0  # planted activation-outlier-style rows
    x = rng.standard_normal((16, 256)).astype(np.float32)
    exact = x @ w

    def rel_err(y):
        return float(np.linalg.norm(np.asarray(y) - exact)
                     / np.linalg.norm(exact))

    e_plain = rel_err(matmul(jnp.asarray(x),
                             quantize_int8(jnp.asarray(w), jnp.float32)))
    qo = quantize_int8_outlier(jnp.asarray(w), 16, scale_dtype=jnp.float32)
    e_out = rel_err(matmul(jnp.asarray(x), qo))
    # The planted rows were selected as outliers...
    assert set(hot).issubset(set(np.asarray(qo.outlier_idx).tolist()))
    # ...and the decomposition recovers well over an order of magnitude.
    assert e_out < e_plain / 10


def test_outlier_int8_act_scales_select_channels():
    from distributed_llm_inference_tpu.ops.quant import quantize_int8_outlier

    rng = np.random.default_rng(1)
    w = rng.standard_normal((64, 32)).astype(np.float32)
    act = np.zeros((64,), np.float32)
    act[[3, 17, 40]] = 100.0  # calibration says these channels run hot
    qo = quantize_int8_outlier(jnp.asarray(w), 3,
                               act_scales=jnp.asarray(act))
    assert sorted(np.asarray(qo.outlier_idx).tolist()) == [3, 17, 40]


def test_outlier_int8_stacked_layers_and_model_forward():
    """quantize_params(outlier_channels=...) on the stacked layer pytree:
    model_apply runs through the lax.scan layer slice and tracks the bf16
    model closely."""
    params = llama.init_params(CFG, jax.random.PRNGKey(3), jnp.float32)
    qp = quantize_params(params, scale_dtype=jnp.float32,
                         outlier_channels=4)
    from distributed_llm_inference_tpu.ops.quant import (
        QuantizedTensorOutlier,
    )

    assert isinstance(qp["layers"]["wq"], QuantizedTensorOutlier)
    assert isinstance(qp["lm_head"], QuantizedTensorOutlier)
    cache = DenseKVCache.create(
        CFG.num_layers, 1, 32, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    qcache = DenseKVCache.create(
        CFG.num_layers, 1, 32, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    toks = jnp.asarray([[5, 9, 2, 11]], jnp.int32)
    n = jnp.full((1,), 4, jnp.int32)
    ref, _ = llama.model_apply(CFG, params, toks, cache, n)
    got, _ = llama.model_apply(CFG, qp, toks, qcache, n)
    err = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert err < 0.05


def test_engine_int8_outlier_generates_and_tp_shards():
    """EngineConfig(quantization="int8_outlier") serves, and the outlier
    leaves shard over a tp mesh (pspec coverage in parallel/tp.py)."""
    params = llama.init_params(CFG, jax.random.PRNGKey(4), jnp.float32)
    eng = InferenceEngine(
        CFG, params,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16),
                     max_seq_len=32, dtype="float32",
                     quantization="int8_outlier"),
        CacheConfig(kind="dense"),
    )
    outs = eng.generate([[1, 2, 3]], SamplingOptions(max_new_tokens=5))
    assert len(outs[0]) == 5
    sharded = InferenceEngine(
        CFG, params,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16),
                     max_seq_len=32, dtype="float32",
                     quantization="int8_outlier"),
        CacheConfig(kind="dense"),
        mesh_cfg=MeshConfig(tp=2),
    )
    assert sharded.generate(
        [[1, 2, 3]], SamplingOptions(max_new_tokens=5)
    ) == outs


# -- W8A8 prefill path (int8 activations on the MXU) -------------------------


def test_w8a8_matmul_close_to_fp():
    from distributed_llm_inference_tpu.ops.quant import w8a8_matmul

    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 64, 128)).astype(np.float32)
    w = rng.standard_normal((128, 96)).astype(np.float32)
    exact = x @ w
    got = np.asarray(w8a8_matmul(
        jnp.asarray(x), quantize_int8(jnp.asarray(w), jnp.float32)
    ))
    err = np.linalg.norm(got - exact) / np.linalg.norm(exact)
    # int8 weights AND int8 per-token activations: ~1% relative is the
    # expected regime (weight-only int8 alone is ~0.5%).
    assert err < 0.02, err


def test_w8a8_activation_outlier_rows_keep_their_scale():
    """Per-token scales: one huge row must not crush the others' precision."""
    from distributed_llm_inference_tpu.ops.quant import w8a8_matmul

    rng = np.random.default_rng(6)
    x = rng.standard_normal((1, 8, 64)).astype(np.float32)
    x[0, 3] *= 1000.0
    w = rng.standard_normal((64, 32)).astype(np.float32)
    exact = x @ w
    got = np.asarray(w8a8_matmul(
        jnp.asarray(x), quantize_int8(jnp.asarray(w), jnp.float32)
    ))
    for i in range(8):  # every row individually accurate
        err = np.linalg.norm(got[0, i] - exact[0, i]) / np.linalg.norm(exact[0, i])
        assert err < 0.02, (i, err)


def test_model_apply_head_last_and_none():
    """head="last" logits equal the full head's last valid position;
    head="none" returns no logits but the same cache writes."""
    params = llama.init_params(CFG, jax.random.PRNGKey(7), jnp.float32)

    def cache():
        return DenseKVCache.create(
            CFG.num_layers, 2, 32, CFG.num_kv_heads, CFG.head_dim, jnp.float32
        )

    toks = jnp.asarray([[5, 9, 2, 11], [3, 1, 0, 0]], jnp.int32)
    n = jnp.asarray([4, 2], jnp.int32)
    full, c_full = llama.model_apply(CFG, params, toks, cache(), n)
    last, c_last = llama.model_apply(CFG, params, toks, cache(), n,
                                     head="last")
    np.testing.assert_allclose(np.asarray(last[0, 0]), np.asarray(full[0, 3]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(last[1, 0]), np.asarray(full[1, 1]),
                               rtol=1e-5)
    none, c_none = llama.model_apply(CFG, params, toks, cache(), n,
                                     head="none")
    assert none is None
    np.testing.assert_array_equal(np.asarray(c_none.k), np.asarray(c_full.k))
    np.testing.assert_array_equal(np.asarray(c_none.lengths),
                                  np.asarray(c_full.lengths))


def test_quantized_cache_flash_prefill_path_matches_int8_path():
    """The S >= FLASH_PREFILL_MIN_S dispatch inside the quantized caches'
    attend: flash-over-dequantized-gather must track the int8-score path
    closely (same int8 cache contents, different softmax realization)."""
    from distributed_llm_inference_tpu.cache import base as cache_base
    from distributed_llm_inference_tpu.cache.dense import QuantizedDenseKVCache

    params = llama.init_params(CFG, jax.random.PRNGKey(8), jnp.float32)
    toks = jnp.asarray(
        np.random.default_rng(9).integers(0, CFG.vocab_size, (1, 128))
    )
    n = jnp.asarray([128], jnp.int32)

    def run():
        cache = QuantizedDenseKVCache.create(
            CFG.num_layers, 1, 256, CFG.num_kv_heads, CFG.head_dim,
            jnp.float32,
        )
        logits, _ = llama.model_apply(CFG, params, toks, cache, n,
                                      head="last")
        return np.asarray(logits)

    ref = run()  # int8-score path (MIN_S default 1024 > 128)
    old = cache_base.FLASH_PREFILL_MIN_S
    cache_base.FLASH_PREFILL_MIN_S = 64  # the policy reads this at call time
    try:
        got = run()  # flash path (interpret mode on CPU)
    finally:
        cache_base.FLASH_PREFILL_MIN_S = old
    err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert err < 5e-3, err


# -- EngineConfig surface for the quantization knobs --------------------------


def test_engine_config_outlier_channels_and_act_scales():
    """outlier_channels / act_scales round-trip from EngineConfig into the
    int8_outlier decomposition: channel count honored, calibration scales
    steer the selection."""
    from distributed_llm_inference_tpu.ops.quant import QuantizedTensorOutlier

    params = llama.init_params(CFG, jax.random.PRNGKey(4), jnp.float32)
    act = np.zeros((CFG.hidden_size,), np.float32)
    act[[1, 5, 9]] = 100.0  # calibration: these input channels run hot
    ecfg = EngineConfig(
        max_batch_size=2, prefill_buckets=(8, 16), max_seq_len=32,
        dtype="float32", quantization="int8_outlier", outlier_channels=3,
        act_scales={"wq": jnp.asarray(act)},
    )
    hash(ecfg)  # the pytree-valued field must not break hashability
    eng = InferenceEngine(CFG, params, ecfg, CacheConfig(kind="dense"))
    wq = eng.params["layers"]["wq"]
    assert isinstance(wq, QuantizedTensorOutlier)
    assert wq.outlier_idx.shape[-1] == 3
    idx = np.asarray(wq.outlier_idx).reshape(CFG.num_layers, -1)
    for layer_idx in idx:
        assert sorted(layer_idx.tolist()) == [1, 5, 9]
    outs = eng.generate([[1, 2, 3]], SamplingOptions(max_new_tokens=3))
    assert len(outs[0]) == 3


@pytest.mark.parametrize("host", [True, False], ids=["host-leaf", "device-leaf"])
@pytest.mark.parametrize("bits,layout", [(8, "grouped"), (4, "grouped"),
                                         (4, "split")])
def test_quantize_params_stack_bytes_equal_whole_leaf(bits, layout, host):
    """``quantize_params`` takes a layer stack one layer at a time, from
    the host or the device (a 7B bf16 tree cannot sit on a 16 GB chip
    beside its int8 copy): the stored bytes are those of the whole-leaf
    quantizers, int4 nibbles included."""
    from distributed_llm_inference_tpu.ops.quant import (
        quantize_int4, quantize_int4_split, quantize_int8, quantize_params,
    )

    w = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 96), jnp.bfloat16)
    whole = {
        (8, "grouped"): lambda: quantize_int8(w),
        (4, "grouped"): lambda: quantize_int4(w, 64),
        (4, "split"): lambda: quantize_int4_split(w),
    }[bits, layout]()
    leaf = np.asarray(w) if host else w
    out = quantize_params(
        {"layers": {"wq": leaf}}, bits=bits, int4_layout=layout
    )["layers"]["wq"]
    assert type(out) is type(whole)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
