"""What a device trace calls things: a stable ``name=`` on every Pallas
kernel under ``ops/`` and ``jax.named_scope`` around the model's parts.
Fusion and custom-call numbers (``closed_call.41``, ``fusion.153``) move with
every recompile; these names are what a reduction of the trace reads."""

import ast
import glob
import os

import jax
import jax.numpy as jnp

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.engine.sampling import SamplingParams, sample
from distributed_llm_inference_tpu.models import llama

OPS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "distributed_llm_inference_tpu", "ops",
)


def pallas_calls():
    """(file, line, enclosing function, the call) of every ``pallas_call``."""
    for path in sorted(glob.glob(os.path.join(OPS, "*.py"))):
        tree = ast.parse(open(path).read())
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"):
                    yield os.path.basename(path), node.lineno, fn.name, node


def names_through_a_parameter(fn_name):
    """The names a kernel takes through its wrapper's ``name`` parameter:
    that parameter's default and every constant a caller under ``ops/``
    passes for it (the latent wrappers call the per-head wrappers under
    their own names)."""
    out = []
    for path in sorted(glob.glob(os.path.join(OPS, "*.py"))):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.FunctionDef) and node.name == fn_name:
                a = node.args
                named = dict(zip(
                    [x.arg for x in a.args[len(a.args) - len(a.defaults):]],
                    a.defaults,
                ))
                out.append(named["name"])
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "id", None) == fn_name):
                out += [k.value for k in node.keywords if k.arg == "name"]
    return out


def test_every_pallas_call_has_a_name_of_its_own():
    names = {}
    for path, line, fn, call in pallas_calls():
        kw = {k.arg: k.value for k in call.keywords}
        assert "name" in kw, f"{path}:{line} pallas_call without name="
        given = [kw["name"]]
        if isinstance(kw["name"], ast.Name):
            assert kw["name"].id == "name", f"{path}:{line}"
            given = names_through_a_parameter(fn)
        for value in given:
            # a constant: the same whatever the block sizes and shapes
            assert isinstance(value, ast.Constant), f"{path}:{line}"
            assert isinstance(value.value, str) and value.value
            names.setdefault(value.value, []).append(f"{path}:{line} {fn}")
    assert len(names) >= 21
    for kernel in ("latent_paged_attention", "quantized_latent_paged_attention",
                   "latent_ragged_paged_attention",
                   "quantized_latent_ragged_paged_attention",
                   "sparse_latent_paged_fused_attention",
                   "sparse_latent_ragged_paged_attention",
                   "moe_grouped_matmul"):
        assert kernel in names, kernel
    shared = {n: at for n, at in names.items() if len(at) > 1}
    assert not shared, shared


def lowered_text(cfg, tokens=4):
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = DenseKVCache.create(
        cfg.num_layers, 1, 16, cfg.num_kv_heads, cfg.head_dim, jnp.float32
    )

    def forward(params, tokens, cache, key):
        logits, cache = llama.model_apply(
            cfg, params, tokens, cache, jnp.full((1,), 4, jnp.int32),
            head="last",
        )
        sp = SamplingParams.create(1, 0.0, 0, 1.0)
        return sample(logits[:, 0], key, sp), cache

    return jax.jit(forward).lower(
        params, jnp.zeros((1, tokens), jnp.int32), cache, jax.random.PRNGKey(1)
    ).as_text(debug_info=True)


def scoped(text, scope):
    """An operation's location is ``<scope path>/<primitive>``: inside the
    layer scan without the ``jit(...)`` prefix, outside it with."""
    return any(f"{a}{scope}{b}" in text for a in '"/' for b in '"/')


def test_a_lowered_forward_carries_the_model_scopes():
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
    )
    text = lowered_text(cfg)
    for scope in ("attention", "mlp", "head", "sampler"):
        assert scoped(text, scope), scope
    assert "moe_experts" not in text


def test_a_lowered_moe_forward_carries_the_expert_scopes():
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2,
    )
    text = lowered_text(cfg)
    for scope in ("mlp/moe_router", "mlp/moe_experts", "mlp/moe_combine"):
        assert scoped(text, scope), scope


def test_a_lowered_grouped_moe_prefill_carries_the_kernel_and_its_scopes(monkeypatch):
    """A prefill wide enough for the grouped dispatch (at a row tile of 8,
    32 tokens are): the sort's scope beside the three of a routed MLP and
    three calls of ``moe_grouped_matmul`` a routed layer's scan body; a
    4-token step of the same model (one row tile: the live path) carries
    the same four and the kernel; 12 tokens, between the two, dense-combine
    and have neither the sort nor the kernel."""
    from distributed_llm_inference_tpu.ops import moe

    monkeypatch.setattr(moe, "ROW_TILE", 8)
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = DenseKVCache.create(
        cfg.num_layers, 1, 32, cfg.num_kv_heads, cfg.head_dim, jnp.float32
    )
    text = jax.jit(lambda p, t, c: llama.model_apply(
        cfg, p, t, c, jnp.full((1,), 32, jnp.int32), head="last",
    )).lower(params, jnp.zeros((1, 32), jnp.int32), cache).as_text(
        debug_info=True
    )
    for scope in ("mlp/moe_router", "mlp/moe_sort", "mlp/moe_experts",
                  "mlp/moe_combine"):
        assert scoped(text, scope), scope
    assert "moe_grouped_matmul" in text
    step = lowered_text(cfg)
    for scope in ("mlp/moe_router", "mlp/moe_sort", "mlp/moe_experts",
                  "mlp/moe_combine"):
        assert scoped(step, scope), scope
    assert "moe_grouped_matmul" in step
    between = lowered_text(cfg, tokens=12)
    assert "moe_grouped_matmul" not in between and "moe_sort" not in between


def test_a_lowered_two_segment_forward_carries_a_scope_a_segment():
    """A leading dense layer before routed ones with a shared expert: one
    scope a segment, and ``moe_shared`` beside the three of a routed MLP."""
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=16, num_shared_experts=1,
        first_dense_layers=1, moe_scoring="sigmoid", moe_select_bias=True,
        moe_routed_scale=2.0, family="mixtral",
    )
    text = lowered_text(cfg)
    for scope in ("dense_stack", "moe_stack", "mlp/moe_router",
                  "mlp/moe_experts", "mlp/moe_combine", "mlp/moe_shared"):
        assert scoped(text, scope), scope


def test_a_one_segment_forward_is_in_its_segments_scope():
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
    )
    text = lowered_text(cfg)
    assert scoped(text, "dense_stack") and not scoped(text, "moe_stack")


def test_a_lowered_sparse_forward_carries_the_selections_scopes_and_kernels():
    """A model that selects its keys, through the indexed int8 cache with
    its kernels (interpreted here): the three scopes of
    ``ops/sparse_attention.py`` inside ``attention`` and the kernels' own
    names, in a prefill chunk and in the fused decode scan."""
    from distributed_llm_inference_tpu.cache.paged import indexed_cache_class
    from distributed_llm_inference_tpu.config import SparseAttentionConfig

    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16, qk_norm=True,
        sparse=SparseAttentionConfig(2, 8, 4), family="keye_vl2",
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = indexed_cache_class(True, 8).create(
        2, 1, 5, 8, 4, 2, 16, jnp.float32, use_kernel=True, use_ragged=True
    )
    one = jnp.ones((1,), jnp.int32)
    prefill = jax.jit(
        lambda p, t, c: llama.model_apply(cfg, p, t, c, 8 * one, head="last")
    ).lower(params, jnp.zeros((1, 8), jnp.int32), cache).as_text(debug_info=True)
    decode = jax.jit(lambda p, t, c: llama.multi_decode_apply(
        cfg, p, t, c, 4, lambda i, logits, st: (t[:, 0], one, st, logits),
        jnp.zeros(()), one,
    )).lower(params, jnp.zeros((1, 1), jnp.int32), cache).as_text(debug_info=True)
    for text, kernels in (
        (prefill, ["sparse_ragged_paged_attention"]),
        (decode, ["sparse_paged_fused_attention", "index_tail_flush",
                  "paged_tail_flush"]),
    ):
        for scope in ("attention/index_scores", "attention/index_select",
                      "attention/sparse_attention"):
            assert scoped(text, scope), scope
        for kernel in kernels:
            assert kernel in text, kernel
    dense = lowered_text(ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
    ))
    assert "index_scores" not in dense and "sparse_attention" not in dense


def test_a_lowered_shared_selection_over_a_latent_carries_its_scopes_and_kernels():
    """A latent stack whose layers share a learned selection (score, reuse,
    reuse, score), through the indexed int8 latent cache with its kernels
    (interpreted here): a segment's scope names its part in the selection
    under its MLP kind; ``index_scores`` / ``index_select`` exist in the
    scoring segments only and ``index_reuse`` in the reusing ones only; and
    the latent kernels run under the selection's names, in a prefill chunk
    and in the fused decode scan."""
    from distributed_llm_inference_tpu.cache.latent import (
        indexed_latent_cache_class,
    )
    from distributed_llm_inference_tpu.config import (
        LatentConfig, SparseAttentionConfig,
    )
    from distributed_llm_inference_tpu.ops import paged_attention as pa
    from distributed_llm_inference_tpu.ops import ragged_attention as ra

    kinds = ("score", "reuse", "reuse", "score")
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=4,
        num_heads=2, num_kv_heads=2, head_dim=16,
        latent=LatentConfig(rank=16, rope_head_dim=8, nope_head_dim=16,
                            v_head_dim=16, q_lora_rank=24),
        sparse=SparseAttentionConfig(2, 16, 4, rope_dim=8),
        index_layers=kinds, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=16, num_shared_experts=1, first_dense_layers=1,
        moe_scoring="sigmoid", moe_select_bias=True, family="glm_moe_dsa",
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert [k for k in params if k.startswith("layers")] == [
        "layers_0_dense", "layers_1_moe", "layers_2_moe"]
    assert "wk_i" in params["layers_0_dense"] and "wk_i" in params["layers_2_moe"]
    assert "wk_i" not in params["layers_1_moe"] and "wq_a" in params["layers_1_moe"]
    cache = indexed_latent_cache_class(
        True, 16, tuple(k == "score" for k in kinds)
    ).create(4, 1, 5, 8, 4, 1, 24, jnp.float32, use_kernel=True, use_ragged=True)
    assert cache.ik_pages.shape[0] == 2
    one = jnp.ones((1,), jnp.int32)
    prefill = jax.jit(
        lambda p, t, c: llama.model_apply(cfg, p, t, c, 8 * one, head="last")
    ).lower(params, jnp.zeros((1, 8), jnp.int32), cache).as_text(debug_info=True)
    decode = jax.jit(lambda p, t, c: llama.multi_decode_apply(
        cfg, p, t, c, 4, lambda i, logits, st: (t[:, 0], one, st, logits),
        jnp.zeros(()), one,
    )).lower(params, jnp.zeros((1, 1), jnp.int32), cache).as_text(debug_info=True)
    assert (pa.KERNEL_LATENT_DECODE, pa.KERNEL_LATENT_INDEX_FLUSH,
            ra.KERNEL_LATENT_PREFILL) == (
        "sparse_latent_paged_fused_attention", "latent_index_tail_flush",
        "sparse_latent_ragged_paged_attention")
    for text, kernels in (
        (prefill, [ra.KERNEL_LATENT_PREFILL]),
        (decode, [pa.KERNEL_LATENT_DECODE, pa.KERNEL_LATENT_INDEX_FLUSH,
                  "paged_tail_flush"]),
    ):
        for scope in ("dense_stack/index_score_layers",
                      "moe_stack/index_reuse_layers",
                      "moe_stack/index_score_layers",
                      "attention/index_scores", "attention/index_select",
                      "attention/sparse_attention",
                      "attention/index_reuse/sparse_attention"):
            assert scoped(text, scope), scope
        for kernel in kernels:
            assert kernel in text, kernel
        # a reusing segment scores nothing, a scoring one reuses nothing
        lines = text.splitlines()
        assert not [l for l in lines
                    if "index_reuse_layers" in l and "index_scores" in l]
        assert not [l for l in lines
                    if "index_score_layers" in l and "index_reuse/" in l]


def test_a_lowered_stack_of_two_attention_kinds_carries_a_scope_and_kernels_a_kind():
    """Window and full layers in one stack, through the int8 two-pool cache
    with its kernels (interpreted here): a segment's scope names its MLP
    kind and, under it, its attention kind; inside ``attention`` the kind has
    a scope of its own; and the window pool's calls of the kernels' bodies
    run under the window pool's names, beside the full pool's under the
    bodies' own."""
    from distributed_llm_inference_tpu.cache.paged import two_pool_cache_class

    kinds = ("window", "window", "full", "window")
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=4,
        num_heads=2, num_kv_heads=2, head_dim=16, qk_norm=True,
        sliding_window=8, layer_attention=kinds, full_attention_rope=False,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
        num_shared_experts=1, first_dense_layers=1, moe_scoring="sigmoid",
        moe_select_bias=True, expert_shares=2, family="exaone_moe",
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert params["layers_1_moe"]["we_g"].shape[:2] == (1, 2)     # held
    assert params["layers_1_moe"]["router"].shape[-1] == 4        # scored
    cache = two_pool_cache_class(True, kinds, 8).create(
        1, 1, 5, 8, 96, 2, 16, jnp.float32, use_kernel=True, use_ragged=True
    )
    one = jnp.ones((1,), jnp.int32)
    prefill = jax.jit(
        lambda p, t, c: llama.model_apply(cfg, p, t, c, 8 * one, head="last")
    ).lower(params, jnp.zeros((1, 8), jnp.int32), cache).as_text(debug_info=True)
    decode = jax.jit(lambda p, t, c: llama.multi_decode_apply(
        cfg, p, t, c, 4, lambda i, logits, st: (t[:, 0], one, st, logits),
        jnp.zeros(()), one,
    )).lower(params, jnp.zeros((1, 1), jnp.int32), cache).as_text(debug_info=True)
    for text, kernels in (
        (prefill, ["window_ragged_paged_attention",
                   "quantized_ragged_paged_attention"]),
        (decode, ["window_paged_fused_attention", "window_tail_flush",
                  "quantized_paged_fused_attention", "paged_tail_flush"]),
    ):
        for scope in ("dense_stack/window_layers", "moe_stack/window_layers",
                      "moe_stack/full_layers", "attention/window_attention",
                      "attention/full_attention", "mlp/moe_shared"):
            assert scoped(text, scope), scope
        for kernel in kernels:
            assert kernel in text, kernel
    alike = lowered_text(ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16, sliding_window=8,
        family="mistral",
    ))
    assert "window_layers" not in alike and "window_attention" not in alike
