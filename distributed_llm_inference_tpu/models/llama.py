"""Llama-family decoder stack as pure JAX functions.

TPU-first re-expression of the reference's model layer
(``/root/reference/distributed_llm_inference/models/llama/model.py`` and
``modules.py``). Design notes:

* ``LlamaBlock`` — a module holding a Python list of decoder layers iterated in
  a Python loop (``model.py:22,59-71``) — becomes ``block_apply``: a pure
  function over *stacked* layer parameters driven by ``lax.scan``, so compile
  time is O(1) in depth and the whole block is one XLA computation.
* The CUDA-graphed decode fast paths (``modules.py:73-76,159-162,176-179``)
  disappear: ``jax.jit`` of the step function is the graph.
* The vestigial single-device ``pretraining_tp`` weight slicing
  (``modules.py:44-59,107-110``) is dropped; real tensor parallelism is applied
  externally via ``NamedSharding`` on these same parameter arrays
  (see ``parallel/tp.py``).
* Like the reference's block (``model.py:16-76``), ``block_apply`` is strictly a
  hidden-states→hidden-states pipeline stage; embedding / final norm / lm_head
  live in ``model_apply`` (the client-side layers the reference never wrote,
  SURVEY §1).

Weight layout: all projections are stored ``[in_features, out_features]``
(transposed from torch ``nn.Linear``) so the forward is plain ``x @ w``.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import LayerSegment, ModelConfig
from ..ops import hyper_connections as mhc
from ..ops.attention import gqa_attention
from ..ops.moe import moe_mlp
from ..ops.norms import rms_norm
from ..ops.quant import matmul as qmatmul
from ..ops.rotary import RopeAngles, apply_rope, rope_cos_sin, rope_inv_freq
from ..ops.sparse_attention import IndexInputs

Params = Dict[str, Any]

# A stack is ``ModelConfig.segments``: runs of layers that are all alike,
# each one ``lax.scan`` over ``params[segment.key]``. A segment's kind names
# its MLP and its scope in a device trace; its attention (window or full)
# comes with it as its window, its RoPE switch and its part of the cache.
# A further kind is a row here, its leaves in :func:`_mlp_params`, and its
# branch in :func:`_mlp_residual`.
SEGMENT_SCOPES = {"dense": "dense_stack", "moe": "moe_stack"}


def _segment_cache(cache, seg: LayerSegment, seq_len: Optional[int] = None):
    """The cache a segment's layers read and write: the cache itself, or the
    pool of the segment's attention kind where the cache holds two
    (``cache/paged.py``: the two-pool classes), or, where layers share a
    learned selection, the cache's view for the segment's part in it
    (``cache/latent.py``: ``index_view``; the selection then rides the
    cache's own layer state, which is the scans' carry, from a scoring
    segment into the reusing one behind it; ``seq_len``, the dispatch's
    queries a row, sizes that state: the fused decode scan, whose tail
    carries it, passes none)."""
    if seg.index is not None:
        return cache.index_view(
            seg.index, seg.index_start - seg.cache_start, seq_len
        )
    return cache if seg.pool is None else cache.pool_view(seg.pool)


def _segment_scope(seg: LayerSegment):
    """A segment's scope in a device trace: its MLP kind's, and under it the
    attention kind's where the stack has two, or its part in a shared
    selection (``index_score_layers`` / ``index_reuse_layers``)."""
    name = SEGMENT_SCOPES[seg.kind]
    if seg.pool is not None:
        name = f"{name}/{seg.attention}_layers"
    if seg.index is not None:
        name = f"{name}/index_{seg.index}_layers"
    return jax.named_scope(name)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def init_layer_params(
    cfg: ModelConfig, key: jax.Array, num_layers: int, dtype=jnp.bfloat16,
    kind: Optional[str] = None, index: Optional[str] = None,
) -> Params:
    """Random (normal 0.02) stacked parameters for ``num_layers`` decoder
    layers of one segment ``kind`` (default: the kind of the stack's last
    segment — a Mixtral's or a dense model's only one). ``index``: the
    segment's part in a shared selection (``LayerSegment.index``); a
    "reuse" segment's layers have no indexer."""
    h, d = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    kind = kind or cfg.segments[-1].kind
    keys = jax.random.split(key, 8)

    def w(k, *shape):
        return (jax.random.normal(k, (num_layers, *shape), jnp.float32) * 0.02).astype(
            dtype
        )

    if cfg.use_latent:
        # MLA (latent KV) attention parameter set — see
        # :func:`_latent_attention` for how each projection is consumed.
        lat = cfg.latent
        dn = lat.nope_head_dim or d
        dr = lat.rope_head_dim
        dv = lat.v_head_dim or d
        qr = lat.q_lora_rank
        p = {
            "attn_norm": jnp.ones((num_layers, h), dtype),
            # Queries from the hidden state, or (compressed queries) down
            # to ``q_lora_rank``, an RMSNorm, and up to the heads.
            **({"wq": w(keys[0], h, hq * (dn + dr))} if qr is None else {
                "wq_a": w(keys[0], h, qr),
                "q_a_norm": jnp.ones((num_layers, qr), dtype),
                "wq_b": w(jax.random.fold_in(keys[0], 1), qr, hq * (dn + dr)),
            }),
            # Down-projection to the stored form: [c ; k_rope_pre].
            "wkv_a": w(keys[1], h, lat.rank + dr),
            "kv_norm": jnp.ones((num_layers, lat.rank), dtype),
            # Key up-projection (absorbed into the query at apply time).
            "wk_b": w(keys[2], lat.rank, hq, dn),
            # Value up-projection (applied after the softmax).
            "wv_b": w(keys[7], lat.rank, hq, dv),
            "wo": w(keys[3], hq * dv, h),
            "mlp_norm": jnp.ones((num_layers, h), dtype),
        }
    else:
        p = {
            "attn_norm": jnp.ones((num_layers, h), dtype),
            "wq": w(keys[0], h, hq * d),
            "wk": w(keys[1], h, hkv * d),
            "wv": w(keys[2], h, hkv * d),
            "wo": w(keys[3], hq * d, h),
            "mlp_norm": jnp.ones((num_layers, h), dtype),
        }
    if cfg.loop is not None:
        # A looped block norms each sublayer's OUTPUT before it is added.
        p["attn_out_norm"] = jnp.ones((num_layers, h), dtype)
        p["mlp_out_norm"] = jnp.ones((num_layers, h), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((num_layers, d), dtype)
        p["k_norm"] = jnp.ones((num_layers, d), dtype)
    if cfg.use_retention:
        # The gate of a retention layer: one output a key-value head, its
        # biases spread so that the gates lie between 0.9 and 0.999.
        p["w_gate"] = w(jax.random.fold_in(keys[3], 7), h, hkv)
        p["b_gate"] = jnp.broadcast_to(
            jnp.linspace(2.2, 6.9, hkv, dtype=jnp.float32), (num_layers, hkv)
        )
    if cfg.use_sparse and index != "reuse":
        # The indexer (see :func:`_index_inputs`): index queries (from the
        # compressed query where the block has one), ONE index key a token
        # under a LayerNorm, and a weight a head.
        sa = cfg.sparse
        ik = jax.random.split(keys[3], 4)
        q_in = (cfg.latent.q_lora_rank if cfg.use_latent else None) or h
        p["wq_i"] = w(ik[1], q_in, sa.index_heads * sa.index_dim)
        p["wk_i"] = w(ik[2], h, sa.index_dim)
        p["w_i"] = w(ik[3], h, sa.index_heads)
        p["k_i_norm"] = jnp.ones((num_layers, sa.index_dim), dtype)
        p["k_i_norm_bias"] = jnp.zeros((num_layers, sa.index_dim), dtype)
    p.update(_mlp_params(cfg, kind, w, keys))
    if cfg.hyper is not None:
        p.update(_hyper_params(cfg, jax.random.fold_in(key, 9), num_layers))
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((num_layers, hq * d), dtype)
        p["bk"] = jnp.zeros((num_layers, hkv * d), dtype)
        p["bv"] = jnp.zeros((num_layers, hkv * d), dtype)
    return p


def _hyper_params(cfg: ModelConfig, key, num_layers: int) -> Params:
    """The hyper-connection leaves of a layer's two sublayers
    (``ops/hyper_connections.py``), float32: projections drawn so that the
    maps' logits have a spread of about 1, biases normal."""
    hc, out = cfg.hyper, {}
    shapes = mhc.leaf_shapes(hc, cfg.hidden_size)
    for n, prefix in enumerate(("hc_attn", "hc_mlp")):
        k_phi, k_bias = jax.random.split(jax.random.fold_in(key, n))
        out[f"{prefix}_phi"] = jax.random.normal(
            k_phi, (num_layers, *shapes["phi"]), jnp.float32
        ) * shapes["phi"][1] ** -0.5
        out[f"{prefix}_alpha"] = jnp.ones((num_layers, 3), jnp.float32)
        out[f"{prefix}_bias"] = jax.random.normal(
            k_bias, (num_layers, *shapes["bias"]), jnp.float32
        )
    return out


def _mlp_params(cfg: ModelConfig, kind: str, w, keys) -> Params:
    """The MLP leaves of one segment kind (``w(key, *shape)`` draws a
    stacked matrix)."""
    h = cfg.hidden_size
    if kind == "dense":
        inter = cfg.intermediate_size
        return {
            "wg": w(keys[4], h, inter),
            "wu": w(keys[5], h, inter),
            "wd": w(keys[6], inter, h),
        }
    if kind != "moe":
        raise ValueError(f"unknown segment kind {kind!r}")
    # the router's width, and the expert matrices held here (ops/moe.py)
    e, held = cfg.num_experts, cfg.num_held_experts
    f = cfg.expert_intermediate_size
    p = {
        "router": w(keys[7], h, e),
        "we_g": w(keys[4], held, h, f),
        "we_u": w(keys[5], held, h, f),
        "we_d": w(keys[6], held, f, h),
    }
    more = jax.random.split(keys[7], 4)
    if cfg.moe_select_bias:
        # A float32 parameter of the router (selection only, ops/moe.py).
        p["router_bias"] = w(more[0], e).astype(jnp.float32)
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * f
        p["ws_g"] = w(more[1], h, fs)
        p["ws_u"] = w(more[2], h, fs)
        p["ws_d"] = w(more[3], fs, h)
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Full-model parameters (embedding + one stacked dict a segment +
    head)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    segments = cfg.segments
    seg_keys = (
        [k_layers] if len(segments) == 1
        else list(jax.random.split(k_layers, len(segments)))
    )
    params: Params = {
        "embed": (
            jax.random.normal(k_embed, (cfg.vocab_size, cfg.hidden_size), jnp.float32)
            * 0.02
        ).astype(dtype),
        **{
            seg.key: init_layer_params(
                cfg, k, seg.count, dtype, seg.kind, seg.index
            )
            for seg, k in zip(segments, seg_keys)
        },
        "final_norm": jnp.ones((cfg.hidden_size,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.hidden_size, cfg.vocab_size), jnp.float32)
            * 0.02
        ).astype(dtype)
    if cfg.loop is not None:
        # The exit gate behind every lap (:func:`_loop_exit`): one
        # ``hidden_size -> 1`` projection and its bias, shared by the laps.
        params["exit_w"] = (
            jax.random.normal(
                jax.random.fold_in(k_head, 1), (cfg.hidden_size,), jnp.float32
            ) * 0.02
        ).astype(dtype)
        params["exit_b"] = jnp.zeros((), jnp.float32)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _decoder_layer(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    layer_state: Tuple[jnp.ndarray, ...],
    cache,
    rope: RopeAngles,
    q_pos: jnp.ndarray,
    num_new: jnp.ndarray,
    attention_fn=gqa_attention,
    index_rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    segment: Optional[LayerSegment] = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """One decoder layer: pre-norm attention + pre-norm SwiGLU MLP.

    Mirrors the reference layer structure (``modules.py:146-184``) minus its
    double-residual deviation (SURVEY §2.9.3). ``index_rope``: the rotary
    tables of a learned selection's index queries and keys (their own
    width; :func:`_index_rope`). ``segment``: the layer's segment, for its
    sliding window (None: the model's one window, a stack of like layers).
    A layer without RoPE is handed the identity rotation as ``rope``
    (:func:`_rope_angles`).
    """
    b, s = x.shape[:2]
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.sliding_window if segment is None else segment.window
    # where the stack has two attention kinds, each has its scope inside
    # ``attention``
    kind_scope = (
        jax.named_scope(f"{segment.attention}_attention")
        if segment is not None and segment.pool is not None
        else contextlib.nullcontext()
    )

    # The scopes (``attention`` here, ``mlp`` / ``moe_*`` below, ``head``,
    # ``sampler``) are the stable part of every operation's name in a device
    # trace: fusion numbers move with each recompile, these do not.
    with jax.named_scope("attention"):
        h, mix = _stream_read(cfg, p, "hc_attn", x)
        h = rms_norm(h, p["attn_norm"], cfg.rms_norm_eps)
        if cfg.use_latent:
            attn_flat, new_state = _latent_attention(
                cfg, p, h, layer_state, cache, rope, q_pos, num_new,
                attention_fn, index_rope,
            )
        else:
            q = qmatmul(h, p["wq"])
            k = qmatmul(h, p["wk"])
            v = qmatmul(h, p["wv"])
            # Biases applied iff the checkpoint carries them (HF
            # `attention_bias`).
            if "bq" in p:
                q = q + p["bq"]
                k = k + p["bk"]
                v = v + p["bv"]
            q = q.reshape(b, s, hq, d)
            k = k.reshape(b, s, hkv, d)
            v = v.reshape(b, s, hkv, d)
            if "q_norm" in p:
                q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
            # A learned selection hands the cache its index inputs beside
            # q, k, v; every other model's call is the one it always was.
            more = (
                {"index": _index_inputs(cfg, p, h, index_rope)}
                if cfg.use_sparse else {}
            )
            if "w_gate" in p:
                # A retention layer hands the cache a log-gate a key-value
                # head beside q, k, v (``cache/retention.py``).
                more["gate"] = _retention_gate(p, h)
            with kind_scope:
                attn, new_state = cache.attend(
                    layer_state, q, k, v, rope, q_pos, num_new,
                    window, attention_fn, d**-0.5, **more,
                )
            attn_flat = attn.reshape(b, s, hq * d)
        o = qmatmul(attn_flat, p["wo"])
        if "bo" in p:
            o = o + p["bo"]
        if "attn_out_norm" in p:
            # a looped block's "sandwich": the output is normed, then added
            o = rms_norm(o, p["attn_out_norm"], cfg.rms_norm_eps)
        x = _stream_write(x, o, mix)
    return _mlp_residual(cfg, p, x, s, num_new), new_state


def _retention_gate(p: Params, h):
    """A retention layer's log-gates ``[B, S, Hkv]`` float32: ``logsigmoid``
    of a projection of the normed hidden state (``g_proj``), with its bias
    where the layer has one."""
    with jax.named_scope("retention_gate"):
        g = qmatmul(h, p["w_gate"]).astype(jnp.float32)
        if "b_gate" in p:
            g = g + p["b_gate"].astype(jnp.float32)
        return jax.nn.log_sigmoid(g)


def _stream_read(cfg: ModelConfig, p: Params, prefix: str, x):
    """What a sublayer reads of the residual stream, and what
    :func:`_stream_write` needs to write its output back: the stream itself
    ``[B, S, H]`` and nothing, or, where the stream is ``cfg.hyper.mult``
    rows wide (``[B, S, n, H]``), the rows mixed by the sublayer's
    hyper-connection maps (``ops/hyper_connections.py``) and the two maps
    of the way back."""
    if cfg.hyper is None:
        return x, None
    return mhc.pre_mix(cfg.hyper, p, prefix, x, cfg.rms_norm_eps)


def _stream_write(x, y, mix):
    """The residual stream behind a sublayer whose output is ``y``."""
    return x + y if mix is None else mhc.post_mix(x, y, mix)


def _index_inputs(cfg, p, h, index_rope, cq=None) -> IndexInputs:
    """The lightning indexer's projections (DeepSeek-V3.2's):
    ``index_heads`` queries, taken from the compressed query ``cq`` where
    the block has one and from the normed hidden state ``h`` where it has
    none, and ONE key of ``index_dim`` a token from ``h``, the key under a
    LayerNorm first, both rotated over their whole width or over their
    first ``rope_dim``; a weight a head from ``h``, scaled by ``index_heads
    ** -0.5`` and the scores' ``index_dim ** -0.5``. The three matrices
    stay in the model's dtype (``ops/quant.py``)."""
    sa = cfg.sparse
    b, s, _ = h.shape
    cos, sin = index_rope
    rot = apply_rope
    if sa.rope_dim is not None:
        def rot(x, cos, sin):
            return jnp.concatenate([
                apply_rope(x[..., : sa.rope_dim], cos, sin),
                x[..., sa.rope_dim:],
            ], axis=-1)
    qi = qmatmul(h if cq is None else cq, p["wq_i"]).reshape(
        b, s, sa.index_heads, sa.index_dim
    )
    ki = qmatmul(h, p["wk_i"]).astype(jnp.float32)
    mean = jnp.mean(ki, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
    ki = (ki - mean) * jax.lax.rsqrt(var + cfg.rms_norm_eps)
    ki = (
        ki * p["k_i_norm"].astype(jnp.float32)
        + p["k_i_norm_bias"].astype(jnp.float32)
    ).astype(h.dtype)
    w = qmatmul(h, p["w_i"]) * (sa.index_heads ** -0.5 * sa.index_dim ** -0.5)
    return IndexInputs(
        q=rot(qi, cos, sin),
        k=rot(ki[:, :, None, :], cos, sin)[:, :, 0],
        w=w,
        topk=sa.topk,
    )


def _index_rope(cfg: ModelConfig, positions):
    """Rotary tables ``[B, S, index_dim]`` of the index queries and keys:
    the model's ``rope_theta`` over the indexer's own width. None for a
    model that selects no keys."""
    if not cfg.use_sparse:
        return None
    return rope_cos_sin(
        positions,
        rope_inv_freq(
            cfg.sparse.rope_dim or cfg.sparse.index_dim, cfg.rope_theta,
            cfg.rope_scaling,
        ),
    )


def _mlp_residual(cfg, p, x, s, num_new):
    """Pre-norm MLP + residual (shared by the dense and latent attention
    branches of :func:`_decoder_layer`)."""
    b = x.shape[0]
    with jax.named_scope("mlp"):
        h2, mix = _stream_read(cfg, p, "hc_mlp", x)
        h2 = rms_norm(h2, p["mlp_norm"], cfg.rms_norm_eps)
        # The segment's kind, read off its leaves: a routed layer carries
        # a router.
        if "router" in p:
            # Positions past a row's num_new pick no expert: bucket padding
            # takes no row of a prefill's dispatch, and a decode step's
            # stopped or empty slots (num_new 0) make no expert live.
            valid = (
                jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
                < num_new[:, None]
            )
            mlp = moe_mlp(cfg, p, h2, valid=valid)
        else:
            mlp = qmatmul(
                jax.nn.silu(qmatmul(h2, p["wg"])) * qmatmul(h2, p["wu"]),
                p["wd"],
            )
        if "mlp_out_norm" in p:
            mlp = rms_norm(mlp, p["mlp_out_norm"], cfg.rms_norm_eps)
        return _stream_write(x, mlp, mix)


def _latent_attention(
    cfg: ModelConfig,
    p: Params,
    h: jnp.ndarray,
    layer_state: Tuple[jnp.ndarray, ...],
    cache,
    rope: RopeAngles,
    q_pos: jnp.ndarray,
    num_new: jnp.ndarray,
    attention_fn=gqa_attention,
    index_rope=None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """Absorbed-MLA attention over the latent cache.

    The cache stores ONE fused ``[c ; k_rope]`` latent per token (``c`` =
    shared ``rank``-dim KV latent, ``k_rope`` = decoupled rotary key,
    shared across heads). Two algebraic moves let attention run directly
    over that stored form, so the kernels' page walk doubles as the
    latent→K/V decompression (no per-token K/V ever materializes):

    * The key up-projection is ABSORBED into the query:
      ``q·k = q_nope·(w_uk c) = (q_nope w_uk)·c`` — so the query handed to
      the cache is ``[q_nope @ w_uk[h] ; q_rope]`` and K is the latent
      itself (one KV "head"; GQA broadcast covers all ``Hq`` heads).
    * The value up-projection is DEFERRED past the softmax: with
      ``V = [c ; k_rope]`` the attention output's first ``rank`` dims are
      ``sum_j p_j c_j``, up-projected per head afterwards
      (``sum_j p_j v_j = w_uv (sum_j p_j c_j)``).

    Rope is applied here, to the rope slices only (``rope`` tables are
    built for ``rope_head_dim`` — see :func:`block_apply`); the cache must
    not rotate anything. The softmax scale is
    :func:`_latent_softmax_scale`'s: ``(dn + dr)**-0.5``, the effective
    per-head query dim of the UN-absorbed formulation, times YaRN's factor.

    Compressed queries (``LatentConfig.q_lora_rank``: the layer has
    ``wq_a``, ``q_a_norm``, ``wq_b``): ``cq = RMSNorm(h wq_a)``, ``q = cq
    wq_b``. Under a learned selection a layer that has an indexer hands the
    cache its index inputs (the index queries from ``cq``); a layer that has
    none hands nothing, and the cache attends to the selection its layer
    state carries from the scoring layer before (``cache/latent.py``).
    """
    lat = cfg.latent
    b, s, _ = h.shape
    hq, d = cfg.num_heads, cfg.head_dim
    dn = lat.nope_head_dim or d
    dr = lat.rope_head_dim
    dv = lat.v_head_dim or d
    rank = lat.rank

    cq = None
    if "wq_a" in p:
        cq = rms_norm(qmatmul(h, p["wq_a"]), p["q_a_norm"], cfg.rms_norm_eps)
        q = qmatmul(cq, p["wq_b"])
    else:
        q = qmatmul(h, p["wq"])
    q = q.reshape(b, s, hq, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    ckv = qmatmul(h, p["wkv_a"])  # [B, S, rank + dr]
    c = rms_norm(ckv[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
    k_rope = apply_rope(
        ckv[..., rank:][:, :, None, :], rope.cos, rope.sin
    )  # [B, S, 1, dr]
    q_rope = apply_rope(q_rope, rope.cos, rope.sin)
    # Absorbed query: q_lat[b,s,h,r] = q_nope[b,s,h,:] · w_uk[r,h,:].
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, p["wk_b"])
    q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B, S, Hq, rank+dr]
    kv = jnp.concatenate(
        [c[:, :, None, :], k_rope], axis=-1
    )  # [B, S, 1, rank+dr] — the STORED form the cache scatters verbatim
    more = (
        {"index": _index_inputs(cfg, p, h, index_rope, cq)}
        if "wk_i" in p else {}
    )
    attn, new_state = cache.attend(
        layer_state, q_eff, kv, kv, rope, q_pos, num_new,
        None, attention_fn, _latent_softmax_scale(cfg), **more,
    )
    # Deferred value up-projection from the latent-space attention result.
    o = jnp.einsum("bshr,rhd->bshd", attn[..., :rank], p["wv_b"])
    return o.reshape(b, s, hq * dv), new_state


def _latent_softmax_scale(cfg: ModelConfig) -> float:
    """THE softmax scale of latent attention: ``(dn + dr) ** -0.5``, the
    query width of the un-absorbed form, times what a YaRN block's
    ``mscale_all_dim`` makes of it (``RopeScaling.softmax_factor``). The
    ragged, paged and fused kernels take it as the operand ``cache.attend``
    hands them."""
    lat = cfg.latent
    width = (lat.nope_head_dim or cfg.head_dim) + lat.rope_head_dim
    factor = 1.0 if cfg.rope_scaling is None else cfg.rope_scaling.softmax_factor
    return width ** -0.5 * factor


def _rope_dim(cfg: ModelConfig) -> int:
    """Rotary table width: the decoupled rope key dim under latent (MLA)
    attention — only that slice of q/k is rotated — else the head dim."""
    return (
        cfg.latent.rope_head_dim if cfg.use_latent else cfg.head_dim
    )


def _rope_angles(inv_freq, positions, rotate: bool = True) -> RopeAngles:
    """The rotary tables of ``positions``; for a segment whose layers do not
    rotate (``LayerSegment.rope``), the identity rotation (cos 1, sin 0), so
    that the caches, which rotate what they are handed, need no switch."""
    cos, sin = rope_cos_sin(positions, inv_freq)
    if not rotate:
        cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)
    return RopeAngles(inv_freq, cos, sin)


def _split_whole_stacks(layer_params: Params, also=()):
    """Partition the layer dict: half-split int4 leaves are captured WHOLE
    (their Pallas matmul indexes the layer in its block index map), and so
    are the leaves named in ``also`` (the expert stacks of a dispatch that
    takes the grouped kernel: :func:`_grouped_stacks`); everything else
    rides the scan's xs and gets sliced for free. Slicing such a stack per
    scan step would copy the layer's weights through HBM before every
    kernel call — the copy traffic is why int4 decode measured slower than
    int8 before this split, and a third to a half of a grouped expert
    layer's time."""
    from ..ops.quant import QuantizedTensor4Split

    whole = {
        k: v
        for k, v in layer_params.items()
        if isinstance(v, QuantizedTensor4Split) or k in also
    }
    scanned = {k: v for k, v in layer_params.items() if k not in whole}
    return whole, scanned


def _layer_views(whole: Params, idx) -> Params:
    """A layer's views of the stacks captured whole."""
    from ..ops.moe import LayerOf
    from ..ops.quant import QuantizedTensor4Split, QuantizedTensor4SplitView

    return {
        k: QuantizedTensor4SplitView(
            v.q, v.scale_lo, v.scale_hi, idx, v.in_dim, v.out_dim
        ) if isinstance(v, QuantizedTensor4Split) else LayerOf(v, idx)
        for k, v in whole.items()
    }


def _grouped_stacks(cfg: ModelConfig, layer_params: Params, x) -> tuple:
    """The expert stacks of a routed segment whose dispatch of ``x``'s shape
    takes the grouped kernel, by the grouped or the live path
    (``ops/moe.py:traced_path``, what ``moe_mlp`` asks inside the layer),
    else none."""
    from ..ops import moe

    if "router" in layer_params and moe.traced_path(cfg, x) in moe.KERNEL_PATHS:
        return moe.GROUPED_STACKS
    return ()


def block_apply(
    cfg: ModelConfig,
    layer_params: Params,
    x: jnp.ndarray,
    cache,
    num_new: jnp.ndarray,
    attention_fn=gqa_attention,
    first_layer: int = 0,
    segment: Optional[LayerSegment] = None,
    laps=None,
):
    """Run a block (contiguous or not) of decoder layers over hidden states.

    The pipeline-stage analog of ``LlamaBlock.forward``
    (``/root/reference/distributed_llm_inference/models/llama/model.py:25-76``):
    hidden states in, hidden states out, cache threaded explicitly. ``cache``
    holds stacked per-layer k/v with leading dim equal to this block's layer
    count; ``lax.scan`` slices one layer's params+cache per step. A
    segment of a longer stack (``ModelConfig.segments``) passes its
    ``first_layer``: its ``n`` stacked layers then read and write the cache's
    layers ``first_layer .. first_layer + n``; and itself as ``segment``,
    for its window and its RoPE switch (None: the model's one window, RoPE
    on: a block of a stack whose layers are all alike).

    ``laps`` (a looped stack's: ``(steps, lap_end, state)``) runs the block
    ``steps`` times over the same weights as ONE scan of ``steps x n``
    layer applications, which parts the row of the weights from the row of
    the cache: application ``i`` runs layer ``i % n`` over cache layer
    ``first_layer + i`` (lap ``t`` of layer ``l`` owns row ``t x n + l``),
    and behind each lap ``lap_end(x, lap, state) -> (x, state)`` runs (the
    final norm and the exit gate). One scan and not a scan of laps around
    the scan of layers: a pool carried by two loops is no longer the
    donated parameter itself, and the TPU compiler then moves the WHOLE
    pool into the layout its scatter prefers and back (2 x 3.75 GB of
    temporaries at Ouro-2.6B's pool, a described-v5e compile:
    tests/test_chip_compile.py). Returns ``(x, cache, state)`` then.

    Returns ``(x, cache)`` with the cache's k/v updated (lengths NOT advanced —
    call ``cache.advance(num_new)`` after the last block of the model so that
    multiple blocks of one pipeline see consistent write offsets).
    """
    inv_freq = rope_inv_freq(_rope_dim(cfg), cfg.rope_theta, cfg.rope_scaling)
    q_pos = cache.q_positions(x.shape[1])
    rot_pos = cache.rope_positions(x.shape[1], num_new)
    rope = _rope_angles(inv_freq, rot_pos, segment is None or segment.rope)
    index_rope = _index_rope(cfg, rot_pos)

    stacks = cache.layer_stacks  # tuple of [L, ...] arrays (k/v [+ scales])
    num_stack = layer_params["attn_norm"].shape[0]
    # Which row of each stack a layer owns: the layer's own, unless the cache
    # says otherwise (a plane with rows for some layers only, a state that
    # layers share: cache/latent.py).
    rows_of = getattr(cache, "stack_rows", None)

    # Cache buffers ride the scan CARRY and are updated in place at the layer
    # index — carries are aliased by XLA, so a decode step writes one token
    # per layer. Returning per-layer state as stacked scan outputs instead
    # would materialize a full copy of the whole cache every step, doubling
    # HBM traffic on the bandwidth-bound decode path.
    whole_w, scanned_w = _split_whole_stacks(
        layer_params, _grouped_stacks(cfg, layer_params, x)
    )

    # A prefill-family dispatch over a cache that says so hands the layer the
    # WHOLE carried stacks and its row's index, and takes the updated stacks
    # back: the cache writes and reads them at (layer, page), and no layer's
    # plane is sliced out of the carry and written back (a copy of the plane
    # each way, and XLA's scatter wants two relayouts between them).
    whole_state = x.shape[1] > 1 and getattr(
        cache, "ragged_reads_whole_stacks", False
    )

    def step(carry, xs, at=None):
        x, bufs = carry
        p, idx = xs
        # whole stacks are this block's own: indexed from its first layer
        p = {**p, **_layer_views(whole_w, idx - first_layer if first_layer else idx)}
        if at is not None:
            idx = at    # a lap's row of the cache; the weights' stays above
        if whole_state:
            layer_state = (*bufs, idx)
        else:
            rows = (idx,) * len(bufs) if rows_of is None else rows_of(idx)
            layer_state = tuple(
                jax.lax.dynamic_index_in_dim(b, r, 0, keepdims=False)
                for b, r in zip(bufs, rows)
            )
        out, new_state = _decoder_layer(
            cfg, p, x, layer_state, cache, rope, q_pos, num_new, attention_fn,
            index_rope, segment,
        )
        if whole_state:
            return (out, tuple(new_state)), None
        bufs = tuple(
            jax.lax.dynamic_update_index_in_dim(b, n, r, 0)
            for b, n, r in zip(bufs, new_state, rows)
        )
        return (out, bufs), None

    if laps is not None:
        steps, lap_end, state = laps

        def application(carry, i):
            x, bufs, state = carry
            layer = i % num_stack
            p = jax.tree.map(
                lambda w: jax.lax.dynamic_index_in_dim(
                    w, layer, 0, keepdims=False
                ),
                scanned_w,
            )
            with jax.named_scope("loop_lap"):
                (x, bufs), _ = step(
                    (x, bufs), (p, first_layer + layer), first_layer + i
                )
            x, state = jax.lax.cond(
                layer == num_stack - 1,
                lambda x, state: lap_end(x, i // num_stack, state),
                lambda x, state: (x, state),
                x, state,
            )
            return (x, bufs, state), None

        (x, new_stacks, state), _ = jax.lax.scan(
            application, (x, stacks, state), jnp.arange(steps * num_stack)
        )
        return x, cache.with_layer_stacks(*new_stacks), state
    (x, new_stacks), _ = jax.lax.scan(
        step, (x, stacks),
        (scanned_w, jnp.arange(first_layer, first_layer + num_stack)),
    )
    return x, cache.with_layer_stacks(*new_stacks)


def model_apply(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,
    cache,
    num_new: jnp.ndarray,
    attention_fn=gqa_attention,
    block_fn=None,
    head: str = "all",
):
    """Full model forward: embed → layers → final norm → logits.

    This is the client-side capability the reference lacks entirely (SURVEY §1:
    "There is no client layer"). Returns ``(logits[B, S, V], cache)`` with the
    cache advanced. ``block_fn`` overrides how the layer stack runs (e.g. the
    ``pp``-staged pipeline, ``parallel/pipeline.py``); it must match
    :func:`block_apply`'s signature minus ``attention_fn``.

    ``head``: "all" computes logits at every position; "last" only at each
    row's final valid position (``num_new - 1``) — a prefill only samples
    there, and the full-vocab matmul over S positions is pure waste (at
    Llama-3-8B's 128k vocab it is ~6% of a 128-token prefill, and S/chunk of
    every chunked long-prompt step); "none" skips the head (chunked prefill
    interiors), returning ``None`` logits. Shapes: "last" → [B, 1, V].
    """
    x = _embed(cfg, params, tokens)
    if cfg.loop is not None:
        if block_fn is not None:
            raise ValueError(LOOP_NEEDS_ONE_STAGE)
        return _looped_model_apply(
            cfg, params, x, cache, num_new, attention_fn, head
        )
    if block_fn is None:
        for seg in cfg.segments:
            with _segment_scope(seg):
                x, part = block_apply(
                    cfg, params[seg.key], x,
                    _segment_cache(cache, seg, x.shape[1]),
                    num_new, attention_fn, first_layer=seg.cache_start,
                    segment=seg,
                )
            cache = (
                part if seg.pool is None
                else cache.with_pool_view(seg.pool, part)
            )
    else:
        # A staged pipeline divides ONE stacked dict among its stages.
        (seg,) = cfg.segments
        x, cache = block_fn(cfg, params[seg.key], x, cache, num_new)
    if head == "none":
        return None, cache.advance(num_new)
    if head == "last":
        last = jnp.maximum(num_new - 1, 0)[:, None, None].astype(jnp.int32)
        if cfg.hyper is not None:
            last = last[..., None]      # the stream's rows go with the position
        x = jnp.take_along_axis(x, last, axis=1)
    logits = apply_head(cfg, params, _stream_exit(cfg, x))
    return logits, cache.advance(num_new)


#: why a looped stack runs whole in one place (what every refusal says)
LOOP_NEEDS_ONE_STAGE = (
    "layers run several times: a lap must return to the first stage, with "
    "the client-side final norm and the exit gate between laps, which no "
    "stage protocol here does (pp stages, relay chains of partial blocks "
    "and a draft's own stack pass a hidden state down ONCE); serve a looped "
    "model whole on one device or under tp"
)


def _last_positions(x, num_new):
    """``x [B, S, H]`` at each row's last valid position: ``[B, 1, H]``."""
    last = jnp.maximum(num_new - 1, 0)[:, None, None].astype(jnp.int32)
    return jnp.take_along_axis(x, last, axis=1)


def _loop_exit_start(x):
    """The exit selection before the first lap, for the positions ``x [B, S,
    H]`` stands for: ``(the chosen lap's hidden state, the weight still
    inside, the summed exit weights, the chosen lap or -1)``."""
    b, s = x.shape[:2]
    return (
        jnp.zeros_like(x),
        jnp.ones((b, s), jnp.float32),
        jnp.zeros((b, s), jnp.float32),
        jnp.full((b, s), -1, jnp.int32),
    )


def _loop_exit(cfg: ModelConfig, params: Params, h, lap, state):
    """The exit gate behind lap ``lap`` and the selection so far. ``h [B, S,
    H]`` is the lap's final-normed hidden state; ``lam = sigmoid(w . h + b)``
    is the share of the weight still inside that leaves here, the last lap
    takes what is left, and a position is served the FIRST lap at which the
    summed weights reach ``exit_threshold`` (the last lap if none does: at
    the published threshold of 1 always the last). Float32 throughout."""
    chosen, inside, summed, at = state
    f32 = jnp.float32
    lam = jax.nn.sigmoid(
        jnp.einsum("bsh,h->bs", h.astype(f32), params["exit_w"].astype(f32))
        + params["exit_b"].astype(f32)
    )
    last = lap == cfg.loop.steps - 1
    summed = summed + jnp.where(last, inside, lam * inside)
    take = (at < 0) & ((summed >= cfg.loop.exit_threshold) | last)
    return (
        jnp.where(take[..., None], h, chosen),
        inside * (1.0 - lam),
        summed,
        jnp.where(take, lap, at),
    )


def _lap_end(cfg: ModelConfig, params: Params, x, lap, state, pick=None):
    """What follows the stack in every lap: the model's one final norm,
    whose output enters the next lap, and (``state`` not None) the exit
    gate and the selection at the positions ``pick`` takes of it."""
    with jax.named_scope("loop_exit"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        if state is not None:
            state = _loop_exit(
                cfg, params, x if pick is None else pick(x), lap, state
            )
    return x, state


def _looped_model_apply(cfg, params, x, cache, num_new, attention_fn, head):
    """:func:`model_apply` behind the embedding for a looped stack
    (``ModelConfig.loop``): ``block_apply`` with its ``laps``, ONE
    ``lax.scan`` over ``steps x layers`` layer applications, so a program
    compiles one layer whatever the laps' number. Lap ``t`` runs the whole
    stack over cache layers ``t x L .. (t + 1) x L``, then the final norm
    and the exit gate (:func:`_lap_end`); the head reads the hidden state
    the selection chose, which is already normed. Every lap runs whatever
    the gate says."""
    (seg,) = cfg.segments
    pick = None
    if head == "last":
        pick = functools.partial(_last_positions, num_new=num_new)
    state = None
    if head != "none":
        state = _loop_exit_start(x if pick is None else pick(x))
    with _segment_scope(seg):
        _, cache, state = block_apply(
            cfg, params[seg.key], x, cache, num_new, attention_fn,
            first_layer=seg.cache_start, segment=seg,
            laps=(
                cfg.loop.steps,
                lambda x, lap, state: _lap_end(cfg, params, x, lap, state, pick),
                state,
            ),
        )
    cache = cache.advance(num_new)
    if head == "none":
        return None, cache
    return apply_head(cfg, params, state[0], normed=True), cache


def _embed(cfg: ModelConfig, params: Params, tokens):
    """The stack's entry: the tokens' embeddings ``[B, S, H]``, replicated
    into the ``cfg.hyper.mult`` rows of a widened stream ``[B, S, n, H]``
    where the model has one."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.hyper is None:
        return x
    return jnp.broadcast_to(
        x[:, :, None, :], (*x.shape[:2], cfg.hyper.mult, x.shape[-1])
    )


def _stream_exit(cfg: ModelConfig, x):
    """The stack's exit: a widened stream's rows summed (in float32) into
    the ``[B, S, H]`` the final norm and the head take."""
    if cfg.hyper is None:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


class _TailView:
    """Cache stand-in handed to ``_decoder_layer`` inside the fused decode
    scan: its ``layer_state`` is the concatenation of the real cache's
    READ-ONLY big planes and the small mutable tail planes; ``attend``
    splits them and delegates to the cache's ``tail_attend``. Returned
    layer state echoes the big planes unchanged (the driver writes back
    only the tail half)."""

    def __init__(self, cache, base_len, tail_len, step_idx, num_big,
                 walk=None):
        self.cache = cache
        self.base_len = base_len
        self.tail_len = tail_len
        self.step_idx = step_idx
        self.num_big = num_big
        self.walk = {} if walk is None else {"walk": walk}

    def q_positions(self, seq_len):
        return (self.base_len + self.tail_len)[:, None]

    def rope_positions(self, seq_len, num_new):
        return self.q_positions(seq_len)

    def attend(self, layer_state, q, k_new, v_new, rope, q_pos, num_new,
               sliding_window, attention_fn, scale=None, **more):
        big = layer_state[: self.num_big]
        tail = layer_state[self.num_big:]
        out, new_tail = self.cache.tail_attend(
            big, tail, q, k_new, v_new, rope, self.base_len, self.tail_len,
            self.step_idx, num_new, sliding_window, scale, **more,
            **self.walk,
        )
        return out, (*big, *new_tail)


def multi_decode_apply(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,
    cache,
    num_steps: int,
    step_fn,
    init_state,
    init_num_new: jnp.ndarray,
    exit_laps: bool = False,
):
    """``num_steps`` fused decode steps with a WRITE-BEHIND KV tail.

    The per-step scan path writes each new token into the big KV buffers
    with per-row dynamic offsets — which lowers to a serial while-loop over
    batch rows on TPU (measured ~26 ms/step at batch 80, Llama-7B shapes,
    more than the step's entire ideal HBM traffic; a scatter instead aborts
    under GSPMD). Here the big buffers stay READ-ONLY for all K steps: they
    ride the layer scan as sliced operands (like the weights, which scan
    slices for free), each step's fresh k/v lands in a small per-layer tail
    buffer at a SCALAR slot index (one vectorized write), and the tail is
    merged into the big buffers once at the end. Attention runs over the two
    segments (big + tail) under one joint softmax
    (``ops.attention.gqa_attention_segments``).

    ``tokens``: ``[B, 1]`` first input tokens. ``step_fn(i, logits, state)``
    → ``(next_tokens [B], next_num_new [B] int32, state, emit)`` carries
    sampling/stop logic; ``num_new`` must be non-increasing per row across
    steps (a finished row stays finished) so each row's tail slots stay
    contiguous. Returns ``(emits stacked [K, ...], cache flushed+advanced)``.

    A looped stack (``ModelConfig.loop``) runs its laps INSIDE a step, as
    one ``lax.scan`` over the lap: lap ``t`` of layer ``l`` reads the big
    planes' and writes the tail's layer ``t x L + l`` (the tail is a plane a
    CACHE layer), the final norm and the exit gate follow each lap, and the
    head reads the chosen lap's hidden state. ``exit_laps``: the emits come
    back as ``(emits, laps [K, B])``, the lap the selection took for each
    row's token of each step.

    The dense cache kinds implement the tail protocol
    (``tail_init`` / ``tail_attend`` / ``tail_flush``) natively, and
    ``PagedKVCache`` implements it over its page pool in two forms: with the
    kernel the pool segment runs the Pallas paged kernel with exported
    softmax stats, joint-merged with the tail; without it (a mesh engine,
    the CPU) every row's table span is gathered contiguous once a window
    (``tail_big_stacks``) and the two segments share one softmax in pure
    XLA — see ``cache/paged.py``. Callers fall back to per-step
    ``model_apply`` for a cache that says it has no tail (``has_tail``).
    """
    inv_freq = rope_inv_freq(_rope_dim(cfg), cfg.rope_theta, cfg.rope_scaling)
    segments = cfg.segments
    # The cache's pools: itself, or one a kind of attention where it holds
    # two (``pool_view``). Each has its own read-only big planes, its own
    # tail and its own flush; a segment's scan takes its pool's. With one
    # pool the carry's leaves and the program are what they always were.
    names = list(dict.fromkeys(seg.pool for seg in segments))
    pools = [
        _ScanPool(_segment_cache(
            cache, next(seg for seg in segments if seg.pool == name)
        ))
        for name in names
    ]
    base_len = cache.lengths
    # What a pool's sweep derives from the window alone (its ``tail_walk``;
    # most have none): the table and the pool's lengths stand through the
    # steps, and a row decodes from the first or not at all (``num_new`` does
    # not grow), so it is built once, outside both scans.
    walks = [
        pool.cache.tail_walk(num_steps, base_len, init_num_new)
        if hasattr(pool.cache, "tail_walk") else None
        for pool in pools
    ]
    # a step's dispatch to the experts is the carried tokens' [B, 1]
    split_w = [
        _split_whole_stacks(
            params[seg.key], _grouped_stacks(cfg, params[seg.key], tokens)
        )
        for seg in segments
    ]

    def token_step(carry, i):
        tokens, tails, tail_len, num_new, state = carry
        tails = list(tails)
        x = _embed(cfg, params, tokens)
        q_pos = (base_len + tail_len)[:, None]
        # one table a RoPE switch: the rotation, and the identity where a
        # segment's layers do not rotate
        ropes = {}
        for seg in segments:
            if seg.rope not in ropes:
                ropes[seg.rope] = _rope_angles(inv_freq, q_pos, seg.rope)
        index_rope = _index_rope(cfg, q_pos)

        def layer_step(pool, view, rope, seg, whole_w, offset, carry2, xs):
            x, tail_bufs = carry2
            p = xs[0]
            idx = xs[-1]
            # whole stacks are a segment's own: indexed from its first layer
            first = seg.cache_start
            p = {**p, **_layer_views(whole_w, idx - first if first else idx)}
            if offset is not None:
                # a lap's rows of the cache: the weights' row stays ``idx``
                idx = idx + offset
            if pool.whole_big:
                big_state = (*pool.big_stacks, idx)
            else:
                big_state = tuple(xs[1 : 1 + pool.num_big])
            if pool.whole_tail:
                tail_state = tail_bufs
            else:
                tail_state = tuple(
                    jax.lax.dynamic_index_in_dim(b, idx, 0, keepdims=False)
                    for b in tail_bufs
                )
            out, new_state = _decoder_layer(
                cfg, p, x, (*big_state, *tail_state), view, rope, q_pos,
                num_new, index_rope=index_rope, segment=seg,
            )
            if pool.whole_tail:
                tail_bufs = tuple(new_state[pool.view_num_big:])
            else:
                tail_bufs = tuple(
                    jax.lax.dynamic_update_index_in_dim(b, n, idx, 0)
                    for b, n in zip(tail_bufs, new_state[pool.view_num_big:])
                )
            return (out, tail_bufs), None

        def run_segment(x, seg, whole_w, scanned_w, lap=None, lap_big=None):
            """``x`` through a segment's layers, its pool's tail updated
            in ``tails``. ``lap`` (a looped stack's, traced) offsets the
            cache's rows by whole stacks, and ``lap_big`` are that lap's
            layers of the big planes."""
            at = names.index(seg.pool)
            pool = pools[at]
            view = _TailView(
                pool.cache if seg.index is None
                else _segment_cache(cache, seg),
                base_len, tail_len, i, pool.view_num_big, walks[at],
            )
            rope = ropes[seg.rope]
            # The read-only big planes ride a segment's scan as ITS layers'
            # slice (the whole of them for a one-segment stack).
            lo, hi = seg.cache_start, seg.cache_start + seg.count
            if lap is not None:
                seg_big = lap_big
            else:
                seg_big = () if pool.whole_big else (
                    pool.big_stacks if seg.count == pool.num_stack
                    else tuple(b[lo:hi] for b in pool.big_stacks)
                )
            # (a looped stack's segment scope is around its laps)
            with _segment_scope(seg) if lap is None else jax.named_scope(
                "loop_lap"
            ):
                (x, tails[at]), _ = jax.lax.scan(
                    functools.partial(
                        layer_step, pool, view, rope, seg, whole_w,
                        None if lap is None else lap * seg.count,
                    ),
                    (x, tails[at]),
                    (scanned_w, *seg_big, jnp.arange(lo, hi)),
                )
            return x

        lap_at = None
        if cfg.loop is None:
            for seg, (whole_w, scanned_w) in zip(segments, split_w):
                x = run_segment(x, seg, whole_w, scanned_w)
            logits = apply_head(cfg, params, _stream_exit(cfg, x))
        else:
            # The laps of a looped stack: one scan, the lap's layers of the
            # big planes riding it as its xs (sliced for free, as a layer's
            # are), the tail whole in its carry.
            (seg,), ((whole_w, scanned_w),) = segments, split_w
            laps = cfg.loop.steps
            lap_big = () if pools[0].whole_big else tuple(
                b.reshape(laps, seg.count, *b.shape[1:])
                for b in pools[0].big_stacks
            )

            def lap_step(carry, xs):
                x, tails[0], exit_state = carry
                x = run_segment(
                    x, seg, whole_w, scanned_w, xs[0], tuple(xs[1:])
                )
                x, exit_state = _lap_end(cfg, params, x, xs[0], exit_state)
                return (x, tails[0], exit_state), None

            with _segment_scope(seg):
                (_, tails[0], exit_state), _ = jax.lax.scan(
                    lap_step, (x, tails[0], _loop_exit_start(x)),
                    (jnp.arange(laps), *lap_big),
                )
            lap_at = exit_state[3][:, 0]
            logits = apply_head(cfg, params, exit_state[0], normed=True)
        next_tokens, next_num_new, state, emit = step_fn(i, logits[:, 0], state)
        if exit_laps:
            emit = (emit, lap_at)
        tail_len = tail_len + num_new
        return (
            (next_tokens[:, None], tuple(tails), tail_len, next_num_new, state),
            emit,
        )

    zero_len = jnp.zeros_like(base_len)
    tails = tuple(pool.cache.tail_init(num_steps) for pool in pools)
    (_, tails, tail_len, _, _), emits = jax.lax.scan(
        token_step, (tokens, tails, zero_len, init_num_new, init_state),
        jnp.arange(num_steps),
    )
    if names == [None]:
        return emits, cache.tail_flush(tails[0], tail_len)
    for name, pool, tail in zip(names, pools, tails):
        cache = cache.with_pool_view(
            name, pool.cache.tail_flush(tail, tail_len), lengths=True
        )
    return emits, cache


class _ScanPool:
    """What :func:`multi_decode_apply` holds of one pool of the cache for
    its scans: the read-only big planes, and how they and the tail are
    handed to a layer."""

    def __init__(self, cache):
        self.cache = cache
        # ``tail_big_stacks`` lets a cache hand the scan a DIFFERENT
        # read-only view of its big planes than its storage layout — the
        # quantized paged cache gathers its page pool to contiguous per-row
        # buffers ONCE here (per-layer pool slices feeding a kernel
        # materialize a full pool copy per layer per step; the gather
        # amortizes to ~2% of a step over K).
        self.big_stacks = (
            cache.tail_big_stacks()
            if hasattr(cache, "tail_big_stacks")
            else cache.layer_stacks
        )
        self.num_big = len(self.big_stacks)
        self.num_stack = self.big_stacks[0].shape[0]
        # Whole-stack mode (Pallas big-segment kernels): the big buffers
        # are NOT sliced per layer — a dynamic-slice feeding a custom call
        # materializes a full HBM copy of that layer's K/V every (layer,
        # step). Instead the stacks pass through whole with the layer
        # index appended; the kernel's block index map resolves the layer,
        # so the operand is zero-copy.
        self.whole_big = getattr(cache, "tail_reads_whole_big", False)
        # Whole-tail mode (in-kernel tail): like the big stacks, the tail
        # buffers pass through UNSLICED — the kernel aliases them in place
        # and indexes the layer itself, so the scan neither slices nor
        # re-inserts per-layer tail state.
        self.whole_tail = getattr(cache, "tail_in_kernel", False)
        self.view_num_big = self.num_big + 1 if self.whole_big else self.num_big


def apply_head(
    cfg: ModelConfig, params: Params, x: jnp.ndarray, normed: bool = False
) -> jnp.ndarray:
    """Final norm + lm_head (tied to the embedding when absent): ``[..., H]``
    hidden states → fp32 logits ``[..., V]``. ``normed``: ``x`` has passed
    the final norm already (a looped stack's laps end in it)."""
    with jax.named_scope("head"):
        if not normed:
            x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        return qmatmul(x, head).astype(jnp.float32)


# ---------------------------------------------------------------------------
# HF checkpoint conversion
# ---------------------------------------------------------------------------

_LAYER_KEY_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    "self_attn.o_proj.bias": ("bo", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
    # a retention block (``brumby``): Qwen3's per-head norms and the gate
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    "self_attn.g_proj.weight": ("w_gate", True),
    "self_attn.g_proj.bias": ("b_gate", False),
    "mlp.gate_proj.weight": ("wg", True),
    "mlp.up_proj.weight": ("wu", True),
    "mlp.down_proj.weight": ("wd", True),
}


def convert_hf_layer(
    cfg: ModelConfig,
    state: Mapping[str, np.ndarray],
    layer_idx: int,
    dtype=jnp.bfloat16,
) -> Dict[str, np.ndarray]:
    """Convert one HF decoder layer's tensors to our naming/layout.

    ``state`` maps full HF keys (``model.layers.{i}.…``) to numpy arrays — the
    per-layer streaming analog of the reference's
    ``get_block_state_dict`` prefix filter
    (``/root/reference/distributed_llm_inference/utils/model.py:40-44``).
    """
    prefix = f"model.layers.{layer_idx}."
    out: Dict[str, np.ndarray] = {}
    for suffix, (name, transpose) in _LAYER_KEY_MAP.items():
        key = prefix + suffix
        if key not in state:
            continue
        arr = np.asarray(state[key])
        if transpose:
            arr = arr.T
        out[name] = arr.astype(jnp.dtype(dtype))
    # MLA (DeepSeek-V2/V3) latent attention: the joint kv_b_proj
    # [Hq*(dn+dv), rank] splits into the key up-projection (absorbed into
    # the query) and the value up-projection (applied post-softmax).
    kvb_key = prefix + "self_attn.kv_b_proj.weight"
    if cfg.use_latent and kvb_key in state:
        lat = cfg.latent
        dn = lat.nope_head_dim or cfg.head_dim
        dr = lat.rope_head_dim
        dv = lat.v_head_dim or cfg.head_dim
        kvb = np.asarray(state[kvb_key]).T.reshape(
            lat.rank, cfg.num_heads, dn + dv
        )
        out["wk_b"] = kvb[..., :dn].astype(jnp.dtype(dtype))
        out["wv_b"] = kvb[..., dn:].astype(jnp.dtype(dtype))
        # The checkpoint's rotary slices hold (even, odd) pairs side by
        # side (``apply_rotary_pos_emb`` de-interleaves them before
        # ``rotate_half``); ``ops/rotary.py`` rotates halves, so the rope
        # columns are stored de-interleaved once, here.
        halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
        # Compressed queries (``q_lora_rank``): q_a_proj, its RMSNorm and
        # q_b_proj where a block without them has q_proj.
        for suffix, name, transpose in (
            ("self_attn.q_a_proj.weight", "wq_a", True),
            ("self_attn.q_a_layernorm.weight", "q_a_norm", False),
            ("self_attn.q_b_proj.weight", "wq_b", True),
        ):
            if prefix + suffix in state:
                arr = np.asarray(state[prefix + suffix])
                out[name] = (arr.T if transpose else arr).astype(
                    jnp.dtype(dtype)
                )
        for name in ("wq", "wq_b"):  # the queries' up-projection
            if name in out:
                wq = out[name].reshape(-1, cfg.num_heads, dn + dr)
                out[name] = np.concatenate(
                    [wq[..., :dn], wq[..., dn:][..., halves]], -1
                ).reshape(out[name].shape)
        akey = prefix + "self_attn.kv_a_proj_with_mqa.weight"
        if akey in state:
            wkv_a = np.asarray(state[akey]).T
            out["wkv_a"] = np.concatenate(
                [wkv_a[:, : lat.rank], wkv_a[:, lat.rank :][:, halves]], -1
            ).astype(jnp.dtype(dtype))
        nkey = prefix + "self_attn.kv_a_layernorm.weight"
        if nkey in state:
            out["kv_norm"] = np.asarray(state[nkey]).astype(jnp.dtype(dtype))
    if cfg.num_experts > 0:
        out.update(_convert_hf_experts(cfg, state, prefix, jnp.dtype(dtype)))
    return out


def _convert_hf_experts(cfg, state, prefix, dtype) -> Dict[str, np.ndarray]:
    """A routed layer's gate and expert tensors, stacked ``[E, …]``:
    Mixtral's ``block_sparse_moe.*`` (w1/w3/w2) or DeepSeek-V2/V3's
    ``mlp.gate`` / ``mlp.experts.{e}`` / ``mlp.shared_experts``. Empty for
    a layer that has neither (a leading dense layer: its ``mlp.*_proj`` are
    in ``_LAYER_KEY_MAP``)."""
    def t(key):
        return np.asarray(state[key]).T.astype(dtype)

    def stack(pattern):
        return np.stack([
            t(pattern.format(e=e)) for e in range(cfg.num_experts)
        ])

    out: Dict[str, np.ndarray] = {}
    if prefix + "block_sparse_moe.gate.weight" in state:
        ep = prefix + "block_sparse_moe.experts.{e}."
        out["router"] = t(prefix + "block_sparse_moe.gate.weight")
        out["we_g"] = stack(ep + "w1.weight")  # gate_proj
        out["we_d"] = stack(ep + "w2.weight")  # down_proj
        out["we_u"] = stack(ep + "w3.weight")  # up_proj
    elif prefix + "mlp.gate.weight" in state:
        ep = prefix + "mlp.experts.{e}."
        out["router"] = t(prefix + "mlp.gate.weight")
        bias = prefix + "mlp.gate.e_score_correction_bias"
        if bias in state:
            out["router_bias"] = np.asarray(state[bias]).astype(np.float32)
        out["we_g"] = stack(ep + "gate_proj.weight")
        out["we_u"] = stack(ep + "up_proj.weight")
        out["we_d"] = stack(ep + "down_proj.weight")
        sp = prefix + "mlp.shared_experts."
        if sp + "gate_proj.weight" in state:
            out["ws_g"] = t(sp + "gate_proj.weight")
            out["ws_u"] = t(sp + "up_proj.weight")
            out["ws_d"] = t(sp + "down_proj.weight")
    return out


def convert_hf_state_dict(
    cfg: ModelConfig,
    state: Mapping[str, np.ndarray],
    layer_ids: Optional[Sequence[int]] = None,
    dtype=jnp.bfloat16,
    consume: bool = False,
) -> Params:
    """Convert an HF state dict (every family of ``models/registry.py``)
    into our param pytree.

    ``layer_ids`` selects an arbitrary list of layers (the block a node
    serves), mirroring ``LlamaBlock(config, layer_ids)``
    (``/root/reference/distributed_llm_inference/models/llama/model.py:17``):
    one stacked dict under ``"layers"``, so the block lies inside one
    segment of the stack. When ``layer_ids`` is None, converts the full
    model including embeddings and head, one stacked dict a segment
    (``ModelConfig.segments``).

    The stacked layer leaves stay HOST (numpy) arrays: whoever serves them
    places them (``InferenceEngine`` / ``BlockBackend``), one leaf at a
    time through ``quantize_params`` or straight to their mesh shards. A
    7B bf16 tree placed whole here is 14.5 GB of a 16 GB chip before its
    int8 copy exists.

    ``consume``: the caller owns ``state`` (a dict) and is done with it —
    each layer's tensors are dropped from it as soon as they are converted,
    so the host holds the checkpoint once plus one stacked leaf instead of
    three times over (state, per-layer copies, stacks: 35 GiB and counting
    for a 14.5 GB checkpoint on a 40 GiB host — my chip run, PR 21).
    """
    if (
        cfg.qk_norm and not cfg.use_retention
    ) or cfg.use_sparse or cfg.hyper is not None or cfg.loop is not None:
        # by what the converter lacks, whatever the family's name: a latent
        # block's compressed queries are mapped (``convert_hf_layer``), and a
        # retention block's checkpoint is Qwen3's with a ``g_proj``
        # (``_LAYER_KEY_MAP``); an indexer's tensors and a widened stream's
        # maps, and a looped block's output norms and gate, are not
        raise ValueError(
            f"family {cfg.family!r} has no checkpoint converter: the key "
            "names of its checkpoint (the per-head q/k norms', an "
            "indexer's, the hyper-connections', a looped block's output "
            "norms' and exit gate's) are not known to this "
            "program, and a guessed converter is worse than none"
        )

    def stacked(ids):
        per_layer = []
        for i in ids:
            per_layer.append(convert_hf_layer(cfg, state, i, dtype))
            if consume:
                prefix = f"model.layers.{i}."
                for key in [k for k in state if k.startswith(prefix)]:
                    del state[key]
        names = set(per_layer[0])
        if any(set(layer) != names for layer in per_layer):
            raise ValueError(
                f"layers {list(ids)} are not all alike (a block lies inside "
                f"one segment of the stack: {cfg.segments})"
            )
        # pop: each name's per-layer copies are freed as soon as stacked
        return {
            name: np.stack([layer.pop(name) for layer in per_layer])
            for name in list(per_layer[0])
        }

    if layer_ids is not None:
        return {"layers": stacked(list(layer_ids))}
    params: Params = {
        seg.key: stacked(range(seg.start, seg.start + seg.count))
        for seg in cfg.segments
    }
    params.update(convert_hf_non_layer(cfg, state, dtype))
    return params


def convert_hf_non_layer(
    cfg: ModelConfig, state: Mapping[str, np.ndarray], dtype=jnp.bfloat16
) -> Params:
    """The client-side tensors (embedding, final norm, lm_head) — what a
    mid-pipeline block node never loads (SURVEY §1: the reference has no
    client layer at all)."""
    params: Params = {
        "embed": jnp.asarray(
            np.asarray(state["model.embed_tokens.weight"]).astype(jnp.dtype(dtype))
        ),
        "final_norm": jnp.asarray(
            np.asarray(state["model.norm.weight"]).astype(jnp.dtype(dtype))
        ),
    }
    if not cfg.tie_word_embeddings and "lm_head.weight" in state:
        params["lm_head"] = jnp.asarray(
            np.asarray(state["lm_head.weight"]).T.astype(jnp.dtype(dtype))
        )
    return params
