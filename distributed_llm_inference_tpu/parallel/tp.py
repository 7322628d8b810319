"""Tensor-parallel sharding rules (Megatron-style, GSPMD-compiled).

The reference's "tensor parallelism" is the vestigial HF ``pretraining_tp``
path: slicing q/k/v/o weights on ONE device and summing partial ``F.linear``
results (``/root/reference/distributed_llm_inference/models/llama/modules.py:
44-59,107-110``) — no collectives, no process groups. Here TP is real and
declarative: parameters get ``NamedSharding`` annotations over the ``tp`` mesh
axis and XLA's SPMD partitioner inserts the all-reduces (as ICI collectives)
that Megatron would issue via NCCL:

* column-parallel: ``wq/wk/wv`` (head dim), ``wg/wu`` (MLP features) — each
  device computes its heads/features, no communication;
* row-parallel: ``wo``, ``wd`` (contracting dim sharded) — XLA inserts the
  ``psum`` over ``tp`` after the matmul;
* KV cache heads are sharded over ``tp`` so cache reads/writes stay local;
* embedding is vocab-sharded (gather crosses ``tp`` once per step);
  ``lm_head`` shards the logits' vocab dim (argmax/top-k run sharded).

No model code changes: the same ``model_apply`` runs on 1 device or a pod —
only the shardings of its inputs differ.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import ModelConfig

__all__ = [
    "layer_pspecs",
    "param_pspecs",
    "cache_pspecs",
    "shard_pytree",
    "validate_tp",
]

# Stacked per-layer parameters: leading axis is the layer stack. ``pp`` shards
# that axis when pipelining (parallel/pipeline.py); None here (pure TP).
_LAYER_RULES: Dict[str, P] = {
    "attn_norm": P("pp", None),
    "wq": P("pp", None, "tp"),
    "wk": P("pp", None, "tp"),
    "wv": P("pp", None, "tp"),
    "bq": P("pp", "tp"),
    "bk": P("pp", "tp"),
    "bv": P("pp", "tp"),
    "wo": P("pp", "tp", None),
    "bo": P("pp", None),
    "mlp_norm": P("pp", None),
    # a looped block's norms of the sublayers' outputs
    "attn_out_norm": P("pp", None),
    "mlp_out_norm": P("pp", None),
    "wg": P("pp", None, "tp"),
    "wu": P("pp", None, "tp"),
    "wd": P("pp", "tp", None),
    # MoE (Mixtral): experts axis [L, E, in, out] — experts sharded over
    # ``ep`` (each device computes its local experts; the combine contraction
    # psums over ep), features over ``tp`` like the dense MLP; router
    # replicated.
    "router": P("pp", None, None),
    "we_g": P("pp", "ep", None, "tp"),
    "we_u": P("pp", "ep", None, "tp"),
    "we_d": P("pp", "ep", "tp", None),
}


def _strip_pp(spec: P, use_pp: bool) -> P:
    if use_pp:
        return spec
    return P(None, *spec[1:])


def layer_pspecs(use_pp: bool = False) -> Dict[str, P]:
    """PartitionSpecs for the stacked layer-param dict.

    ``use_pp=True`` additionally shards the leading layer-stack axis over the
    ``pp`` mesh axis (each pipeline stage holds its contiguous slice of
    layers — the mesh-native form of the reference's per-node layer blocks,
    ``server/worker.py:13-14``).
    """
    return {k: _strip_pp(v, use_pp) for k, v in _LAYER_RULES.items()}


def _maybe_qspec(param: Any, spec: P) -> Any:
    """Weight spec → spec pytree; quantized weights need a matching
    :class:`QuantizedTensor` node whose per-output-channel scale drops the
    contracted (second-to-last) axis of the weight spec. int4 grouped
    weights ``[..., G, gs, out]`` carry the contracted axis's sharding on the
    group axis (whole groups per device), replicating within a group."""
    from ..ops.quant import (
        QuantizedTensor, QuantizedTensor4, QuantizedTensor4Split,
        QuantizedTensorOutlier,
    )

    if isinstance(param, QuantizedTensorOutlier):
        # Outlier indices address the CONTRACTED axis: replicate them and
        # the fp side-weights' K axis (K ≈ 32 — the side matmul is noise);
        # the out axis follows the body's sharding.
        return QuantizedTensorOutlier(
            q=spec, scale=P(*spec[:-2], spec[-1]),
            outlier_idx=P(*spec[:-2], None),
            outlier_w=P(*spec[:-2], None, spec[-1]),
        )
    if isinstance(param, QuantizedTensor):
        return QuantizedTensor(q=spec, scale=P(*spec[:-2], spec[-1]))
    if isinstance(param, QuantizedTensor4):
        return QuantizedTensor4(
            q=P(*spec[:-2], spec[-2], None, spec[-1]),
            scale=P(*spec[:-2], spec[-2], spec[-1]),
        )
    if isinstance(param, QuantizedTensor4Split):
        # Half-split packing interleaves channel j with j + out_pad/2 in one
        # byte column: a tp column shard of the packed axis would hold a
        # non-contiguous channel set and scramble the row-parallel concat
        # order. Replicate in/out axes (layer/pp lead axes keep their spec);
        # tp>1 int4 serving uses the grouped XLA layout instead. in/out_dim
        # are STATIC aux data and must match the param's or tree.map raises.
        return QuantizedTensor4Split(
            q=P(*spec[:-2], None, None),
            scale_lo=P(*spec[:-2], None, None),
            scale_hi=P(*spec[:-2], None, None),
            in_dim=param.in_dim,
            out_dim=param.out_dim,
        )
    return spec


def param_pspecs(params: Dict[str, Any], use_pp: bool = False) -> Dict[str, Any]:
    """Spec pytree matching a full or block-only param pytree (bf16 or
    int8-quantized leaves)."""
    lp = layer_pspecs(use_pp)
    out: Dict[str, Any] = {}
    if "layers" in params:
        out["layers"] = {
            k: _maybe_qspec(v, lp[k]) for k, v in params["layers"].items()
        }
    if "embed" in params:
        out["embed"] = P("tp", None)
    if "final_norm" in params:
        out["final_norm"] = P(None)
    if "lm_head" in params:
        out["lm_head"] = _maybe_qspec(params["lm_head"], P(None, "tp"))
    if "exit_w" in params:  # a looped stack's exit gate: replicated
        out["exit_w"], out["exit_b"] = P(None), P()
    return out


def cache_pspecs(cache: Any, use_pp: bool = False) -> Any:
    """Spec pytree for a KV cache (dense/paged/sink).

    KV heads shard over ``tp`` (reads/writes stay device-local); batch rows
    over ``dp``; the layer axis over ``pp`` when pipelining.
    """
    from ..cache.dense import DenseKVCache, QuantizedDenseKVCache
    from ..cache.paged import PagedKVCache, QuantizedPagedKVCache
    from ..cache.sink import QuantizedSinkKVCache, SinkKVCache

    pp = "pp" if use_pp else None
    if isinstance(cache, QuantizedDenseKVCache):
        # Head-major layout: [L, B, Hkv, T, D] — kv heads (axis 2) over tp.
        kv = P(pp, "dp", "tp", None, None)
        sc = P(pp, "dp", "tp", None)
        return QuantizedDenseKVCache(
            k=kv, v=kv, ks=sc, vs=sc, lengths=P("dp"),
            use_kernel=cache.use_kernel,
        )
    if isinstance(cache, DenseKVCache):
        kv = P(pp, "dp", None, "tp", None)
        return DenseKVCache(k=kv, v=kv, lengths=P("dp"))
    if isinstance(cache, QuantizedPagedKVCache):
        # Pool layout [L, P, Hkv, PS, D] + scale planes [L, P, Hkv, PS]:
        # kv heads over tp, pages replicated (any row may read any page).
        kv = P(pp, None, "tp", None, None)
        sc = P(pp, None, "tp", None)
        return QuantizedPagedKVCache(
            k_pages=kv, v_pages=kv, ks_pages=sc, vs_pages=sc,
            page_table=P("dp", None), lengths=P("dp"),
            page_size=cache.page_size, use_kernel=cache.use_kernel,
        )
    if isinstance(cache, PagedKVCache):
        kv = P(pp, None, "tp", None, None)
        return PagedKVCache(
            k_pages=kv, v_pages=kv, page_table=P("dp", None), lengths=P("dp"),
            page_size=cache.page_size, use_kernel=cache.use_kernel,
        )
    if isinstance(cache, QuantizedSinkKVCache):
        # Head-major ring + sink planes: kv heads (axis 2) over tp.
        kv = P(pp, "dp", "tp", None, None)
        sc = P(pp, "dp", "tp", None)
        return QuantizedSinkKVCache(
            k=kv, v=kv, ks=sc, vs=sc, sk=kv, sv=kv, sks=sc, svs=sc,
            lengths=P("dp"), num_sinks=cache.num_sinks,
            ring_slots=cache.ring_slots, use_kernel=cache.use_kernel,
        )
    if isinstance(cache, SinkKVCache):
        kv = P(pp, "dp", None, "tp", None)
        return SinkKVCache(k=kv, v=kv, seen=P("dp"), num_sinks=cache.num_sinks)
    raise TypeError(f"unknown cache type {type(cache)}")


def shard_pytree(tree: Any, mesh: Mesh, specs: Any) -> Any:
    """``device_put`` every leaf with its NamedSharding (host → mesh)."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )


def validate_tp(cfg: ModelConfig, tp: int, sp: int = 1, ep: int = 1) -> None:
    """Fail fast on invalid degree combinations (divisibility constraints)."""
    if cfg.num_kv_heads % tp != 0:
        raise ValueError(
            f"tp={tp} must divide num_kv_heads={cfg.num_kv_heads} "
            "(KV heads are sharded over tp)"
        )
    if cfg.intermediate_size % tp != 0:
        raise ValueError(
            f"tp={tp} must divide intermediate_size={cfg.intermediate_size}"
        )
    if cfg.vocab_size % tp != 0:
        raise ValueError(f"tp={tp} must divide vocab_size={cfg.vocab_size}")
    if sp > 1 and cfg.num_heads % sp != 0:
        raise ValueError(
            f"sp={sp} must divide num_heads={cfg.num_heads} (ring attention "
            "all-to-alls heads across sp)"
        )
    if ep > 1:
        if cfg.num_experts == 0:
            raise ValueError(f"ep={ep} requires an MoE model (num_experts > 0)")
        if cfg.num_experts % ep != 0:
            raise ValueError(
                f"ep={ep} must divide num_experts={cfg.num_experts}"
            )
