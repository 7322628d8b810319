"""Model-family registry.

The reference hardcodes one family — Llama
(``/root/reference/distributed_llm_inference/models/llama/``). Here the
decoder stack (``models/llama.py``) is a single parameterized program whose
config switches cover the supported families; this registry is the explicit
map from HF ``model_type`` to that program plus each family's architectural
quirks, and the extension point for families that need more than config
switches (a new entry supplies its own ``convert_state_dict`` / ``apply``).

Families:

* ``llama``   — the baseline (GQA, RoPE incl. llama3 scaling, SwiGLU).
* ``mistral`` — + sliding-window attention (``ModelConfig.sliding_window``).
* ``exaone_moe`` — K-EXAONE's block: window and full layers in one stack
  (``ModelConfig.layer_attention``; RoPE in the window layers only), per-head
  q/k norms, sigmoid-routed experts beside a shared one behind a leading
  dense layer, of which this program may hold a share
  (``ModelConfig.expert_shares``).
* ``qwen2``   — + q/k/v projection biases (``qkv_bias``) and (2.5-era
  configs) tied embeddings.
* ``mixtral`` — + MoE MLP (``num_experts``/``num_experts_per_tok``), expert
  parallelism over the ``ep`` mesh axis (``ops/moe.py``).
* ``mla``     — the DeepSeek-V2/V3 block (``deepseek_v2``, ``deepseek_v3``:
  DeepSeek-V2-Lite, Moonlight-16B-A3B): latent (low-rank) KV attention
  (``ModelConfig.latent``: a shared per-token KV latent and a decoupled
  rotary key, served through the latent paged cache, ``cache/latent.py``)
  and, where the config has them, routed experts beside shared ones behind
  leading dense layers (``ModelConfig.segments``), with softmax or sigmoid
  routing (``ops/moe.py:route``). A latent model without experts is the
  same entry.
* ``keye_vl2`` — the ``KeyeVL2`` language model (Keye-VL-2.0-30B-A3B): GQA
  with three switches beside it: ``qk_norm`` (an RMSNorm over each query
  and key head before RoPE), ``sparse`` (a learned top-k key selection:
  an indexer whose keys are cached beside K and V,
  ``ModelConfig.sparse``, ``ops/sparse_attention.py``) and ``moe``
  (softmax-routed experts of ``moe_intermediate_size``, no shared expert,
  no dense layer). Its checkpoint's key names are not known to this
  program: :func:`llama.convert_hf_state_dict` refuses the family.

* ``glm_moe_dsa`` — GLM-5.2's block: the latent of ``mla`` with compressed
  queries (``LatentConfig.q_lora_rank``), sigmoid-routed experts beside a
  shared one behind leading dense layers (of which this program may hold a
  share), AND a learned top-k selection over the stored latents whose
  indexer exists only in some layers (``ModelConfig.index_layers``): the
  others attend to the selection of the nearest scoring layer before them
  (``cache/latent.py``: the indexed latent classes). The one family in
  which ``latent`` and ``sparse`` compose.

* ``xing4_0`` — Xing4.0's block: the latent of ``mla`` with compressed
  queries under YaRN, sigmoid-routed experts beside a shared one behind
  leading dense layers, and a residual stream ``hc_mult`` rows wide, mixed
  by manifold-constrained hyper-connections around every sublayer
  (``ModelConfig.hyper``, ``ops/hyper_connections.py``). Its checkpoint's
  key names for those maps are not known to this program:
  :func:`llama.convert_hf_state_dict` refuses the family.

* ``brumby`` — Brumby-14B-Base's block: the Qwen3 block (``qk_norm``) with
  softmax attention replaced by power retention in every layer
  (``ModelConfig.retention``: squared scores decayed by a learned gate a
  key-value head, over a fixed-size state a row that the cache manager
  holds beside a short paged K/V tail; ``cache/retention.py``,
  ``ops/power_retention.py``). Its checkpoint is Qwen3's with a ``g_proj``
  beside q, k, v: the converter maps it.

* ``ouro`` — Ouro's looped block (Ouro-2.6B): the dense block with each
  sublayer's OUTPUT normed before it is added, the whole stack run
  ``total_ut_steps`` times over the same weights, each lap with its own rows
  of the cache (``ModelConfig.loop``, ``ModelConfig.cache_layers``), the
  final norm between laps and one exit gate behind each lap
  (``models/llama.py``: the lap scans of ``model_apply`` and
  ``multi_decode_apply``). Its checkpoint's key names for the output norms
  and the gate are not known to this program:
  :func:`llama.convert_hf_state_dict` refuses the family.

The switches are independent: a family may permit any of them together
(``mla`` permits experts AND requires the latent); what a family does not
permit is refused by :func:`validate_config`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

from ..config import ModelConfig
from . import llama

__all__ = ["ModelFamily", "FAMILIES", "get_family", "validate_config"]


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    # HF `model_type` strings served by this entry.
    hf_model_types: Tuple[str, ...]
    # Capability switches the family is allowed to use.
    sliding_window: bool = False
    qkv_bias: bool = False
    moe: bool = False
    # Latent (MLA) KV attention: the family both permits AND requires
    # ``ModelConfig.latent`` — the latent attention has its own projection
    # set, so a family's attention is one or the other; its MLP switch
    # (``moe``) is independent of it.
    latent: bool = False
    # A per-head RMSNorm of q and k, and the learned top-k key selection.
    qk_norm: bool = False
    sparse: bool = False
    # Window and full layers in one stack (``ModelConfig.layer_attention``).
    layer_attention: bool = False
    # A residual stream several rows wide (``ModelConfig.hyper``).
    hyper: bool = False
    # Power retention in place of softmax attention: the family both
    # permits AND requires ``ModelConfig.retention``.
    retention: bool = False
    # Layers that run several times, an exit gate behind each lap: the
    # family both permits AND requires ``ModelConfig.loop``.
    loop: bool = False
    # The compute/conversion program (shared stack for all current families).
    apply: Callable = llama.model_apply
    block_apply: Callable = llama.block_apply
    init_params: Callable = llama.init_params
    convert_state_dict: Callable = llama.convert_hf_state_dict


FAMILIES: Dict[str, ModelFamily] = {
    f.name: f
    for f in (
        ModelFamily("llama", ("llama",)),
        ModelFamily("mistral", ("mistral",), sliding_window=True),
        ModelFamily("qwen2", ("qwen2",), sliding_window=True, qkv_bias=True),
        ModelFamily("mixtral", ("mixtral",), sliding_window=True, moe=True),
        ModelFamily(
            "mla", ("mla", "deepseek_v2", "deepseek_v3"), latent=True,
            moe=True,
        ),
        ModelFamily(
            "keye_vl2", ("keye_vl2", "KeyeVL2"), moe=True, qk_norm=True,
            sparse=True,
        ),
        ModelFamily(
            "exaone_moe", ("exaone_moe",), sliding_window=True, moe=True,
            qk_norm=True, layer_attention=True,
        ),
        ModelFamily(
            "glm_moe_dsa", ("glm_moe_dsa",), latent=True, moe=True,
            sparse=True,
        ),
        ModelFamily(
            "xing4_0", ("xing4_0",), latent=True, moe=True, hyper=True,
        ),
        ModelFamily("brumby", ("brumby",), qk_norm=True, retention=True),
        ModelFamily("ouro", ("ouro",), loop=True),
    )
}

_BY_HF_TYPE = {
    t: fam for fam in FAMILIES.values() for t in fam.hf_model_types
}


def get_family(name_or_cfg) -> ModelFamily:
    """Look up by family name, HF ``model_type``, or a :class:`ModelConfig`."""
    name = (
        name_or_cfg.family
        if isinstance(name_or_cfg, ModelConfig)
        else str(name_or_cfg)
    )
    fam = FAMILIES.get(name) or _BY_HF_TYPE.get(name)
    if fam is None:
        raise KeyError(
            f"unsupported model family {name!r} (supported: "
            f"{sorted(FAMILIES)})"
        )
    return fam


def validate_config(cfg: ModelConfig) -> ModelFamily:
    """Fail fast when a config uses switches its family doesn't support
    (e.g. an MoE llama config is almost certainly a conversion bug)."""
    fam = get_family(cfg)
    if cfg.sliding_window is not None and not fam.sliding_window:
        raise ValueError(
            f"family {fam.name!r} does not use sliding_window "
            f"(got {cfg.sliding_window})"
        )
    if cfg.num_experts > 0 and not fam.moe:
        raise ValueError(
            f"family {fam.name!r} is dense but config has "
            f"num_experts={cfg.num_experts}"
        )
    if cfg.num_experts == 0 and (
        cfg.num_shared_experts or cfg.first_dense_layers
    ):
        raise ValueError(
            "shared experts and leading dense layers belong to a stack "
            "with routed experts (num_experts is 0)"
        )
    if cfg.num_experts > 0 and not (
        0 <= cfg.first_dense_layers < cfg.num_layers
    ):
        raise ValueError(
            f"first_dense_layers={cfg.first_dense_layers} leaves no expert "
            f"layer among num_layers={cfg.num_layers}"
        )
    if cfg.moe_scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown moe_scoring {cfg.moe_scoring!r}")
    if cfg.qkv_bias and not fam.qkv_bias:
        raise ValueError(f"family {fam.name!r} does not use qkv_bias")
    if cfg.latent is not None and not fam.latent:
        raise ValueError(
            f"family {fam.name!r} does not use latent KV attention "
            f"(use the 'mla' family)"
        )
    if cfg.hyper is not None:
        if not fam.hyper:
            raise ValueError(
                f"family {fam.name!r} does not widen its residual stream "
                f"(ModelConfig.hyper; use the 'xing4_0' family)"
            )
        if cfg.hyper.mult < 2 or cfg.hyper.sinkhorn_iters < 1:
            raise ValueError(
                f"a widened stream has at least 2 rows and 1 Sinkhorn "
                f"round (got {cfg.hyper})"
            )
    if cfg.qk_norm and not fam.qk_norm:
        raise ValueError(f"family {fam.name!r} does not use qk_norm")
    if cfg.sparse is not None and not fam.sparse:
        raise ValueError(
            f"family {fam.name!r} does not use a learned key selection "
            f"(ModelConfig.sparse; use the 'keye_vl2' family, or "
            f"'glm_moe_dsa' over a latent)"
        )
    if cfg.index_layers is not None:
        if cfg.sparse is None:
            raise ValueError(
                "index_layers says which layers of a learned key selection "
                "score (ModelConfig.sparse is None)"
            )
        if not fam.latent:
            raise ValueError(
                f"family {fam.name!r} scores a selection in every layer "
                f"(ModelConfig.index_layers is the indexed latent cache's)"
            )
        if len(cfg.index_layers) != cfg.num_layers or (
            set(cfg.index_layers) - {"score", "reuse"}
        ) or cfg.index_layers[0] != "score":
            raise ValueError(
                f"index_layers names 'score' or 'reuse' for each of the "
                f"{cfg.num_layers} layers, the first 'score' (got "
                f"{cfg.index_layers})"
            )
    if cfg.layer_attention is not None:
        if not fam.layer_attention:
            raise ValueError(
                f"family {fam.name!r} does not mix window and full layers "
                f"(ModelConfig.layer_attention)"
            )
        if len(cfg.layer_attention) != cfg.num_layers or (
            set(cfg.layer_attention) - {"window", "full"}
        ):
            raise ValueError(
                f"layer_attention names 'window' or 'full' for each of the "
                f"{cfg.num_layers} layers (got {cfg.layer_attention})"
            )
        if "window" in cfg.layer_attention and not cfg.sliding_window:
            raise ValueError("a window layer needs ModelConfig.sliding_window")
    if cfg.expert_shares != 1 and (
        cfg.expert_shares < 1
        or cfg.num_experts % cfg.expert_shares
        or not 0 <= cfg.expert_share_index < cfg.expert_shares
    ):
        raise ValueError(
            f"expert_shares={cfg.expert_shares} must divide num_experts="
            f"{cfg.num_experts}, with expert_share_index="
            f"{cfg.expert_share_index} under it"
        )
    if (cfg.retention is not None) != fam.retention:
        raise ValueError(
            f"family {fam.name!r} "
            + ("requires" if fam.retention else "does not use")
            + " power retention (ModelConfig.retention; the 'brumby' family)"
        )
    if cfg.retention is not None and cfg.head_dim % 2:
        raise ValueError(
            "power retention's feature map pairs the halves of an even "
            f"head_dim (got head_dim={cfg.head_dim})"
        )
    if (cfg.loop is not None) != fam.loop:
        raise ValueError(
            f"family {fam.name!r} "
            + ("requires" if fam.loop else "does not use")
            + " layers that run several times (ModelConfig.loop; the 'ouro' "
            "family)"
        )
    if cfg.loop is not None:
        if cfg.loop.steps < 1 or not 0.0 < cfg.loop.exit_threshold <= 1.0:
            raise ValueError(
                "a looped stack makes at least one lap and leaves at a "
                f"summed exit weight in (0, 1] (got {cfg.loop})"
            )
        if len(cfg.segments) != 1:
            raise ValueError(
                "a looped stack is ONE run of like layers: the lap scans "
                f"carry one segment (got {cfg.segments})"
            )
    if fam.latent and (cfg.latent is None or not cfg.latent.enabled):
        raise ValueError(
            f"family {fam.name!r} requires an enabled ModelConfig.latent"
        )
    return fam
