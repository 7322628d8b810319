"""Configuration dataclasses for the TPU-native distributed inference framework.

The reference has no config system — configuration is plain kwargs
(``/root/reference/distributed_llm_inference/utils/model.py:75-80``,
``models/llama/cache.py:11``) plus HF ``AutoConfig``. Here everything is a
frozen dataclass so configs are hashable and can be closed over by ``jax.jit``
as static arguments.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Rotary-embedding scaling: Llama-3's "llama3", "linear", or DeepSeek's
    "yarn" (``ops/rotary.py``: the frequencies' blend by ``beta_fast`` /
    ``beta_slow``, and the softmax scale's ``mscale_all_dim`` factor)."""

    rope_type: str = "default"  # "default" | "llama3" | "linear" | "yarn"
    factor: float = 1.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    # YaRN (arXiv:2309.00071, as DeepSeek-V2/V3's modeling code reads it)
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def from_hf(d: Optional[Mapping[str, Any]]) -> Optional["RopeScaling"]:
        if d is None:
            return None
        scaling = RopeScaling(
            rope_type=d.get("rope_type", d.get("type", "default")),
            factor=float(d.get("factor", 1.0)),
            low_freq_factor=float(d.get("low_freq_factor", 1.0)),
            high_freq_factor=float(d.get("high_freq_factor", 4.0)),
            original_max_position_embeddings=int(
                d.get("original_max_position_embeddings", 8192)
            ),
            beta_fast=float(d.get("beta_fast", 32.0)),
            beta_slow=float(d.get("beta_slow", 1.0)),
            mscale=float(d.get("mscale", 1.0)),
            mscale_all_dim=float(d.get("mscale_all_dim", 0.0)),
        )
        if scaling.rope_type == "yarn" and scaling.mscale != scaling.mscale_all_dim:
            # cos and sin would be scaled by mscale(factor, mscale) /
            # mscale(factor, mscale_all_dim): 1 in every published DeepSeek
            # block, and the caches that re-derive key angles from
            # ``inv_freq`` alone (cache/sink.py) carry no such factor
            raise ValueError(
                f"config key 'rope_scaling' = {dict(d)!r} is not implemented: "
                "a YaRN block whose 'mscale' and 'mscale_all_dim' differ "
                "scales cos and sin, which ops/rotary.py does not"
            )
        return scaling

    @property
    def softmax_factor(self) -> float:
        """What YaRN multiplies a latent block's softmax scale by:
        ``mscale(factor, mscale_all_dim) ** 2`` (DeepSeek-V2/V3's
        attention), 1 for every other scaling."""
        if self.rope_type != "yarn" or not self.mscale_all_dim or self.factor <= 1:
            return 1.0
        return (0.1 * self.mscale_all_dim * math.log(self.factor) + 1.0) ** 2


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Latent (low-rank) KV attention — MLA-style compression.

    Per-head K/V is replaced by ONE shared ``rank``-dim latent per token
    plus a ``rope_head_dim``-dim decoupled rotary key shared across heads.
    The cache stores ``[latent ; rope_key]`` (``lat_dim`` floats/token) and
    attention runs directly over it in the absorbed formulation: queries are
    up-projected into latent space (``w_uk`` folded into the query) and the
    attention output's latent slice is up-projected to per-head values
    (``w_uv``), so no per-token K/V decompression ever materializes — the
    kernels read the stored latents in place. This is a different MODEL
    (its own weights, gated via the ``mla`` registry family), not a lossy
    re-encoding of an existing one: quality parity is a training-time
    property; byte-exactness with the non-latent path is not expected.
    """

    enabled: bool = True
    # Shared KV latent rank (DeepSeek-V2 ``kv_lora_rank``).
    rank: int = 64
    # Decoupled rotary key/query head dim (``qk_rope_head_dim``); rope is
    # applied ONLY to this slice — the latent itself is position-free,
    # which is what makes one stored latent serve every head.
    rope_head_dim: int = 16
    # No-rope query/key head dim (``qk_nope_head_dim``); None = head_dim.
    nope_head_dim: Optional[int] = None
    # Per-head value dim (``v_head_dim``): the width ``wv_b`` up-projects
    # the latent to and ``wo`` consumes; None = head_dim.
    v_head_dim: Optional[int] = None
    # Compressed queries (``q_lora_rank``): the query is projected down to
    # this width, RMS-normed and projected up to the heads (``wq_a``,
    # ``q_a_norm``, ``wq_b`` where a block without it has ``wq``); a
    # learned selection's index queries are projected from the compressed
    # query too. None = queries straight from the hidden state.
    q_lora_rank: Optional[int] = None

    @property
    def lat_dim(self) -> int:
        """Stored per-token width: latent rank + decoupled rope key."""
        return self.rank + self.rope_head_dim


@dataclasses.dataclass(frozen=True)
class HyperConnectionConfig:
    """Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): the
    residual stream of a token is ``mult`` rows of ``hidden_size``, and
    around every attention and MLP sublayer three maps computed from the
    stream itself read the sublayer's input out of the rows (``H_pre``),
    mix the rows among themselves (``H_res``: made doubly stochastic by
    ``sinkhorn_iters`` column-and-row normalisations of ``exp`` of its
    logits clamped to ``res_clamp``, ``eps`` in the divisors) and write the
    sublayer's output back (``H_post``). ``ops/hyper_connections.py``."""

    mult: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    res_clamp: Tuple[float, float] = (-30.0, 30.0)


@dataclasses.dataclass(frozen=True)
class SparseAttentionConfig:
    """Learned sparse attention (DeepSeek-V3.2's lightning indexer and top-k
    selection): ``sa_config`` of a ``KeyeVL2`` block over per-head K/V, or
    ``index_n_heads`` / ``index_head_dim`` / ``index_topk`` of a block with
    a latent, where the selection is of stored latents
    (``cache/latent.py``: the indexed latent classes) and only the layers
    ``ModelConfig.index_layers`` marks score one.

    Beside ``q``, ``k``, ``v`` a layer projects ``index_heads`` index queries
    and ONE index key of ``index_dim`` a token, and a weight a head; the
    index score of a (query, key) pair is the weighted sum over heads of
    ``relu(qI . kI)``, and a query attends only to the ``topk`` positions
    ``s <= t`` of largest score (all of them while ``t < topk``), the same
    positions for every attention head. The index key is cached beside K
    and V in the page pool (``cache/paged.py``: the indexed cache classes);
    scores, selection and the masked attention are ``ops/sparse_attention.py``.
    """

    index_heads: int = 16
    index_dim: int = 64
    topk: int = 2048
    # How many of an index query's and key's leading dims are rotated
    # (a latent block rotates ``qk_rope_head_dim`` of them); None = all.
    rope_dim: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class RetentionConfig:
    """Power retention (Manifest AI, arXiv:2507.04239) in place of softmax
    attention: a query weighs key ``j <= t`` by ``(q_t . k_j) ** 2`` (the
    one degree ``ops/power_retention.py``'s feature map is written for)
    times the decay ``exp(G_t - G_j)`` of a learned log-gate a key-value
    head summed over the positions between, and divides by the summed
    weights plus ``eps``. With ``phi(x) . phi(y) = (x . y) ** 2`` that is a
    recurrence over a state of FIXED size a row, a layer and a key-value
    head (``[D, head_dim]`` float32 and its sum of keys), which
    the cache manager holds beside a short paged K/V tail
    (``cache/retention.py``; the feature map and the kernels:
    ``ops/power_retention.py``)."""

    eps: float = 1e-6


@dataclasses.dataclass(frozen=True)
class LoopConfig:
    """Layers that run several times (Ouro's looped stack, arXiv:2510.25741):
    every token crosses the WHOLE stack ``steps`` times, each lap with the
    same weights and its own rows of the cache (lap ``t`` of layer ``l``
    attends to what lap ``t`` of layer ``l`` wrote: cache row ``t x layers +
    l``). Each sublayer's output is normed before it is added, the final norm
    ends every lap and its output enters the next, and one gate behind each
    lap says with which weight a position would leave there; the head reads
    the first lap at which the summed weights reach ``exit_threshold`` (the
    last, at the published threshold of 1). Every lap runs whatever the gate
    says (``models/llama.py``)."""

    steps: int = 4
    exit_threshold: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerSegment:
    """A run of consecutive decoder layers that are all alike: one
    ``lax.scan`` over one stacked parameter dict (``params[key]``). The
    cache's layer axis runs through every segment: this one owns cache
    layers ``start .. start + count``."""

    kind: str   # a key of ``models.llama.SEGMENT_SCOPES``: "dense" | "moe"
    key: str    # where the segment's stacked parameters live in the tree
    start: int
    count: int
    # The attention of its layers (``ModelConfig.attention_kinds``): "window"
    # layers see the last ``window`` keys, "full" layers every key (``window``
    # None); ``rope`` says whether its queries and keys are rotated.
    attention: str = "full"
    window: Optional[int] = None
    rope: bool = True
    # Its part of the cache. A stack of ONE attention kind has one pool and
    # ``pool`` is None: the segment owns cache layers ``cache_start ..
    # cache_start + count`` with ``cache_start == start``. A stack of two
    # kinds keeps each kind's layers in a pool of its own (``pool`` = the
    # kind), and ``cache_start`` counts the earlier layers of that kind.
    pool: Optional[str] = None
    cache_start: int = 0
    # Its part in a learned selection that layers share
    # (``ModelConfig.index_layers``): "score" layers have an indexer and
    # choose, "reuse" layers attend to what the nearest scoring layer before
    # them chose; None where every layer is alike in that too.
    # ``index_start`` counts the scoring layers before the segment: its
    # rows of the cache's index plane start there.
    index: Optional[str] = None
    index_start: int = 0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a decoder-only transformer.

    Covers the Llama family (the reference's only model family —
    ``/root/reference/distributed_llm_inference/models/llama/model.py``) plus
    Mistral (``sliding_window``), Qwen2 (``qkv_bias``), Mixtral's experts
    (``num_experts``/``num_experts_per_tok``), the DeepSeek-V2/V3 block
    (``latent`` attention; routed experts beside shared ones behind leading
    dense layers; the routing rule's switches) and the ``KeyeVL2`` block
    (GQA with a per-head RMSNorm of queries and keys, ``qk_norm``; a learned
    top-k key selection, ``sparse``; softmax-routed experts of
    ``moe_intermediate_size`` with no latent).
    """

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    # Mistral-style sliding-window attention; None = full causal.
    sliding_window: Optional[int] = None
    # Qwen2-style bias on q/k/v projections.
    qkv_bias: bool = False
    # MoE (Mixtral): 0 experts = dense MLP.
    # ``num_experts`` is the ROUTER's width: the experts a token's scores
    # run over. How many of them this program holds is
    # :attr:`num_held_experts` (all, unless ``expert_shares`` > 1).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Width of one routed expert (``moe_intermediate_size``); None =
    # ``intermediate_size`` (Mixtral: every MLP of the model is an expert).
    moe_intermediate_size: Optional[int] = None
    # Shared experts (``n_shared_experts``): applied to every token beside
    # the routed ones, as ONE SwiGLU MLP of this many expert widths.
    num_shared_experts: int = 0
    # Leading layers whose MLP is dense, ``intermediate_size`` wide
    # (``first_k_dense_replace``); the rest route.
    first_dense_layers: int = 0
    # The routing rule (``ops/moe.py:route``). ``moe_scoring``: "softmax"
    # (Mixtral, DeepSeek-V2) or "sigmoid" (DeepSeek-V3, Moonlight) over all
    # experts. ``moe_select_bias``: selection is by score PLUS a per-expert
    # parameter (``topk_method`` "noaux_tc"); the weights never carry it.
    # ``moe_norm_topk``: the selected weights are divided by their sum.
    # ``moe_routed_scale``: then multiplied by this.
    moe_scoring: str = "softmax"
    moe_select_bias: bool = False
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    # Latent (MLA-style) KV compression; requires the "mla" family and the
    # paged cache kind. None = conventional per-head K/V.
    latent: Optional[LatentConfig] = None
    # RMSNorm over each query and key head's ``head_dim`` (its own gain,
    # ``q_norm`` / ``k_norm``) before RoPE: the Qwen3-style block.
    qk_norm: bool = False
    # Learned top-k key selection (an indexer beside GQA); requires the
    # "keye_vl2" family and the paged cache kind. None = every key.
    sparse: Optional[SparseAttentionConfig] = None
    # Which layers of a stack that selects its keys score a selection of
    # their own ("score") and which attend to the selection of the nearest
    # scoring layer before them ("reuse"), a layer (``indexer_types``:
    # "full" | "shared"); None = every layer scores. The first layer scores.
    index_layers: Optional[Tuple[str, ...]] = None
    # The layers' attention, one of "window" | "full" a layer, where they are
    # not all alike (``layer_types``); None = every layer the same: a window
    # layer where ``sliding_window`` is set, else a full one. A window layer
    # sees keys ``j`` with ``t - sliding_window < j <= t``.
    layer_attention: Optional[Tuple[str, ...]] = None
    # Whether a FULL layer of a stack of two kinds rotates its queries and
    # keys (EXAONE 4.0's hybrid rule: RoPE in the window layers only).
    full_attention_rope: bool = True
    # The share of each expert layer's experts held here: share
    # ``expert_share_index`` of ``expert_shares`` contiguous, equal shares
    # of the ``num_experts`` the router scores (``ops/moe.py``). 1 share =
    # every expert is here.
    expert_shares: int = 1
    expert_share_index: int = 0
    # A residual stream ``hyper.mult`` rows wide, mixed by
    # manifold-constrained hyper-connections around every sublayer
    # (``hc_mult``); None = the plain ``x + f(x)``.
    hyper: Optional[HyperConnectionConfig] = None
    # Power retention in place of softmax attention, in every layer
    # (``model_type`` "brumby"); None = softmax attention.
    retention: Optional[RetentionConfig] = None
    # Layers that run several times (``total_ut_steps``: ``model_type``
    # "ouro"), with an exit gate behind each lap; None = once.
    loop: Optional[LoopConfig] = None
    # Model family tag ("llama", "mistral", "qwen2", "mixtral", "mla",
    # "keye_vl2", "exaone_moe", "glm_moe_dsa", "xing4_0", "brumby", "ouro").
    family: str = "llama"

    @property
    def loop_steps(self) -> int:
        """Laps a token makes over the stack: 1 but for a looped model."""
        return 1 if self.loop is None else self.loop.steps

    @property
    def cache_layers(self) -> int:
        """THE count of a cache's layers: a lap of a layer has rows of its
        own, so a looped stack caches ``loop_steps x num_layers`` layers of K
        and V for ``num_layers`` layers of weights. What every cache
        constructor takes where the weights take ``num_layers``."""
        return self.loop_steps * self.num_layers

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def expert_intermediate_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def num_held_experts(self) -> int:
        """Expert matrices a routed layer holds: the router's width over the
        shares."""
        return self.num_experts // self.expert_shares

    @property
    def first_held_expert(self) -> int:
        return self.expert_share_index * self.num_held_experts

    @property
    def attention_kinds(self) -> Tuple[str, ...]:
        """"window" | "full" | "retention", a layer. One window for the
        whole model (Mistral) is every layer a window layer; a model with
        a retention part has retention layers only."""
        if self.retention is not None:
            return ("retention",) * self.num_layers
        if self.layer_attention is not None:
            return self.layer_attention
        kind = "window" if self.sliding_window is not None else "full"
        return (kind,) * self.num_layers

    @property
    def mixed_attention(self) -> bool:
        """THE two-kinds predicate: window and full layers in one stack, so
        the cache keeps a pool a kind (``cache/paged.py``)."""
        return len(set(self.attention_kinds)) > 1

    @property
    def segments(self) -> Tuple[LayerSegment, ...]:
        """THE description of the stack: homogeneous segments in layer
        order, runs of layers alike in MLP ("dense" | "moe"), attention
        ("window" | "full") and part in a shared selection ("score" |
        "reuse", ``index_layers``). Initialisation, the forward programs, the
        checkpoint converter, the quantiser and the census all walk this. A
        stack whose layers are all alike is one segment under ``"layers"``,
        the parameter tree every such model has always had; a stack with
        leading dense layers is a dense segment then an expert one; window
        and full layers alternate segments, each with its window, its RoPE
        switch and its part of the cache."""
        moe = self.num_experts > 0
        k = self.first_dense_layers if moe else 0
        kinds = self.attention_kinds
        mixed = self.mixed_attention
        index = self.index_layers or (None,) * self.num_layers
        runs = []                  # [mlp, attention, index, start, count]
        for i in range(self.num_layers):
            mlp = "moe" if moe and i >= k else "dense"
            if runs and runs[-1][:3] == [mlp, kinds[i], index[i]]:
                runs[-1][4] += 1
            else:
                runs.append([mlp, kinds[i], index[i], i, 1])
        seen = {"window": 0, "full": 0, "retention": 0, "score": 0}
        out = []
        for n, (mlp, att, idx, start, count) in enumerate(runs):
            out.append(LayerSegment(
                mlp,
                "layers" if len(runs) == 1 else f"layers_{n}_{mlp}",
                start, count,
                attention=att,
                window=self.sliding_window if att == "window" else None,
                rope=att == "window" or not mixed or self.full_attention_rope,
                pool=att if mixed else None,
                cache_start=seen[att] if mixed else start,
                index=idx,
                index_start=seen["score"],
            ))
            seen[att] += count
            if idx == "score":
                seen["score"] += count
        return tuple(out)

    @property
    def index_scoring(self) -> Optional[Tuple[bool, ...]]:
        """Which layers score a selection, a layer; None for a stack that
        selects no keys. What the cache's index plane has rows for."""
        if not self.use_sparse:
            return None
        if self.index_layers is None:
            return (True,) * self.num_layers
        return tuple(kind == "score" for kind in self.index_layers)

    @property
    def num_expert_layers(self) -> int:
        return sum(s.count for s in self.segments if s.kind == "moe")

    @property
    def use_latent(self) -> bool:
        """THE latent predicate — every consumer (model, engine, bench)
        branches on this, so a present-but-disabled ``LatentConfig`` is
        uniformly the baseline per-head path, never a half-latent mix."""
        return self.latent is not None and self.latent.enabled

    @property
    def use_sparse(self) -> bool:
        """THE selection predicate (as :attr:`use_latent` is the latent's)."""
        return self.sparse is not None

    @property
    def use_retention(self) -> bool:
        """THE retention predicate: the layers keep a fixed-size state a
        row and no keys that grow with the context."""
        return self.retention is not None

    @staticmethod
    def from_hf_config(hf: Any) -> "ModelConfig":
        """Build from a ``transformers`` PretrainedConfig (or plain dict)."""
        get = (lambda k, d=None: hf.get(k, d)) if isinstance(hf, dict) else (
            lambda k, d=None: getattr(hf, k, d)
        )
        model_type = get("model_type", "llama")
        num_heads = get("num_attention_heads", 32)
        hidden = get("hidden_size", 4096)
        latent = None
        moe = {}
        if get("kv_lora_rank", None):
            # DeepSeek-V2/V3-style checkpoint: map the latent dims and
            # normalize the family tag to the registry's "mla". What the
            # block has and this program does not implement is refused by
            # the key's name: a model served under its name is that model.
            _refuse_unimplemented(get)
            latent = LatentConfig(
                rank=int(get("kv_lora_rank")),
                rope_head_dim=int(get("qk_rope_head_dim", 64)),
                nope_head_dim=get("qk_nope_head_dim", None),
                v_head_dim=get("v_head_dim", None),
                q_lora_rank=get("q_lora_rank", None),
            )
            model_type = _LATENT_FAMILIES.get(model_type, "mla")
        extra = {}
        if latent is not None:
            if get("n_routed_experts", None):
                moe, share = _routing_keys(
                    get, held="n_routed_experts", shared="n_shared_experts",
                    scoring="softmax",
                    select_bias=get("topk_method", "greedy") == "noaux_tc",
                )
                extra.update(share)
            if get("index_topk", None):
                extra.update(_index_keys(get, latent))
        if model_type == "KeyeVL2":
            _refuse_unimplemented_keye(get)
            sa = get("sa_config", None) or {}
            if (sa.get("indexer_num_kv_heads", 1) or 1) != 1:
                raise ValueError(
                    "config key 'sa_config.indexer_num_kv_heads' = "
                    f"{sa.get('indexer_num_kv_heads')!r} is not implemented: "
                    "the index plane holds ONE index key a token"
                )
            model_type = "keye_vl2"
            extra = dict(
                qk_norm=True,
                sparse=SparseAttentionConfig(
                    index_heads=int(sa.get("indexer_num_heads", 16)),
                    index_dim=int(sa.get("indexer_head_dim", 64)),
                    topk=int(sa.get("topk", 2048)),
                ) if sa else None,
            )
            if get("num_experts", 0) or get("num_local_experts", 0):
                moe = dict(
                    moe_intermediate_size=get("moe_intermediate_size", None),
                    moe_scoring="softmax",
                    moe_norm_topk=bool(get("norm_topk_prob", False)),
                )
        # a ``KeyeVL2`` block's ``sliding_window`` counts only under
        # ``use_sliding_window`` (refused above): the key alone states no
        # window
        window = (
            None if model_type == "keye_vl2" else get("sliding_window", None)
        )
        experts = (
            get("num_local_experts", 0) or get("n_routed_experts", 0)
            or get("num_experts", 0) or 0
        )
        if model_type == "exaone_moe":
            moe, extra = _exaone_moe_keys(get)
            window = extra.pop("sliding_window")
        if model_type == "brumby":
            extra, window = _brumby_keys(get), None
        if model_type == "ouro":
            extra, window = _ouro_keys(get), None
        # the ROUTER's width, where the block's key counts a share held here
        experts = extra.pop("num_experts", experts)
        # A block may nest its RoPE keys (``rope_parameters``: theta and
        # type together) where older ones spread them at the top level.
        nested = get("rope_parameters", None) or {}
        rope_scaling = get("rope_scaling", None)
        if rope_scaling is None and nested.get(
            "rope_type", "default"
        ) != "default":
            rope_scaling = nested
        return ModelConfig(
            vocab_size=get("vocab_size", 32000),
            hidden_size=hidden,
            intermediate_size=get("intermediate_size", 11008),
            num_layers=get("num_hidden_layers", 32),
            num_heads=num_heads,
            num_kv_heads=get("num_key_value_heads", num_heads) or num_heads,
            head_dim=get("head_dim", None) or hidden // num_heads,
            rms_norm_eps=get("rms_norm_eps", 1e-5),
            rope_theta=(
                get("rope_theta", None) or nested.get("rope_theta", 10000.0)
            ),
            rope_scaling=RopeScaling.from_hf(rope_scaling),
            max_position_embeddings=get("max_position_embeddings", 4096),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            sliding_window=window,
            qkv_bias=bool(get("attention_bias", False)) or model_type in ("qwen2",),
            num_experts=experts,
            num_experts_per_tok=get("num_experts_per_tok", 2) or 2,
            latent=latent,
            hyper=_hyper_keys(get) if get("hc_mult", None) else None,
            family=model_type,
            **moe,
            **extra,
        )


def _refuse_unimplemented(get) -> None:
    """Keys of a DeepSeek-V2/V3 ``config.json`` whose published meaning
    this program does not compute. Each raises under its own name."""
    refused = {
        "n_group": (get("n_group", 1) or 1) > 1,
        "topk_group": (get("topk_group", 1) or 1) > 1,
        "num_nextn_predict_layers": (
            get("num_nextn_predict_layers", 0) or 0
        ) > 0,
        "moe_layer_freq": (get("moe_layer_freq", 1) or 1) != 1,
        "topk_method": get("topk_method", "greedy")
        not in ("greedy", "noaux_tc"),
        "scoring_func": get("scoring_func", "softmax")
        not in ("softmax", "sigmoid"),
    }
    for key, bad in refused.items():
        if bad:
            raise ValueError(
                f"config key {key!r} = {get(key)!r} is not implemented: "
                f"routing by groups, next-token-prediction "
                f"layers and interleaved dense layers are outside what "
                f"models/llama.py computes"
            )


def _brumby_keys(get) -> dict:
    """The keys of a ``brumby`` ``config.json`` (Brumby-14B-Base): the Qwen3
    block (per-head q/k norms) with power retention in every layer. The
    published keys state neither the degree, the gate's form nor the
    normaliser's epsilon (a configuration file lists them as assumed):
    degree 2 (the feature map's), one log-gate a key-value head (the gate
    projection's width is ``num_kv_heads``), ``rms_norm_eps``. A window the
    block switches on is refused by the key's name."""
    if get("use_sliding_window", False):
        _refuse(
            get, "use_sliding_window",
            "a retention layer keeps a decayed state and no window of keys",
        )
    return dict(
        qk_norm=True,
        retention=RetentionConfig(eps=float(get("rms_norm_eps", 1e-6))),
    )


def _ouro_keys(get) -> dict:
    """The keys of an ``ouro`` ``config.json`` (Ouro-2.6B): the dense block
    run ``total_ut_steps`` times with an exit gate behind each lap read at
    ``early_exit_threshold``. The published keys state neither the norms of
    the sublayers' outputs, nor that the final norm feeds the next lap, nor
    the gate's form (a configuration file lists them as assumed). A window
    the block switches on is refused by the key's name."""
    if get("use_sliding_window", False):
        _refuse(
            get, "use_sliding_window",
            "every layer of a looped stack attends to its lap's whole context",
        )
    if any(t != "full_attention" for t in get("layer_types", None) or ()):
        _refuse(get, "layer_types", "full_attention in every layer")
    return dict(loop=LoopConfig(
        steps=int(get("total_ut_steps", 4)),
        exit_threshold=float(get("early_exit_threshold", 1.0)),
    ))


_ATTENTION_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def _exaone_moe_keys(get):
    """The keys of an ``exaone_moe`` ``config.json`` (K-EXAONE): window and
    full layers by ``layer_types``, a leading dense layer by
    ``mlp_layer_types`` / ``first_k_dense_replace``, DeepSeek-V3's routing
    keys without a latent, and (a benchmark configuration's own key)
    ``expert_share``: ``{"router_experts", "shares", "index"}``, the share
    of each layer's experts that ``num_experts`` counts. Returns ``(moe,
    extra)`` keyword groups for :class:`ModelConfig`. What the block has
    and this program does not compute raises under its key's name."""
    def refuse(key, why):
        _refuse(get, key, why)

    layers = get("num_hidden_layers", 32)
    if (get("num_nextn_predict_layers", 0) or 0) > 0:
        refuse(
            "num_nextn_predict_layers",
            "a step of this engine yields one token a row (the fused decode "
            "scan, the write-behind tail); multi-token prediction layers "
            "are not served",
        )
    if (get("n_group", 1) or 1) > 1 or (get("topk_group", 1) or 1) > 1:
        refuse("n_group", "routing by groups of experts")
    if get("scoring_func", "sigmoid") not in ("softmax", "sigmoid"):
        refuse("scoring_func", "softmax or sigmoid scores")
    window = get("sliding_window", None)
    types = get("layer_types", None)
    kinds = None
    if types is not None:
        if len(types) != layers or any(t not in _ATTENTION_KINDS for t in types):
            refuse(
                "layer_types",
                f"one of {sorted(_ATTENTION_KINDS)} for each of the "
                f"{layers} layers",
            )
        kinds = tuple(_ATTENTION_KINDS[t] for t in types)
        if "window" in kinds and not window:
            refuse("sliding_window", "a window layer needs its window")
        # which layers have a window; its size is ``sliding_window``'s
        windows = get("sliding_windows", None)
        if windows is not None and [bool(w) for w in windows] != [
            k == "window" for k in kinds
        ]:
            refuse(
                "sliding_windows",
                "a window for every sliding_attention layer, 0 for the "
                "full ones",
            )
        if "window" not in kinds:
            window = None
        if len(set(kinds)) == 1:
            kinds = None
    moe, share = _routing_keys(
        get, held="num_experts", shared="num_shared_experts",
        scoring="sigmoid",
        # the selection bias of DeepSeek-V3's gate: assumed, the config has
        # no key for it (benchmark/configs/k-exaone-236b-a23b.json)
        select_bias=True,
    )
    extra = dict(
        qk_norm=True,
        layer_attention=kinds,
        full_attention_rope=False,
        sliding_window=window,
        **share,
    )
    return moe, extra


#: ``model_type`` of a block with a latent -> its family, where that is not
#: "mla" (``models/registry.py``)
_LATENT_FAMILIES = {"glm_moe_dsa": "glm_moe_dsa", "xing4_0": "xing4_0"}
_INDEX_KINDS = {"full": "score", "shared": "reuse"}


def _refuse(get, key, why):
    raise ValueError(
        f"config key {key!r} = {get(key)!r} is not implemented: {why}"
    )


def _routing_keys(get, held: str, shared: str, scoring: str,
                  select_bias: bool):
    """THE reader of DeepSeek-V3's routing keys, for every family that has
    them (``exaone_moe``, ``deepseek_v2`` / ``deepseek_v3``,
    ``glm_moe_dsa``): the leading dense layers by ``first_k_dense_replace``
    and, where the block lists them, ``mlp_layer_types``; the routing rule;
    and (a benchmark configuration's own key) ``expert_share``:
    ``{"router_experts", "shares", "index"}``, the share of each layer's
    experts that the block's ``held`` key counts. ``held`` / ``shared`` name
    the family's keys for the experts and the shared experts, ``scoring``
    its default rule. Returns ``(moe, share)`` keyword groups for
    :class:`ModelConfig`; ``moe`` is empty for a block without experts."""
    layers = get("num_hidden_layers", 32)
    first_dense = get("first_k_dense_replace", 0) or 0
    mlps = get("mlp_layer_types", None)
    if mlps is not None:
        dense = sum(1 for m in mlps if m == "dense")
        if len(mlps) != layers or list(mlps) != (
            ["dense"] * dense + ["sparse"] * (layers - dense)
        ):
            _refuse(
                get, "mlp_layer_types",
                "leading dense layers and then expert layers only",
            )
        if dense != first_dense:
            _refuse(
                get, "first_k_dense_replace", "agreement with mlp_layer_types"
            )
    count = get(held, 0) or 0
    share = get("expert_share", None) or {}
    shares = int(share.get("shares", 1))
    router = int(share.get("router_experts", count))
    if router != count * shares or not 0 <= int(share.get("index", 0)) < shares:
        _refuse(
            get, "expert_share",
            f"router_experts = {held} ({count}) x shares, and an index "
            f"under shares",
        )
    moe = dict(
        moe_intermediate_size=get("moe_intermediate_size", None),
        num_shared_experts=get(shared, 0) or 0,
        first_dense_layers=first_dense,
        moe_scoring=get("scoring_func", scoring),
        moe_select_bias=select_bias,
        moe_norm_topk=bool(get("norm_topk_prob", False)),
        moe_routed_scale=float(get("routed_scaling_factor", 1.0) or 1.0),
    ) if count else {}
    return moe, dict(
        num_experts=router,
        expert_shares=shares,
        expert_share_index=int(share.get("index", 0)),
    )


def _hyper_keys(get) -> HyperConnectionConfig:
    """A block's hyper-connection keys: ``hc_mult`` rows, and with it
    ``hc_sinkhorn_iters``, ``hc_eps`` and the ``mhc_h_res_clamp_min`` /
    ``_max`` pair, none of which has a default here: a block that widens
    its stream states how the mixing map is constrained."""
    for key in (
        "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max",
    ):
        if get(key, None) is None:
            _refuse(
                get, key,
                f"a block with hc_mult = {get('hc_mult')!r} states it",
            )
    return HyperConnectionConfig(
        mult=int(get("hc_mult")),
        sinkhorn_iters=int(get("hc_sinkhorn_iters")),
        eps=float(get("hc_eps")),
        res_clamp=(
            float(get("mhc_h_res_clamp_min")), float(get("mhc_h_res_clamp_max"))
        ),
    )


def _index_keys(get, latent: LatentConfig) -> dict:
    """A latent block's lightning indexer (``index_n_heads`` /
    ``index_head_dim`` / ``index_topk``) and which layers have one
    (``indexer_types``: "full" scores, "shared" attends to the selection of
    the nearest "full" layer before it). Without the list every layer
    scores; ``index_topk_freq`` alone is not read as a pattern (the list is
    the block's own statement of it)."""
    layers = get("num_hidden_layers", 32)
    types = get("indexer_types", None)
    kinds = None
    if types is not None:
        if len(types) != layers or any(t not in _INDEX_KINDS for t in types):
            _refuse(
                get, "indexer_types",
                f"one of {sorted(_INDEX_KINDS)} for each of the {layers} "
                f"layers",
            )
        if types[0] != "full":
            _refuse(
                get, "indexer_types",
                "a first layer that scores: a shared layer has no selection "
                "before it to attend to",
            )
        kinds = tuple(_INDEX_KINDS[t] for t in types)
        if "reuse" not in kinds:
            kinds = None
    elif (get("index_topk_freq", 1) or 1) != 1:
        _refuse(
            get, "index_topk_freq",
            "a selection shared by layers is read from indexer_types, a "
            "layer; the period alone does not say which layers score",
        )
    return dict(
        sparse=SparseAttentionConfig(
            index_heads=int(get("index_n_heads", 32)),
            index_dim=int(get("index_head_dim", 128)),
            topk=int(get("index_topk", 2048)),
            rope_dim=latent.rope_head_dim,
        ),
        index_layers=kinds,
    )


def _refuse_unimplemented_keye(get) -> None:
    """Keys of a ``KeyeVL2`` ``config.json`` whose published meaning this
    program does not compute. Each raises under its own name."""
    refused = {
        "decoder_sparse_step": (get("decoder_sparse_step", 1) or 1) != 1,
        "mlp_only_layers": bool(get("mlp_only_layers", None)),
        "use_sliding_window": bool(get("use_sliding_window", False)),
    }
    for key, bad in refused.items():
        if bad:
            raise ValueError(
                f"config key {key!r} = {get(key)!r} is not implemented: "
                f"dense layers between the expert layers and windowed "
                f"layers are outside what models/llama.py computes for "
                f"this family (every layer routes, every layer selects)"
            )


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Axes: data, pipeline(stage), tensor, sequence.

    Replaces the reference's (absent) process-group story: the vestigial
    single-device ``pretraining_tp`` weight slicing at
    ``/root/reference/distributed_llm_inference/models/llama/modules.py:44-59``
    becomes real multi-device TP via ``jax.sharding.Mesh`` + NamedSharding.
    """

    dp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1  # sequence/context parallel degree
    ep: int = 1  # expert parallel degree (MoE experts sharded across devices)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("dp", "pp", "ep", "tp", "sp")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.dp, self.pp, self.ep, self.tp, self.sp)

    @property
    def num_devices(self) -> int:
        return self.dp * self.pp * self.ep * self.tp * self.sp


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """KV-cache policy.

    ``window_length``/``num_sink_tokens`` carry the reference's signature
    StreamingLLM sink-cache capability
    (``/root/reference/distributed_llm_inference/models/llama/cache.py:11``)
    into a static-shape design; paged parameters size the vLLM-style paged
    pool used for bounded-context serving.
    """

    kind: str = "paged"  # "paged" | "sink" | "dense"
    # KV value quantization: None (model dtype) | "int8" (per-token/head
    # scales; dense kind only) — halves the decode path's dominant HBM
    # traffic at large batch.
    kv_quant: Optional[str] = None
    max_sessions: int = 32
    page_size: int = 64
    num_pages: int = 512
    max_pages_per_session: int = 64
    # Automatic prefix caching (paged kind): finished sessions' full prompt
    # pages are content-addressed; new sessions sharing a prompt prefix map
    # the cached pages instead of recomputing their KV.
    prefix_caching: bool = False
    # sink-cache policy (kind == "sink")
    window_length: int = 1024
    num_sink_tokens: int = 4


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving engine policy: batching, buckets, dtypes, quantization."""

    max_batch_size: int = 8
    prefill_buckets: Tuple[int, ...] = (128, 512, 2048)
    max_seq_len: int = 4096
    max_new_tokens: int = 512
    dtype: str = "bfloat16"
    # None | "int8" | "int4" | "int8_outlier" (LLM.int8()-style fp outlier
    # channels beside the int8 body — the reference's bnb threshold=5.0).
    quantization: Optional[str] = None
    # Decode attention-window buckets (dense cache kinds): each decode step
    # reads only the smallest bucket >= the longest live row instead of the
    # full max_seq_len buffer (one executable per bucket; big bandwidth win
    # early in long-context serving). None = auto ladder; () disables.
    decode_windows: Optional[Tuple[int, ...]] = None
    # None (default) = auto (engine/plan.py): ON for the int8 DENSE cache on
    # a real TPU backend; OFF elsewhere, where CPU tests would crawl through
    # interpret mode. Which side is faster is not measured on the chip; no
    # cell on the other side (ROADMAP D5).
    use_pallas_attention: Optional[bool] = None
    # Ragged mixed-phase attention (engine/plan.py + ops/ragged_attention.py):
    # prefill-family dispatches pad to ONE width (prefill_chunk_tokens) so
    # mixed-length traffic stops recompiling per bucket, paged caches on TPU
    # serve multi-token rows through the ragged Pallas kernel (pages read in
    # place — no contiguous gather copy), and long GREEDY prompts co-schedule
    # chunked prefill with live decode ticks. Token streams are byte-exact
    # with the flag on or off (the legacy admission partition and PRNG key
    # order are preserved; only pad widths change). None (default) = auto:
    # ON for paged caches on a real TPU backend, OFF elsewhere (CPU keeps
    # the legacy bucketed default; tests opt in explicitly).
    ragged_attention: Optional[bool] = None
    # Token width of one chunked-prefill dispatch under ragged mode — also
    # THE single prefill pad width (capped at the legacy chunk cap so chunk
    # boundaries match the legacy path). None = the largest prefill bucket.
    prefill_chunk_tokens: Optional[int] = None
    # Fraction of decode ticks that may also carry a chunked-prefill
    # dispatch when long-prompt admission rides the decode cadence (credit
    # accumulator; 1.0 = every tick, 0 = never co-schedule — long prompts
    # fall back to standalone prefill). With no live decode rows chunks
    # stream at full speed regardless.
    chunk_decode_share: float = 0.5
    # Tokens decoded per device dispatch (lax.scan over the decode step with
    # sampling, EOS and per-row token budgets all in-graph). K-step decode
    # pays one host round trip per K tokens instead of one per token; what
    # a round trip costs against the step's HBM traffic is not measured on
    # a directly attached chip.
    # Tradeoff: tokens stream to consumers every K steps, not every step.
    # None (default) = auto: 16 when the engine's fused write-behind-tail
    # path composes with the cache/mesh (the headline configuration), else 1
    # (pp meshes and caches without a tail path keep per-token dispatch).
    decode_steps: Optional[int] = None
    # speculative decoding
    speculative_k: int = 0  # 0 = disabled
    # Adaptive speculation (every engine with a draft model): when the
    # MEASURED tokens-per-round EMA sags below ``speculative_probe_below``
    # (None = auto, 0.55*(k+1)), the engine probes the plain fused-decode
    # path for ``speculative_probe_len`` ticks and serves whichever path
    # measured faster, re-probing every ``speculative_probe_period`` ticks.
    # Rows' token streams are identical either way (both are greedy argmax);
    # switching back re-syncs the draft cache (one chunked draft prefill
    # per speculative session). Addresses low-acceptance regimes where a
    # round's k draft forwards + verify cost more than the tokens they
    # yield.
    speculative_probe_below: Optional[float] = None
    speculative_probe_period: int = 48
    speculative_probe_len: int = 8
    # Propose→verify→accept ROUNDS fused into one device dispatch (draft
    # scan, k+1-position verify, acceptance, cache rollback and draft
    # catch-up all in-graph, lax.scan over rounds). Each synchronous
    # speculative tick otherwise pays 2+ host round trips per round (their
    # cost against a round's device time is not measured on a directly
    # attached chip). None = auto: decode_steps' token
    # budget divided by k+1 proposals per round (>=1); 1 recovers
    # per-round dispatch.
    speculative_rounds: Optional[int] = None
    # quantization="int8_outlier": fp input channels carried beside the int8
    # body per projection (LLM.int8()-inspired decomposition), and optional
    # calibration activation absmax per weight name ({"wq": [..., in], ...})
    # steering the channel choice the way LLM.int8() does — without it the
    # proxy is weight-row energy. A pytree-of-arrays field: excluded from
    # hashing/eq so EngineConfig stays hashable.
    outlier_channels: int = 32
    act_scales: Optional[Any] = dataclasses.field(
        default=None, hash=False, compare=False
    )


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """HTTP gateway policy (``serving/server.py``): admission control,
    per-request deadlines, and graceful drain for the OpenAI-compatible
    ``/v1/completions`` front door."""

    host: str = "0.0.0.0"
    port: int = 8000  # 0 = ephemeral (the bound port is reported after bind)
    # Admission bound: completions in flight through the gateway (waiting in
    # the engine queue + decoding). At the bound new requests get 429 with
    # a Retry-After header — backpressure a load balancer can act on —
    # instead of growing an unbounded queue.
    max_queue_depth: int = 64
    retry_after_s: float = 1.0
    # Per-request deadline (seconds): the request body's "timeout_s"
    # overrides the default, capped at the max. An expired deadline cancels
    # the underlying generation (engine.cancel) so abandoned requests stop
    # burning decode slots.
    default_timeout_s: float = 120.0
    max_timeout_s: float = 600.0
    # Cap on a request's max_tokens (an unbounded ask pins a decode slot).
    max_tokens_cap: int = 2048
    # Graceful drain (SIGTERM): stop admitting, give in-flight requests this
    # long to finish, cancel the rest, then exit.
    drain_timeout_s: float = 30.0
    # Driver-loop sleep when the engine has no work (seconds).
    idle_sleep_s: float = 0.002
    # Reported as the OpenAI "model" field in responses.
    model_name: str = "distributed-llm-inference-tpu"
    # Circuit breaker (serving/breaker.py): after this many consecutive
    # backend failures the gateway fails fast (503 + Retry-After) instead
    # of burning a full timeout per doomed request ...
    breaker_failure_threshold: int = 5
    # ... for this long, then admits trial traffic again (half-open) ...
    breaker_recovery_s: float = 5.0
    # ... and closes after this many consecutive trial successes.
    breaker_success_threshold: int = 1
    # Background backend health-probe period (seconds; 0 disables). Probes
    # can open the breaker with zero traffic and drive recovery.
    breaker_probe_interval_s: float = 1.0


@dataclasses.dataclass(frozen=True)
class SchedConfig:
    """SLO-aware multi-tenant admission policy (``sched/``): tenant
    identity + token-bucket rate limits, a weighted-fair admission queue
    with ``interactive``/``batch`` priority lanes the engine honors when
    picking sessions each tick, deadline-aware shedding at admission, and
    the locality-vs-load placement weighting the routing backends share.
    Scheduling reorders ADMISSIONS only — per-request token streams are
    byte-exact with the scheduler on or off."""

    # Tenant a request lands on when it carries no API key (Authorization
    # bearer / x-api-key header) and no "user" field.
    default_tenant: str = "anon"
    # Lane when the request body names none ("interactive" | "batch").
    default_lane: str = "interactive"
    # Per-tenant token-bucket rate limit over TOKEN cost (prompt tokens +
    # max_tokens — big prompts pay for their weight). 0 disables rate
    # limiting. Rejections are 429s whose Retry-After is the bucket's
    # actual refill time for this request, not a constant.
    rate_tokens_per_s: float = 0.0
    # Bucket capacity (burst allowance) in tokens; 0 = 2 s of rate.
    burst_tokens: float = 0.0
    # Weighted-fair queue: virtual-time shares. Per-tenant weight
    # overrides as (tenant, weight) pairs; everyone else gets the default.
    default_weight: float = 1.0
    weights: Tuple[Tuple[str, float], ...] = ()
    # Guaranteed batch-lane admission share under interactive pressure
    # (anti-starvation): one batch candidate is interleaved after every
    # ~1/batch_share - 1 interactive picks. 0 = strict priority.
    batch_share: float = 0.125
    # Pending (admitted, pre-first-token) requests per lane before new
    # ones get 429 queue_full.
    max_lane_depth: int = 256
    # Deadline-aware shedding: reject at admission (before any prefill
    # FLOPs) when the EMA-estimated queue wait + prefill time exceeds the
    # request's remaining deadline times this headroom factor. <1 sheds
    # more eagerly; 0 disables.
    shed_headroom: float = 1.0
    # EMA smoothing for the prefill-rate / queue-wait estimator.
    ema_alpha: float = 0.2
    # Placement hint weighting: matched prefix tokens equivalent to one
    # unit of node load. A prefix holder wins the routing decision only
    # while its extra load, scaled by this, stays under the match length.
    locality_tokens_per_load: float = 256.0


@dataclasses.dataclass(frozen=True)
class PrefixConfig:
    """Fleet-wide prefix/KV reuse policy (``prefixstore/``): copy-on-write
    shared prefix pages inside one engine, a bounded host-DRAM spill tier
    for evicted prefix pages, and prefix-aware request routing across the
    fleet. Requires ``CacheConfig.prefix_caching`` (paged cache) for the
    engine-level layers; routing knobs apply to the gateway backends."""

    # Live copy-on-write sharing: sessions register their full prompt pages
    # at ADMISSION (not just at release), so concurrent sessions sharing a
    # prefix attach to the same device pages; a session whose write offset
    # lands inside a shared page splits it copy-on-write first.
    prefix_share: bool = True
    # Host-DRAM spill arena byte budget for evicted prefix pages (stored
    # form: int8+scales or value-dtype bits). 0 disables spilling.
    spill_bytes_max: int = 0
    # Gateway backends route a request to the node advertising the longest
    # matching prefix head (falling back to least-loaded).
    route_by_prefix: bool = True
    # Minimum matched prefix TOKENS before prefix-aware routing overrides
    # the least-loaded choice (sub-page matches are never worth a detour).
    min_shared_tokens: int = 0


@dataclasses.dataclass(frozen=True)
class DisaggConfig:
    """Disaggregated prefill/decode policy (``disagg/``, ``serving``'s
    ``DisaggBackend``): how the gateway ships prompts to the prefill pool
    and imports the returned KV planes into the local decode engine."""

    # Max bytes of KV payload per relay frame. The codec splits a session's
    # plane blob into ceil(total/kv_frame_bytes) frames so one transfer
    # never monopolizes the relay socket (and stays under any frame cap a
    # deployment configures on the hub).
    kv_frame_bytes: int = 4 * 1024 * 1024
    # End-to-end budget for one prefill+transfer round trip (request put ->
    # last KV frame). On expiry the gateway abandons the transfer and falls
    # back to local prefill — a slow pool degrades, never wedges.
    transfer_timeout_s: float = 30.0
    # How long submit() waits for a prefill-role node to appear in the
    # directory before falling back locally (0 = don't wait: an empty pool
    # falls back immediately).
    prefill_wait_s: float = 0.0
    # Degrade to local prefill on any transfer/admission failure. Disabled,
    # failures surface as terminal error events instead (strict mode for
    # capacity experiments where silent local prefill would skew numbers).
    fallback_local: bool = True
    # Prefill worker lease heartbeat period (seconds).
    heartbeat_s: float = 2.0
    # Directory lease TTL for fleet workers (seconds). A node whose
    # heartbeat lapses for this long drops out of ``alive()`` and is
    # treated as dead by the recovery gateway. Keep comfortably above
    # ``heartbeat_s`` (>= 2x) so one dropped heartbeat is not a death.
    lease_ttl_s: float = 6.0
    # Decode nodes export a session checkpoint (KV planes + RNG + token
    # tail via ``encode_session``) after the first token and then every
    # N engine ticks. Smaller = less replay work after a crash, more
    # transfer bytes during healthy decode. 0 disables periodic
    # checkpoints (first-token checkpoint still ships).
    checkpoint_interval_ticks: int = 8
    # How many times the gateway will migrate one stream to a new node
    # after decode-node deaths before failing the request.
    resume_max_attempts: int = 2
    # Deadline-aware shedding during recovery storms: a resume is shed
    # (terminal ``shed`` event, no migration) when the request's
    # remaining deadline budget is under ``shed_headroom_s`` multiplied
    # by the number of concurrently recovering requests.
    shed_headroom_s: float = 0.5
    # A stream with no frames for this long triggers a directory
    # liveness probe; the node must also be absent from ``alive()``
    # (lease expired) before it is declared dead. 0 derives the window
    # from ``lease_ttl_s``.
    dead_after_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Elastic fleet policy (``fleet/``): drain, rebalance, autoscale and
    the bytes-vs-latency cost model behind the query-move / page-ship /
    migrate placement decision.

    The cost-model fields are *seeds*: ``wire_bytes_per_s`` and
    ``prefill_s_per_token`` are refined online from measured transfers
    (EMA with ``cost_ema_alpha``); the rest stay as configured.
    """

    # --- drain -----------------------------------------------------------
    # How long ``FleetController.drain`` waits for the node's directory
    # load to reach zero (all in-flight sessions handed off) before
    # fencing anyway. Fencing a half-drained node is safe — the shipped
    # checkpoints re-home the stragglers through crash recovery — but
    # waiting lets the cheap path finish first.
    drain_timeout_s: float = 15.0
    # --- rebalance -------------------------------------------------------
    # Period of the controller's hot-node scan (seconds).
    rebalance_interval_s: float = 5.0
    # A decode node is "hot" when its heartbeat load exceeds this factor
    # times the pool's mean load (needs >= 2 live nodes to act).
    hot_load_factor: float = 2.0
    # Max sessions asked to migrate off a hot node per rebalance pass
    # (the node picks its longest-running routes first).
    rebalance_max_sessions: int = 2
    # --- autoscale -------------------------------------------------------
    # Period of the scale in/out evaluation (seconds).
    autoscale_interval_s: float = 1.0
    # Scale out when mean load per live decode node stays above this for
    # ``scale_hold_s``; scale in when it stays below ``scale_in_load``.
    scale_out_load: float = 3.0
    scale_in_load: float = 0.5
    scale_hold_s: float = 3.0
    # Pool size bounds the autoscaler respects (scale-in never drains
    # below ``min_nodes``; scale-out never spawns past ``max_nodes``).
    min_nodes: int = 1
    max_nodes: int = 8
    # --- cost model ------------------------------------------------------
    # Estimated KV bytes per cached prefix token (all layers, stored
    # form). Sizes the page-ship transfer in the cost comparison.
    kv_bytes_per_token: float = 4096.0
    # Seed estimate of node-to-node relay throughput; refined online
    # from measured page-ship round trips.
    wire_bytes_per_s: float = 1.0e9
    # Queueing penalty: seconds of extra latency per unit of directory
    # load difference when the query moves to the (busier) prefix holder.
    queue_s_per_load: float = 0.05
    # Seed estimate of recompute cost when neither the query nor the
    # pages move (plain migration: the target re-prefills the prefix);
    # refined online from observed prefill timings when available.
    prefill_s_per_token: float = 1.0e-3
    # Never page-ship prefixes whose estimated KV footprint exceeds this
    # (the transfer would monopolize the relay; migrate instead).
    page_ship_max_bytes: int = 64 * 1024 * 1024
    # EMA smoothing for the measured-rate updates (0 disables learning).
    cost_ema_alpha: float = 0.2


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Distributed request tracing + engine flight recorder
    (``utils/tracing.py``). A server constructed WITHOUT a TraceConfig
    has tracing fully off: no recorder is attached, no TraceContext is
    minted, frames carry no trace keys, and the engine's flight-recorder
    slot stays ``None`` — the decode tick pays one attribute load.
    """

    # Master switch. With a TraceConfig present but ``enabled`` False the
    # plumbing behaves exactly like the no-config case.
    enabled: bool = True
    # Fraction of requests minted a TraceContext at the gateway
    # ([0, 1]). Unsampled requests take the ``ctx is None`` fast path
    # everywhere — sampling is the production cost dial.
    trace_sample_rate: float = 1.0
    # Per-node SpanRecorder ring size. Eviction is counted
    # (``trace_spans_dropped``) and surfaced in /healthz — never silent.
    recorder_capacity: int = 100_000
    # Flight-recorder ring size: per-engine-tick records kept for
    # ``/debug/ticks``.
    ticks_capacity: int = 512
    # Per-node timeout for the ``trace.pull`` collector. A node that
    # misses it is dropped from the stitched trace (partial trace, with
    # ``trace_pull_failures`` counted) — collection never wedges.
    collect_timeout_s: float = 2.0
