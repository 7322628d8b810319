"""Mixture-of-experts MLP with expert-parallel sharding: routed experts,
optional shared experts, and the routing rule the config names.

The reference has no MoE layers — it only reuses hivemind's *moe.server*
machinery for serving scaffolding (SURVEY §2.3;
``/root/reference/distributed_llm_inference/server/backend.py:5``). MoE here is
a capability extension required for the Mixtral and DeepSeek-V2/V3 families.

Routing is ONE function (:func:`route`) whose rule the config chooses:
Mixtral's (softmax over ALL expert logits in fp32, top-k, renormalise) and
DeepSeek-V3's (sigmoid scores, selection by score plus a per-expert bias,
weights from the scores alone, normalised and scaled). Three
compute strategies sit behind it, all-static shapes; :func:`dispatch_path`
picks one from the dispatch's shape, and ``moe_mlp`` has no option for it:

* **dropless grouped dispatch** (wherever a dispatch's (token, pick) pairs
  fill the held experts' row tiles: a prefill, a chunk) — every
  token's own experts and no others. Pairs are stable-sorted by expert, each
  expert's group padded to ``ROW_TILE`` rows, and one Pallas kernel
  (:func:`grouped_matmul`, traced as ``moe_grouped_matmul``) runs gate, up
  and down over the row tiles, each against its expert's weight blocks
  (``moe_mlp_grouped``). No capacity and no dropped pair, so a token's
  result depends neither on its co-batched rows nor on where a chunk
  boundary falls. The whole path around the kernel is gathers (a scatter
  would serialize on TPU), and under a layer scan the kernel reads a
  layer's matrices out of the stack (:class:`LayerOf`: a slice a scan
  step would copy every held expert's weights before each call).
* **the live path** (a dispatch whose tokens fit ONE row tile: every decode
  step, a verify step or a bucket of a few tokens a row) — the experts that
  a LIVE row picked and no others (``moe_mlp_live``). A step of 4–32 rows
  is bound by READING expert weights, and most of what dense-combine reads
  there is for rows nobody decodes: 4 live rows x 2 picks over 8 experts
  touch 5.5 of them. The same kernel runs with one tile an expert, every
  tile the dispatch's rows, the held experts ordered live first: a tile
  past the live count fetches and computes nothing. A dead row (a stopped
  or empty slot: ``valid`` false) picks nothing. Around the three calls
  there is no sort of pairs, no gather and no permutation: the routing, one
  ``argsort`` of a ``[held]`` bool and the ``[N, held]`` combine of
  dense-combine over the tiles that were written. The stacks ride whole
  here too. Measured on a v5e (``tools/profile_grouped_moe.py
  --decode-rows``): the kernel streams a 16- or 32-row tile at 700–750 GB/s,
  dense-combine's fusions at 733–749, so with every expert live the path
  costs what dense-combine does (-0.3% at Mixtral's widths, +0.3–0.5% at a
  share of 16 of 6144 x 2048, +2.5% extrapolated at 64 of 2048 x 1408,
  where 32 rows leave two experts dead and it is 0.6% faster) and the rule
  is the shape alone.
* **dense-combine** (what lies between the two: a narrow bucket, a wide
  verify; and any dispatch under a mesh) — every held expert processes
  every token and a ``[B, S, held]`` combine matrix (zero off the top-k)
  weights the outputs. With experts sharded over ``ep`` the combine
  contraction becomes a ``psum`` XLA inserts automatically, where the
  kernel's expert-indexed reads and the grouped path's gathers trip GSPMD.

Shared experts (``p["ws_g"]``/``ws_u``/``ws_d``, present where the config
has them) are one SwiGLU MLP every token passes through, added to the routed
sum under the scope ``moe_shared``.

Two counts, not one. ``ModelConfig.num_experts`` is the ROUTER's width: every
token is scored over all of them and picks its ``k`` among all of them.
``ModelConfig.num_held_experts`` is how many expert matrices this program
holds (``we_*`` are ``[held, ...]``): all of them, or one of
``expert_shares`` contiguous shares (experts ``first_held_expert ..
first_held_expert + held``), as one chip of an expert-parallel deployment
holds. A layer with a share computes ITS experts' part of the routed sum
(the combine matrix keeps the held columns; a pick that lives elsewhere adds
nothing here) plus the shared expert, and that partial result goes on.
Nothing here stands in for the absent experts or for an exchange.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax._src import mesh as _mesh_lib
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..config import ModelConfig
from . import quant

__all__ = [
    "moe_mlp", "route", "router_weights", "expert_rows_per_token",
    "dispatch_path", "traced_path", "grouped_matmul", "LayerOf",
    "GROUPED_STACKS", "KERNEL_PATHS", "expected_live_experts",
]

class LayerOf(NamedTuple):
    """One layer's expert matrices, VIEWED out of the layer-stacked tensor
    (``stack``: ``[L, E, K, N]`` or its int8 :class:`QuantizedTensor`;
    ``index``: the layer, an int32 scalar of the layer scan). Slicing the
    stack a scan step would copy every held expert's weights through HBM
    before each kernel call, which is most of what the grouped dispatch
    saves; :func:`grouped_matmul` names the layer in its block index maps
    instead (``models/llama.py:_split_whole_stacks``, as for the int4
    stacks)."""

    stack: Any
    index: Any


# The grouped dispatch pads each expert's group of rows to this many (one
# MXU pass a tile) and reads its weights in blocks of at most
# ``WEIGHT_BLOCK`` ``(in, out)`` channels. Measured on a v5e at the four
# routed configurations' widths (``tools/profile_grouped_moe.py``): a
# 256-row tile pads a 16- or 64-expert layer's groups past what the dense
# combine costs, and ``(2048, 2048)`` blocks read 2–7% better than
# ``(2048, 1024)``.
ROW_TILE = 128
WEIGHT_BLOCK = (2048, 2048)
# The live path pads its dispatch's tokens, its one tile, to a multiple of
# this many rows (bf16's sublane tile).
LIVE_ROWS = 16
# The leaves of a routed layer that :func:`grouped_matmul` reads: the ones
# a layer scan hands over whole, as :class:`LayerOf` views, where the
# dispatch takes one of the paths that run the kernel.
GROUPED_STACKS = ("we_g", "we_u", "we_d")
KERNEL_PATHS = ("grouped", "live")


def route(cfg: ModelConfig, x: jnp.ndarray, router: jnp.ndarray, bias=None):
    """The routing rule, as the config states it. ``x``: ``[..., H]``;
    ``router``: ``[H, E]``; ``bias``: ``[E]`` or None. Returns ``(weights
    [..., k] fp32, experts [..., k] int32)``.

    Scores over ALL experts in fp32: softmax (Mixtral, DeepSeek-V2) or
    sigmoid (DeepSeek-V3 ``MoEGate``). Selection: the ``k`` largest of the
    scores, or of scores PLUS ``bias`` where the checkpoint carries one
    (``e_score_correction_bias``, ``topk_method`` "noaux_tc"; groups of one
    make the published group step the identity). The bias chooses and is
    never weighed: the weights are the selected SCORES, divided by their
    sum (``moe_norm_topk``), times ``moe_routed_scale``.
    """
    k = cfg.num_experts_per_tok
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top_w, top_i = jax.lax.top_k(scores, k)
    else:
        _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.moe_norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    if cfg.moe_routed_scale != 1.0:
        top_w = top_w * cfg.moe_routed_scale
    return top_w, top_i


def router_weights(
    cfg: ModelConfig, x: jnp.ndarray, router: jnp.ndarray, bias=None,
    valid=None,
) -> jnp.ndarray:
    """:func:`route` as the dense combine matrix.

    ``x``: ``[B, S, H]``; ``router``: ``[H, E]``, E the router's width.
    Returns ``[B, S, held]``: a token's routing weights at those of its
    selected experts that are held here, 0 elsewhere (``held`` = E unless
    the layer holds a share; a pick outside the share matches no column).
    A token whose ``valid`` (``[B, S]`` bool) is false picks nothing: its
    row is zero whatever its hidden state holds.
    """
    return _combine_matrix(cfg, x, router, bias, valid)[0]


def _combine_matrix(cfg: ModelConfig, x, router, bias=None, valid=None):
    """``(combine [B, S, held] fp32, picked [held] bool)``: the matrix of
    :func:`router_weights`, and which held experts some valid token
    selected."""
    held = cfg.num_held_experts
    with jax.named_scope("moe_router"):
        top_p, top_i = route(cfg, x, router, bias)
        if cfg.expert_shares > 1:
            top_i = top_i - cfg.first_held_expert
        if valid is not None:
            # select, never multiply: a dead row's scores may be anything
            top_p = jnp.where(valid[..., None], top_p, 0)
            top_i = jnp.where(valid[..., None], top_i, held)
        # a pick past the held columns (another share's, a dead row's)
        # matches none
        one_hot = jax.nn.one_hot(top_i, held, dtype=jnp.float32)
        combine = jnp.einsum("bsk,bske->bse", top_p, one_hot)
        return combine, jnp.any(one_hot > 0, axis=(0, 1, 2))


def under_mesh() -> bool:
    """Whether the program being traced runs sharded: inside a mesh context
    of more than one device (the engine's ``with mesh:`` around every step
    of a sharded deployment). Read at trace time, as the compiler reads
    it; ``with mesh:`` has no public reader in jax 0.9 (``jax.sharding.
    get_abstract_mesh`` sees ``use_mesh`` only)."""
    return _mesh_lib.thread_resources.env.physical_mesh.size > 1


def dispatch_path(
    cfg: ModelConfig, rows: int, seq_len: int, sharded: bool = False
) -> str:
    """The compute strategy of a dispatch of ``rows x seq_len`` tokens:
    ``"grouped"``, ``"live"`` or ``"dense"`` (module docstring). A static
    function of the shape, the config and whether a mesh shards the program.

    Grouped where the pairs each expert EXPECTS fill a row tile, ``rows x
    seq_len x k / E >= ROW_TILE``: below that most of a tile is padding and
    the weight reads lead. Live where the dispatch's tokens all fit ONE
    row tile, ``rows x seq_len <= ROW_TILE``: every decode step and the
    verify steps and buckets of a few tokens a row (the two never meet:
    ``E > k``). What lies between (a narrow bucket, a wide verify) is
    dense, and so is a ``sharded`` program whatever its shape: the
    kernel's expert-indexed reads and the grouped path's gathers trip
    GSPMD under ``ep``/``tp``.
    """
    tokens = rows * seq_len
    if not sharded:
        if tokens * cfg.num_experts_per_tok >= cfg.num_experts * ROW_TILE:
            return "grouped"
        if tokens <= ROW_TILE:
            return "live"
    return "dense"


def traced_path(cfg: ModelConfig, x: jnp.ndarray) -> str:
    """:func:`dispatch_path` of a dispatch of ``x`` (``[B, S, H]``) as the
    program being traced sees it: what ``moe_mlp`` will take, and what a
    layer scan asks before it hands the expert stacks over."""
    return dispatch_path(cfg, x.shape[0], x.shape[1], under_mesh())


def expected_live_experts(cfg: ModelConfig, tokens: float) -> float:
    """The held experts that ``tokens`` valid tokens are EXPECTED to pick
    between them under uniform routing: each misses a given expert with
    probability ``1 - k / E``, so ``held x (1 - (1 - k / E)^tokens)``.
    Which they pick is data the host does not see."""
    miss = 1.0 - cfg.num_experts_per_tok / cfg.num_experts
    return cfg.num_held_experts * (1.0 - miss ** tokens)


def expert_rows_per_token(
    cfg: ModelConfig,
    seq_len: int,
    rows: int = 1,
    valid_share: float = 1.0,
    sharded: bool = False,
):
    """``(needed, computed)``: expert MLPs one token's result needs in one
    expert layer (its selected experts and the shared ones) and how many
    the program runs a PADDED token of a dispatch of ``rows x seq_len``
    tokens of which ``valid_share`` are real, by the path
    :func:`dispatch_path` takes there: every routed HELD expert under
    dense-combine; under the grouped dispatch the valid tokens' own picks
    plus half a row tile an expert, what padding each group to whole tiles
    costs on average; under
    the live path the experts the dispatch's valid tokens are expected to
    pick between them (:func:`expected_live_experts`: every row of the
    one tile passes through each of them). The
    census behind ``moe_expert_rows_*``. Where the layer holds a share,
    ``needed`` is an EXPECTATION: of a token's ``k`` picks over the
    router's ``E``, ``k * held / E`` fall here on average (uniform
    routing); which do is data the host does not see, and so is how full
    a group's last tile is and which experts a step's rows pick."""
    shared = cfg.num_shared_experts
    k = cfg.num_experts_per_tok
    held = cfg.num_held_experts
    if cfg.expert_shares > 1:
        k = k * held / cfg.num_experts
    path = dispatch_path(cfg, rows, seq_len, sharded)
    if path == "grouped":
        tile_pad = held * ROW_TILE / 2 / (rows * seq_len)
        return k + shared, k * valid_share + tile_pad + shared
    if path == "live":
        live = expected_live_experts(cfg, rows * seq_len * valid_share)
        return k + shared, live + shared
    return k + shared, held + shared


# Dense-combine runs a dispatch's tokens whole up to this many a row (every
# cell before PR 32 pads to 2048 or fewer: their programs are as they were),
# and in blocks of ``DENSE_COMBINE_BLOCK`` past it. Since the grouped
# dispatch takes the wide prefills, what still walks is a dispatch wider
# than 2048 under a mesh, or one whose pairs do not fill its router's
# tiles (:func:`dispatch_path`).
DENSE_COMBINE_TOKENS = 2048
DENSE_COMBINE_BLOCK = 1024


def _shared_experts(p, x: jnp.ndarray):
    """The shared experts: one SwiGLU MLP over every token."""
    with jax.named_scope("moe_shared"):
        return quant.matmul(
            jax.nn.silu(quant.matmul(x, p["ws_g"])) * quant.matmul(x, p["ws_u"]),
            p["ws_d"],
        )


def moe_mlp(
    cfg: ModelConfig,
    p,
    x: jnp.ndarray,
    valid=None,
) -> jnp.ndarray:
    """SwiGLU expert MLPs + weighted combine.

    ``p["router"]``: ``[H, E]``, E the router's width; ``p["we_g"]`` /
    ``p["we_u"]``: ``[held, H, F]``; ``p["we_d"]``: ``[held, F, H]`` (held =
    E, or this program's share of E: the module docstring; the expert axis
    shardable over ``ep``, F over ``tp``);
    ``p["router_bias"]`` ``[E]`` and the shared experts' ``p["ws_*"]`` where
    the model has them (:func:`route`, :func:`_shared_experts`).

    The path is :func:`dispatch_path`'s, from ``x``'s shape and whether the
    trace runs under a mesh; there is no option. A prefill-scale dispatch
    takes the dropless grouped dispatch (:func:`moe_mlp_grouped`): each
    token's own experts, exact, and independent of co-batched rows and of
    chunk boundaries, which is what lets it be the default. A dispatch
    whose tokens fit one row tile (a decode or verify step) takes the live
    path (:func:`moe_mlp_live`): dense-combine's sum over the experts a
    valid token picked, whose weights are the only ones read, at the
    grouped kernel's precision. What lies between and sharded programs
    keep dense-combine, the latter bit for bit as before.
    ``valid`` (``[B, S]`` bool) marks real tokens: bucket padding and a
    decode step's dead rows take no row of an expert in the grouped
    path and make no expert live; dense-combine computes them like any
    other (their results are read by nobody).
    """
    path = traced_path(cfg, x)
    if path == "grouped":
        out = moe_mlp_grouped(cfg, p, x, valid)
    elif path == "live":
        out = moe_mlp_live(cfg, p, x, valid)
    elif x.shape[1] > DENSE_COMBINE_TOKENS and x.shape[1] % DENSE_COMBINE_BLOCK == 0:
        # A wide dispatch (a 4096-wide chunk over 128 experts: ``[b, s, E,
        # H]`` alone is 2.1 GB in bf16) walks its tokens a block at a
        # time. A token's result does not depend on its neighbours, so the
        # numbers are the whole dispatch's.
        b, s, h = x.shape
        blocks = jnp.moveaxis(
            x.reshape(b, s // DENSE_COMBINE_BLOCK, DENSE_COMBINE_BLOCK, h), 1, 0
        )
        routed = {k: v for k, v in p.items() if not k.startswith("ws_")}
        out = jax.lax.map(lambda xb: _dense_combine(cfg, routed, xb), blocks)
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, h)
    else:
        out = _dense_combine(cfg, p, x)
    if "ws_g" in p:
        out = out + _shared_experts(p, x)
    return out


def _dense_combine(cfg: ModelConfig, p, x: jnp.ndarray) -> jnp.ndarray:
    """Every held expert over every token, weighted by the combine matrix."""
    combine = router_weights(
        cfg, x, p["router"], p.get("router_bias")
    ).astype(x.dtype)
    with jax.named_scope("moe_experts"):
        t = quant.einsum("bsh,ehf->bsef", x, p["we_g"])
        u = quant.einsum("bsh,ehf->bsef", x, p["we_u"])
        y = quant.einsum("bsef,efh->bseh", jax.nn.silu(t) * u, p["we_d"])
    with jax.named_scope("moe_combine"):
        return jnp.einsum("bse,bseh->bsh", combine, y)


def _routed_pairs(cfg: ModelConfig, p, xf: jnp.ndarray, valid):
    """Route ``xf`` (``[N, H]``) and flatten to its ``N * k`` (token, pick)
    pairs, pair ``j`` of token ``j // k``. Returns ``(weights [N, k] fp32,
    pair_e [N * k])``: a pair's expert as an index into the HELD stack, or
    the sentinel ``held`` where the pair is PARKED, its weight zeroed: the
    token is bucket padding (``valid`` false), or the pick lives in another
    share. A stable sort by ``pair_e`` leaves the parked pairs behind every
    real expert's group."""
    e, k = cfg.num_held_experts, cfg.num_experts_per_tok
    with jax.named_scope("moe_router"):
        top_p, top_i = route(cfg, xf, p["router"], p.get("router_bias"))
    here = None
    if cfg.expert_shares > 1:
        top_i = top_i - cfg.first_held_expert
        here = (top_i >= 0) & (top_i < e)
    if valid is not None:
        vf = jnp.broadcast_to(valid.reshape(-1, 1), top_i.shape)
        here = vf if here is None else here & vf
    if here is not None:
        top_i = jnp.where(here, top_i, e)
        top_p = top_p * here.astype(top_p.dtype)
    return top_p, top_i.reshape(-1)


def _block(dim: int, cap: int) -> int:
    """The widest block of ``dim`` channels within ``cap``: all of them, or
    their largest divisor in whole 128-lane tiles."""
    if dim <= cap:
        return dim
    for b in range(cap - cap % 128, 0, -128):
        if dim % b == 0:
            return b
    return dim


def _grouped_kernel(te_ref, at_ref, x_ref, w_ref, *rest, n_k, quantized):
    """One (out block, row tile, in block) step of :func:`grouped_matmul`.

    ``te_ref`` (a tile's expert) and ``at_ref`` (``[2]``: the tiles that
    hold a group's rows, and the layer) are in SMEM and already resolved
    the blocks: ``x_ref`` ``[tile, bk]`` of the tile's rows, ``w_ref``
    ``[1, 1, bk, bn]`` of its expert's weights in its layer, ``s_ref``
    ``[1, 1, bn]`` their f32 scales (int8 weights only), ``o_ref``
    ``[tile, bn]``, ``acc_ref`` f32. A step of a tile past the live ones
    names the blocks of the step before it, so nothing is fetched, and
    computes nothing."""
    if quantized:
        s_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    kk = pl.program_id(2)

    @pl.when(pl.program_id(1) < at_ref[0])
    def _live():
        @pl.when(kk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        acc_ref[...] += jnp.dot(
            x, w_ref[0, 0].astype(x.dtype), preferred_element_type=jnp.float32
        )

        @pl.when(kk == n_k - 1)
        def _store():
            acc = acc_ref[...]
            if quantized:
                acc = acc * s_ref[0]
            o_ref[...] = acc.astype(o_ref.dtype)


def grouped_matmul(
    x: jnp.ndarray,
    w,
    tile_expert: jnp.ndarray,
    live_tiles: jnp.ndarray,
    row_tile: int,
    blocks=None,
    interpret=None,
) -> jnp.ndarray:
    """Rows grouped by expert times their expert's matrix, ragged.

    ``x``: ``[M, K]``, ``M`` whole ``row_tile``s, every tile's rows ONE
    expert's, or ONE tile of rows that every tile shares (``M ==
    row_tile``: the live path's gate and up, each expert over the same
    tokens); ``w``: ``[E, K, N]`` or its int8 :class:`QuantizedTensor`,
    or a :class:`LayerOf` the layer-stacked form of either;
    ``tile_expert``: ``[tiles]`` int32, a tile's expert;
    ``live_tiles``: int32 scalar, the leading tiles that hold rows.
    Returns ``[tiles * row_tile, N]`` in ``x``'s dtype; rows of a tile past
    the live ones are NOT written (the caller reads none of them).

    The grid is (out blocks, row tiles, in blocks), the weight block of the
    tile's expert (and of the view's layer) chosen from the prefetched
    scalars. int8 weights are read as int8 and converted in VMEM,
    activations stay in ``x``'s dtype, the accumulator is f32 and takes
    the per-(expert, out channel) scale once, at the end:
    ``quant.einsum``'s precision, with the scale applied before the
    rounding to ``x``'s dtype and not after it.

    ``interpret``: None runs the kernel on a TPU and, on any other
    backend, the same product in plain XLA (:func:`_grouped_reference`:
    every routed model's decode step reaches this function, and the
    interpreted kernel costs a CPU program about a second of lowering and
    compiling a call); True interprets the kernel there (the tests of the
    kernel itself), False compiles it.
    """
    blocks = blocks or WEIGHT_BLOCK
    layer = 0
    if isinstance(w, LayerOf):
        w, layer = w
    quantized = isinstance(w, quant.QuantizedTensor)
    wq, scale = (w.q, w.scale) if quantized else (w, None)
    if wq.ndim == 3:
        wq = wq[None]       # one layer's own matrices: a stack of one
    elif quantized:
        # the scales are small: a layer's ride as a slice
        scale = jax.lax.dynamic_index_in_dim(scale, layer, 0, keepdims=False)
    if interpret is None and jax.default_backend() != "tpu":
        return _grouped_reference(
            x, wq, scale, layer, tile_expert, live_tiles, row_tile
        )
    m, k_dim = x.shape
    _, e, _, n_dim = wq.shape
    bk, bn = _block(k_dim, blocks[0]), _block(n_dim, blocks[1])
    n_k, n_n, tiles = k_dim // bk, n_dim // bn, tile_expert.shape[0]
    shared_rows = m == row_tile

    def tile(t, at):
        # the tile whose blocks a step names: itself, or the last live one
        return jnp.maximum(jnp.minimum(t, at[0] - 1), 0)

    def k_block(t, kk, at):
        return jnp.where(t < at[0], kk, n_k - 1)

    in_specs = [
        pl.BlockSpec(
            (row_tile, bk),
            lambda nn, t, kk, te, at: (
                0 if shared_rows else tile(t, at), k_block(t, kk, at)
            ),
        ),
        pl.BlockSpec(
            (1, 1, bk, bn),
            lambda nn, t, kk, te, at: (
                at[1], te[tile(t, at)], k_block(t, kk, at), nn
            ),
        ),
    ]
    operands = [x, wq]
    if quantized:
        in_specs.append(pl.BlockSpec(
            (1, 1, bn), lambda nn, t, kk, te, at: (te[tile(t, at)], 0, nn)
        ))
        operands.append(scale.astype(jnp.float32).reshape(e, 1, n_dim))
    return pl.pallas_call(
        functools.partial(_grouped_kernel, n_k=n_k, quantized=quantized),
        name="moe_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_n, tiles, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (row_tile, bn), lambda nn, t, kk, te, at: (tile(t, at), nn)
            ),
            scratch_shapes=[pltpu.VMEM((row_tile, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * row_tile, n_dim), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        tile_expert.astype(jnp.int32),
        jnp.stack([
            jnp.asarray(live_tiles, jnp.int32), jnp.asarray(layer, jnp.int32)
        ]),
        *operands,
    )


def _grouped_reference(x, wq, scale, layer, tile_expert, live_tiles, row_tile):
    """:func:`grouped_matmul` in plain XLA, for a backend the kernel does
    not compile for: each tile's rows times its expert's matrix of the
    layer, at the kernel's precision (an f32 sum, the scale before the
    rounding). The rows of a tile past the live ones, which the kernel
    leaves unwritten, are NaN here: a caller that multiplies where it
    should select shows."""
    tiles = tile_expert.shape[0]
    with jax.named_scope("moe_grouped_matmul"):
        w = jax.lax.dynamic_index_in_dim(wq, layer, 0, keepdims=False)
        rows = jnp.broadcast_to(
            x.reshape(-1, row_tile, x.shape[-1]),
            (tiles, row_tile, x.shape[-1]),
        )
        acc = jnp.einsum(
            "trk,tkn->trn", rows, w[tile_expert].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        if scale is not None:
            acc = acc * scale[tile_expert].astype(jnp.float32)[:, None, :]
        written = jnp.arange(tiles, dtype=jnp.int32) < live_tiles
        out = jnp.where(written[:, None, None], acc, jnp.nan).astype(x.dtype)
        return out.reshape(tiles * row_tile, -1)


def moe_mlp_grouped(
    cfg: ModelConfig,
    p,
    x: jnp.ndarray,
    valid=None,
    row_tile=None,
    blocks=None,
) -> jnp.ndarray:
    """Dropless grouped dispatch: the routed sum of :func:`moe_mlp`, each
    token through its own held experts and no others.

    The ``N * k`` pairs (:func:`_routed_pairs`) are stable-sorted by
    expert; each expert's group takes whole ``row_tile``-row tiles of a
    ``[M, H]`` buffer that its tokens' rows are gathered into once, ``M`` the
    static worst case ``N * k + held * (row_tile - 1)`` in whole tiles;
    gate, up and down are three calls of :func:`grouped_matmul` over it,
    which skip the tiles behind the last group (the parked pairs sit
    there and cost nothing); undoing the sort is a gather, and the combine a
    dense ``[N, k]`` weighted sum in f32. No capacity: every pair that is
    not parked is computed. Gather-only (a scatter lowers to a serial row
    loop on TPU and trips GSPMD — see cache/dense.py).
    """
    b, s, h = x.shape
    e, k = cfg.num_held_experts, cfg.num_experts_per_tok
    n = b * s
    row_tile = row_tile or ROW_TILE
    xf = x.reshape(n, h)
    top_p, pair_e = _routed_pairs(cfg, p, xf, valid)

    with jax.named_scope("moe_sort"):
        order = jnp.argsort(pair_e, stable=True)
        # e + 1 bounds: the parked pairs sit past EVERY group's end.
        bounds = jnp.searchsorted(
            pair_e[order], jnp.arange(e + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)
        group_start, count = bounds[:e], bounds[1:] - bounds[:e]
        group_tiles = (count + row_tile - 1) // row_tile
        tile_end = jnp.cumsum(group_tiles)
        first_row = (tile_end - group_tiles) * row_tile   # [e], in the buffer
        live_tiles = tile_end[e - 1]

        tiles = -(-(n * k + e * (row_tile - 1)) // row_tile)
        tile_expert = jnp.minimum(
            jnp.searchsorted(
                tile_end, jnp.arange(tiles, dtype=jnp.int32), side="right"
            ),
            e - 1,
        ).astype(jnp.int32)
        # Row r of the buffer holds the pair at sorted position group_start
        # + (r - first_row) of its tile's expert; past the group's count it
        # is padding (any token's row: its result is read by nobody).
        row_e = jnp.repeat(tile_expert, row_tile)
        row = jnp.arange(tiles * row_tile, dtype=jnp.int32)
        src = group_start[row_e] + row - first_row[row_e]
        row_tok = order[jnp.clip(src, 0, n * k - 1)] // k
        gathered = xf[row_tok]

    mm = functools.partial(
        grouped_matmul, tile_expert=tile_expert, live_tiles=live_tiles,
        row_tile=row_tile, blocks=blocks,
    )
    with jax.named_scope("moe_experts"):
        t = mm(gathered, p["we_g"])
        u = mm(gathered, p["we_u"])
        y = mm(jax.nn.silu(t) * u, p["we_d"])

    # Back to pair order (a gather: the inverse permutation says where in
    # the sorted order, hence in the buffer, a pair's row is), then a dense
    # [N, k] weighted combine. A parked pair's row was never written:
    # select, do not multiply.
    with jax.named_scope("moe_combine"):
        held = pair_e < e
        pe = jnp.minimum(pair_e, e - 1)
        rank = jnp.argsort(order).astype(jnp.int32)     # place in the sort
        pair_row = first_row[pe] + rank - group_start[pe]
        pair_out = jnp.where(
            held[:, None], y[jnp.where(held, pair_row, 0)], 0
        ).reshape(n, k, h)
        out = jnp.einsum(
            "nk,nkh->nh", top_p.astype(jnp.float32),
            pair_out.astype(jnp.float32),
        )
        return out.reshape(b, s, h).astype(x.dtype)


def moe_mlp_live(
    cfg: ModelConfig, p, x: jnp.ndarray, valid=None, blocks=None
) -> jnp.ndarray:
    """The routed sum of :func:`moe_mlp` for a dispatch whose tokens fit
    one row tile (a decode or verify step): dense-combine restricted to
    the experts that a VALID token picked, whose weights are the only ones
    read.

    The tokens ride as one tile of ``LIVE_ROWS``-padded rows, a dead row
    (``valid`` false: a stopped or empty slot, bucket padding) zeroed and
    picking nothing. The held experts are ordered live first (one stable
    ``argsort`` of a ``[held]`` bool) and gate, up and down are three calls
    of :func:`grouped_matmul` with one tile an expert in that order, every
    tile the dispatch's rows: a tile past the live count fetches and
    computes nothing. The combine is the ``[N, held]`` matrix of
    :func:`router_weights`, its columns in the same order, over the tiles
    that were written (selected, never multiplied: an unwritten tile holds
    anything). No pair is sorted, no row gathered, nothing permuted back;
    the precision is the grouped dispatch's (int8 read and converted in
    VMEM, an f32 accumulator, the scale before the rounding, the combine
    in f32). Every row dead: zeros.
    """
    b, s, h = x.shape
    e, n = cfg.num_held_experts, b * s
    tile = -(-n // LIVE_ROWS) * LIVE_ROWS
    combine, picked = _combine_matrix(
        cfg, x, p["router"], p.get("router_bias"), valid
    )
    with jax.named_scope("moe_sort"):
        order = jnp.argsort(~picked, stable=True).astype(jnp.int32)
        live = jnp.sum(picked, dtype=jnp.int32)
        xf = x.reshape(n, h)
        if valid is not None:
            xf = jnp.where(valid.reshape(n, 1), xf, 0)
        xf = jnp.pad(xf, ((0, tile - n), (0, 0)))
    mm = functools.partial(
        grouped_matmul, tile_expert=order, live_tiles=live, row_tile=tile,
        blocks=blocks,
    )
    with jax.named_scope("moe_experts"):
        t = mm(xf, p["we_g"])
        u = mm(xf, p["we_u"])
        y = mm(jax.nn.silu(t) * u, p["we_d"])
    with jax.named_scope("moe_combine"):
        written = jnp.arange(e, dtype=jnp.int32) < live
        y = jnp.where(
            written[:, None, None], y.reshape(e, tile, h)[:, :n], 0
        ).astype(jnp.float32)
        out = jnp.einsum("ne,enh->nh", combine.reshape(n, e)[:, order], y)
        return out.reshape(b, s, h).astype(x.dtype)
