"""Inference engine: bucketed prefill + single compiled decode step over the
active batch, with a continuous-batching scheduler.

This is the TPU-native replacement for the serving machinery the reference
delegated to hivemind and never finished: the batching role of its
``TaskPool(self.forward, …)``
(``/root/reference/distributed_llm_inference/server/backend.py:42``) and the
per-``generation_id`` multi-tenancy of its cache (``models/llama/cache.py:14-19``)
become: sessions pinned to batch rows of ONE preallocated cache, admitted and
evicted between steps, with every device computation a cached ``jax.jit``
executable (the role CUDA-graph capture plays in the reference,
``utils/cuda.py:6`` — XLA compilation *is* the graph; bucketing keeps the
executable count finite).

Step anatomy (host orchestrates, device computes):
  1. admit — move waiting sessions into free slots (pages allocated for paged
     caches), run bucketed single-row prefill(s), sample the first token.
  2. decode — one jitted step over all slots; inactive rows carry
     ``active=0`` and are masked throughout.
  3. retire — EOS / length / capacity sessions leave their slots; pages freed.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import math
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cache.base import window_ladder
from ..cache.dense import DenseKVCache, QuantizedDenseKVCache
from ..cache.latent import (
    LatentPagedKVCache, QuantizedLatentPagedKVCache,
    indexed_latent_cache_class,
)
from ..cache.paged import (
    PageAllocator, PagedKVCache, QuantizedPagedKVCache, indexed_cache_class,
    two_pool_cache_class, window_pages_bound,
)
from ..cache.retention import retention_cache_class
from ..cache.sink import QuantizedSinkKVCache, SinkKVCache

# Cache kinds implementing the StreamingLLM sink-window policy (unbounded
# streams, fixed memory): scheduler paths that special-case the sink ring
# must cover both the bf16 and the int8/kernel variants.
_SINK_KINDS = (SinkKVCache, QuantizedSinkKVCache)
from ..config import CacheConfig, EngineConfig, ModelConfig, PrefixConfig
from ..models import llama
from ..ops.ragged_attention import _block_q
from ..utils.metrics import Metrics
from ..utils.tracing import BOOT, FlightRecorder, Span
from .plan import AttentionPlan
from .sampling import SamplingOptions, SamplingParams, sample
from .session import Session, SessionState

# ``_region`` with the flight recorder off: one shared, re-entrant no-op.
_NO_REGION = contextlib.nullcontext()
#: the step programs whose dispatches ``plan.note_dispatch`` records: what
#: the dispatch clock wraps while it is armed (``_clock_turned``)
_CLOCKED_PROGRAMS = (
    "_prefill", "_prefill_fresh", "_prefill_ns", "_prefill_batch",
    "_prefill_batch_standalone", "_decode", "_decode_k",
)
#: the spans under a traced session's ``engine.first_token``, in the order
#: of ``DispatchClock.first_token``'s pieces
_FIRST_TOKEN_SPANS = (
    "engine.prefill_wait", "engine.prefill_own", "engine.first_token_deliver"
)

# Seconds without a resident session before ``_shrink_if_idle`` gives the
# cache's high-water shape back. Every (old, new) shape on the way back up
# is an executable of its own: 0.6 s each to load from the compile cache
# and 8-10 s to compile (chip runs, PERF.md §6, PR 24), where keeping a
# paged table at its widest cost the requests that come next a tenth of a
# decode step at most (the scale rows' gather, 1.3 of 12.4 ms at 47 slots;
# since PR 61 the in-place sweep copies a live page's scale rows itself and
# a wide table costs a decode step nothing) and an idle engine nothing. So a
# pause between two requests must not
# shrink, and 30 s outlasts 95% of the gaps of arrivals as sparse as one
# in ten seconds. Not swept; a constant, not an option.
IDLE_SHRINK_S = 30.0

# Rows of one batched admission dispatch (``_prefill_group``).
GROUP_ROWS = 8

# Deferred prefill programs in flight at once under overlapped admission
# (``_overlap_ok``): a flood past it spills to the synchronous path, so the
# device's queue of prefill work stays bounded. Not swept on the chip.
OVERLAP_MAX_INFLIGHT = 4


def window_pool_pages(window: int, page_size: int, batch: int,
                      chunk_tokens: int, decode_tokens: int) -> int:
    """Pages of the WINDOW pool of a stack of window and full layers: the
    null page, what every row holds while it decodes (its window and the
    ``decode_tokens`` a dispatch and the one in flight may write), and what
    the rows of ONE prefill dispatch hold while it is assembled (a chunk of
    ``chunk_tokens`` a row, ``GROUP_ROWS`` rows at most), in whole 128s. It
    does not grow with the context: that is the pool's point. Not an
    option: the window, the batch and the chunk size it."""
    rows = batch * window_pages_bound(window, page_size, decode_tokens)
    burst = min(batch, GROUP_ROWS) * window_pages_bound(
        window, page_size, chunk_tokens
    )
    return -(-(1 + rows + burst) // 128) * 128


class InferenceEngine:
    """Single-host continuous-batching engine over one model replica.

    ``attention_fn`` lets callers swap the XLA attention for a Pallas kernel;
    ``model_fns`` hooks other model families (Mistral = Llama + sliding
    window; see ``models/registry.py``).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        engine_cfg: Optional[EngineConfig] = None,
        cache_cfg: Optional[CacheConfig] = None,
        rng: Optional[jax.Array] = None,
        attention_fn=None,
        mesh_cfg=None,
        draft=None,
        prefix_cfg=None,
        trace_cfg=None,
    ):
        """``mesh_cfg`` (a :class:`MeshConfig`) serves one sharded deployment
        of the model: tp/ep shard within a replica, dp shards batch rows, and
        pp>1 runs the GPipe-staged pipeline program per batched step; the
        scheduler is untouched either way.

        ``draft = (draft_cfg, draft_params)`` enables speculative decoding
        for sessions that opt in via ``SamplingOptions.speculative`` (greedy
        rows only): the draft proposes ``EngineConfig.speculative_k`` tokens
        and the target verifies them in ONE forward, with speculative and
        normal sessions sharing that same batched step (normal rows run it
        as a plain 1-token decode via per-row ``num_new`` masking). Output
        is identical to non-speculative greedy decoding."""
        self.cfg = cfg
        self._mesh_cfg = mesh_cfg
        self.ecfg = engine_cfg or EngineConfig()
        if self.ecfg.quantization in ("int8", "int4", "int8_outlier"):
            from ..ops.quant import quantize_params

            qkw = {}
            if self.ecfg.quantization == "int8_outlier":
                # LLM.int8()-inspired decomposition: fp input channels per
                # projection ride a side matmul. APPROXIMATES (does not yet
                # reproduce) bitsandbytes threshold=5.0 — channel choice is
                # steered by calibration activation absmax when
                # EngineConfig.act_scales is provided, else by weight-row
                # energy as a proxy.
                qkw["outlier_channels"] = self.ecfg.outlier_channels
                if self.ecfg.act_scales is not None:
                    qkw["act_scales"] = self.ecfg.act_scales
            if self.ecfg.quantization == "int4":
                # Unsharded (or dp/ep-only) serving decodes through the
                # Pallas half-split kernel; tp/pp meshes keep the grouped
                # XLA layout (the packed channel order doesn't column-shard),
                # with group counts divisible by tp (whole groups per device).
                solo = mesh_cfg is None or (
                    mesh_cfg.tp == 1 and mesh_cfg.pp == 1
                )
                qkw["int4_layout"] = "split" if solo else "grouped"
                if not solo:
                    qkw["group_multiple"] = mesh_cfg.tp
            params = quantize_params(
                params, bits=4 if self.ecfg.quantization == "int4" else 8,
                **qkw,
            )
        elif self.ecfg.quantization is not None:
            raise ValueError(f"unknown quantization {self.ecfg.quantization!r}")
        # A loaded checkpoint's layer stacks arrive as host arrays
        # (utils/checkpoint.py): quantize_params above moved one layer at a
        # time, and what is still on the host is placed here — or, under a
        # mesh, by shard_pytree below, each shard straight to its device.
        self.params = (
            params if mesh_cfg is not None else jax.device_put(params)
        )
        self.ccfg = cache_cfg or CacheConfig()
        self.pcfg = prefix_cfg or PrefixConfig()
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.metrics = Metrics()
        # Flight recorder (``trace_cfg`` = a config.TraceConfig): a bounded
        # ring of per-tick records behind /debug/ticks, and the host clock
        # that splits every tick into phases (``_region``). None when
        # tracing is off — step() then pays one attribute load + branch, no
        # allocation, no host sync (the DC301 decode-tick contract).
        self.flight = (
            FlightRecorder(trace_cfg.ticks_capacity, self.metrics)
            if trace_cfg is not None and trace_cfg.enabled
            else None
        )
        # The recorder's dispatch clock (``_clocked``): enqueue, return and
        # device-ready stamps on every noted dispatch while somebody reads
        # the ticks (``clock.armed``: ``_clock_turned`` hears of it). None
        # with the recorder: nothing to arm.
        self._clock = self.flight.clock if self.flight is not None else None
        self._unclocked: Dict[str, Any] = {}    # the programs, while wrapped
        self._noted_rows: Sequence[Session] = ()
        if self._clock is not None:
            self._clock.on_turn = weakref.WeakMethod(self._clock_turned)
        # The gateway's span recorder (``EngineBackend.attach_tracer``): a
        # traced session's ``engine.queue`` / ``engine.first_token`` spans
        # go where the gateway's own do. None = no request spans.
        self.tracer = None
        # Scheduler lock (SURVEY §5.2): slots/cache/allocator are mutated
        # only by step()/collect_finished() under this lock (single-writer).
        # submit()/cancel() are deliberately LOCK-FREE — step() holds the
        # lock across whole device steps, and request admission/cancellation
        # must not stall on that; they rely on GIL-atomic deque/dict ops and
        # state flags the scheduler observes at tick boundaries.
        self._lock = threading.Lock()
        # Deferred page-table installs: (row, slot_idx, page) triples batched
        # into ONE scatter dispatch (sequential assign_pages calls CHAIN —
        # each consumes the previous table — so a growth tick where every row
        # crosses a page boundary paid one dispatch per row; not measured on
        # a directly attached chip).
        self._pending_installs: List[Tuple[int, int, int]] = []

        self.batch = self.ecfg.max_batch_size
        dtype = jnp.dtype(self.ecfg.dtype)
        b, cc = self.batch, self.ccfg
        # Dispatch-shape and kernel policy is owned by the AttentionPlan
        # (engine/plan.py): it resolves use_pallas_attention's auto rule
        # (ON for the int8 DENSE cache on a real TPU; neither side of the
        # rule is measured on the chip and no cell is on the other side,
        # ROADMAP D5), routes paged multi-token rows through the ragged
        # mixed-phase kernel on TPU, and owns every prefill-family pad
        # width below.
        self.plan = AttentionPlan(self.ecfg, self.ccfg, metrics=self.metrics)
        if self.flight is not None:
            self.plan.dispatches = []  # a tick's worth; step() takes it
        if mesh_cfg is not None:
            # Mesh engines keep the legacy path end to end: ring/sp prefill
            # is a different collective-bearing program and the ragged
            # kernel is single-device.
            self.plan.enabled = False
        _sel = self.plan.select()
        self._use_pallas = _sel.use_pallas
        # Sessions parked mid chunked-prefill (slot held, decode-ineligible;
        # advanced by _chunk_dispatch on the decode cadence).
        self._chunking: List[Session] = []
        self._windows: Tuple[int, ...] = ()
        # prefixstore state: host spill arena (paged + prefix_caching +
        # spill budget only) and the cumulative prompt-token reuse ratio
        # behind the prefix_hit_rate gauge.
        self._spill = None
        self._prefix_seen = 0
        self._prefix_hits = 0
        if cc.kv_quant not in (None, "int8"):
            raise ValueError(f"unknown kv_quant {cc.kv_quant!r}")
        if cc.kv_quant is not None and cc.kind not in (
            "dense", "paged", "sink"
        ):
            raise ValueError(
                f"kv_quant={cc.kv_quant!r} is only supported for the dense, "
                f"paged, and sink caches (got kind={cc.kind!r})"
            )
        if cc.prefix_caching and cc.kind != "paged":
            raise ValueError(
                f"prefix_caching requires the paged cache (got kind={cc.kind!r})"
            )
        if cfg.hyper is not None:
            # The stream between layers is ``hyper.mult`` rows of
            # ``hidden_size`` a token; what a mesh (tp / ep all-reduces,
            # pp stage hand-offs, sp rings) carries between chips is ONE
            # row, and is refused here by the key's name.
            if mesh_cfg is not None:
                raise ValueError(
                    f"hc_mult = {cfg.hyper.mult} (ModelConfig.hyper) is "
                    "single-device only: the mesh programs (tp, ep, pp, sp) "
                    "carry a residual stream of one row between chips "
                    f"(got {mesh_cfg})"
                )
            self.plan.mhc_mixes_per_token = 2 * cfg.num_layers
        self._latent = cfg.use_latent
        if self._latent:
            # Latent (MLA) attention stores ONE low-rank [rank + dr] vector
            # per token instead of per-head K/V — only the paged pool has
            # the plane machinery (ingest/export/CoW/spill) wired for it,
            # and the mesh programs shard per-head pools.
            if cc.kind != "paged":
                raise ValueError(
                    "ModelConfig.latent requires the paged cache "
                    f"(got kind={cc.kind!r})"
                )
            if mesh_cfg is not None:
                raise ValueError(
                    "latent KV attention is single-device only (mesh "
                    "sharding of the latent pool is not implemented)"
                )
        if cfg.use_sparse:
            # A learned key selection caches an index key a token beside K
            # and V: the page pool's indexed classes hold that plane, on
            # one device.
            if cc.kind != "paged":
                raise ValueError(
                    "ModelConfig.sparse requires the paged cache "
                    f"(got kind={cc.kind!r})"
                )
            if mesh_cfg is not None:
                raise ValueError(
                    "a learned key selection is single-device only (mesh "
                    "sharding of the index plane is not implemented)"
                )
            if self._latent and draft is not None:
                raise ValueError(
                    "a learned key selection over a latent does not compose "
                    "with a draft model (the verify pass would need the "
                    "selection of every proposed position)"
                )
            self.plan.sparse_topk = cfg.sparse.topk
            # of the layers that attend under a selection, those that score
            # one of their own (the rest reuse: ModelConfig.index_layers)
            self.plan.index_layers = (
                sum(cfg.index_scoring), cfg.num_layers
            )
        # Window and full layers in one stack: the cache keeps a pool a kind
        # (cache/paged.py, the two-pool classes), and the window pool's
        # pages leave a row as its window passes them. What is written for
        # ONE run of pages a row is refused here by name.
        two_pools = cfg.mixed_attention
        self.window_allocator = None
        if two_pools:
            if cc.kind != "paged":
                raise ValueError(
                    "a stack of window and full layers "
                    "(ModelConfig.layer_attention) requires the paged cache "
                    f"(got kind={cc.kind!r})"
                )
            for bad, what in (
                (mesh_cfg is not None,
                 "a mesh (sharding of the window pool is not implemented)"),
                (cc.prefix_caching,
                 "prefix_caching (a shared page chain would need its "
                 "window pages too)"),
                (draft is not None,
                 "a draft model (speculative decoding over a separate "
                 "dense cache)"),
            ):
                if bad:
                    raise ValueError(
                        "a stack of window and full layers does not "
                        f"compose with {what}"
                    )
        # Retention layers keep a fixed-size state a row beside a short paged
        # K/V tail (cache/retention.py). What carries K/V planes between
        # places, and every mesh, is refused here by the family's name.
        self._retention = cfg.use_retention
        if self._retention:
            name = f"family {cfg.family!r} (ModelConfig.retention)"
            if cc.kind != "paged":
                raise ValueError(
                    f"{name} requires the paged cache (got kind={cc.kind!r})"
                )
            for bad, what in (
                (mesh_cfg is not None,
                 f"a mesh ({mesh_cfg}: sharding of the state pool over tp, "
                 "ep, pp, sp or dp is not implemented)"),
                (cc.prefix_caching,
                 "prefix_caching (a shared prefix would need a snapshot of "
                 "the state at its end, not its pages)"),
                (self.pcfg.spill_bytes_max > 0,
                 "a spill tier (a folded page holds nothing to reload)"),
                (draft is not None,
                 "a draft model (a rejected proposal cannot be taken back "
                 "out of a folded state)"),
            ):
                if bad:
                    raise ValueError(f"{name} does not compose with {what}")
        if cfg.loop is not None:
            # Layers that run several times: the laps live inside the model
            # programs (models/llama.py), over a cache of ``cache_layers``.
            # What passes a hidden state down its stages ONCE is refused
            # here by the mechanism's name.
            name = f"family {cfg.family!r} (ModelConfig.loop)"
            if mesh_cfg is not None and mesh_cfg.pp > 1:
                raise ValueError(
                    f"{name} does not compose with pp={mesh_cfg.pp}: "
                    + llama.LOOP_NEEDS_ONE_STAGE
                )
            for bad, what in (
                (mesh_cfg is not None and mesh_cfg.sp > 1,
                 "sp ring prefill (parallel/ring.py runs the stack once "
                 "over its own K/V)"),
                (draft is not None,
                 "a draft model (the verify pass and its rollback have not "
                 "seen a cache of laps x layers)"),
            ):
                if bad:
                    raise ValueError(f"{name} does not compose with {what}")
            # (laps, layers, the layers' stored bytes: what a lap reads)
            self.plan.loop = (
                cfg.loop.steps, cfg.num_layers,
                sum(
                    leaf.nbytes for seg in cfg.segments
                    for leaf in jax.tree.leaves(self.params[seg.key])
                ),
            )
        self.plan.latent = self._latent
        # The census walks the stack's attention kinds: (window, layers) a
        # kind. A stack of ONE kind counts one layer, as it always did
        # (every layer is alike); a stack of two weighs each by its layers.
        kinds = cfg.attention_kinds
        self.plan.attention_layers = tuple(
            (
                cfg.sliding_window if kind == "window" else None,
                kinds.count(kind) if two_pools else 1,
            )
            for kind in dict.fromkeys(kinds)
        )
        if cfg.num_experts > 0:
            from ..ops.moe import dispatch_path, expert_rows_per_token

            def expert_rows(rows, seq_len, valid_share):
                # What the traced step sees (``ops/moe.py:under_mesh``):
                # every step of a sharded engine runs in ``with self.mesh``.
                sharded = self.mesh is not None and self.mesh.size > 1
                needed, computed = expert_rows_per_token(
                    cfg, seq_len, rows, valid_share, sharded
                )
                return (
                    needed * cfg.num_expert_layers,
                    computed * cfg.num_expert_layers,
                    dispatch_path(cfg, rows, seq_len, sharded),
                    # one layer's routed experts: held, and run a token
                    (cfg.num_held_experts, computed - cfg.num_shared_experts),
                )

            self.plan.expert_rows = expert_rows
        if cc.kind == "dense":
            cache_cls = (
                QuantizedDenseKVCache if cc.kv_quant == "int8" else DenseKVCache
            )
            # For the int8 cache, use_pallas_attention selects its OWN decode
            # kernel (ops/quant_attention.py — streams int8 through VMEM);
            # the flash kernel below expects bf16 K/V and would force the
            # dequantizing fallback.
            create_kw = (
                {"use_kernel": self._use_pallas}
                if cc.kv_quant == "int8" else {}
            )
            # Start at the smallest bucket; _ensure_capacity grows the buffer
            # (one pad-copy per growth) as sequences lengthen. Decode
            # bandwidth tracks the LIVE context, not max_seq_len: a padded
            # max-size buffer costs ~30% of decode throughput at 7B shapes
            # early in long-context serving. Growth re-creates buffers and
            # re-applies the mesh shardings (_reshard_cache) — under pp/dp
            # meshes too: each bucket shape compiles its own pipelined
            # executable exactly as the plain path does.
            self._windows = self._window_ladder()
            first = self._windows[0] if self._windows else self.ecfg.max_seq_len
            self.cache = cache_cls.create(
                cfg.cache_layers, b, first, cfg.num_kv_heads,
                cfg.head_dim, dtype, **create_kw,
            )
            self.allocator = None
        elif cc.kind == "paged":
            # The gather path materializes [B, table_width * page_size, ...]
            # per layer, so decode traffic tracks the TABLE WIDTH, not the
            # live length. Start narrow and pad columns as sessions lengthen
            # (cheap: the table is tiny and the pool never moves);
            # max_pages_per_session is the virtual cap.
            grow_ok = mesh_cfg is None or (mesh_cfg.pp == 1 and mesh_cfg.dp == 1)
            self._windows = () if not grow_ok else self._window_ladder(
                cap=min(self.ecfg.max_seq_len,
                        cc.max_pages_per_session * cc.page_size),
                strict=False,  # a small paged capacity caps dense-tuned
                               # ladders rather than rejecting them
            )
            self._first_slots = (
                max(1, -(-self._windows[0] // cc.page_size))
                if self._windows else cc.max_pages_per_session
            )
            if self._latent:
                # One shared latent "head" per token: the pool stores the
                # fused [rank + rope_head_dim] stored form (f32, or int8 +
                # f32 scales) and the kernels decompress in place via the
                # same page-table walk (K = V = stored latent; the value
                # up-projection happens past softmax in the model).
                latent_cls = (
                    QuantizedLatentPagedKVCache
                    if cc.kv_quant == "int8" else LatentPagedKVCache
                )
                more = {}
                if cfg.use_sparse:
                    # the selection is of stored latents: an index plane
                    # (in the model's dtype) with rows for the layers that
                    # score, beside the latent planes
                    latent_cls = indexed_latent_cache_class(
                        cc.kv_quant == "int8", cfg.sparse.index_dim,
                        cfg.index_scoring,
                    )
                    more["dtype"] = dtype
                self.cache = latent_cls.create(
                    cfg.cache_layers, b, cc.num_pages, cc.page_size,
                    self._first_slots, 1, cfg.latent.lat_dim,
                    use_kernel=self._use_pallas,
                    use_ragged=_sel.use_ragged, **more,
                )
            else:
                paged_cls = (
                    QuantizedPagedKVCache
                    if cc.kv_quant == "int8" else PagedKVCache
                )
                if cfg.use_sparse:
                    paged_cls = indexed_cache_class(
                        cc.kv_quant == "int8", cfg.sparse.index_dim
                    )
                pool_layers, more = cfg.cache_layers, {}
                if self._retention or two_pools:
                    # A ROLLING pool (``_pool_reach``): a row holds the
                    # pages its next dispatch reads or writes, whatever its
                    # context. What admission leaves free of it (``_admit``):
                    # one chunk's pages and every row's while it decodes (a
                    # dispatch and the one in flight).
                    sizes = (self._pool_reach, cc.page_size)
                    chunk = self.ecfg.prefill_buckets[-1]
                    ahead = 2 * (self.ecfg.decode_steps or 16)
                    self._window_reserve = window_pages_bound(
                        *sizes, chunk
                    ) + b * window_pages_bound(*sizes, ahead)
                    self._pending_window_installs: List[
                        Tuple[int, int, int]
                    ] = []
                if self._retention:
                    # the rolling pool is the ONE pool: ``num_pages`` is
                    # held to its bound and not to the contexts
                    paged_cls = retention_cache_class(
                        cfg.head_dim, cfg.retention.eps,
                        cc.kv_quant == "int8",
                    )
                    least = 1 + self._window_reserve + window_pages_bound(
                        *sizes, chunk
                    )
                    if cc.num_pages < least:
                        raise ValueError(
                            f"num_pages={cc.num_pages} is under what "
                            f"{b} rows of retention layers hold around one "
                            f"{chunk}-token dispatch: {least} pages of "
                            f"{cc.page_size} (a chunk in flight, a chunk "
                            "held back for admission, every row's open "
                            "page and look-ahead, the null page)"
                        )
                    state = paged_cls.FEATURE_DIM * (cfg.head_dim + 1) * 4
                    self.plan.retention_state_bytes = (
                        cfg.num_layers * cfg.num_kv_heads * state
                    )
                if two_pools:
                    # ``num_pages`` sizes the FULL layers' pool (the one
                    # that grows with the context); the window pool is
                    # sized by the window: by what a prefill dispatch
                    # writes a row (a chunk) and what a decode dispatch
                    # and the one in flight may.
                    paged_cls = two_pool_cache_class(
                        cc.kv_quant == "int8", kinds, cfg.sliding_window
                    )
                    pool_layers = paged_cls.num_layers_of("full")
                    more["window_pages"] = window_pool_pages(
                        *sizes, b, chunk, ahead
                    )
                    self.window_allocator = PageAllocator(
                        more["window_pages"]
                    )
                self.cache = paged_cls.create(
                    pool_layers, b, cc.num_pages, cc.page_size,
                    self._first_slots, cfg.num_kv_heads, cfg.head_dim, dtype,
                    use_kernel=self._use_pallas,
                    use_ragged=_sel.use_ragged, **more,
                )
            self.allocator = PageAllocator(cc.num_pages)
            if self._retention:
                # the rolling pool IS the pool: its pages come and go by
                # the window pool's rule, a row keeps no run of pages
                self.window_allocator = self.allocator
            # The q block the ragged kernel picks at a pad width over THIS
            # pool (heads, stored width, element size): the plan's census
            # of live tiles (note_dispatch) walks that kernel's grid.
            _, _, pool_heads, _, pool_width = self.cache.k_pages.shape
            pool_itemsize = self.cache.k_pages.dtype.itemsize
            self.plan.ragged_block_q = lambda width: _block_q(
                width, cfg.num_heads, pool_heads, pool_width, cc.page_size,
                dtype.itemsize, pool_itemsize,
            )
            # Stored KV footprint per token across all layers — the number
            # the latent cache exists to shrink.
            self.metrics.gauge(
                "kv_bytes_per_token",
                float(sum(
                    pool.shape[0] * pool.dtype.itemsize
                    * math.prod(pool.shape[2:]) // cc.page_size
                    for pool in (
                        getattr(self.cache, f)
                        for f in type(self.cache).PLANE_FIELDS.values()
                    )
                )),
            )
            if cc.prefix_caching and self.pcfg.spill_bytes_max > 0:
                # Host-DRAM spill tier (prefixstore/): registered prefix
                # pages evicted by the refcount-aware LRU snapshot their
                # stored-form tiles into a bounded host arena instead of
                # vanishing; a later admission whose chain reaches the key
                # reloads them with one host->device copy.
                from ..prefixstore import HostSpillArena

                self._spill = HostSpillArena(self.pcfg.spill_bytes_max)
                self.allocator.on_evict = self._spill_page
            self._warm_table_write()
        elif cc.kind == "sink":
            if cc.kv_quant == "int8":
                self.cache = QuantizedSinkKVCache.create(
                    cfg.cache_layers, b, cc.window_length, cc.num_sink_tokens,
                    cfg.num_kv_heads, cfg.head_dim, dtype,
                    use_kernel=self._use_pallas,
                )
            else:
                self.cache = SinkKVCache.create(
                    cfg.cache_layers, b, cc.window_length, cc.num_sink_tokens,
                    cfg.num_kv_heads, cfg.head_dim, dtype,
                )
            self.allocator = None
        else:
            raise ValueError(f"unknown cache kind {cc.kind}")

        self.mesh = None
        self._use_pp = False
        self._cache_pspecs = None
        if mesh_cfg is not None:
            from ..parallel import (
                build_mesh, cache_pspecs, param_pspecs, shard_pytree,
                validate_tp,
            )

            if mesh_cfg.sp != 1:
                # sp is a PREFILL-side program (parallel/ring.py): prompts
                # past the ring threshold prefill sequence-sharded over sp,
                # then hand their KV to the (sp-replicated) decode path.
                if mesh_cfg.pp != 1:
                    raise ValueError(
                        "sp>1 ring prefill does not compose with pp serving "
                        f"(got {mesh_cfg})"
                    )
                if cc.kind not in ("dense", "paged"):
                    raise ValueError(
                        "sp>1 ring prefill requires a dense or paged cache "
                        "kind (contiguous ring KV ingest; the sink ring "
                        f"evicts on write; got kind={cc.kind!r})"
                    )
            if mesh_cfg.pp > 1 and cc.kind not in ("dense", "paged"):
                # Paged composes: the pool's layer axis leads every array, so
                # pp stages hold their own layers' pages (pipeline's
                # SHARED_FIELDS path); page-table installs already dispatch
                # the GSPMD-safe chunked DUS route under any mesh. The sink
                # ring's fused write-behind tail has no staged variant.
                raise ValueError(
                    f"pp>1 serving requires the dense or paged cache "
                    f"(got {cc.kind!r})"
                )
            if self.batch % (mesh_cfg.pp * mesh_cfg.dp) != 0:
                raise ValueError(
                    f"max_batch_size {self.batch} must divide by pp*dp = "
                    f"{mesh_cfg.pp}*{mesh_cfg.dp} (microbatch row groups)"
                )
            if mesh_cfg.pp > 1 and cfg.num_layers % mesh_cfg.pp != 0:
                raise ValueError(
                    f"num_layers {cfg.num_layers} not divisible by "
                    f"pp={mesh_cfg.pp}"
                )
            validate_tp(cfg, mesh_cfg.tp, ep=mesh_cfg.ep)
            self._use_pp = mesh_cfg.pp > 1
            self.mesh = build_mesh(mesh_cfg)
            self.params = shard_pytree(
                self.params, self.mesh, param_pspecs(self.params, self._use_pp)
            )
            self._cache_pspecs = lambda c: cache_pspecs(c, self._use_pp)
            self._shard_pytree = shard_pytree
            self.cache = shard_pytree(
                self.cache, self.mesh, self._cache_pspecs(self.cache)
            )
            self._warm_table_write()  # sharded table → new executable

        self.sessions: Dict[str, Session] = {}
        self.waiting: collections.deque[Session] = collections.deque()
        self.slots: List[Optional[str]] = [None] * self.batch



        attention = attention_fn
        if (
            attention is None
            and self._use_pallas
            and not isinstance(
                self.cache,
                (QuantizedDenseKVCache, PagedKVCache, QuantizedSinkKVCache),
            )
        ):
            # Caches with their OWN kernels (int8 dense, paged) must keep
            # attention unset: swapping in flash here would both force their
            # dequantizing/gathering fallbacks AND disable the fused tail
            # path (tail_capable requires the default attention).
            from ..ops.flash_attention import flash_attention

            attention = flash_attention  # falls back to XLA on decode shapes
        mkw = {} if attention is None else {"attention_fn": attention}
        # pp>1: batched steps run the GPipe-staged pipeline program
        # (parallel/pipeline.py). Single-row prefill cannot microbatch (one
        # row), so it keeps the plain program — GSPMD streams each pp stage's
        # layer weights to the computation, which for a once-per-admission
        # bucket-sized prefill is an acceptable ICI cost.
        batch_mkw = dict(mkw)
        if self._use_pp:
            from ..parallel.pipeline import pipeline_block_apply

            mesh = self.mesh
            pkw = dict(mkw)

            def _pp_block_fn(cfg_, layers_, x_, cache_, num_new_):
                return pipeline_block_apply(
                    cfg_, layers_, x_, cache_, num_new_, mesh, **pkw
                )

            batch_mkw["block_fn"] = _pp_block_fn

        def _prefill_row(params, tokens, cache, row, n_valid, key, sp):
            """A row's final (or only) prefill piece THROUGH ITS PAGE TABLE
            (or its dense row): the cache writes the piece behind the row's
            history and attends to both. Every row with history comes here
            (a prefix hit, the tail of a chunked prompt), and every fresh
            one where a kernel reads the pages in place or the cache cannot
            install a contiguous K/V (``_prefill_row_fresh`` below)."""
            # ``row`` and ``n_valid`` are traced: one compile per prefill
            # bucket shape, not per (row, length) combination.
            sub = cache.select_row(row)
            logits, sub = llama.model_apply(
                cfg, params, tokens, sub, n_valid[None], head="last", **mkw
            )
            cache = cache.merge_row(sub, row)
            token = sample(logits[:, 0], key, sp)
            return token[0], cache

        def _prefill_row_fresh(params, tokens, cache, row, n_valid, key, sp):
            """A FRESH row's whole prompt in one piece, where no kernel
            reads the pages in place: the row is at position 0, so every
            position it may attend to is in the dispatch's own K/V. The
            model runs over a scratch dense cache as wide as the piece (S x
            S causal attention), and the K/V it leaves is installed into
            the row's pages as whole page tiles (``ingest_row``), where
            ``_prefill_row`` scatters position by position into the pool
            and attends the row's whole table span: ``_ring_prefill_row``
            without the ring. Same logits but for the order of sums, the
            same sample with the same key, the same pages."""
            layers, _, kv_heads, _, head_dim = cache.k_pages.shape
            scratch = DenseKVCache.create(
                layers, 1, tokens.shape[1], kv_heads, head_dim,
                cache.k_pages.dtype,
            )
            logits, scratch = llama.model_apply(
                cfg, params, tokens, scratch, n_valid[None], head="last",
                **mkw
            )
            sub = cache.select_row(row).ingest_row(
                scratch.k, scratch.v, n_valid
            )
            cache = cache.merge_row(sub, row)
            token = sample(logits[:, 0], key, sp)
            return token[0], cache

        def _prefill_row_nosample(params, tokens, cache, row, n_valid):
            """Chunked-prefill body: fill cache; head skipped entirely
            (an interior chunk samples nothing — the full-vocab matmul
            over the chunk was pure waste)."""
            sub = cache.select_row(row)
            _, sub = llama.model_apply(
                cfg, params, tokens, sub, n_valid[None], head="none", **mkw
            )
            # The tokens written, as a result of its own: what is ready
            # when the chunk has run (the dispatch clock waits on it; the
            # cache is donated to the next dispatch).
            return n_valid + 0, cache.merge_row(sub, row)

        def _prefill_rows(params, tokens, cache, rows, n_valid, key, sp):
            """Batched admission: k sessions' prompts in ONE bucketed
            dispatch over a compact k-row sub-cache (``tokens [k, S]``,
            ``rows``/``n_valid`` ``[k]`` traced — one executable per
            (k-bucket, prompt-bucket)). k sequential single-row prefills
            cost k weight sweeps plus k host round trips; batched rows
            share every weight fetch.

            This IN-PLACE form (gather rows → compute → scatter back, full
            cache in one program) is kept for the PAGED pool, whose shared
            page arrays can't live in a standalone sub-cache. Dense/sink
            kinds use the SPLIT pair below: the compiler of the
            installation rounds 1–5 ran on crashed on the combined program
            between b88×T256 (= 22.5k, compiles) and b96×T256 (= 24.5k,
            crashes) — bisected r5: the batched-prefill program, not the
            decode scan; form-independent (scatter, DUS-chain, no-donation
            all crash) — while the standalone-prefill + merge-only programs
            compiled at every serving shape tried (b160×T256 included).
            Not retried on jax 0.9.0 with a directly attached chip."""
            sub = cache.select_rows(rows)
            logits, sub = llama.model_apply(
                cfg, params, tokens, sub, n_valid, head="last", **mkw
            )
            cache = cache.merge_rows(sub, rows)
            toks = sample(logits[:, 0], key, sp)
            return toks, cache

        def _prefill_rows_standalone(params, tokens, sub, n_valid, key, sp):
            """Split batched admission, program A: prefill into a FRESH
            compact k-row cache — no [L, B, T] array anywhere in the
            program (admission rows start at length 0, so there is nothing
            to gather). Program B (`_merge_rows_only`) scatters the result
            rows into the big cache."""
            logits, sub = llama.model_apply(
                cfg, params, tokens, sub, n_valid, head="last", **mkw
            )
            toks = sample(logits[:, 0], key, sp)
            return toks, sub

        def _merge_rows_only(cache, sub, rows):
            return cache.merge_rows(sub, rows)

        def _decode_step(params, tokens, cache, active, key, sp):
            logits, cache = llama.model_apply(
                cfg, params, tokens, cache, active.astype(jnp.int32),
                **batch_mkw,
            )
            token = sample(logits[:, 0], key, sp)
            return token, cache

        # The write-behind tail composes with tp/ep/dp sharding (its scalar
        # slot writes and flush gather partition) but not with the staged
        # pipeline program, which pp engines use per step instead. A paged
        # cache says itself whether it has the protocol (``has_tail``): the
        # value-dtype and the int8 pool always do (their big segment is a
        # kernel's sweep of the pages in place where there is a kernel, and
        # every row's table span gathered once a window, pure XLA, where
        # there is none: a mesh engine, the CPU); the int8 latent pool with
        # the Pallas kernel does (its own rope-free tail_attend over the
        # fused in-place sweep); the float32 latent pool, the int8 latent
        # pool without the kernel and the value-dtype indexed pool decode a
        # token a dispatch (their tail_init raises).
        tail_capable = (
            attention is None
            and not self._use_pp
            and (
                self.cache.has_tail
                if isinstance(self.cache, PagedKVCache)
                else isinstance(
                    self.cache,
                    (DenseKVCache, QuantizedDenseKVCache,
                     QuantizedSinkKVCache),
                )
            )
        )
        if tail_capable and isinstance(self.cache, QuantizedSinkKVCache):
            # The fused window must fit the ring span: a tail longer than
            # the ring would have tail tokens evicting EACH OTHER, which the
            # tail segment's prefix-validity cannot express. (The bf16 sink
            # ring is never tail-capable — it has no tail protocol.)
            k_want = (
                self.ecfg.decode_steps
                if self.ecfg.decode_steps is not None else 16
            )
            tail_capable = self.cache.ring_slots >= max(1, k_want)
        # decode_steps=None (the default) resolves to the fused fast path
        # wherever it composes: the engine should serve its best configuration
        # out of the box, not behind a flag.
        self.decode_steps = (
            self.ecfg.decode_steps
            if self.ecfg.decode_steps is not None
            else (16 if tail_capable else 1)
        )
        K = self.decode_steps
        if (
            tail_capable and K > 1
            and isinstance(self.cache, QuantizedPagedKVCache)
            and self.cache.use_kernel
        ):
            # the fused scan decodes through the in-place sweep by copies
            # wherever the table is wide enough: the plan counts its pages
            _, _, pool_heads, _, pool_width = self.cache.k_pages.shape
            self.plan.sweep_pool = (
                pool_heads, pool_width, type(self.cache).INPLACE_CTX
            )
        if (
            tail_capable and K > 1 and hasattr(self.cache, "tail_walk")
            and not self._retention   # (its walk lists live ROWS, no blocks)
            and jax.eval_shape(
                lambda c: c.tail_walk(K, c.lengths, c.lengths), self.cache
            ) is not None
        ):
            # the fused scan's sweep of the latent pool walks a list of its
            # live blocks: the plan counts the grid steps it names
            _, _, pool_heads, _, pool_width = self.cache.k_pages.shape
            self.plan.walked_pool = (pool_heads, pool_width)

        looped = cfg.loop is not None

        def _decode_scan(params, tokens, cache, active, key, sp, eos_ids, budget):
            """``K`` fused decode steps in one dispatch: sampling, EOS stops,
            and per-row token budgets all carried on device. Rows that stop
            (EOS / budget) keep computing but write nothing (``num_new=0``)
            and emit ``-1``. Returns ``(emitted [K, B], cache)``.

            Every cache with the tail protocol (the dense kinds, the int8
            sink ring, the paged pools with or without a kernel: see
            ``tail_capable`` above) runs the write-behind-tail fast path
            (``llama.multi_decode_apply`` — big KV buffers read-only through
            all K steps); the others scan ``model_apply`` per step. A third
            result, for a looped stack through the fast path only: the lap
            each emitted token's position left at ``[K, B]`` (None, no
            output at all, otherwise).
            """
            if tail_capable:
                def step_fn(i, logits, alive):
                    nxt = sample(logits, jax.random.fold_in(key, i), sp)
                    emitted = jnp.where(alive, nxt, -1)
                    alive = alive & (nxt != eos_ids) & (i + 1 < budget)
                    return nxt, alive.astype(jnp.int32), alive, emitted

                emitted, cache = llama.multi_decode_apply(
                    cfg, params, tokens, cache, K, step_fn,
                    active, active.astype(jnp.int32), exit_laps=looped,
                )
                # a looped stack's scan also says which lap each token's
                # position left at; laps is None, no output at all, for
                # every other model
                emitted, laps = emitted if looped else (emitted, None)
                return emitted, cache, laps

            def one(carry, i):
                tok, cache, alive = carry
                logits, cache = llama.model_apply(
                    cfg, params, tok, cache, alive.astype(jnp.int32),
                    **batch_mkw,
                )
                nxt = sample(logits[:, 0], jax.random.fold_in(key, i), sp)
                emitted = jnp.where(alive, nxt, -1)
                alive = alive & (nxt != eos_ids) & (i + 1 < budget)
                return (nxt[:, None], cache, alive), emitted

            (_, cache, _), emitted = jax.lax.scan(
                one, (tokens, cache, active), jnp.arange(K)
            )
            return emitted, cache, None

        donate = jax.default_backend() == "tpu"
        dk = dict(donate_argnums=(2,)) if donate else {}
        self._prefill = self._with_mesh(jax.jit(_prefill_row, **dk))
        self._prefill_ns = self._with_mesh(jax.jit(_prefill_row_nosample, **dk))
        # A fresh row's one-piece prompt prefills against its own K/V where
        # the cache can install it (``fresh_install``) and no kernel reads
        # the pages in place; None where every row goes through its table.
        # Not under ``pp``: the staged program has not seen a scratch cache.
        self._prefill_fresh = None
        if (
            isinstance(self.cache, PagedKVCache)
            and self.cache.fresh_install()
            and not self.cache.use_ragged
            and not self._use_pp
        ):
            self._prefill_fresh = self._with_mesh(
                jax.jit(_prefill_row_fresh, **dk)
            )
        # the (table slots, pad width) shapes each of the two is loaded at
        # (``_pair_widths``)
        self._fresh_widths, self._table_widths = set(), set()
        # which of two counters a prefill-family dispatch's rows move
        # (``_note_prefill``): whether the cache writes and reads its pool in
        # place by (layer, page) there, or is handed a layer's planes
        self._pool_inplace = bool(
            getattr(self.cache, "ragged_reads_whole_stacks", False)
        )
        self._prefill_batch = jax.jit(_prefill_rows, **dk)
        self._prefill_batch_standalone = jax.jit(_prefill_rows_standalone, **dk)
        mdk = (
            dict(donate_argnums=(0,))
            if jax.default_backend() == "tpu" else {}
        )
        self._merge_rows_only = jax.jit(_merge_rows_only, **mdk)
        # Batched admission needs select_rows/merge_rows (gather/scatter over
        # the batch axis) and a single-device computation: a scatter over a
        # dp/pp-sharded batch aborts under GSPMD, and ring prefill is a
        # different program entirely.
        self._batch_admission = (
            self.mesh is None and hasattr(self.cache, "select_rows")
        )
        self._decode = self._with_mesh(jax.jit(_decode_step, **dk))
        self._decode_k = self._with_mesh(jax.jit(_decode_scan, **dk))

        # -- pipelined decode ticks -------------------------------------------
        # Dispatch tick N from a device-resident carry of tick N-1's final
        # tokens, THEN resolve tick N-1's emitted tokens (the host copy
        # overlaps tick N's compute). What the per-tick host round trip
        # otherwise costs is not measured on a directly attached chip.
        self._pending = None
        self._carry = None
        self._carry_ok = np.zeros(self.batch, np.bool_)
        self._idle_since = time.monotonic()  # last time every slot was free
        # -- overlapped (stall-free) admission ---------------------------------
        # With a pipelined tick in flight, admission prefills DISPATCH as
        # usual (the program queues behind the running tick: JAX dispatch is
        # async) but the host defers the blocking first-token fetch: each
        # record below holds (sessions, device tokens, skips) until the next
        # tick boundary, where the fetch rides the tick resolve's device_get.
        # The sampled tokens scatter into the carry so the next tick consumes
        # them with NO host round trip, and ``_admit_pend`` charges one
        # conservative in-flight token per row. Device programs and RNG order
        # are the synchronous path's: token streams are byte-exact with an
        # engine that never overlaps (``_overlap_ok`` says when this one does:
        # on one chip, and under a mesh too where ``_batch_whole`` holds).
        self._inflight_admits: List[Tuple[List[Session], jax.Array, List[int]]] = []
        self._admit_pend = np.zeros(self.batch, np.int32)
        # Is the batch axis whole on every device: one chip, or a mesh whose
        # only axis over 1 is ``tp``? The deferred carry scatter needs that.
        self._batch_whole = self.mesh is None or self.mesh.size == self.mesh.shape["tp"]
        # Events produced OUTSIDE step() (admit_prefilled's synchronous
        # first-token delivery, on a gateway thread): step() drains them.
        self._ext_produced: List[Tuple[str, int, bool]] = []
        # Admission-ordering hook (set_admission_order): None = FIFO.
        self._admission_order = None
        # Any tail-capable cache pipelines (dense kinds and the paged pools'
        # fused windows); the sink ring (no tail) and draft-model engines
        # keep the synchronous flow.
        self._pipelined = K > 1 and tail_capable and draft is None

        def _carry_combine(fresh, carry, use_carry):
            return jnp.where(use_carry[:, None], carry, fresh)

        def _carry_merge(em_last, old, act):
            return jnp.where(act[:, None], em_last[:, None], old)

        def _carry_scatter(carry, toks, rows):
            # Overlapped admission: deferred first tokens land in the
            # pipelined carry at their rows. Padding entries use an
            # out-of-range row — the scatter drops them (same contract as
            # merge_rows).
            return carry.at[rows, 0].set(toks)

        self._carry_combine = self._with_mesh(jax.jit(_carry_combine))
        self._carry_merge = self._with_mesh(jax.jit(_carry_merge))
        self._carry_scatter = self._with_mesh(jax.jit(_carry_scatter))

        # -- ring (sequence-parallel) prefill (SURVEY §5.7) -------------------
        self._ring_prefill = None
        self._sp = 1
        if mesh_cfg is not None and mesh_cfg.sp > 1:
            from ..parallel.ring import ring_prefill

            self._sp = mesh_cfg.sp
            mesh = self.mesh

            def _ring_prefill_row(params, tokens, cache, row, n_valid, key, sp):
                """One admitted session's prompt, sequence-sharded over the
                ``sp`` ring; the resulting KV is quantized/laid out by the
                cache's ``ingest_row`` and decode proceeds identically to a
                chunked prefill."""
                logits, ks, vs = ring_prefill(
                    cfg, params, tokens, n_valid[None], mesh
                )
                sub = cache.select_row(row).ingest_row(ks, vs, n_valid)
                cache = cache.merge_row(sub, row)
                token = sample(logits[:, 0], key, sp)
                return token[0], cache

            self._ring_prefill = self._with_mesh(
                jax.jit(_ring_prefill_row, **dk)
            )

        # -- speculative decoding (draft model) ----------------------------------
        self.draft = None
        self.spec_stats = {"proposed": 0, "accepted": 0, "steps": 0}
        if draft is not None:
            dcfg, dparams = draft
            if dcfg.loop is not None:
                raise ValueError(
                    f"a draft of family {dcfg.family!r} (ModelConfig.loop): "
                    + llama.LOOP_NEEDS_ONE_STAGE
                )
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft and target must share a vocabulary")
            if isinstance(self.cache, _SINK_KINDS):
                raise ValueError(
                    "speculative decoding needs rollback-capable caches "
                    "(dense/paged); the sink ring evicts on write"
                )
            if self.ecfg.speculative_k < 1:
                raise ValueError(
                    f"speculative_k must be >= 1 with a draft model, got "
                    f"{self.ecfg.speculative_k}"
                )
            self.draft = (dcfg, jax.device_put(dparams))
            sk = self.ecfg.speculative_k
            self.draft_cache = DenseKVCache.create(
                dcfg.num_layers, b, self.ecfg.max_seq_len, dcfg.num_kv_heads,
                dcfg.head_dim, dtype,
            )

            def _draft_prefill_row(dp_, tokens, dcache, row, n_valid):
                sub = dcache.select_row(row)
                _, sub = llama.model_apply(
                    dcfg, dp_, tokens, sub, n_valid[None], head="none"
                )
                return dcache.merge_row(sub, row)

            def _draft_propose(dp_, tokens, dcache, active):
                """k greedy draft tokens per active row; draft cache
                advances k for active rows."""
                def one(carry, _):
                    tok, dc = carry
                    logits, dc = llama.model_apply(
                        dcfg, dp_, tok, dc, active.astype(jnp.int32)
                    )
                    nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                    return (nxt[:, None], dc), nxt

                (_, dcache), toks = jax.lax.scan(
                    one, (tokens, dcache), None, length=sk
                )
                return toks, dcache  # [k, B]

            def _draft_catchup(dp_, tokens, dcache, mask):
                _, dcache = llama.model_apply(
                    dcfg, dp_, tokens, dcache, mask.astype(jnp.int32),
                    head="none",  # cache ingest only — logits unused
                )
                return dcache

            def _verify(params_, tokens, prop, spec_mask, cache, num_new,
                        key, sp):
                """One target forward over [last, p1..pk] (speculative rows,
                num_new=k+1) and [last, pad…] (normal rows, num_new=1). The
                verify sequence is built IN-GRAPH from the draft's proposals
                so the host never has to fetch them before dispatching —
                the proposal copy overlaps the verify compute. Returns
                per-position argmax (acceptance), the position-0 sample
                (normal rows' token), and the cache (advanced per-row; the
                caller rolls speculative rows back)."""
                seq = jnp.concatenate(
                    [tokens, jnp.where(spec_mask[:, None], prop.T, 0)],
                    axis=1,
                )
                logits, cache = llama.model_apply(
                    cfg, params_, seq, cache, num_new, **batch_mkw
                )
                preds = jnp.argmax(logits, -1).astype(jnp.int32)  # [B, k+1]
                sampled = sample(logits[:, 0], key, sp)
                return preds, sampled, cache

            self._draft_prefill = jax.jit(_draft_prefill_row, **dk)
            self._draft_propose = jax.jit(_draft_propose, **dk)
            self._draft_catchup = jax.jit(_draft_catchup, **dk)
            # Donate the CACHE (position 4 in the new signature — NOT the
            # proposals, which the host fetches after dispatch).
            vdk = dict(donate_argnums=(4,)) if donate else {}
            self._verify = self._with_mesh(jax.jit(_verify, **vdk))

            # -- fused multi-round speculation --------------------------------
            # R propose→verify→accept rounds in ONE dispatch: acceptance,
            # EOS/budget stops, target-cache rollback (a per-row lengths
            # decrement — validity derives from lengths) and draft catch-up
            # all carried on device. The synchronous tick pays 2+ host
            # round trips per round (not measured on a directly attached
            # chip against the round's device time). Output is bit-identical to
            # plain greedy decoding (same argmax decisions, same prefixes).
            self.spec_rounds = (
                self.ecfg.speculative_rounds
                if self.ecfg.speculative_rounds is not None
                else max(1, (self.ecfg.decode_steps or 16) // (sk + 1))
            )
            R = self.spec_rounds

            def _spec_round_fn(params_, dparams_, tokens, cache, dcache,
                               spec, active, eos_ids, budget, key, sp,
                               catch_tok, catch):
                """``R`` fused speculative rounds. Returns
                ``(pack [R, B, k+3] int32, tok_carry [B, 1],
                catch_tok [B, 1], catch [B], cache, dcache)`` — pack =
                emits (k+1 slots, -1 padded) ++ acc ++ palive per round,
                ONE array so the host pays ONE fetch instead of three per
                tick (a fetch's fixed cost is not measured on a directly
                attached chip).

                ``catch_tok``/``catch`` carry the draft's PENDING catch-up
                token: on full acceptance the draft never consumed its own
                final proposal, and r4 paid a dedicated masked draft
                forward per round (~2.3 ms — a full sweep of the draft
                weights) to feed it back. Instead the NEXT round's first
                draft step consumes ``[p_k, tok]`` as a 2-position forward
                (per-row ``num_new = 1 + catch``) — the catch-up rides a
                weight sweep that was happening anyway, across dispatches
                too (the pending pair is device-carried alongside the
                token carry and returned for the next tick)."""
                b_ = tokens.shape[0]
                jidx = jnp.arange(sk + 1, dtype=jnp.int32)[None, :]

                def one_round(carry, i):
                    tok, cache, dcache, alive, used, ctok, cm = carry
                    palive = (alive & spec).astype(jnp.int32)

                    # First draft step folds the pending catch-up in:
                    # rows with cm consume [p_k, tok] (2 positions), the
                    # rest [tok, pad] (1); the next-token logits sit at
                    # position num_new-1 = cm.
                    cmi = cm.astype(jnp.int32)
                    first_seq = jnp.where(
                        cm[:, None],
                        jnp.concatenate([ctok, tok], axis=1),
                        jnp.concatenate(
                            [tok, jnp.zeros((b_, 1), jnp.int32)], axis=1
                        ),
                    )
                    lgd, dcache = llama.model_apply(
                        dcfg, dparams_, first_seq, dcache,
                        palive * (1 + cmi),
                    )
                    first_nxt = jnp.argmax(
                        jnp.take_along_axis(
                            lgd, cmi[:, None, None], axis=1
                        )[:, 0],
                        -1,
                    ).astype(jnp.int32)

                    def dstep(c2, _):
                        t2, dc = c2
                        lgd2, dc = llama.model_apply(
                            dcfg, dparams_, t2, dc, palive
                        )
                        nxt = jnp.argmax(lgd2[:, 0], -1).astype(jnp.int32)
                        return (nxt[:, None], dc), nxt

                    (_, dcache), rest = jax.lax.scan(
                        dstep, (first_nxt[:, None], dcache), None,
                        length=sk - 1,
                    )
                    prop = jnp.concatenate(
                        [first_nxt[None, :], rest], axis=0
                    )  # [k, B]
                    prop_t = prop.T  # [B, k]
                    seq = jnp.concatenate(
                        [tok, jnp.where(spec[:, None], prop_t, 0)], axis=1
                    )
                    num_new = jnp.where(
                        alive, jnp.where(spec, sk + 1, 1), 0
                    ).astype(jnp.int32)
                    lg, cache = llama.model_apply(
                        cfg, params_, seq, cache, num_new, **batch_mkw
                    )
                    preds = jnp.argmax(lg, -1).astype(jnp.int32)  # [B, k+1]
                    sampled = sample(
                        lg[:, 0], jax.random.fold_in(key, i), sp
                    )

                    agree = prop_t == preds[:, :sk]
                    acc = jnp.sum(
                        jnp.cumprod(agree.astype(jnp.int32), axis=1), axis=1
                    )  # [B] longest agreeing prefix
                    pred_at_acc = jnp.take_along_axis(
                        preds, acc[:, None], axis=1
                    )
                    prop_ext = jnp.pad(prop_t, ((0, 0), (0, 1)))
                    cand = jnp.where(
                        jidx < acc[:, None], prop_ext, pred_at_acc
                    )
                    plain = jnp.concatenate(
                        [sampled[:, None],
                         jnp.zeros((b_, sk), jnp.int32)], axis=1
                    )
                    cand = jnp.where(spec[:, None], cand, plain)

                    count = jnp.where(spec, acc + 1, 1) * alive
                    # EOS: truncate at the first emitted EOS; budget:
                    # truncate at the row's remaining token allowance.
                    iseos = cand == eos_ids[:, None]
                    first_eos = jnp.min(
                        jnp.where(iseos, jidx, sk + 2), axis=1
                    )
                    count = jnp.minimum(count, first_eos + 1)
                    rem = jnp.maximum(budget - used, 0)
                    count = jnp.minimum(count, rem)
                    hit_eos = first_eos < count
                    alive = alive & ~hit_eos & (used + count < budget)

                    # Rollback: the verify wrote num_new positions; the
                    # accepted sequence state is base + count for target
                    # AND draft (both then hold kv for [..., tok,
                    # emitted[0..count-2]]; the next round consumes
                    # emitted[count-1]).
                    cache = cache.replace(
                        lengths=cache.lengths - (num_new - count)
                    )
                    d_roll = palive * jnp.maximum(sk - count, 0)
                    dcache = dcache.replace(
                        lengths=dcache.lengths - d_roll
                    )
                    # Full acceptance: the draft never consumed its own
                    # final proposal — record it as the next round's (or
                    # next DISPATCH's) pending catch-up instead of paying a
                    # dedicated draft forward here. Inactive rows keep any
                    # pending pair untouched.
                    new_catch = (palive == 1) & (count == sk + 1)
                    new_ctok = jnp.take_along_axis(
                        cand, jnp.maximum(count - 2, 0)[:, None], axis=1
                    )
                    cm = jnp.where(palive == 1, new_catch, cm)
                    ctok = jnp.where(palive[:, None] == 1, new_ctok, ctok)

                    emit = jnp.where(jidx < count[:, None], cand, -1)
                    last = jnp.take_along_axis(
                        cand, jnp.maximum(count - 1, 0)[:, None], axis=1
                    )
                    tok = jnp.where(count[:, None] > 0, last, tok)
                    return (
                        (tok, cache, dcache, alive, used + count, ctok, cm),
                        (emit, acc, palive),
                    )

                zero = jnp.zeros((b_,), jnp.int32)
                # UNROLLED rounds: under lax.scan XLA re-stages the loop
                # bodies' small invariant operands (head scales, norms, rope
                # tables) every iteration. R is small.
                carry = (tokens, cache, dcache, active, zero, catch_tok,
                         catch)
                outs = []
                for i in range(R):
                    carry, out = one_round(carry, i)
                    outs.append(out)
                (tok, cache, dcache, _, _, catch_tok, catch) = carry
                pack = jnp.stack([
                    jnp.concatenate(
                        [emit, acc[:, None], palive[:, None]], axis=1
                    )
                    for emit, acc, palive in outs
                ])  # [R, B, k+3]
                return pack, tok, catch_tok, catch, cache, dcache

            sdk = dict(donate_argnums=(3, 4)) if donate else {}
            self._spec_rounds_fn = self._with_mesh(
                jax.jit(_spec_round_fn, **sdk)
            )
            # Pipelined speculation state: the in-flight tick's packed
            # result + bookkeeping, and the device-resident token carry
            # (tick N dispatches from tick N-1's final tokens WITHOUT
            # fetching them — the fetch overlaps tick N's compute).
            # ``_spec_catch`` is the device-carried pending draft catch-up
            # pair (token, mask) the next tick's first draft step consumes.
            self._spec_pending = None
            self._spec_carry = None
            self._spec_catch = None
            self._spec_carry_ok = np.zeros(self.batch, np.bool_)
            self._catch_combine = self._with_mesh(jax.jit(
                lambda c, u: c & u
            ))
            # Adaptive speculation (config.py): a throughput A/B controller.
            # ``mode``: "spec" | "probe_plain" | "plain" | "probe_spec".
            # Rates are measured tokens/s over windows of probe_len ticks;
            # probing the plain path is gated on the MEASURED
            # tokens-per-round EMA sagging below the break-even band (high
            # acceptance never pays the probe's mode-switch cost).
            self._spec_suspended = False
            # Injectable clock for the A/B controller — tests drive window
            # wall time deterministically instead of sleeping through it.
            self._spec_clock = time.monotonic
            self._spec_ctl = {
                "mode": "spec", "win_t0": None, "win_tok0": 0.0,
                "win_ticks": 0, "spec_rate": None, "plain_rate": None,
                "cooldown": 0, "stat0": dict(self.spec_stats),
                "tpr_ema": None,
                # Resident-set signature at the current window's start:
                # composition churn mid-window re-baselines the window
                # (ADVICE r5 — mixed-composition rates bias the A/B).
                "comp": None,
            }
        BOOT.mark("engine_built", self.flight)

    def _sink_cap(self) -> int:
        """Stream-length bound for sink sessions. The bf16 ring rotates at
        window-relative (bounded) positions, so its streams are limited only
        by the int32 ``seen`` counter; the quantized ring stores keys rotated
        at ABSOLUTE positions, whose f32 RoPE angles (``pos * inv_freq``)
        lose ~``pos * 6e-8`` rad of precision on the highest-frequency
        channel — bound streams at 2^20 tokens (~0.06 rad worst-case drift)
        rather than let attention quality decay silently."""
        return (1 << 20) if isinstance(
            self.cache, QuantizedSinkKVCache
        ) else (1 << 30)

    def _window_ladder(
        self, cap: Optional[int] = None, strict: bool = True
    ) -> Tuple[int, ...]:
        """See :func:`cache.base.window_ladder`; ``decode_windows`` is the
        custom override."""
        return window_ladder(
            cap if cap is not None else self.ecfg.max_seq_len,
            custom=self.ecfg.decode_windows, strict=strict,
        )

    def _ensure_capacity(self, needed_len: int) -> None:
        """Grow the cache's attended span to the smallest bucket covering
        ``needed_len``: dense kinds zero-pad-copy their buffers; the paged
        kind just pads TABLE columns (the pool never moves). Per-bucket
        executables compile once."""
        if not self._windows or needed_len <= self.cache.max_len:
            return
        if isinstance(self.cache, PagedKVCache):
            ps = self.ccfg.page_size
            slots_needed = -(-needed_len // ps)
            # Ladder entries never exceed max_pages_per_session * page_size
            # (the __init__ cap), so each candidate slot count is in range.
            new_slots = next(
                (-(-w // ps) for w in self._windows
                 if -(-w // ps) >= slots_needed),
                self.ccfg.max_pages_per_session,
            )
            if new_slots > self.cache.page_table.shape[1]:
                self.cache = self.cache.resize_table(new_slots)
                self._reshard_cache()
                self._warm_table_write()  # new table shape → new executable
                self.metrics.counter("cache_growths")
            return
        if not isinstance(self.cache, (DenseKVCache, QuantizedDenseKVCache)):
            return
        new_t = next(
            (w for w in self._windows if w >= needed_len),
            self.ecfg.max_seq_len,
        )
        self.cache = self.cache.grow_to(new_t)
        self._reshard_cache()
        self.metrics.counter("cache_growths")

    def _warm_table_write(self) -> None:
        """Pre-compile the page-table install for the CURRENT table
        shape/sharding (a null-page write over slot (0, 0) — already 0, and
        every row's table is reset at admission anyway). Without this the
        first mid-serving page growth after creation, a table widen, or a
        re-shard stalls a decode tick on a compile."""
        if isinstance(self.cache, PagedKVCache):
            # DISCARD the results: we only want the executables compiled;
            # the writes themselves would stomp a live row's first page
            # mapping when re-warming after a mid-serving table widen.
            self.cache.assign_pages(0, [0])
            if self._mesh_cfg is not None:
                # Mesh installs dispatch binary-decomposed run chunks:
                # warm every power-of-two length up to the table width.
                n = 2
                while n <= self.cache.page_table.shape[1]:
                    self.cache.assign_pages(0, [0] * n)
                    n *= 2
            if self._mesh_cfg is None:
                # Both batched-install pad buckets (_flush_installs) —
                # mesh engines never dispatch these (their installs stay
                # on the chained per-page path), so don't compile them.
                for pad in set(self._install_pads()):
                    self.cache.assign_pages_batch([0], [0], [0], pad_to=pad)

    def _install_pads(self) -> Tuple[int, int]:
        """(small, large) flush-pad buckets, owned by the plan: the large
        one covers a growth tick (<= one install per row) and any
        admission's prompt pages in one cached executable."""
        return self.plan.install_pads(
            self.batch, self.ccfg.max_pages_per_session
        )

    def _queue_install(self, row: int, slot_idx: int, page: int) -> None:
        """Defer a page-table install; :meth:`_flush_installs` applies every
        pending one in a single batched dispatch (mesh-sharded tables:
        one dynamic-update-slice per CONTIGUOUS per-row run — a scatter
        over a sharded table aborts under GSPMD, but chaining one dispatch
        per page paid a host round trip each)."""
        self._pending_installs.append((row, slot_idx, page))

    def _flush_installs(self) -> None:
        if self.window_allocator is not None and self._pending_window_installs:
            # the window table's installs, in the same two warmed pad
            # buckets (its shape is the full table's)
            pending = self._pending_window_installs
            self._pending_window_installs = []
            self._install_batches(pending, "assign_window_pages_batch")
        if not self._pending_installs:
            return
        pending = self._pending_installs
        self._pending_installs = []
        if getattr(self, "mesh", None) is not None:
            # Group each row's pages into contiguous slot runs, then split
            # every run into POWER-OF-TWO chunks: one assign_pages (a DUS,
            # GSPMD-safe) per chunk. Binary decomposition keeps the set of
            # dispatched lengths to the pre-warmed {1, 2, 4, ...} ladder —
            # an arbitrary run length would compile a fresh executable per
            # length (a compile stall mid-serving), and padding a run to
            # a bucket cannot work here (the DUS clamps at the table edge
            # and would shift the write window onto other slots).
            runs: List[Tuple[int, int, List[int]]] = []
            for row, slot_idx, page in pending:
                if (
                    runs
                    and runs[-1][0] == row
                    and runs[-1][1] + len(runs[-1][2]) == slot_idx
                ):
                    runs[-1][2].append(page)
                else:
                    runs.append((row, slot_idx, [page]))
            for row, start, pages in runs:
                while pages:
                    n = 1 << (len(pages).bit_length() - 1)  # largest pow2 <=
                    self.cache = self.cache.assign_pages(
                        row, pages[:n], start
                    )
                    start += n
                    pages = pages[n:]
            return
        self._install_batches(pending, "assign_pages_batch")

    def _install_batches(self, pending, assign: str) -> None:
        """``pending`` (row, slot, page) installs through the cache's
        batched table write ``assign``."""
        # Exactly TWO pad buckets (both pre-compiled by _warm_table_write):
        # small flushes (one admission's prompt pages) and everything else.
        # Arbitrary pow2 pads would each compile mid-serving the first time
        # a new length appeared (a compile stall). A flush larger
        # than the big bucket (growth tick + oversized admission backlog in
        # one tick) splits into bucket-sized chunks — each a warmed
        # executable — instead of silently compiling an unwarmed length.
        small, big = self._install_pads()
        while pending:
            n = small if len(pending) <= small else big
            rows, slots_, pages = zip(*pending[:n])
            self.cache = getattr(self.cache, assign)(
                rows, slots_, pages, pad_to=n
            )
            pending = pending[n:]

    def _reshard_cache(self) -> None:
        """Re-apply the mesh shardings after a growth/shrink re-created the
        cache buffers (new arrays come back default-sharded; leaving them so
        would silently replicate the cache and serialize every step)."""
        if self.mesh is not None:
            self.cache = self._shard_pytree(
                self.cache, self.mesh, self._cache_pspecs(self.cache)
            )

    def _with_mesh(self, fn):
        """Run a jitted step inside the mesh context when serving sharded."""
        if self.mesh is None:
            return fn

        @functools.wraps(fn)  # keeps the jitted step reachable (.lower)
        def go(*a, **k):
            with self.mesh:
                return fn(*a, **k)

        return go

    # -- public API -----------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        options: Optional[SamplingOptions] = None,
        deadline: Optional[float] = None,
        sched_key: Optional[tuple] = None,
        trace=None,
    ) -> str:
        """Queue a prompt; returns its generation_id. Thread-safe.

        ``deadline`` is an absolute ``time.monotonic()`` instant: past it the
        scheduler reaps the session like a cancel (finish_reason
        ``"deadline"``), whether it is still queued or actively decoding.

        ``sched_key`` is the gateway scheduler's admission-ordering stamp
        (see :meth:`set_admission_order`); sessions without one are
        admitted FIFO.

        ``trace`` is the request's distributed TraceContext (None for
        unsampled requests); it rides the Session for span attribution
        and never affects scheduling or tokens."""
        if BOOT.first_request is None:
            BOOT.mark("first_request", self.flight)
        return self._submit_session(
            prompt, options, deadline, sched_key=sched_key, trace=trace
        ).generation_id

    def _submit_session(self, prompt, options, deadline=None,
                        sched_key=None, trace=None) -> Session:
        # Lock-free on purpose: step() holds the scheduler lock across whole
        # device steps (hundreds of ms at 7B shapes), and request-handler
        # threads must not stall on it. deque.append and dict insertion are
        # GIL-atomic; the scheduler only observes the session at its next
        # admission pass.
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        s = Session(
            prompt=list(prompt),
            options=options or SamplingOptions(),
            deadline=deadline,
            sched_key=sched_key,
            trace=trace,
        )
        self.sessions[s.generation_id] = s
        self.waiting.append(s)
        self.metrics.counter("sessions_submitted")
        return s

    def set_admission_order(self, fn) -> None:
        """Install the gateway scheduler's admission-ordering hook:
        ``fn(pending_sessions) -> ordered_sessions``, called under the
        engine lock at each tick with the reaped waiting queue. The
        engine admits a PREFIX of the returned order (free slots and
        page-pool pressure permitting) instead of FIFO-popping. The hook
        must be a pure reordering — a result that drops or invents
        sessions is discarded and the tick falls back to FIFO. Ordering
        affects WHICH sessions are admitted each tick, never the tokens
        any individual session produces. ``None`` restores FIFO."""
        self._admission_order = fn

    def cancel(self, generation_id: str) -> None:
        """Thread-safe and non-blocking: sets a monotonic flag; the
        scheduler converts it to the CANCELLED state at the next tick
        boundary (state transitions stay single-writer — a direct state
        write here could race the scheduler's own WAITING→ACTIVE transition
        mid-admission and be silently stomped)."""
        s = self.sessions.get(generation_id)
        if s is None or s.state == SessionState.FINISHED:
            return
        s.cancel_requested = True

    def step(self) -> List[Tuple[str, int, bool]]:
        """One scheduler tick: admit + decode. Returns
        ``[(generation_id, token, finished), …]`` events. ``token == -1``
        signals a finish without a new token (capacity rejection/exhaustion) —
        streaming consumers must not append it.

        Pipelined engines (a fused decode scan over a tail-capable cache,
        no draft model) dispatch the next device tick BEFORE resolving the
        previous one, so a tick's tokens arrive one ``step()`` later than
        they were dispatched."""
        # Flight recorder: host-clock only (no device_get, no
        # block_until_ready of its own), and None unless a TraceConfig
        # enabled it, so the disabled tick pays one attribute load + branch.
        # Its clock is read and written under the scheduler lock alone, as
        # is everything else a tick touches (waiting for the lock is the
        # tick's ``outside``).
        with self._lock:
            fr = self.flight
            if fr is None:
                return self._run_tick()
            tick = fr.begin()
            queued0 = len(self.waiting)
            # The profiler's host plane carries the tick (step_num = the
            # tick record's id) and, nested in it, the ``engine.<phase>``
            # regions.
            with jax.profiler.StepTraceAnnotation(
                "engine_tick", step_num=tick
            ):
                produced = self._run_tick()
            queued1 = len(self.waiting)
            dispatches, self.plan.dispatches = self.plan.dispatches, []
            fr.end(
                kind="pipelined" if self._pipelined else "plain",
                occupancy=sum(1 for g in self.slots if g is not None),
                queued=queued1,
                admitted=max(0, queued0 - queued1),
                chunking=len(self._chunking),
                parked=sum(
                    1 for s in self._chunking if s.parked_key is not None
                ),
                overlap_inflight=len(self._inflight_admits),
                pending=self._pending is not None,
                events=len(produced),
                dispatch=self.plan.last_dispatch,
                dispatches=dispatches,
                free_pages=(
                    self.allocator.free_count
                    if self.allocator is not None else None
                ),
                **self._window_pool_fields(),
            )
        return produced

    def _refuse_two_pools(self, what: str) -> None:
        """What moves a row's KV as ONE run of pages (disaggregated export
        and admission, preemption with resume) is refused by name for a
        stack of window and full layers: a window layer holds only the
        row's last pages; and for a stack of retention layers, whose rows
        hold a state and an open page. Such a row is neither exported nor
        resumed."""
        if self._retention:
            raise ValueError(
                f"{what} is not implemented for family {self.cfg.family!r} "
                "(ModelConfig.retention): a row's past is a folded state and "
                "its open page, not a run of pages that can be shipped"
            )
        if self.window_allocator is not None:
            raise ValueError(
                f"{what} is not implemented for a stack of window and full "
                "layers (ModelConfig.layer_attention): the window layers' "
                "pool keeps only a row's last pages"
            )

    def _window_pool_fields(self) -> Dict[str, int]:
        """A tick record's fields of the window pool (a stack of window and
        full layers; nothing otherwise): its free pages, beside
        ``free_pages`` for the full layers' pool, the most window pages a
        row holds now, which the window bounds whatever the context, and
        the pages both pools hold for live rows. The gauges say the same on
        ``/metrics``."""
        if self.window_allocator is None:
            return {}
        most = max(
            (len(s.window_pages) for s in self.sessions.values()
             if s.slot is not None),
            default=0,
        )
        if self._retention:
            # one pool: ``free_pages`` says the rest
            return {"row_window_pages_max": most}
        free = self.window_allocator.free_count
        self.metrics.gauge("window_pool_free_pages", float(free))
        self.metrics.gauge("kv_pool_free_pages", float(self.allocator.free_count))
        return {
            "free_window_pages": free, "row_window_pages_max": most,
            # pages live rows hold, (full layers' pool, window pool)
            "kv_pages_held": [
                self.allocator.num_pages - 1 - self.allocator.free_count,
                self.window_allocator.num_pages - 1 - free,
            ],
        }

    def _run_tick(self) -> List[Tuple[str, int, bool]]:
        """One tick's work; the caller holds the scheduler lock."""
        produced: List[Tuple[str, int, bool]] = []
        if self._ext_produced:
            produced.extend(self._ext_produced)
            self._ext_produced.clear()
        if self._pipelined:
            prev = self._pending
            with self._region("dispatch"):
                self._pending = self._dispatch_tick(produced, prev)
            with self._region("deliver"):
                self._resolve_pending(produced, prev)
            # Chunked-prefill co-scheduling rides BEHIND the decode
            # dispatch (device-ordered after it) and after the resolve,
            # so a final chunk's deferred first token rides the NEXT
            # tick's device_get exactly like an overlapped admission.
            with self._region("admit"):
                self._chunk_dispatch(produced)
                self._admit(produced)
        else:
            with self._region("admit"):
                self._admit(produced)
                self._chunk_dispatch(produced)
            if any(
                gid is not None and not self.sessions[gid].chunking
                for gid in self.slots
            ):
                with self._region("dispatch"):
                    self._decode_tick(produced)
            elif (
                self.draft is not None
                and self._spec_pending is not None
            ):
                # Every speculative session left (cancel/finish burst)
                # with a tick in flight and nothing was admitted:
                # _decode_tick won't run to drain it, so resolve here —
                # otherwise has_work() reports the orphaned pending
                # tick forever.
                with self._region("deliver"):
                    self._spec_flush(produced)
        return produced

    # -- the tick's host clock (utils/tracing.py FlightRecorder) -------------

    def _region(self, phase: str):
        """Where the drive thread is, for the flight recorder: a
        ``TraceAnnotation("engine.<phase>")`` on the profiler's host plane
        and the region's host seconds on the tick in progress, each region
        timed once (a region inside another suspends the outer one). A
        shared no-op with the recorder off."""
        fr = self.flight
        return _NO_REGION if fr is None else fr.region(phase)

    def _fetch(self, x):
        """``jax.device_get`` for the tick path: the time the drive thread
        waits here is the tick's ``blocked`` phase, measured at the sync
        itself and taken out of whichever phase encloses it. A clocked
        dispatch whose result is among ``x`` is ready once this returns."""
        with self._region("blocked"):
            got = jax.device_get(x)
        clk = self._clock
        if clk is not None and clk.armed:
            clk.fetched(x)
        return got

    def _clock_turned(self, armed: bool) -> None:
        """The dispatch clock was armed or disarmed (the drive thread, at a
        tick's start): while it is armed the attributes of the step programs
        (``_CLOCKED_PROGRAMS``) hold ``_clocked`` around the program, and
        otherwise the program itself. So every call site is written as if
        there were no clock, and an engine nobody watches calls its programs
        with the stack it had before there was one: a wrapper's frame on
        the path to a program's first call moved the lowering's inner loops
        across a boundary of CPython's frame stack and cost 0.33 s a prefill
        program, 6 s of chat's set-up (PERF.md §6, PR 41)."""
        self._noted_rows = ()
        if armed and not self._unclocked:
            for name in _CLOCKED_PROGRAMS:
                fn = getattr(self, name)
                if fn is None:      # this engine has no ``_prefill_fresh``
                    continue
                self._unclocked[name] = fn
                wrapped = functools.partial(self._clocked, fn)
                wrapped.__wrapped__ = fn
                setattr(self, name, wrapped)
        elif not armed:
            while self._unclocked:
                setattr(self, *self._unclocked.popitem())

    def _clocked(self, fn, *args):
        """Call the program of the dispatch just noted
        (``plan.note_dispatch``) under the armed dispatch clock: stamps as
        the drive thread enters the compiled call and as it returns, and the
        call's first result (the tokens, a chunk's count; never the cache)
        kept for the ready stamp: the clock's watcher waits on it, unless
        the drive thread fetches it at once (a decode dispatch of the
        synchronous tick: its ``_fetch`` is the stamp, and no watcher is
        woken beside a drive thread whose host time is the device's idle
        time). The sessions whose prompt a prefill-family dispatch carries
        (``_note_prefill``) remember it."""
        clk = self._clock
        entry = clk.enter()
        kind, shape = self.plan.last_dispatch[:2]
        decode = kind == "decode"
        rows, self._noted_rows = self._noted_rows, ()
        if not decode:
            for s in rows:
                s.prompt_clock.append(entry)
        out = fn(*args)
        clk.leave(
            entry, out[0], kind, shape[1] if decode else 1,
            watched=self._pipelined or not decode,
        )
        return out

    def _live_positions(self, active, pending=None) -> int:
        """The census of a decode dispatch (``plan.note_dispatch``): the
        host-known context lengths of its active rows, summed — with the
        tokens a tick in flight may still deliver (``pending``) counted
        in."""
        live = 0
        for slot in np.flatnonzero(active):
            live += self.sessions[self.slots[slot]].total_len
        if pending is not None:
            live += int(pending[active].sum())
        return live

    def _decode_spans(self, active, steps: int, pending=None):
        """A decode dispatch's queries as ``(first position, queries)``
        pairs, a pair an active row, for the census of what its queries see
        (``plan.note_dispatch``: a selection's keys, a window's, the pages
        a paged pool's sweep attends); None where nothing counts them."""
        if (
            self.plan.sparse_topk is None and not self.plan.windowed
            and self.ccfg.kind != "paged"
        ):
            return None
        return [
            (
                self.sessions[self.slots[slot]].total_len - 1
                + (0 if pending is None else int(pending[slot])),
                steps,
            )
            for slot in np.flatnonzero(active)
        ]

    def _note_prefill(self, kind: str, shape, row_spans, rows) -> None:
        """The census of a prefill-family dispatch (``plan.note_dispatch``):
        ``row_spans`` holds a ``(first position, tokens)`` pair for every
        real row, which is what the ragged kernel sees as ``q_start`` and
        ``num_new``; over a paged cache the table's width goes with it.
        ``rows`` are the sessions whose prompt the dispatch carries, for
        the dispatch clock (``_clocked``). Its real rows count as
        ``prefill_pool_inplace_rows`` or ``prefill_pool_scatter_rows``, by
        the cache (``ragged_reads_whole_stacks``)."""
        self._noted_rows = rows
        if self._pool_inplace:
            self.metrics.counter("prefill_pool_inplace_rows", len(row_spans))
        else:
            self.metrics.counter("prefill_pool_scatter_rows", len(row_spans))
        paged = self.ccfg.kind == "paged"
        self.plan.note_dispatch(
            kind, shape, sum(n for _, n in row_spans), row_spans=row_spans,
            table_width=self.cache.page_table.shape[1] if paged else None,
            query_spans=row_spans,
        )

    def _note_admitted(self, s: Session) -> None:
        """The admission dispatch takes ``s`` (called right before its
        prefill is dispatched, or as it is parked for chunked prefill):
        ``engine_queue_wait`` observes submit → now, and a traced session
        records the same as its ``engine.queue`` span."""
        now = time.monotonic()
        s.admit_time = now
        self.metrics.observe("engine_queue_wait", now - s.submit_time)
        if s.trace is not None and self.tracer is not None:
            self._request_span("engine.queue", s, s.submit_time, now)

    def _note_first_token(self, s: Session) -> None:
        """``engine_first_token_wait`` observes admission dispatch → now,
        and under an armed dispatch clock its three pieces, which sum to it
        (``DispatchClock.first_token``): the wait for the device, the
        device's time on the session's own prompt dispatches, and the time
        the token lay ready. A traced session records the same as its
        ``engine.first_token`` span and, under it, one span a piece."""
        wait = s.first_token_time - s.admit_time
        self.metrics.observe("engine_first_token_wait", wait)
        pieces = ()
        clocked, s.prompt_clock = s.prompt_clock, []
        clk = self._clock
        # the pieces of a prompt that one lease of the clock saw whole
        if clocked and clk.armed and clk.armed_at <= s.admit_time:
            pieces = clk.first_token(clocked, wait)
            self.metrics.observe("engine_first_token_prefill_wait", pieces[0])
            self.metrics.observe("engine_first_token_prefill_own", pieces[1])
            self.metrics.observe("engine_first_token_deliver", pieces[2])
        if s.trace is None or self.tracer is None:
            return
        whole = self._request_span(
            "engine.first_token", s, s.admit_time, s.first_token_time
        )
        t = s.admit_time
        for name, seconds in zip(_FIRST_TOKEN_SPANS, pieces):
            seconds = max(0.0, seconds)
            self._request_span(name, s, t, t + seconds, parent=whole)
            t += seconds

    def _request_span(
        self, name: str, s: Session, m0: float, m1: float, parent=None
    ):
        """One span of a traced session between two ``time.monotonic()``
        readings, stamped on the epoch clock like every other span of the
        request, as a child of the request's context (or of ``parent``).
        Returns the span's own context."""
        c = (parent or s.trace).child()
        fr = self.flight
        self.tracer.record(Span(
            name, time.time() - (time.monotonic() - m0), m1 - m0,
            {"gen_id": s.generation_id,
             "tick": fr.tick if fr is not None else None},
            trace_id=c.trace_id, span_id=c.span_id, parent_id=c.parent_id,
            node="engine",
        ))
        return c

    def has_work(self) -> bool:
        with self._lock:
            return (
                bool(self.waiting)
                or any(s is not None for s in self.slots)
                or self._pending is not None
                or bool(self._inflight_admits)
                or bool(self._ext_produced)
                or getattr(self, "_spec_pending", None) is not None
            )

    def active_sessions(self) -> int:
        """Resident (decoding) sessions. Lock-free snapshot for
        observability — a concurrent tick may shift it by the time the
        caller reads it."""
        return sum(1 for g in self.slots if g is not None)

    def queue_depth(self) -> int:
        """Sessions waiting for a slot. Lock-free snapshot."""
        return len(self.waiting)

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        options: Optional[SamplingOptions] = None,
        max_steps: int = 100_000,
    ) -> List[List[int]]:
        """Blocking convenience API: run all prompts to completion."""
        # Hold the Session objects themselves: a concurrent
        # collect_finished() may reap the dict entries at any point.
        subs = [self._submit_session(p, options) for p in prompts]
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
        return [s.generated for s in subs]

    def collect_finished(self) -> Dict[str, Session]:
        """Remove and return finished/cancelled sessions. Callers that stream
        via ``step()`` must collect periodically or host memory grows with
        total requests served."""
        with self._lock:
            # list(): submit() inserts into the dict lock-free; a snapshot
            # keeps concurrent submission from breaking this iteration.
            done = {
                gid: s
                for gid, s in list(self.sessions.items())
                if s.state in (SessionState.FINISHED, SessionState.CANCELLED)
                and s.slot is None
            }
            for gid in done:
                del self.sessions[gid]
            return done

    # -- prefix/KV reuse (prefixstore/) ---------------------------------------

    def _note_prefix(self, total: int, reused: int) -> None:
        """Uniform prefix-reuse accounting: EVERY admission path (local
        ``_admit``, disaggregated ``admit_prefilled``, spill reloads — they
        land in the shared-page count) reports through here, so the
        ``prefix_cached_tokens`` counter and the cumulative token-weighted
        ``prefix_hit_rate`` gauge cannot drift between paths."""
        self._prefix_seen += total
        self._prefix_hits += reused
        if reused:
            self.metrics.counter("prefix_cached_tokens", reused)
        if self._prefix_seen:
            self.metrics.gauge(
                "prefix_hit_rate", self._prefix_hits / self._prefix_seen
            )

    def _spill_page(self, page: int, key: bytes) -> None:
        """Allocator ``on_evict`` hook: snapshot an evicted registered
        prefix page's stored-form tiles into the host arena (runs under the
        scheduler lock, inside ``alloc``, BEFORE the page returns to the
        free list — content still valid; ``read_page`` blocks until pending
        device writes settle)."""
        tiles = self.cache.read_page(page)
        if self._spill.put(key, tiles):
            self.metrics.counter("prefix_spilled_pages")
        self.metrics.gauge("prefix_spill_bytes", float(self._spill.bytes_used))

    def _reload_spilled(self, keys, shared: List[int], cap: int) -> List[int]:
        """Extend a device-registry prefix match with host-arena reloads:
        walk ``keys[len(shared):cap]``, re-checking the registry first (a
        taken entry may have been reloaded by an earlier admission), then
        reloading arena tiles into a fresh page. A rejected (corrupted)
        entry degrades to recompute from that point — never wedges
        admission. Returned pages are referenced like ``lookup``'s."""
        while len(shared) < cap:
            key = keys[len(shared)]
            page = self.allocator.lookup_one(key)
            if page is None:
                tiles = self._spill.take(key)
                if tiles is None:
                    break
                t0 = time.perf_counter()
                try:
                    [page] = self.allocator.alloc(1)
                except MemoryError:
                    self._spill.put(key, tiles)  # park it for a calmer tick
                    break
                try:
                    self.cache = self.cache.write_page(page, tiles)
                except ValueError:
                    # Corrupted arena entry: reject BEFORE it can poison
                    # the pool; recompute covers the rest of the prompt.
                    self.allocator.free([page])
                    self.metrics.counter("prefix_reload_errors")
                    break
                self.allocator.register(page, key)
                self.metrics.counter("prefix_spill_reloads")
                self.metrics.observe(
                    "prefix_reload_ms", (time.perf_counter() - t0) * 1e3
                )
            shared.append(page)
        self.metrics.gauge("prefix_spill_bytes", float(self._spill.bytes_used))
        return shared

    def advertised_prefix_heads(self, limit: int = 1024) -> List[str]:
        """Hex chain keys this node can serve a prefix hit from — device
        registry plus spill arena — newest-biased and bounded; what the
        decode node advertises to the block directory each heartbeat."""
        if self.allocator is None or not self.ccfg.prefix_caching:
            return []
        with self._lock:
            keys = self.allocator.registered_keys(limit)
            if self._spill is not None:
                dev = set(keys)
                keys += [k for k in self._spill.keys() if k not in dev]
        return [k.hex() for k in keys[-limit:]]

    def prefix_match_tokens(self, prompt) -> int:
        """Longest locally-cached prefix of ``prompt`` in TOKENS
        (page-granular), WITHOUT taking page references — the gateway's
        routing probe for preferring a prefix-holding engine."""
        if self.allocator is None or not self.ccfg.prefix_caching:
            return 0
        ps = self.ccfg.page_size
        keys = PageAllocator.chain_keys(prompt, ps)
        matched = 0
        with self._lock:
            for key in keys:
                if self.allocator.peek(key) is None and not (
                    self._spill is not None and key in self._spill
                ):
                    break
                matched += ps
        return matched

    def export_prefix_pages(self, prompt):
        """Stored-form tiles of the longest locally cached prefix of
        ``prompt`` — device registry pages plus spill-arena entries,
        page-granular, WITHOUT taking page references — for the fleet
        page-ship path (``fleet.pages``). Returns ``(page_size, items)``
        where ``items`` is an ordered ``[(chain_key, tiles), ...]`` list
        ready for ``kv_codec.encode_pages``; empty when prefix caching
        is off or nothing matches. Tiles round-trip verbatim, so the
        importer's pages are bit-exact with this node's."""
        if self.allocator is None or not self.ccfg.prefix_caching:
            return self.ccfg.page_size, []
        ps = self.ccfg.page_size
        keys = PageAllocator.chain_keys(prompt, ps)
        items = []
        with self._lock:
            for key in keys:
                page = self.allocator.peek(key)
                if page is not None:
                    items.append((key, self.cache.read_page(page)))
                    continue
                tiles = (self._spill.peek(key)
                         if self._spill is not None else None)
                if tiles is None:
                    break
                items.append((key, tiles))
        return ps, items

    def import_prefix_pages(self, page_size: int, items) -> int:
        """Install shipped prefix pages (``kv_codec.decode_pages`` items)
        into this engine's pool: each page lands registered at refcount
        0 — immediately servable to prefix-matching admissions, evictable
        (LRU, via the spill arena when configured) under pressure, exactly
        like a page left behind by a released session. Already-resident
        keys are skipped; pool pressure parks tiles in the arena instead
        (still servable); a tile/page-shape mismatch raises ``ValueError``
        after freeing the staged page. Returns pages made servable."""
        if self.allocator is None or not self.ccfg.prefix_caching:
            return 0
        if int(page_size) != self.ccfg.page_size:
            raise ValueError(
                f"page-ship size {page_size} != pool page size "
                f"{self.ccfg.page_size}")
        installed = 0
        with self._lock:
            for key, tiles in items:
                if self.allocator.peek(key) is not None:
                    continue  # already device-resident
                if self._spill is not None and key in self._spill:
                    continue  # already arena-resident
                try:
                    [page] = self.allocator.alloc(1)
                except MemoryError:
                    if self._spill is not None and self._spill.put(key, tiles):
                        installed += 1  # servable from the arena
                        continue
                    break
                try:
                    self.cache = self.cache.write_page(page, tiles)
                except ValueError:
                    self.allocator.free([page])
                    self.metrics.counter("prefix_reload_errors")
                    raise
                self.allocator.register(page, key)
                self.allocator.free([page])  # registered, refcount 0
                installed += 1
        if installed:
            self.metrics.counter("fleet_pages_imported", installed)
        return installed

    # -- disaggregated prefill/decode (disagg/) -------------------------------

    def prefill_export(self, prompt, options=None):
        """Prefill-pool entry point: run ONE prompt's bucketed admission
        prefill on this engine, sample its first token, and export
        ``(planes, first_token, chain)`` for a remote decode pool — then
        release the row (the session never decodes here).

        ``planes`` is :meth:`export_kv_row`'s host dict; ``chain`` is the
        prompt's page-granular hash chain (``PageAllocator.chain_keys``
        over ``CacheConfig.page_size``), shipped so the importer can verify
        the KV answers the prompt it asked about. Lifecycle knobs
        (eos/max_new_tokens) are neutralized for the local run — the
        decode pool owns those decisions, and a first token that happened
        to hit eos must not finish-and-free the row before its KV is
        exported. Sampling knobs pass through untouched, so the sampled
        first token is byte-identical to a colocated engine's.

        Raises ``RuntimeError`` when admission fails (capacity rejection
        or page-pool pressure) — callers answer with an error frame and
        the gateway falls back to local prefill."""
        self._refuse_two_pools("disaggregated prefill export")
        if isinstance(self.cache, _SINK_KINDS):
            raise ValueError(
                "disaggregated prefill unsupported for sink caches"
            )
        run_opts = dataclasses.replace(
            options or SamplingOptions(),
            max_new_tokens=1 << 30, eos_token_id=-1,
        )
        with self._lock:
            produced: List[Tuple[str, int, bool]] = []
            s = self._submit_session(prompt, run_opts)
            try:
                self._admit(produced)
                if not s.generated:
                    reason = s.finish_reason or "pool pressure"
                    raise RuntimeError(
                        f"prefill admission failed: {reason}"
                    )
                planes = self.export_kv_row(s)
                chain = PageAllocator.chain_keys(
                    s.prompt, self.ccfg.page_size
                )
                self.metrics.counter("disagg_prefills")
                return planes, s.generated[0], chain
            finally:
                if s.slot is not None:
                    s.state = SessionState.CANCELLED
                    s.finish_reason = "exported"
                    self._release(s)
                else:
                    # Capacity-rejected (already finished) or still queued
                    # under pool pressure — drop the queue entry either way.
                    try:
                        self.waiting.remove(s)
                    except ValueError:
                        pass
                self.sessions.pop(s.generation_id, None)

    def export_kv_row(self, s: Session, n: Optional[int] = None):
        """Contiguous host copies of a resident session's KV in the
        STORED representation (so a same-config importer is bit-exact):
        value planes ``[L, S, Hkv, D]`` under ``"k"``/``"v"`` — bf16 (or
        engine dtype) for value caches, int8 for quantized ones, the
        latter alongside f32 scale planes ``[L, S, Hkv]`` under
        ``"ks"``/``"vs"``. Latent (MLA) caches ship their stored form
        instead: one fused latent plane ``[L, S, 1, rank + dr]`` under
        ``"c"`` (f32, or int8 beside an f32 ``"cs"`` scale plane
        ``[L, S, 1]``) — per-head K/V are never materialized, which is
        what shrinks the disagg wire and migration checkpoints. ``S = n``
        tokens from position 0 — the default ``len(s.prompt)`` covers the
        prompt (disagg prefill export); session checkpoints pass
        ``total_len - 1`` to take the decoded tail too. Keys are
        post-RoPE, as cached. Caller holds the scheduler lock (or owns
        the engine)."""
        self._refuse_two_pools("exporting a row's KV")
        n = len(s.prompt) if n is None else int(n)
        cache = self.cache
        if isinstance(cache, LatentPagedKVCache):
            pages = jnp.asarray(np.asarray(s.pages, np.int32))
            a = jnp.transpose(cache.k_pages[:, pages], (0, 1, 3, 2, 4))
            a = a.reshape(a.shape[0], -1, *a.shape[3:])
            out = {"c": np.asarray(a[:, :n])}
            if isinstance(cache, QuantizedLatentPagedKVCache):
                sc = jnp.transpose(cache.cs_pages[:, pages], (0, 1, 3, 2))
                sc = sc.reshape(sc.shape[0], -1, sc.shape[3])
                out["cs"] = np.asarray(sc[:, :n])
            # a selection's index keys, of the layers that score: [Ls,S,1,D]
            for name, f in getattr(cache, "INDEX_PLANES", {}).items():
                a = jnp.transpose(
                    getattr(cache, f)[:, pages], (0, 1, 3, 2, 4)
                )
                out[name] = np.asarray(
                    a.reshape(a.shape[0], -1, *a.shape[3:])[:, :n]
                )
            return out
        if isinstance(cache, PagedKVCache):
            pages = jnp.asarray(np.asarray(s.pages, np.int32))

            def vals(pool):  # [L,P,H,ps,D] -> [L,S,H,D]
                a = jnp.transpose(pool[:, pages], (0, 1, 3, 2, 4))
                a = a.reshape(a.shape[0], -1, *a.shape[3:])
                return np.asarray(a[:, :n])

            out = {"k": vals(cache.k_pages), "v": vals(cache.v_pages)}
            if isinstance(cache, QuantizedPagedKVCache):

                def scales(pool):  # [L,P,H,ps] -> [L,S,H]
                    a = jnp.transpose(pool[:, pages], (0, 1, 3, 2))
                    a = a.reshape(a.shape[0], -1, a.shape[3])
                    return np.asarray(a[:, :n])

                out["ks"] = scales(cache.ks_pages)
                out["vs"] = scales(cache.vs_pages)
            # what a learned selection stores beside K and V: [L,S,1,D]
            for name, f in getattr(cache, "INDEX_PLANES", {}).items():
                out[name] = vals(getattr(cache, f))
            return out
        if isinstance(cache, QuantizedDenseKVCache):
            return {  # head-major [L,B,H,T,D] -> time-major [L,S,H,D]
                "k": np.asarray(jnp.swapaxes(cache.k[:, s.slot, :, :n], 1, 2)),
                "v": np.asarray(jnp.swapaxes(cache.v[:, s.slot, :, :n], 1, 2)),
                "ks": np.asarray(jnp.swapaxes(cache.ks[:, s.slot, :, :n], 1, 2)),
                "vs": np.asarray(jnp.swapaxes(cache.vs[:, s.slot, :, :n], 1, 2)),
            }
        if isinstance(cache, DenseKVCache):
            return {
                "k": np.asarray(cache.k[:, s.slot, :n]),
                "v": np.asarray(cache.v[:, s.slot, :n]),
            }
        raise ValueError(
            f"KV export unsupported for {type(cache).__name__}"
        )

    def _check_planes(self, planes, n: int):
        """Validate shipped KV planes against this cache's stored form and
        return them as device arrays with a batch-1 axis inserted (the
        shape :meth:`_ingest_row` wants). The plane-name set doubles as
        the family/quantization handshake: value caches want ``k``/``v``
        (+ ``ks``/``vs`` when int8), latent caches want ``c`` (+ ``cs``)
        — a mismatch is a structural error, never a silent reinterpret."""
        cache = self.cache
        if isinstance(cache, QuantizedLatentPagedKVCache):
            want = {"c", "cs"}
        elif isinstance(cache, LatentPagedKVCache):
            want = {"c"}
        elif isinstance(
            cache, (QuantizedPagedKVCache, QuantizedDenseKVCache)
        ):
            want = {"k", "v", "ks", "vs"}
        else:
            want = {"k", "v"}
        index_planes = getattr(cache, "INDEX_PLANES", {})
        want |= set(index_planes)
        if set(planes) != want:
            raise ValueError(
                f"KV planes {sorted(planes)} do not match this cache "
                f"(want {sorted(want)}: cache family and quantization "
                f"must agree across pools)"
            )
        if "c" in want:
            shape = (self.cfg.cache_layers, n, 1, self.cfg.latent.lat_dim)
        else:
            shape = (
                self.cfg.cache_layers, n,
                self.cfg.num_kv_heads, self.cfg.head_dim,
            )
        for name in sorted(want):
            expect = shape if name in ("c", "k", "v") else shape[:3]
            if name in index_planes:  # one index key a token a scoring layer
                pool = getattr(cache, index_planes[name])
                expect = (pool.shape[0], n, 1, pool.shape[4])
            got = tuple(np.asarray(planes[name]).shape)
            if got != expect:
                raise ValueError(
                    f"KV plane {name!r} shape {got} != expected {expect}"
                )
        return {name: jnp.asarray(planes[name])[:, None] for name in want}

    def _ingest_row(self, sub, dev, n: int, first_slot: int = 0):
        """Scatter validated planes (from :meth:`_check_planes`) into a
        batch-1 cache view, dispatching on the stored form."""
        cache = self.cache
        index_planes = getattr(cache, "INDEX_PLANES", None)
        if index_planes:
            sub = sub.ingest_index_row(
                {name: dev[name] for name in index_planes}, n,
                first_slot=first_slot,
            )
        if isinstance(cache, LatentPagedKVCache):
            return sub.ingest_latent_row(dev, n, first_slot=first_slot)
        if isinstance(cache, QuantizedPagedKVCache):
            return sub.ingest_planes_row(
                dev["k"], dev["v"], dev["ks"], dev["vs"], n,
                first_slot=first_slot,
            )
        if isinstance(cache, PagedKVCache):
            return sub.ingest_row(
                dev["k"], dev["v"], n, first_slot=first_slot
            )
        if isinstance(cache, QuantizedDenseKVCache):
            return sub.ingest_planes_row(
                dev["k"], dev["v"], dev["ks"], dev["vs"], n
            )
        return sub.ingest_row(dev["k"], dev["v"], n)

    def admit_prefilled(
        self,
        prompt: Sequence[int],
        planes,
        first_token: int,
        options: Optional[SamplingOptions] = None,
        deadline: Optional[float] = None,
        trace=None,
    ) -> Optional[str]:
        """Admit a session whose prompt KV was prefilled REMOTELY: allocate
        a row (and pages), ingest the shipped planes into a batch-1 view,
        seed the prefix cache from the imported prompt pages, and enter
        decode directly — delivering ``first_token`` through the overlap
        machinery (``_defer_admit``) when a pipelined tick is in flight so
        the import never stalls it, else synchronously via the external
        event buffer ``step()`` drains.

        Returns the generation_id, or ``None`` when no slot (or page-pool
        headroom) is free right now — back-pressure the caller resolves by
        falling back to a local :meth:`submit`. Raises ``ValueError`` when
        the planes are structurally incompatible with this engine (wrong
        quantization, shape, or cache family)."""
        self._refuse_two_pools("admitting a remotely prefilled session")
        if isinstance(self.cache, _SINK_KINDS):
            raise ValueError(
                "disaggregated admission unsupported for sink caches"
            )
        if self.mesh is not None:
            raise ValueError("disaggregated admission is single-device only")
        if self.draft is not None:
            raise ValueError(
                "disaggregated admission incompatible with a draft model"
            )
        prompt = list(prompt)
        n = len(prompt)
        if n == 0:
            raise ValueError("empty prompt")
        dev = self._check_planes(planes, n)
        with self._lock:
            slot = next(
                (i for i in range(self.batch) if self.slots[i] is None), None
            )
            if slot is None:
                return None
            s = Session(
                prompt=prompt,
                options=options or SamplingOptions(),
                deadline=deadline,
                trace=trace,
            )
            s.disagg = True
            if not self._capacity_ok(s):
                raise ValueError(
                    "prompt exceeds this engine's per-session capacity"
                )
            self._ensure_capacity(n + 1)
            self.cache = self.cache.reset_rows(jnp.arange(self.batch) == slot)
            if isinstance(self.cache, PagedKVCache):
                ps = self.ccfg.page_size
                need = math.ceil((n + 1) / ps)
                shared: List[int] = []
                if self.ccfg.prefix_caching:
                    s.prefix_keys = PageAllocator.chain_keys(prompt, ps)
                    if self.pcfg.prefix_share:
                        # Attach locally cached prefix pages instead of
                        # re-installing the shipped copy of the same
                        # content (bit-exact either way: stored-form
                        # planes round-trip verbatim across pools). The
                        # FULL chain is eligible — first_token already
                        # rode the frame, so no last-token recompute (and
                        # no CoW) is needed here.
                        shared = self.allocator.lookup(s.prefix_keys)
                        if self._spill is not None and len(shared) < len(
                            s.prefix_keys
                        ):
                            shared = self._reload_spilled(
                                s.prefix_keys, shared, len(s.prefix_keys)
                            )
                if need - len(shared) > self.allocator.free_count:
                    if shared:
                        self.allocator.free(shared)
                    return None  # pool pressure: same signal as a full batch
                s.pages = shared + self.allocator.alloc(need - len(shared))
                shared_len = len(shared) * ps
                try:
                    for i, pg in enumerate(s.pages):
                        self._queue_install(slot, i, pg)
                    self._flush_installs()  # the ingest scatter reads the table
                    if shared_len < n:
                        sub = self.cache.select_row(slot)
                        sub = self._ingest_row(
                            sub, dev, n, first_slot=len(shared)
                        )
                        self.cache = self.cache.merge_row(sub, slot)
                    else:
                        # Whole prompt served from shared pages: nothing to
                        # ingest, just set the row's write offset.
                        self.cache = self.cache.replace(
                            lengths=self.cache.lengths.at[slot].set(n)
                        )
                    if shared:
                        self.metrics.counter(
                            "prefix_pages_shared", len(shared)
                        )
                    if self.ccfg.prefix_caching:
                        # Imported prompt pages seed the prefix cache exactly
                        # like locally prefilled ones (no-op for the shared
                        # head — those keys are already registered).
                        for i, key in enumerate(s.prefix_keys):
                            self.allocator.register(s.pages[i], key)
                        self._note_prefix(n, shared_len)
                except BaseException:
                    # The session was never published — nothing else frees
                    # these pages if the ingest/prefix path raises.
                    self.allocator.free(s.pages)
                    s.pages = []
                    s.prefix_keys = []
                    raise
            else:
                sub = self.cache.select_row(slot)
                sub = self._ingest_row(sub, dev, n)
                self.cache = self.cache.merge_row(sub, slot)
            self.sessions[s.generation_id] = s
            s.slot = slot
            s.state = SessionState.ACTIVE
            self.slots[slot] = s.generation_id
            self.metrics.counter("sessions_submitted")
            self.metrics.counter("disagg_admitted")
            # Consume the RNG split a local prefill would have spent on its
            # first-token sample: the decode-tick key sequence then matches
            # a colocated engine's byte-for-byte (sampled-parity contract).
            self._next_key()
            first = int(first_token)
            if self._overlap_ok():
                self._defer_admit(
                    [s], jnp.asarray([first], jnp.int32),
                    np.asarray([slot], np.int32), [n],
                )
            else:
                self.metrics.counter("admit_sync_sessions")
                self._finish_prefill(
                    s, first, np.asarray(prompt, np.int32),
                    self._ext_produced, n,
                )
            return s.generation_id

    # -- session checkpoint / migration (crash recovery) ----------------------

    def export_session(self, generation_id: str):
        """Snapshot a RESIDENT mid-decode session for migration to another
        engine: host KV planes for its first ``total_len - 1`` positions
        (prompt + ``generated[:-1]`` — the KV-after-decode invariant: the
        last generated token is the next decode input and has no cache
        entry yet), the generated-token tail, sampling options, and the
        engine's RNG key state, all JSON/codec-friendly (planes excepted).

        The in-flight pipelined tick (and any overlapped admissions) is
        drained first so device KV and host bookkeeping agree — drained
        tokens land in ``_ext_produced`` and reach consumers through the
        next ``step()``, so none are lost. Checkpoints therefore always
        sit on a tick boundary, which is what makes a resumed engine's
        RNG-key consumption realign with the source's (byte-exact resume
        contract; see :meth:`resume_session`).

        Returns ``None`` when the session is unknown, not resident, or
        finished during the drain (the terminal event is already on its
        way to the consumer — nothing to migrate)."""
        self._refuse_two_pools("preemption (export_session)")
        with self._lock:
            s = self.sessions.get(generation_id)
            if s is None or s.state != SessionState.ACTIVE:
                return None
            prev, self._pending = self._pending, None
            if prev is not None or self._inflight_admits:
                self._resolve_pending(self._ext_produced, prev)
            if s.state != SessionState.ACTIVE or s.slot is None:
                return None
            if not s.generated:
                return None  # no committed token yet — nothing to anchor on
            planes = self.export_kv_row(s, s.total_len - 1)
            snapshot = {
                "prompt": list(s.prompt),
                "generated": list(s.generated),
                "options": dataclasses.asdict(s.options),
                "rng": np.asarray(self.rng).tolist(),
                "resumes": s.resumes,
                "planes": planes,
            }
            self.metrics.counter("sessions_exported")
            return snapshot

    def resume_session(
        self,
        snapshot,
        deadline: Optional[float] = None,
        trace=None,
    ) -> Optional[str]:
        """Re-admit a session exported by :meth:`export_session` and keep
        decoding from its exact position: ingest KV for
        ``len(prompt) + len(generated) - 1`` tokens, publish the session
        with its original prompt/generated split (prefix-cache keys cover
        prompt pages only), and let the next tick feed ``last_token`` —
        no token is emitted here, decode simply continues.

        Byte-exact resume contract: when this engine is QUIET (no other
        resident/waiting sessions, no tick in flight) the snapshot's RNG
        key replaces the engine's, so with the same model/config/batch
        the continued sample stream is bit-identical to the source
        engine's — the gateway's recovery replay depends on this. On a
        busy engine the RNG is left alone (greedy streams stay exact;
        sampled ones continue from this engine's key sequence).

        Returns the new generation_id, ``None`` on slot/page pressure
        (caller retries elsewhere), and raises ``ValueError`` on
        structural mismatch (quantization/shape/cache family) or a
        snapshot that is already complete."""
        self._refuse_two_pools("resume (resume_session)")
        if isinstance(self.cache, _SINK_KINDS):
            raise ValueError("session resume unsupported for sink caches")
        if self.mesh is not None:
            raise ValueError("session resume is single-device only")
        if self.draft is not None:
            raise ValueError("session resume incompatible with a draft model")
        prompt = [int(t) for t in snapshot["prompt"]]
        generated = [int(t) for t in snapshot["generated"]]
        if not prompt:
            raise ValueError("empty prompt")
        if not generated:
            raise ValueError("snapshot carries no generated tokens")
        opts = snapshot.get("options")
        if isinstance(opts, dict):
            known = {f.name for f in dataclasses.fields(SamplingOptions)}
            opts = SamplingOptions(
                **{k: v for k, v in opts.items() if k in known}
            )
        options = opts or SamplingOptions()
        if len(generated) >= options.max_new_tokens:
            raise ValueError("snapshot is already at max_new_tokens")
        if options.eos_token_id >= 0 and generated[-1] == options.eos_token_id:
            raise ValueError("snapshot already ended at eos")
        planes = snapshot["planes"]
        n = len(prompt) + len(generated) - 1
        limit = (
            self.ecfg.max_seq_len
            if isinstance(self.cache, (DenseKVCache, QuantizedDenseKVCache))
            else self.ccfg.max_pages_per_session * self.ccfg.page_size
        )
        if n + 1 > limit:
            raise ValueError(
                "snapshot exceeds this engine's per-session capacity"
            )
        dev = self._check_planes(planes, n)
        with self._lock:
            slot = next(
                (i for i in range(self.batch) if self.slots[i] is None), None
            )
            if slot is None:
                return None
            quiet = (
                not self.waiting
                and not self._inflight_admits
                and self._pending is None
                and all(g is None for g in self.slots)
            )
            s = Session(
                prompt=prompt,
                options=options,
                deadline=deadline,
                generated=generated,
                trace=trace,
            )
            s.disagg = True
            s.resumes = int(snapshot.get("resumes", 0)) + 1
            self._ensure_capacity(n + 1)
            self.cache = self.cache.reset_rows(jnp.arange(self.batch) == slot)
            if isinstance(self.cache, PagedKVCache):
                ps = self.ccfg.page_size
                need = math.ceil((n + 1) / ps)
                if need > self.allocator.free_count:
                    return None  # pool pressure: same signal as a full batch
                s.pages = self.allocator.alloc(need)
                try:
                    for i, pg in enumerate(s.pages):
                        self._queue_install(slot, i, pg)
                    self._flush_installs()
                    sub = self.cache.select_row(slot)
                    sub = self._ingest_row(sub, dev, n)
                    self.cache = self.cache.merge_row(sub, slot)
                    if self.ccfg.prefix_caching:
                        # Only prompt-covered pages are content-addressable;
                        # generated-tail pages depend on sampling.
                        s.prefix_keys = PageAllocator.chain_keys(prompt, ps)
                        for i, key in enumerate(s.prefix_keys):
                            self.allocator.register(s.pages[i], key)
                except BaseException:
                    self.allocator.free(s.pages)
                    s.pages = []
                    s.prefix_keys = []
                    raise
            else:
                sub = self.cache.select_row(slot)
                sub = self._ingest_row(sub, dev, n)
                self.cache = self.cache.merge_row(sub, slot)
            self.sessions[s.generation_id] = s
            s.slot = slot
            s.state = SessionState.ACTIVE
            self.slots[slot] = s.generation_id
            self._carry_ok[slot] = False  # next tick feeds last_token fresh
            if quiet and snapshot.get("rng") is not None:
                self.rng = jnp.asarray(
                    np.asarray(snapshot["rng"], dtype=np.uint32)
                )
            self.metrics.counter("sessions_submitted")
            self.metrics.counter("sessions_resumed")
            return s.generation_id

    # -- scheduling internals -------------------------------------------------

    def _next_key(self) -> jax.Array:
        self.rng, k = jax.random.split(self.rng)
        return k

    def _bucket_for(self, n: int) -> int:
        # Still the admission-partition key in ragged mode (plan docstring:
        # partition == PRNG key order), even though pad widths differ.
        return self.plan.bucket_for(n)

    def _max_chunk(self) -> int:
        """Largest prefill chunk the cache accepts (sink ring constraint)."""
        if isinstance(self.cache, _SINK_KINDS):
            return min(
                self.ecfg.prefill_buckets[-1],
                self.ccfg.window_length - self.ccfg.num_sink_tokens,
            )
        return self.ecfg.prefill_buckets[-1]

    def _capacity_ok(self, s: Session) -> bool:
        if isinstance(self.cache, _SINK_KINDS):
            return True
        limit = (
            self.ecfg.max_seq_len
            if isinstance(self.cache, (DenseKVCache, QuantizedDenseKVCache))
            else self.ccfg.max_pages_per_session * self.ccfg.page_size
        )
        return len(s.prompt) + 1 <= limit

    def _shrink_if_idle(self) -> None:
        """With no resident sessions, re-create the dense buffer at the
        smallest bucket (nothing to copy) — one long-context session must not
        pin its high-water-mark buffer (and its decode bandwidth cost) for
        the rest of the process — once it has had none for
        :data:`IDLE_SHRINK_S`. Shapes revisited later hit the jit cache."""
        if not self._windows or any(g is not None for g in self.slots):
            return
        if time.monotonic() - self._idle_since < IDLE_SHRINK_S:
            return
        if isinstance(self.cache, PagedKVCache):
            if self.cache.page_table.shape[1] > self._first_slots:
                # With no resident sessions every row is either already
                # reset or will be reset at its next admission (stale ids
                # are masked until then) — truncating columns is free and
                # restores the narrow gather.
                self.cache = self.cache.resize_table(self._first_slots)
                self._reshard_cache()
            return
        if not isinstance(self.cache, (DenseKVCache, QuantizedDenseKVCache)):
            return
        if self.cache.max_len > self._windows[0]:
            kw = (
                {"use_kernel": self.cache.use_kernel}
                if isinstance(self.cache, QuantizedDenseKVCache) else {}
            )
            self.cache = type(self.cache).create(
                self.cfg.cache_layers, self.batch, self._windows[0],
                self.cfg.num_kv_heads, self.cfg.head_dim,
                jnp.dtype(self.ecfg.dtype), **kw,
            )
            self._reshard_cache()

    def _admit(self, produced) -> None:
        # Installs queued by a tick that ended up dispatching nothing must
        # land before _shrink_if_idle can rebuild (and re-shape) the table.
        self._flush_installs()
        # Reap sessions cancelled or deadline-expired since the last tick
        # (cancel() is non-blocking and only sets the flag; deadlines are
        # observed here, at tick boundaries). Each reap emits a terminal
        # ``(gid, -1, True)`` event so streaming consumers (the HTTP
        # gateway) see every stream end.
        now = time.monotonic()
        for slot, gid in enumerate(self.slots):
            if gid is None:
                continue
            s = self.sessions[gid]
            expired = (
                not s.cancel_requested
                and s.deadline is not None
                and now >= s.deadline
            )
            if (s.cancel_requested or expired) and s.slot is not None:
                s.state = SessionState.CANCELLED
                s.finish_reason = "deadline" if expired else "cancelled"
                if expired:
                    self.metrics.counter("sessions_deadline_expired")
                self._release(s)
                produced.append((gid, -1, True))
        self._shrink_if_idle()
        admitted: List[Tuple[Session, int]] = []
        free_slots = [i for i in range(self.batch) if self.slots[i] is None]
        candidates: List[Session] = []
        if free_slots and self.waiting:
            # Reap cancelled/expired entries anywhere in the queue (the
            # FIFO path only ever saw them at the head; with ordered
            # admission a cancelled mid-queue entry must not linger just
            # because the scheduler ranks it low). Each reap emits the
            # terminal event streaming consumers are owed.
            for dropped in [
                w for w in self.waiting
                if w.cancel_requested
                or (w.deadline is not None and now >= w.deadline)
            ]:
                self.waiting.remove(dropped)
                dropped.state = SessionState.CANCELLED
                if dropped.cancel_requested:
                    dropped.finish_reason = "cancelled"
                else:
                    dropped.finish_reason = "deadline"
                    self.metrics.counter("sessions_deadline_expired")
                produced.append((dropped.generation_id, -1, True))
            candidates = list(self.waiting)
            if self._admission_order is not None and len(candidates) > 1:
                # Scheduler-ordered admission (sched/): the hook ranks the
                # pending sessions; the tick admits a prefix of its order.
                # Defensive: a result that is not a permutation of the
                # queue is discarded — a buggy policy must never lose or
                # invent sessions.
                # Host-only: the hook ranks Session objects and never
                # touches a device, so nothing a chip raises can land here;
                # what it does catch is counted, not dropped.
                try:
                    ordered = list(self._admission_order(candidates))
                except Exception:  # noqa: BLE001 - policy must not kill ticks
                    self.metrics.counter("admission_order_errors")
                    ordered = candidates
                if len(ordered) == len(candidates) and (
                    {id(x) for x in ordered} == {id(x) for x in candidates}
                ):
                    candidates = ordered
        if candidates and free_slots:
            # ONE capacity widen for the whole admission burst (the
            # per-session _ensure_capacity below then no-ops). An oversized
            # backlog landing on the same tick as a growth otherwise walks
            # the ladder one rung per admitted session — each rung a table
            # widen plus a _warm_table_write recompile, observed as
            # back-to-back cache_growths inside one _admit.
            needs = [
                len(c.prompt) + 1
                for c in candidates[: len(free_slots)]
                if self._capacity_ok(c)
            ]
            if needs:
                self._ensure_capacity(max(needs))
        ci = 0
        for slot in free_slots:
            if ci >= len(candidates):
                break
            s = candidates[ci]
            ci += 1
            if not self._capacity_ok(s):
                self.waiting.remove(s)
                self._finish(s, "capacity", produced)
                self.metrics.counter("sessions_rejected")
                continue
            self._ensure_capacity(len(s.prompt) + 1)
            # Reset the row BEFORE installing pages (reset wipes the row's
            # page table).
            self.cache = self.cache.reset_rows(jnp.arange(self.batch) == slot)
            if self.draft is not None:
                self.draft_cache = self.draft_cache.reset_rows(
                    jnp.arange(self.batch) == slot
                )
            shared_len = 0
            if isinstance(self.cache, PagedKVCache):
                ps = self.ccfg.page_size
                n = len(s.prompt)
                # (a retention row holds no run of pages: its pages are the
                # rolling pool's, covered below)
                need = 0 if self._retention else math.ceil((n + 1) / ps)
                shared: List[int] = []
                cow = False
                if self.ccfg.prefix_caching:
                    if s.prefix_keys is None:
                        s.prefix_keys = PageAllocator.chain_keys(s.prompt, ps)
                    # With CoW sharing every FULL prompt page is eligible:
                    # a fully-matched final page is split copy-on-write
                    # below so the last prompt token (whose logits seed the
                    # first sample) recomputes into a private copy. Without
                    # it, cap so the last token's page is never shared.
                    cap = n // ps if self.pcfg.prefix_share else (n - 1) // ps
                    shared = self.allocator.lookup(s.prefix_keys[:cap])
                    if self._spill is not None and len(shared) < cap:
                        shared = self._reload_spilled(s.prefix_keys, shared, cap)
                    # n > 1: a fully-shared 1-token prompt (page_size 1)
                    # would leave NOTHING to prefill — no logits to sample
                    # from — so it drops the match and recomputes instead.
                    cow = bool(shared) and len(shared) * ps == n and n > 1
                    if not cow and shared and len(shared) * ps == n:
                        self.allocator.free([shared.pop()])
                # CoW takes one extra page for the private copy of the
                # fully-shared final page.
                if need - len(shared) + cow > self.allocator.free_count:
                    if shared:
                        self.allocator.free(shared)  # return the refs
                    break  # pool pressure: hold the queue, retry next tick
                if self.window_allocator is not None and (
                    len(self._window_slots(0, self._window_first_tokens(n)))
                    + self._window_reserve
                    > self.window_allocator.free_count
                ):
                    # window-pool pressure: the first dispatch's pages, with
                    # a chunk and every row's decode growth held back so
                    # that no row already admitted ever waits for a page
                    break
                fresh = self.allocator.alloc(need - len(shared) + cow)
                s.pages = shared + fresh  # owned: _release frees via s
                if self.window_allocator is not None:
                    self._window_cover(
                        s, slot, 0, self._window_first_tokens(n)
                    )
                if cow:
                    # Copy-on-write split: the write offset (skip = n-1)
                    # lands INSIDE the last shared page, so the first fresh
                    # page takes its table slot. The device copy is deferred
                    # to dispatch time (_run_prefill) — a same-tick writer's
                    # prefill must enqueue first — so the source ref is
                    # parked on s.cow_src until the copy is enqueued.
                    k = len(shared) - 1
                    s.cow_src = s.pages[k]
                    s.pages[k] = s.pages.pop(k + 1)
                    self.metrics.counter("prefix_cow_copies")
                # Queue the prompt's pages; _flush_installs applies them
                # in ONE pow2-padded scatter dispatch right before the
                # prefill (chained per-page installs paid one dispatch
                # each; per-length whole-run executables paid a compile per
                # new prompt page count).
                for i, pg in enumerate(s.pages):
                    self._queue_install(slot, i, pg)
                shared_len = n - 1 if cow else len(shared) * ps
                if shared_len:
                    self.cache = self.cache.replace(
                        lengths=self.cache.lengths.at[slot].set(shared_len)
                    )
                    self.metrics.counter("prefix_pages_shared", len(shared))
                if self.ccfg.prefix_caching:
                    self._note_prefix(n, shared_len)
                    if self.pcfg.prefix_share:
                        # Register-at-admission: this session's full prompt
                        # pages become shareable NOW (not at release), so
                        # concurrent sessions attach to the same device
                        # pages while the writer is still decoding. Safe:
                        # owned pages hold refs >= 1 (never evicted) and a
                        # same-tick sharer always dispatches after the
                        # writer (groups before singles; singles in
                        # admission order; a sharer has skip > 0 => single).
                        for i, key in enumerate(s.prefix_keys):
                            if i >= len(s.pages):
                                break
                            self.allocator.register(s.pages[i], key)
            self.waiting.remove(s)
            s.slot = slot
            s.state = SessionState.ACTIVE
            self.slots[slot] = s.generation_id
            admitted.append((s, shared_len))
        self._dispatch_prefills(admitted, produced)

    def _dispatch_prefills(self, admitted, produced) -> None:
        """Prefill freshly admitted sessions: same-bucket groups of >= 2
        simple prompts (no chunking, no shared-prefix skip, no ring path)
        go through ONE batched dispatch each; the rest keep the single-row
        path."""
        if not admitted:
            return
        singles: List[Tuple[Session, int]] = []
        groups: Dict[int, List[Session]] = {}
        chunk_cap = self._max_chunk()
        for s, skip in admitted:
            ring = (
                self._ring_prefill is not None
                and len(s.prompt) > self.ecfg.prefill_buckets[-1]
            )
            if (
                self._batch_admission
                and skip == 0
                and not ring
                and len(s.prompt) <= chunk_cap
            ):
                groups.setdefault(
                    self._bucket_for(len(s.prompt)), []
                ).append(s)
            else:
                singles.append((s, skip))
        for bucket, group in groups.items():
            if len(group) < 2:
                singles.extend((s, 0) for s in group)
                continue
            while group:
                self._prefill_group(group[:GROUP_ROWS], bucket, produced)
                group = group[GROUP_ROWS:]
        for s, skip in singles:
            # Long greedy prompts may park for chunk/decode co-scheduling
            # instead of a monolithic synchronous prefill; _chunk_admit
            # draws the session's PRNG key HERE — the same stream position
            # the synchronous path would consume — so parking never
            # perturbs the engine's key order.
            if self._chunk_admit(s, skip):
                continue
            self._run_prefill(s, produced, skip=skip)

    def _overlap_ok(self) -> bool:
        """Overlap THIS admission with the in-flight tick? Requires the
        pipelined carry machinery (so the next tick consumes the deferred
        first token without a host fetch), a tick actually in flight
        (otherwise the synchronous path is already stall-free — there is
        nothing to overlap), a batch axis whole on every device
        (``_batch_whole``: one chip or a ``tp``-only mesh; a ``dp``/``pp``
        mesh shards the axis the deferred carry scatter writes, the GSPMD
        constraint that turns batched admission off, and ``sp``/``ep``
        prefill through other, collective-bearing programs), and head-room
        under the in-flight cap (back-pressure: an admission flood spills
        to the synchronous path, not onto the device's queue)."""
        if not (
            self._pipelined
            and self._pending is not None
            and self._batch_whole
        ):
            return False
        if len(self._inflight_admits) >= OVERLAP_MAX_INFLIGHT:
            self.metrics.counter("admit_overlap_spill")
            return False
        return True

    def _defer_admit(self, group, toks_dev, rows, skips) -> None:
        """Record an overlapped admission: the prefill (and merge) is
        already dispatched; the sampled first tokens stay device-resident.
        They scatter into the pipelined carry so the next tick consumes
        them with no host round trip; ``_admit_pend`` charges one
        conservative in-flight token per row. ``_resolve_pending`` fetches
        and delivers at the next tick boundary."""
        toks_dev = jnp.reshape(toks_dev, (-1,))
        self._carry = self._carry_scatter(
            self._carry, toks_dev, jnp.asarray(rows, jnp.int32)
        )
        now = time.monotonic()
        for s in group:
            s.prefill_inflight = True
            s.prefill_dispatch_t = now
            self._carry_ok[s.slot] = True
            self._admit_pend[s.slot] = 1
        self._inflight_admits.append((list(group), toks_dev, list(skips)))
        self.metrics.counter("admit_overlap_sessions", len(group))
        self.metrics.gauge(
            "admit_overlap_inflight", float(len(self._inflight_admits))
        )

    def _prefill_group(self, group, bucket, produced) -> None:
        """One batched prefill dispatch for <= 8 same-bucket sessions.
        Rows pad to a power of two (duplicating row 0 with ``n_valid = 0``
        — a no-write, no-deliver placeholder) so a handful of executables
        covers every admission burst."""
        self._flush_installs()
        k = len(group)
        nr = 2
        while nr < k:
            nr *= 2
        # Padding entries use an OUT-OF-RANGE row: select_rows clamps the
        # gather, merge_rows drops the write-back (duplicating a real row
        # instead makes the scatter undefined-order and can clobber it
        # with stale pre-prefill content).
        rows = np.full((nr,), self.batch, np.int32)
        n_valid = np.zeros((nr,), np.int32)
        # Ragged mode pads every group to ONE width per row count (the
        # group keeps its bucket-keyed MEMBERSHIP — that is the PRNG-key
        # partition — only the pad width changes, which parity is
        # invariant to).
        width = self.plan.group_shape(bucket, self._max_chunk())
        tokens = np.zeros((nr, width), np.int32)
        opts = [SamplingOptions()] * nr
        for i, s in enumerate(group):
            rows[i] = s.slot
            n_valid[i] = len(s.prompt)
            tokens[i, : len(s.prompt)] = s.prompt
            opts[i] = s.options
        sp = SamplingParams.stack(opts)
        self._note_prefill(
            "prefill", (nr, width), [(0, int(n)) for n in n_valid[:k]],
            group,
        )
        for s in group:
            self._note_admitted(s)
        sub = self._fresh_sub(nr)
        if sub is not None:
            # Split pair (see _prefill_rows_standalone): compact
            # prefill with NO big-cache arrays, then a merge-only
            # dispatch — the combined program crashed the compiler
            # of rounds 1–5 past B×T ≈ 22.5k (not retried since).
            toks, sub = self._prefill_batch_standalone(
                self.params, jnp.asarray(tokens), sub,
                jnp.asarray(n_valid), self._next_key(), sp,
            )
            self.cache = self._merge_rows_only(
                self.cache, sub, jnp.asarray(rows)
            )
        else:
            toks, self.cache = self._prefill_batch(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(rows), jnp.asarray(n_valid),
                self._next_key(), sp,
            )
        if self.window_allocator is not None:
            for s in group:  # enqueued: the next query is the first decode
                self._window_release(s, len(s.prompt))
        if self._overlap_ok():
            # Everything above was dispatch-only; defer the blocking
            # token fetch to the next tick boundary (it rides the tick
            # resolve's device_get) so this tick never stalls on
            # prefill completion.
            self.metrics.counter("batched_prefills", k)
            self._defer_admit(group, toks, rows, [0] * k)
            return
        toks = np.asarray(self._fetch(toks))
        self.metrics.counter("batched_prefills", k)
        self.metrics.counter("admit_sync_sessions", k)
        with self._region("deliver"):
            for i, s in enumerate(group):
                self._finish_prefill(
                    s, int(toks[i]), np.asarray(s.prompt, np.int32),
                    produced, 0,
                )

    def _fresh_sub(self, nr: int):
        """A fresh ``nr``-row cache of the serving kind/shape for the split
        batched-admission prefill, or None for kinds that must keep the
        in-place program (the paged pool's page arrays are SHARED — a
        standalone sub-cache can't hold them). Stale content is irrelevant:
        validity derives from lengths, exactly as for gathered rows."""
        c = self.cache
        cfg, dtype = self.cfg, jnp.dtype(self.ecfg.dtype)
        if isinstance(c, QuantizedDenseKVCache):
            return QuantizedDenseKVCache.create(
                cfg.cache_layers, nr, c.max_len, cfg.num_kv_heads,
                cfg.head_dim, dtype, use_kernel=c.use_kernel,
            )
        if isinstance(c, DenseKVCache):
            return DenseKVCache.create(
                cfg.cache_layers, nr, c.max_len, cfg.num_kv_heads,
                cfg.head_dim, dtype,
            )
        if isinstance(c, QuantizedSinkKVCache):
            return QuantizedSinkKVCache.create(
                cfg.cache_layers, nr, c.window, c.num_sinks,
                cfg.num_kv_heads, cfg.head_dim, dtype,
                use_kernel=c.use_kernel,
            )
        # bf16 SinkKVCache: no select_rows/merge_rows — batch admission is
        # off for it, so no branch here (adding one would dangle on the
        # missing merge_rows the day select_rows appears).
        return None

    def _ring_bucket(self, n: int) -> int:
        """Padded ring length for an ``n``-token prompt: the doubling ladder
        above the largest prefill bucket, capped at ``max_seq_len`` (the
        ingest crop would discard anything above it — computing attention
        over up-to-2x padding would be pure waste), rounded up to a multiple
        of the ``sp`` degree (one executable per bucket)."""
        b = self.ecfg.prefill_buckets[-1]
        while b < n:
            b *= 2
        b = min(b, max(n, self.ecfg.max_seq_len))
        return -(-b // self._sp) * self._sp

    def _run_prefill(self, s: Session, produced, skip: int = 0) -> None:
        """Chunked, bucketed prefill of one admitted session; samples the
        first generated token from the final chunk. ``skip`` tokens at the
        head are already in the cache (shared prefix pages) — the row's
        write offset (``lengths``) was set past them at admission.

        Prompts past the ring threshold on an ``sp>1`` engine prefill
        sequence-sharded over the ring instead (one dispatch for the whole
        prompt; each sp device computes ``bucket/sp`` positions)."""
        self._flush_installs()  # prefill writes through the page table
        self._note_admitted(s)
        if s.cow_src is not None:
            # Deferred copy-on-write split: enqueue the device copy of the
            # fully-shared final page into this session's private page, then
            # drop the parked source ref. Doing this HERE (not at admission)
            # puts the copy after any same-tick writer's prefill dispatch,
            # so the source page's content is settled in device order.
            ps = self.ccfg.page_size
            self.cache = self.cache.copy_page(s.pages[skip // ps], s.cow_src)
            self.allocator.free([s.cow_src])
            s.cow_src = None
        chunk_cap = self._max_chunk()
        prompt = np.asarray(s.prompt, np.int32)
        sp = SamplingParams.create(
            1, s.options.temperature, s.options.top_k, s.options.top_p
        )
        if (
            self._ring_prefill is not None
            and skip == 0
            and len(prompt) > self.ecfg.prefill_buckets[-1]
        ):
            bucket = self._ring_bucket(len(prompt))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : len(prompt)] = prompt
            token, self.cache = self._ring_prefill(
                self.params, jnp.asarray(padded), self.cache, s.slot,
                jnp.int32(len(prompt)), self._next_key(), sp,
            )
            self.metrics.counter("ring_prefills")
            # Ring/sp prefill stays synchronous by design: it only exists
            # on an ``sp`` mesh, which never overlaps (see _overlap_ok).
            self.metrics.counter("admit_sync_sessions")
            self._finish_sync_prefill(s, token, prompt, produced, skip)
            return
        offset = skip
        stride = self.plan.prefill_stride(chunk_cap)
        while len(prompt) - offset > stride:
            chunk = prompt[offset : offset + stride]
            padded = jnp.asarray(chunk)[None, :]
            self._window_step(s, offset, offset + stride)
            self._note_prefill(
                "chunk", (1, stride), [(offset, len(chunk))], (s,)
            )
            self.cache = self._prefill_ns(
                self.params, padded, self.cache, s.slot, jnp.int32(len(chunk))
            )[1]
            offset += stride
        rest = prompt[offset:]
        width = self.plan.final_shape(len(rest), chunk_cap)
        padded = np.zeros((1, width), np.int32)
        padded[0, : len(rest)] = rest
        self._window_step(s, offset, len(prompt))
        self._note_prefill(
            "prefill", (1, width), [(offset, len(rest))], (s,)
        )
        if offset == 0 and self._prefill_fresh is not None:
            self._pair_widths(s, sp, width, self._fresh_widths)
            self.metrics.counter("prefill_fresh_rows")
            token, self.cache = self._prefill_fresh(
                self.params, jnp.asarray(padded), self.cache, s.slot,
                jnp.int32(len(rest)), self._next_key(), sp,
            )
        else:
            self._pair_widths(s, sp, width, self._table_widths)
            self.metrics.counter("prefill_table_rows")
            token, self.cache = self._prefill(
                self.params, jnp.asarray(padded), self.cache, s.slot,
                jnp.int32(len(rest)), self._next_key(), sp,
            )
        if self.window_allocator is not None:
            self._window_release(s, len(prompt))
        if self._overlap_ok():
            # Single-row admissions defer the token fetch exactly like the
            # batched path — the chunked prefill above was dispatch-only.
            self._defer_admit([s], token, np.asarray([s.slot], np.int32),
                              [skip])
            return
        self.metrics.counter("admit_sync_sessions")
        self._finish_sync_prefill(s, token, prompt, produced, skip)

    def _pair_widths(self, s, sp, width, loaded) -> None:
        """Keep the page-table program loaded at every shape (table slots,
        pad width: what the engine's executables are keyed by) the fresh
        program is loaded at, from the first row with history on. ``loaded``
        is the set of the program about to run at ``width``
        (``_fresh_widths`` or ``_table_widths``).

        Where there is no fresh program every prompt loads ``_prefill_row``
        at its width, so a row with history (a prefix hit, the tail of a
        chunked prompt) finds it there. Where there is one, fresh prompts
        load ``_prefill_row_fresh`` instead, and a tail's first visit to a
        width would compile in service. So once a row with history has come,
        each shape of the one is a shape of the other: the table program is
        loaded by a dispatch of NO tokens through the row being admitted
        (``n_valid`` 0 writes the null page alone and leaves the row's
        length), outside the census and the clock and with the engine's key
        unsplit, so the key order is as it was. A warm-up that sends every
        pad width once and one prompt with history leaves every tail its
        program; an engine that never sees such a row loads none."""
        if self._prefill_fresh is None:
            return
        slots = self.cache.page_table.shape[1]
        loaded.add((slots, width))
        if not self._table_widths:
            return
        for shape in sorted(self._fresh_widths - self._table_widths):
            if shape[0] != slots:       # another rung of the table: its turn
                continue                # comes when a row is admitted there
            self.cache = self._unclocked.get("_prefill", self._prefill)(
                self.params, jnp.asarray(np.zeros((1, shape[1]), np.int32)),
                self.cache, s.slot, jnp.int32(0), self.rng, sp,
            )[1]
            self._table_widths.add(shape)

    def _finish_sync_prefill(self, s, token, prompt, produced, skip):
        """A synchronous admission's tail: wait for the sampled first token
        (the tick's ``blocked`` phase) and deliver it."""
        tok = int(np.asarray(self._fetch(token)))
        with self._region("deliver"):
            self._finish_prefill(s, tok, prompt, produced, skip)

    def _chunk_admit(self, s: Session, skip: int) -> bool:
        """Park an admitted long GREEDY prompt for chunk/decode
        co-scheduling instead of a monolithic synchronous prefill: the
        session holds its slot (decode-ineligible) while _chunk_dispatch
        walks the prompt one ``plan.prefill_stride`` chunk per granted
        tick beside the live decode batch. Returns False — caller runs
        the legacy path — unless eligible (ragged mode on, greedy, long
        enough, single-device, no draft, no ring path, and at least one
        OTHER live row to ride beside; alone, the standalone prefill is
        strictly better for TTFT)."""
        if self.mesh is not None or self.draft is not None:
            return False
        if not self.plan.co_schedule_ok(
            len(s.prompt) - skip, s.options.temperature, self._max_chunk()
        ):
            return False
        if (
            self._ring_prefill is not None
            and skip == 0
            and len(s.prompt) > self.ecfg.prefill_buckets[-1]
        ):
            return False
        if self.ccfg.prefix_caching and self.pcfg.prefix_share:
            # Register-at-admission already made this prompt's pages
            # shareable; stretching the writes over ticks would let a later
            # admission attach to pages whose KV isn't written yet. Keep
            # the synchronous path (its writer-before-sharer dispatch
            # ordering is what makes register-at-admission safe).
            return False
        # Park only when another row is decode-LIVE (first token already
        # sampled — a same-tick co-admission that has not prefilled yet
        # does not count): alone, the standalone prefill is strictly
        # better for TTFT, and there is no decode stream to protect.
        others = any(
            gid is not None
            and gid != s.generation_id
            and not self.sessions[gid].chunking
            and self.sessions[gid].generated
            for gid in self.slots
        )
        if not others:
            return False
        if s.cow_src is not None:
            # Deferred CoW split (see _run_prefill): enqueue the device
            # copy before any chunk writes through this row.
            ps = self.ccfg.page_size
            self.cache = self.cache.copy_page(s.pages[skip // ps], s.cow_src)
            self.allocator.free([s.cow_src])
            s.cow_src = None
        self._note_admitted(s)  # parked: it left the queue for a slot
        s.chunking = True
        s.chunk_off = skip
        s.chunk_skip = skip
        # Draw the admission key NOW — the stream position the synchronous
        # prefill would have consumed — and park it for the final chunk's
        # sample, so co-scheduling never perturbs the engine's key order
        # (byte-exact parity with the legacy path).
        s.parked_key = self._next_key()
        self._chunking.append(s)
        return True

    def _chunk_dispatch(self, produced) -> None:
        """Advance co-scheduled chunked prefills by one chunk per granted
        tick (``plan.take_chunk_credit`` rations grants at
        ``chunk_decode_share`` against live decode; full speed when no
        decode rows remain). Interior chunks are keyless cache writes —
        the exact ``_prefill_ns`` program the legacy chunk loop runs — and
        the final chunk samples the first token with the session's parked
        admission key, entering decode via the overlap machinery when
        available."""
        if not self._chunking:
            return
        decode_active = any(
            gid is not None and not self.sessions[gid].chunking
            for gid in self.slots
        )
        if not self.plan.take_chunk_credit(decode_active):
            return
        chunk_cap = self._max_chunk()
        stride = self.plan.prefill_stride(chunk_cap)
        for s in list(self._chunking):
            if s.state is not SessionState.ACTIVE or s.slot is None:
                # A cancel/deadline reap already released the row (and
                # cleared the chunking flags) — just drop the parked entry.
                if s in self._chunking:
                    self._chunking.remove(s)
                continue
            prompt = np.asarray(s.prompt, np.int32)
            rest = len(prompt) - s.chunk_off
            if self.window_allocator is not None:
                self._window_release(s, s.chunk_off)
                if not self._window_cover(
                    s, s.slot, s.chunk_off, s.chunk_off + min(rest, stride)
                ):
                    continue  # window-pool pressure: this chunk waits a tick
            self._flush_installs()  # chunk writes go through the table
            if rest > stride:
                chunk = prompt[s.chunk_off : s.chunk_off + stride]
                self._note_prefill(
                    "chunk", (1, stride), [(s.chunk_off, len(chunk))], (s,)
                )
                self.cache = self._prefill_ns(
                    self.params, jnp.asarray(chunk)[None, :],
                    self.cache, s.slot, jnp.int32(len(chunk)),
                )[1]
                s.chunk_off += stride
                self.plan.note_chunk_rows()
                if self.window_allocator is not None:
                    # enqueued: every query still to come is at or past the
                    # next chunk's first
                    self._window_release(s, s.chunk_off)
                continue
            width = self.plan.final_shape(rest, chunk_cap)
            padded = np.zeros((1, width), np.int32)
            padded[0, :rest] = prompt[s.chunk_off :]
            sp = SamplingParams.create(
                1, s.options.temperature, s.options.top_k, s.options.top_p
            )
            self._note_prefill(
                "prefill", (1, width), [(s.chunk_off, rest)], (s,)
            )
            self.metrics.counter("prefill_table_rows")
            token, self.cache = self._prefill(
                self.params, jnp.asarray(padded), self.cache, s.slot,
                jnp.int32(rest), s.parked_key, sp,
            )
            self.plan.note_chunk_rows()
            s.chunking = False
            s.parked_key = None
            self._chunking.remove(s)
            if self.window_allocator is not None:
                self._window_release(s, len(prompt))
            if self._overlap_ok():
                self._defer_admit(
                    [s], token, np.asarray([s.slot], np.int32),
                    [s.chunk_skip],
                )
                continue
            self.metrics.counter("admit_sync_sessions")
            # distcheck: host-sync-ok(final-chunk first-token fetch — the same one-per-admission sync the legacy _run_prefill path pays)
            self._finish_sync_prefill(
                s, token, prompt, produced, s.chunk_skip
            )

    def _finish_prefill(self, s, token, prompt, produced, skip):
        self._deliver(s, int(token), produced)
        self.metrics.counter("prefill_tokens", len(s.prompt) - skip)
        if self._session_speculative(s):
            # Mirror the FULL prompt into the draft cache (no prefix sharing
            # there; proposals start right after the prompt).
            self._draft_mirror(prompt, s.slot)

    def _draft_mirror(self, tokens, slot) -> None:
        """Chunked prefill of ``tokens`` into the draft cache's ``slot`` row
        (admission-time prompt mirror AND adaptive-resume resync share this
        so their chunking can never drift apart)."""
        dparams = self.draft[1]
        cap = self.ecfg.prefill_buckets[-1]
        off = 0
        while len(tokens) - off > cap:
            chunk = tokens[off : off + cap]
            self.draft_cache = self._draft_prefill(
                dparams, jnp.asarray(chunk)[None, :], self.draft_cache,
                jnp.int32(slot), jnp.int32(len(chunk)),
            )
            off += cap
        rest = tokens[off:]
        bucket = self._bucket_for(len(rest))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(rest)] = rest
        self.draft_cache = self._draft_prefill(
            dparams, jnp.asarray(padded), self.draft_cache, jnp.int32(slot),
            jnp.int32(len(rest)),
        )

    def _session_wants_spec(self, s: Session) -> bool:
        return (
            self.draft is not None
            and s.options.speculative
            and s.options.temperature == 0.0
        )

    def _session_speculative(self, s: Session) -> bool:
        """Speculating NOW — wants it and the adaptive controller has not
        suspended speculation engine-wide (the greedy token streams are
        identical either way, so suspension is invisible to outputs)."""
        return self._session_wants_spec(s) and not self._spec_suspended

    # -- adaptive speculation (throughput A/B controller) ---------------------

    def _draft_resync_all(self) -> None:
        """Re-mirror every speculative session's accepted stream (prompt +
        generated[:-1]) into the draft cache — required after plain-mode
        ticks advanced sessions without the draft. One chunked draft
        prefill per session; cost ≈ one draft weight sweep per
        prefill-bucket chunk."""
        for slot, gid in enumerate(self.slots):
            if gid is None:
                continue
            s = self.sessions[gid]
            if not self._session_wants_spec(s):
                continue
            self.draft_cache = self.draft_cache.reset_rows(
                np.arange(self.batch) == slot
            )
            self._draft_mirror(list(s.prompt) + s.generated[:-1], slot)

    def _spec_suspend(self, produced) -> None:
        if self._spec_pending is not None:
            self._spec_flush(produced)
        self._spec_suspended = True

    def _spec_resume(self) -> None:
        self._draft_resync_all()
        # Fresh tokens next dispatch; the device-carried catch pair is
        # gated off with the carry (the resync already consumed everything).
        self._spec_carry_ok[:] = False
        self._spec_suspended = False

    def _decode_tokens_total(self) -> float:
        return self.metrics.get_counter("decode_tokens")

    def _spec_adapt(self, produced) -> None:
        """Windowed throughput controller (config.py's speculative_probe_*
        knobs). Measures tokens/s of the CURRENT path over windows of
        ``probe_len`` ticks; when spec-mode tokens-per-round sags below the
        break-even band it probes the plain fused path, serves whichever
        measured faster, and re-probes speculation every ``probe_period``
        ticks. Token streams are bit-identical in both modes."""
        if self.draft is None:
            return
        c = self._spec_ctl
        nspec = sum(
            1
            for g in self.slots
            if g is not None and self._session_wants_spec(self.sessions[g])
        )
        if nspec == 0:
            # Disengaged tick (no speculative sessions resident): the next
            # engaged window must NOT span this gap's wall time or its
            # non-speculative tokens.
            c["win_t0"] = None
            return
        now = self._spec_clock()
        tokens = self._decode_tokens_total()
        comp = tuple(self.slots)
        if comp != c.get("comp"):
            # Batch composition changed mid-window (admission / finish /
            # cancel): the window's tokens/s mixes two resident sets and
            # would bias the spec-vs-plain comparison — session churn could
            # latch the wrong mode until the next probe period. Re-baseline
            # the window instead of folding it into the EMA (mirrors the
            # full-disengagement reset above).
            c["comp"] = comp
            if c["win_t0"] is not None:
                self.metrics.counter("spec_adapt_window_resets")
            c.update(win_t0=now, win_tok0=tokens, win_ticks=0,
                     stat0=dict(self.spec_stats))
            return
        if c["win_t0"] is None or c.get("skip", 0) > 0:
            # (Re-)baseline: after engagement gaps and for the first tick
            # after a mode transition — that tick absorbs the new path's
            # one-time jit compile
            # and the transition's flushed/resynced tokens, which would
            # otherwise poison the rate EMA.
            c["skip"] = max(0, c.get("skip", 0) - 1)
            c.update(win_t0=now, win_tok0=tokens, win_ticks=0,
                     stat0=dict(self.spec_stats))
            return
        c["win_ticks"] += 1
        if c["win_ticks"] < max(2, self.ecfg.speculative_probe_len):
            return
        # Window boundary: fold this window's rate into the mode's EMA —
        # normalized PER ACTIVE SPECULATIVE ROW. The composition reset
        # above keeps ``nspec`` constant within a window, but consecutive
        # windows can still run at different speculative occupancy (a spec
        # session finished, a new one admitted between windows); comparing
        # raw batch tokens/s across them would credit occupancy to the
        # mode and latch the wrong path until the next probe.
        rate = (
            (tokens - c["win_tok0"])
            / max(now - c["win_t0"], 1e-9)
            / nspec
        )
        mode = c["mode"]
        rkey = "plain_rate" if mode in ("probe_plain", "plain") else "spec_rate"
        c[rkey] = rate if c[rkey] is None else 0.5 * c[rkey] + 0.5 * rate
        if mode in ("spec", "probe_spec"):
            steps_d = self.spec_stats["steps"] - c["stat0"]["steps"]
            if steps_d > 0:
                tpr = 1.0 + (
                    self.spec_stats["accepted"] - c["stat0"]["accepted"]
                ) / steps_d
                c["tpr_ema"] = tpr if c["tpr_ema"] is None else (
                    0.5 * c["tpr_ema"] + 0.5 * tpr
                )
        c.update(win_t0=now, win_tok0=tokens, win_ticks=0,
                 stat0=dict(self.spec_stats))
        c["cooldown"] = max(0, c["cooldown"] - 1)

        k = self.ecfg.speculative_k
        gate = (
            self.ecfg.speculative_probe_below
            if self.ecfg.speculative_probe_below is not None
            else 0.55 * (k + 1)
        )
        period_windows = max(
            1,
            self.ecfg.speculative_probe_period
            // max(2, self.ecfg.speculative_probe_len),
        )
        if mode == "spec":
            if (
                c["tpr_ema"] is not None
                and c["tpr_ema"] < gate
                and c["cooldown"] == 0
            ):
                self._spec_suspend(produced)
                c.update(mode="probe_plain", win_t0=None, skip=1)
                self.metrics.counter("spec_adapt_probes")
        elif mode == "probe_plain":
            # One full window of plain measured — decide.
            if c["plain_rate"] > (c["spec_rate"] or 0.0):
                c["mode"] = "plain"
                self.metrics.counter("spec_adapt_suspensions")
            else:
                self._spec_resume()
                c.update(mode="spec", win_t0=None, skip=1)
            c["cooldown"] = period_windows
        elif mode == "plain":
            if c["cooldown"] == 0:
                self._spec_resume()
                c.update(mode="probe_spec", win_t0=None, skip=1)
        elif mode == "probe_spec":
            if (c["spec_rate"] or 0.0) >= (c["plain_rate"] or 0.0):
                c["mode"] = "spec"
            else:
                self._spec_suspend(produced)
                c.update(mode="plain", win_t0=None, skip=1)
            c["cooldown"] = period_windows

    # -- pipelined ticks ------------------------------------------------------

    def _dispatch_tick(self, produced, prev):
        """Enqueue the next fused K-step tick using the device-resident
        token carry (tick N-1's final sampled tokens) — no host fetch on the
        input path, so the device queue never drains between ticks. Returns
        the new pending tuple (or None when nothing was dispatched).

        Budgets are CONSERVATIVE: they assume the in-flight tick (``prev``)
        delivers its full budget, so a session can never over-write its
        ``max_new_tokens`` or the buffer; a row whose conservative budget
        hits zero idles one tick (its state resolves next step) instead of
        rolling anything back."""
        K = max(1, self.decode_steps)
        if prev is not None:
            # A slot whose tenant changed since the in-flight tick was
            # dispatched (finish → admit) must not be charged the previous
            # tenant's pending budget.
            pend_b = np.where(
                np.array([g == pg for g, pg in zip(self.slots, prev[3])]),
                prev[1], 0,
            )
        else:
            pend_b = np.zeros((self.batch,), np.int32)
        if self._admit_pend.any():
            # Overlapped admissions dispatched last tick: each row's sampled
            # first token is still in flight (device-resident; this tick
            # consumes it via the carry) — charge it like in-flight tick
            # budget so max_new_tokens and capacity stay exact.
            pend_b = pend_b + self._admit_pend
        fresh = np.zeros((self.batch, 1), np.int32)
        use_carry = np.zeros((self.batch,), np.bool_)
        opts: List[SamplingOptions] = [SamplingOptions()] * self.batch
        budget = np.zeros((self.batch,), np.int32)
        paged = isinstance(self.cache, PagedKVCache)
        sink = isinstance(self.cache, _SINK_KINDS)
        for slot, gid in enumerate(self.slots):
            if gid is None:
                continue
            s = self.sessions[gid]
            if s.chunking:
                # Mid chunked-prefill: the row holds its slot (pages, table)
                # but is not decode-eligible until the final chunk samples
                # its first token — budget stays 0 so the mask excludes it.
                continue
            opts[slot] = s.options
            fresh[slot, 0] = s.last_token
            use_carry[slot] = self._carry_ok[slot]
            pend = int(pend_b[slot])
            if sink:  # the ring evicts; streams are (near-)unbounded
                cap = self._sink_cap()
            elif paged:
                cap = self._run_capacity(s)
            else:
                cap = self.ecfg.max_seq_len
            if pend == 0 and s.total_len + 1 > cap:
                if paged:
                    # One more growth attempt before declaring capacity.
                    cap = self._grow_pages(s, 1)
                if s.total_len + 1 > cap:
                    # Nothing in flight for this row and no room for one
                    # more token: the session ends here (plain-tick rule).
                    self._finish(s, "capacity", produced)
                    continue
            desired = max(0, min(
                K, s.options.max_new_tokens - len(s.generated) - pend
            ))
            if paged and desired > 0:
                # Conservative: pages must cover the in-flight tick's
                # budget AND this one.
                cap = self._grow_pages(s, pend + desired)
            budget[slot] = max(0, min(
                desired, cap - s.total_len - pend,
            ))
        active = np.array(
            [g is not None for g in self.slots], np.bool_
        ) & (budget > 0)
        if not active.any():
            return None
        if self._windows:
            self._ensure_capacity(max(
                self.sessions[g].total_len + int(pend_b[i]) + int(budget[i])
                for i, g in enumerate(self.slots) if g is not None
            ))
        sp = SamplingParams.stack(opts)
        eos_ids = np.asarray([o.eos_token_id for o in opts], np.int32)
        if self._carry is None:
            tokens_dev = jnp.asarray(fresh)
        else:
            tokens_dev = self._carry_combine(
                jnp.asarray(fresh), self._carry, jnp.asarray(use_carry)
            )
        act_dev = jnp.asarray(active)
        self._flush_installs()
        self.plan.note_dispatch("decode", (
            self.batch, K,
            self.cache.page_table.shape[1] if paged
            else int(getattr(self.cache, "max_len", 0)),
        ), self._live_positions(active, pend_b), int(active.sum()),
            query_spans=self._decode_spans(active, K, pend_b))
        emitted, self.cache, laps = self._decode_k(
            self.params, tokens_dev, self.cache, act_dev,
            self._next_key(), sp, jnp.asarray(eos_ids),
            jnp.asarray(budget),
        )
        old = (
            self._carry if self._carry is not None
            else jnp.zeros((self.batch, 1), jnp.int32)
        )
        self._carry = self._carry_merge(emitted[-1], old, act_dev)
        self._carry_ok = self._carry_ok | active
        return (emitted, budget, active, list(self.slots), laps)

    def _resolve_pending(self, produced, prev) -> None:
        """Fetch and deliver the PREVIOUS tick's tokens (the copy overlaps
        the tick just dispatched). Rows that stopped mid-tick but keep
        serving (budget exhaustion) get their device carry invalidated —
        the next dispatch feeds them the host-known last token instead.

        Overlapped admissions dispatched last step resolve here too: their
        deferred first tokens ride the SAME ``device_get`` (one host
        round trip covers the tick and every pending admission),
        then the usual prefill bookkeeping runs. Sessions cancelled while
        their prefill was in flight drop the token (``_deliver``'s guard);
        the admission reap frees their slot and pages right after."""
        admits, self._inflight_admits = self._inflight_admits, []
        if prev is None and not admits:
            return
        fetch = [toks for _, toks, _ in admits]
        if prev is not None:
            # a looped stack's exit laps ride the same fetch (None: no leaf)
            fetch += [prev[4], prev[0]]
        got = self._fetch(fetch)
        if admits:
            self._admit_pend[:] = 0
            self.metrics.gauge("admit_overlap_inflight", 0.0)
            now = time.monotonic()
            for (group, _, skips), toks in zip(admits, got):
                toks = np.asarray(toks).reshape(-1)
                for i, s in enumerate(group):
                    s.prefill_inflight = False
                    if s.prefill_dispatch_t is not None:
                        self.metrics.observe(
                            "admit_to_merge", now - s.prefill_dispatch_t
                        )
                        s.prefill_dispatch_t = None
                    self._finish_prefill(
                        s, int(toks[i]), np.asarray(s.prompt, np.int32),
                        produced, skips[i],
                    )
        if prev is None:
            return
        emitted_dev, budget, active, gids, _ = prev
        emitted, laps = np.asarray(got[-1]), got[-2]
        delivered_total = 0
        for slot, gid in enumerate(gids):
            if gid is None or not active[slot]:
                continue
            s = self.sessions.get(gid)
            if s is None or self.slots[slot] != gid:
                continue  # cancelled/reaped since dispatch
            delivered = 0
            for i in range(int(budget[slot])):
                if s.state != SessionState.ACTIVE:
                    break
                tok = int(emitted[i, slot])
                if tok == -1:  # in-graph stop on an earlier step
                    break
                self._deliver(s, tok, produced)
                delivered += 1
            delivered_total += delivered
            self._count_exit_laps(laps, slot, delivered)
            if delivered < int(budget[slot]) and s.state == SessionState.ACTIVE:
                self._carry_ok[slot] = False
        self.metrics.counter("decode_tokens", delivered_total)

    def _count_exit_laps(self, laps, slot: int, delivered: int) -> None:
        """``loop_exit_lap_sum`` / ``loop_exit_positions``: the lap the exit
        selection took for each of a row's ``delivered`` tokens of a fused
        decode dispatch, as the device reported it beside the tokens
        (``laps [K, B]``; None for a stack that runs once). Their ratio is
        the mean exit lap: ``loop_steps - 1`` at a threshold of 1."""
        if laps is None or not delivered:
            return
        self.metrics.counter(
            "loop_exit_lap_sum", int(np.asarray(laps)[:delivered, slot].sum())
        )
        self.metrics.counter("loop_exit_positions", delivered)

    def _decode_tick(self, produced) -> None:
        self._spec_adapt(produced)
        if self.draft is not None and any(
            g is not None and self._session_speculative(self.sessions[g])
            for g in self.slots
        ):
            return self._speculative_rounds_tick(produced)
        if self.draft is not None and self._spec_pending is not None:
            # Last speculative session retired with a tick in flight.
            self._spec_flush(produced)
        K = max(1, self.decode_steps)
        tokens = np.zeros((self.batch, 1), np.int32)
        opts: List[SamplingOptions] = [SamplingOptions()] * self.batch
        for slot, gid in enumerate(self.slots):
            if gid is None:
                continue
            s = self.sessions[gid]
            if s.chunking:  # mid chunked-prefill: not decode-eligible
                continue
            tokens[slot, 0] = s.last_token
            opts[slot] = s.options

        # Per-row token budget for this tick: how many of the K scan steps
        # may actually append (remaining max_new_tokens and cache capacity).
        budget = np.zeros((self.batch,), np.int32)

        # Paged: grow page tables to cover this tick's budget before the step.
        if isinstance(self.cache, PagedKVCache):
            for slot, gid in enumerate(self.slots):
                if gid is None:
                    continue
                s = self.sessions[gid]
                if s.chunking:
                    continue
                want = min(K, s.options.max_new_tokens - len(s.generated))
                cap = self._grow_pages_for(s, want, produced)
                if cap is None:
                    continue
                budget[slot] = min(want, cap - s.total_len)
        elif isinstance(self.cache, (DenseKVCache, QuantizedDenseKVCache)):
            for slot, gid in enumerate(self.slots):
                if gid is None:
                    continue
                s = self.sessions[gid]
                if s.chunking:
                    continue
                if s.total_len + 1 > self.ecfg.max_seq_len:
                    self._finish(s, "capacity", produced)
                    continue
                budget[slot] = min(
                    K,
                    s.options.max_new_tokens - len(s.generated),
                    self.ecfg.max_seq_len - s.total_len,
                )
        else:  # sink ring: (near-)unbounded stream
            cap = self._sink_cap()
            for slot, gid in enumerate(self.slots):
                if gid is None:
                    continue
                s = self.sessions[gid]
                if s.chunking:
                    continue
                if s.total_len + 1 > cap:
                    self._finish(s, "capacity", produced)
                    continue
                budget[slot] = min(
                    K, s.options.max_new_tokens - len(s.generated),
                    cap - s.total_len,
                )

        # Chunking rows hold slots but must NOT be decode-written (their
        # rows are mid-prefill; a decode write would land at the chunk
        # offset and corrupt the prompt KV).
        active = np.array(
            [
                self.slots[i] is not None
                and not self.sessions[self.slots[i]].chunking
                for i in range(self.batch)
            ],
            np.bool_,
        )
        if not active.any():
            return

        if self._windows:
            self._ensure_capacity(max(
                self.sessions[g].total_len + int(budget[i])
                for i, g in enumerate(self.slots) if g is not None
            ))

        sp = SamplingParams.stack(opts)
        self._flush_installs()
        self.plan.note_dispatch("decode", (
            self.batch, K,
            self.cache.page_table.shape[1]
            if isinstance(self.cache, PagedKVCache)
            else int(getattr(self.cache, "max_len", 0)),
        ), self._live_positions(active), int(active.sum()),
            query_spans=self._decode_spans(active, K))
        laps = None
        if K == 1:
            next_tokens, self.cache = self._decode(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(active), self._next_key(), sp,
            )
            # distcheck: host-sync-ok(the one per-tick fetch for K=1)
            emitted = np.asarray(self._fetch(next_tokens))[None, :]
        else:
            eos_ids = np.asarray(
                [o.eos_token_id for o in opts], np.int32
            )
            emitted, self.cache, laps = self._decode_k(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(active), self._next_key(), sp,
                jnp.asarray(eos_ids), jnp.asarray(budget),
            )
            # distcheck: host-sync-ok(the one per-tick fetch for K>1)
            emitted, laps = self._fetch([emitted, laps])
            emitted = np.asarray(emitted)

        delivered = 0
        with self._region("deliver"):
            for slot, gid in enumerate(list(self.slots)):
                if gid is None or not active[slot]:
                    continue
                s = self.sessions[gid]
                before = delivered
                for i in range(int(budget[slot])):
                    if s.state != SessionState.ACTIVE:
                        break
                    self._deliver(s, int(emitted[i, slot]), produced)
                    delivered += 1
                self._count_exit_laps(laps, slot, delivered - before)
        self.metrics.counter("decode_tokens", delivered)

    def _grow_pages(self, s: Session, want: int) -> int:
        """Grow ``s``'s page run to cover ``want`` more tokens (best
        effort); returns the mapped capacity. Shared by the plain,
        speculative, and pipelined ticks so the table-widen-before-assign
        invariant lives once. Over two pools the window pool's pages move
        with the row too: those its window has passed leave, those the
        tokens need are taken, and the capacity is what both pools map."""
        ps = self.ccfg.page_size
        while not self._retention and len(s.pages) * ps < s.total_len + want:
            if (
                len(s.pages) >= self.ccfg.max_pages_per_session
                or self.allocator.free_count == 0
            ):
                break
            # Widen the page table first: the new slot index must exist
            # (a clamped update would corrupt another slot).
            self._ensure_capacity(len(s.pages) * ps + 1)
            new = self.allocator.alloc(1)
            self._queue_install(s.slot, len(s.pages), new[0])
            s.pages.extend(new)
        if self._retention:
            self._ensure_capacity(
                min(s.total_len + want, self._run_capacity(s))
            )
        cap = self._run_capacity(s)
        if self.window_allocator is not None:
            # every query still to come is at or past the host's count
            self._window_release(s, s.total_len - 1)
            cap = min(cap, self._window_cover(
                s, s.slot, s.total_len - 1, min(cap, s.total_len + want),
                partial=True,
            ))
        return cap

    # -- the window pool's pages (a stack of window and full layers) ---------
    #
    # A window layer's query at position ``t`` reads keys ``t - window < j
    # <= t`` and nothing before them, and the kernels never fetch a table
    # slot that lies wholly before that (``_live_pages``, ``_tile_live``).
    # So a row holds window pages only for the slots its next dispatch
    # reads or writes; the rest are RELEASED, here, on the host, and given
    # to whichever row asks next.
    #
    # Why that is safe under pipelined ticks and overlapped admission,
    # where the device runs behind the host. (1) Release looks FORWARD: a
    # slot is released when every query the row can still make (at or past
    # ``t_min``, a lower bound the host knows: its count of the row's
    # tokens, which a tick in flight can only have raised) sees none of
    # its positions, so no dispatch enqueued from now on reads it. (2)
    # Dispatches already enqueued may still read it, and the new owner's
    # writes are in a dispatch enqueued AFTER them: one device runs its
    # queue in order, so the read is done before the page changes. The
    # page is never handed on "in time", only in queue order. (3) The
    # released slot's id stays in the row's table, stale: the kernels skip
    # it, and an XLA gather of the whole table reads it under the window's
    # mask (cache/paged.py, the two-pool classes' note).

    @property
    def _pool_reach(self) -> int:
        """How far back of a query the rolling pool's pages are read: a
        window layer's window, or ONE position for a stack of retention
        layers, whose every full page is folded into the row's state by the
        dispatch that fills it (``cache/retention.py``): the page a query
        lies in is all it reads of the pool. The release rule, the cover and
        the bounds below are the same code for both."""
        return 1 if self._retention else self.cfg.sliding_window

    def _run_capacity(self, s: Session) -> int:
        """Positions the first pool maps for ``s``: its run of pages, or,
        where the rolling pool is the only one (retention layers), what the
        table can name."""
        ps = self.ccfg.page_size
        if self._retention:
            return min(
                self.ecfg.max_seq_len, self.ccfg.max_pages_per_session * ps
            )
        return len(s.pages) * ps

    def _window_slots(self, lo_pos: int, hi_pos: int) -> range:
        """Table slots a dispatch touches that writes positions ``lo_pos ..
        hi_pos`` of a row: from the slot its first query's window reaches
        back into, to the slot of its last token."""
        ps = self.ccfg.page_size
        first = max(0, lo_pos - self._pool_reach + 1) // ps
        return range(first, (max(hi_pos, lo_pos + 1) - 1) // ps + 1)

    def _window_cover(self, s: Session, row: int, lo_pos: int, hi_pos: int,
                      partial: bool = False) -> int:
        """Give ``s`` (in batch row ``row``) the window pages a dispatch
        that writes ``lo_pos .. hi_pos`` needs and does not hold yet, and
        queue their installs. Returns the positions mapped: ``hi_pos``
        rounded up to its page, or, where the pool runs dry, 0 with nothing
        taken (``partial``: as far as the pages went instead: a decode row
        then stops at that capacity, as it does when the full pool runs
        dry)."""
        ps = self.ccfg.page_size
        need = [
            j for j in self._window_slots(lo_pos, hi_pos)
            if j not in s.window_pages
        ]
        have = self.window_allocator.free_count
        if len(need) > have:
            if not partial:
                return 0
            need = need[:have]
        for j, page in zip(need, self.window_allocator.alloc(len(need))):
            s.window_pages[j] = page
            self._pending_window_installs.append((row, j, page))
        mapped = lo_pos // ps
        while mapped in s.window_pages:
            mapped += 1
        return mapped * ps

    def _window_release(self, s: Session, t_min: int) -> None:
        """Release the window pages of ``s`` that no query at or past
        position ``t_min`` can see (the note above)."""
        keep_from = max(0, t_min - self._pool_reach + 1) // (
            self.ccfg.page_size
        )
        gone = [j for j in s.window_pages if j < keep_from]
        if gone:
            self.window_allocator.free([s.window_pages.pop(j) for j in gone])
            self.metrics.counter("window_pages_released", len(gone))

    def _window_step(self, s: Session, lo_pos: int, hi_pos: int) -> None:
        """A synchronous prefill's next dispatch writes ``lo_pos ..
        hi_pos``: release what the row's window has passed, take what the
        dispatch needs, install. The admission gate holds a chunk's pages
        back for this (``_window_reserve``), so the pool cannot be dry."""
        if self.window_allocator is None:
            return
        self._window_release(s, lo_pos)
        if not self._window_cover(s, s.slot, lo_pos, hi_pos):
            raise MemoryError(
                "window page pool exhausted inside a prefill: the admission "
                "gate's reserve was not kept"
            )
        self._flush_installs()

    def _window_first_tokens(self, n: int) -> int:
        """Tokens of an ``n``-token prompt that its FIRST prefill dispatch
        writes: all of it, or a chunk."""
        return min(n, self.plan.prefill_stride(self._max_chunk()))

    def _grow_pages_for(self, s: Session, want: int, produced) -> Optional[int]:
        """:meth:`_grow_pages` plus the synchronous ticks' rule: a session
        without room for even one more token finishes (capacity)."""
        cap = self._grow_pages(s, want)
        if s.total_len + 1 > cap:
            self._finish(s, "capacity", produced)
            return None
        return cap

    def _spec_rounds_capacity_ok(self, produced, pend_b=None) -> bool:
        """The fused multi-round dispatch cannot grow pages or finish
        sessions mid-scan, so every resident session must have physical
        room for the worst case (``R * (k+1)`` positions per dispatch —
        each round's verify writes k+1 before the in-graph rollback trims
        it — PLUS the in-flight tick's worst case when pipelined). Grows
        pages/buffers up front; returns False (→ the synchronous
        per-round tick, which handles per-round growth and capacity
        degradation) when any row falls short."""
        worst = self.spec_rounds * (self.ecfg.speculative_k + 1)
        for slot, gid in enumerate(self.slots):
            if gid is None:
                continue
            s = self.sessions[gid]
            need = s.total_len + worst + (
                int(pend_b[slot]) if pend_b is not None else 0
            )
            if isinstance(self.cache, PagedKVCache):
                if self._grow_pages(s, need - s.total_len) < need:
                    return False
            else:
                if need > self.ecfg.max_seq_len:
                    return False
        if self._windows and not isinstance(self.cache, PagedKVCache):
            live = [self.sessions[g] for g in self.slots if g is not None]
            if live:
                self._ensure_capacity(
                    max(s.total_len for s in live) + worst + (
                        int(pend_b.max()) if pend_b is not None else 0
                    )
                )
        return True

    def _speculative_rounds_tick(self, produced) -> None:
        """Fused, PIPELINED speculation: each ``step()`` dispatches
        ``spec_rounds`` propose→verify→accept rounds in ONE device call
        (see ``_spec_round_fn``), from a device-resident token carry, and
        THEN resolves the previous tick's packed result — so the fetch
        overlaps the new tick's compute. Token streams are
        identical to the synchronous ``_speculative_tick`` (same greedy
        acceptance rule); events arrive one ``step()`` later."""
        prev = self._spec_pending
        if not self._spec_rounds_capacity_ok(produced, self._spec_pend(prev)):
            # Drain the pipeline FIRST (exactly once), then degrade to the
            # synchronous per-round tick, which handles per-round growth
            # and capacity session finishes.
            self._spec_flush(produced)
            return self._speculative_tick(produced)
        self._spec_pending = self._spec_dispatch(produced, prev)
        self._spec_resolve(produced, prev)

    def _spec_pend(self, prev):
        """Conservative in-flight token charge per slot (0 where the slot's
        tenant changed since dispatch)."""
        if prev is None:
            return np.zeros((self.batch,), np.int32)
        return np.where(
            np.array([g == pg for g, pg in zip(self.slots, prev[4])]),
            prev[3], 0,
        )

    def _spec_flush(self, produced) -> None:
        """Resolve any in-flight speculative tick (pipeline drain — used
        before falling back to the synchronous path)."""
        prev = self._spec_pending
        self._spec_pending = None
        self._spec_resolve(produced, prev)

    def _spec_dispatch(self, produced, prev):
        """Enqueue one fused multi-round speculative tick; returns the
        pending tuple (or None). Budgets are conservative against the
        in-flight tick (``prev``), mirroring ``_dispatch_tick``."""
        k = self.ecfg.speculative_k
        R = self.spec_rounds
        b = self.batch
        pend_b = self._spec_pend(prev)
        fresh = np.zeros((b, 1), np.int32)
        use_carry = np.zeros((b,), np.bool_)
        opts: List[SamplingOptions] = [SamplingOptions()] * b
        spec = np.zeros((b,), np.bool_)
        budget = np.zeros((b,), np.int32)
        for slot, gid in enumerate(self.slots):
            if gid is None:
                continue
            s = self.sessions[gid]
            fresh[slot, 0] = s.last_token
            use_carry[slot] = self._spec_carry_ok[slot]
            opts[slot] = s.options
            spec[slot] = self._session_speculative(s)
            budget[slot] = max(
                0,
                s.options.max_new_tokens - len(s.generated)
                - int(pend_b[slot]),
            )
        active = np.array(
            [g is not None for g in self.slots], np.bool_
        ) & (budget > 0)
        if not active.any():
            return None
        sp = SamplingParams.stack(opts)
        eos_ids = np.asarray([o.eos_token_id for o in opts], np.int32)
        if self._spec_carry is None:
            tokens_dev = jnp.asarray(fresh)
        else:
            tokens_dev = self._carry_combine(
                jnp.asarray(fresh), self._spec_carry,
                jnp.asarray(use_carry),
            )
        if self._spec_catch is None:
            ctok_dev = jnp.zeros((b, 1), jnp.int32)
            cmask_dev = jnp.zeros((b,), jnp.bool_)
        else:
            ctok_dev, cmask_dev = self._spec_catch
            # Rows whose carry is invalid (fresh admissions) also have a
            # freshly prefilled draft cache — no pending catch-up.
            cmask_dev = self._catch_combine(
                cmask_dev, jnp.asarray(use_carry)
            )
        self._flush_installs()
        pack_d, tok_d, ctok_d, cmask_d, self.cache, self.draft_cache = (
            self._spec_rounds_fn(
                self.params, self.draft[1], tokens_dev,
                self.cache, self.draft_cache, jnp.asarray(spec),
                jnp.asarray(active), jnp.asarray(eos_ids),
                jnp.asarray(budget), self._next_key(), sp,
                ctok_dev, cmask_dev,
            )
        )
        self._spec_carry = tok_d
        self._spec_catch = (ctok_d, cmask_d)
        self._spec_carry_ok = self._spec_carry_ok | active
        # Conservative in-flight charge: the tick can deliver at most
        # min(R*(k+1), budget) per row.
        pend = np.minimum(R * (k + 1), budget).astype(np.int32) * active
        return (pack_d, active, spec, pend, list(self.slots))

    def _spec_resolve(self, produced, prev) -> None:
        """Fetch and deliver the previous speculative tick's tokens (the
        packed single-array copy overlaps the tick just dispatched)."""
        if prev is None:
            return
        pack_d, active, spec, _pend, gids = prev
        k = self.ecfg.speculative_k
        # distcheck: host-sync-ok(deferred-fetch: overlaps next dispatch)
        pack = np.asarray(self._fetch(pack_d))  # [R, B, k+3]
        with self._region("deliver"):
            emits = pack[:, :, : k + 1]
            accs = pack[:, :, k + 1]
            palive = pack[:, :, k + 2]
            delivered_total = 0
            for slot, gid in enumerate(gids):
                if gid is None or not active[slot]:
                    continue
                s = self.sessions.get(gid)
                if s is None or self.slots[slot] != gid:
                    continue  # cancelled/reaped since dispatch
                emitted_in_graph = int((emits[:, slot] != -1).sum())
                delivered = 0
                for r in range(emits.shape[0]):
                    for j in range(k + 1):
                        if s.state != SessionState.ACTIVE:
                            break
                        tok = int(emits[r, slot, j])
                        if tok == -1:
                            break
                        self._deliver(s, tok, produced)
                        delivered += 1
                delivered_total += delivered
                if delivered < emitted_in_graph:
                    # Host-side stop mid-pack: the device carry token sits
                    # beyond the session's true last token.
                    self._spec_carry_ok[slot] = False
                if spec[slot]:
                    rounds_run = int(palive[:, slot].sum())
                    self.spec_stats["proposed"] += k * rounds_run
                    self.spec_stats["accepted"] += int(
                        (accs[:, slot] * palive[:, slot]).sum()
                    )
                    self.spec_stats["steps"] += rounds_run
        self.metrics.counter("decode_tokens", delivered_total)

    def _speculative_tick(self, produced) -> None:
        """Draft-propose + ONE-forward verify (greedy speculation): the
        target checks all k proposals in a single k+1-position step — k
        sequential HBM sweeps become one on the bandwidth-bound decode path.
        Acceptance = longest agreeing argmax prefix + the target's own token
        at the first disagreement, so output is IDENTICAL to plain greedy
        decoding. Normal (non-speculative) sessions ride the same verify
        step as a 1-token decode via per-row ``num_new``; cache rollback is
        a per-row ``lengths`` decrement (validity derives from lengths)."""
        k = self.ecfg.speculative_k
        b = self.batch
        tokens = np.zeros((b, 1), np.int32)
        opts: List[SamplingOptions] = [SamplingOptions()] * b
        spec = np.zeros((b,), np.bool_)
        for slot, gid in enumerate(self.slots):
            if gid is None:
                continue
            s = self.sessions[gid]
            tokens[slot, 0] = s.last_token
            opts[slot] = s.options
            spec[slot] = self._session_speculative(s)

        # Capacity: speculative rows need k+1 positions this tick, normal
        # rows 1; a row short of k+1 (but not of 1) decodes plainly (the
        # draft is caught up below so speculation can resume in sync).
        if isinstance(self.cache, PagedKVCache):
            for slot, gid in enumerate(self.slots):
                if gid is None:
                    continue
                s = self.sessions[gid]
                cap = self._grow_pages_for(
                    s, (k + 1) if spec[slot] else 1, produced
                )
                if cap is None:
                    continue
                if spec[slot] and s.total_len + k + 1 > cap:
                    spec[slot] = False
        else:
            for slot, gid in enumerate(self.slots):
                if gid is None:
                    continue
                s = self.sessions[gid]
                if s.total_len + 1 > self.ecfg.max_seq_len:
                    self._finish(s, "capacity", produced)
                    continue
                if spec[slot] and s.total_len + k + 1 > self.ecfg.max_seq_len:
                    spec[slot] = False

        active = np.array([g is not None for g in self.slots], np.bool_)
        if not active.any():
            return
        if self._windows:
            self._ensure_capacity(max(
                self.sessions[g].total_len + ((k + 1) if spec[i] else 1)
                for i, g in enumerate(self.slots) if g is not None
            ))

        dparams = self.draft[1]
        if (active & spec).any():
            prop_d, self.draft_cache = self._draft_propose(
                dparams, jnp.asarray(tokens), self.draft_cache,
                jnp.asarray(active & spec),
            )
        else:
            # Every speculative row was capacity-disabled this tick: skip
            # the k draft forwards (the verify below degrades to a plain
            # batched decode with k unused positions).
            prop_d = jnp.zeros((k, b), jnp.int32)

        num_new = np.where(active, np.where(spec, k + 1, 1), 0).astype(
            np.int32
        )
        sp = SamplingParams.stack(opts)
        self._flush_installs()
        preds_d, sampled_d, self.cache = self._verify(
            self.params, jnp.asarray(tokens), prop_d, jnp.asarray(spec),
            self.cache, jnp.asarray(num_new), self._next_key(), sp,
        )
        # Fetch the proposals AFTER dispatching verify: the copy overlaps
        # the target's k+1-position forward instead of serializing before it.
        # distcheck: host-sync-ok(post-verify fetch overlaps the forward)
        prop, preds, sampled = self._fetch((prop_d, preds_d, sampled_d))
        prop = np.asarray(prop).T  # [B, k]
        preds, sampled = np.asarray(preds), np.asarray(sampled)

        rollback = np.zeros((b,), np.int32)
        d_rollback = np.zeros((b,), np.int32)
        catch_mask = np.zeros((b,), np.int32)
        catch_tok = np.zeros((b, 1), np.int32)
        delivered = 0
        with self._region("deliver"):
            for slot, gid in enumerate(list(self.slots)):
                if gid is None or not active[slot]:
                    continue
                s = self.sessions[gid]
                if spec[slot]:
                    a = 0
                    while a < k and prop[slot, a] == preds[slot, a]:
                        a += 1
                    emitted = [int(t) for t in prop[slot, :a]]
                    emitted.append(int(preds[slot, a]) if a < k
                                   else int(preds[slot, k]))
                    rollback[slot] = k - a
                    if a == k:
                        # Full acceptance: the draft never consumed its own
                        # final proposal — catch it up below.
                        catch_mask[slot] = 1
                        catch_tok[slot, 0] = prop[slot, -1]
                    else:
                        d_rollback[slot] = k - a - 1
                    self.spec_stats["proposed"] += k
                    self.spec_stats["accepted"] += a
                    self.spec_stats["steps"] += 1
                else:
                    emitted = [int(sampled[slot])]
                for t in emitted:
                    if s.state != SessionState.ACTIVE:
                        break
                    self._deliver(s, t, produced)
                    delivered += 1
                if (
                    not spec[slot]
                    and self._session_speculative(s)
                    and s.state == SessionState.ACTIVE
                ):
                    # A speculative session that decoded plainly this tick
                    # (capacity pressure): its draft cache did not see the
                    # consumed token — catch it up, or every later proposal is
                    # positionally garbage (speculation cost with ~0 acceptance).
                    catch_mask[slot] = 1
                    catch_tok[slot, 0] = tokens[slot, 0]
        self.metrics.counter("decode_tokens", delivered)

        # Roll lengths back to the true sequence (rejected positions become
        # invisible). The draft over-ran by k-a-1 on partial acceptance.
        if rollback.any():
            self.cache = self.cache.replace(
                lengths=self.cache.lengths - jnp.asarray(rollback)
            )
        if d_rollback.any():
            self.draft_cache = self.draft_cache.replace(
                lengths=self.draft_cache.lengths - jnp.asarray(d_rollback)
            )
        if catch_mask.any():
            self.draft_cache = self._draft_catchup(
                dparams, jnp.asarray(catch_tok), self.draft_cache,
                jnp.asarray(catch_mask),
            )

    def _deliver(self, s: Session, token: int, produced) -> None:
        if s.cancel_requested or s.state == SessionState.CANCELLED:
            return  # cancelled mid-step; the scheduler reaps the slot next tick
        first = s.first_token_time is None
        s.record_token(token)
        if first and s.admit_time is not None:
            # the host holds the session's first token: the other end of
            # its admission dispatch (``_note_admitted``)
            self._note_first_token(s)
        done_eos = token == s.options.eos_token_id
        done_len = len(s.generated) >= s.options.max_new_tokens
        if done_eos or done_len:
            self._finish(s, "eos" if done_eos else "length", produced, token_emitted=token)
        else:
            produced.append((s.generation_id, token, False))

    def _finish(self, s: Session, reason: str, produced, token_emitted=None) -> None:
        s.state = SessionState.FINISHED
        s.finish_reason = reason
        s.finish_time = time.monotonic()
        # -1 = finish without a new token (the last real token was already
        # streamed on a prior step); consumers must not append it.
        produced.append(
            (s.generation_id, token_emitted if token_emitted is not None else -1, True)
        )
        self._release(s)
        self.metrics.counter("sessions_finished")

    def _release(self, s: Session) -> None:
        # A session reaped mid chunked-prefill has INCOMPLETE prompt KV
        # (only chunk_off tokens written): its pages must not be registered
        # as shareable prefix content below.
        partial = s.chunking
        if partial:
            s.chunking = False
            s.parked_key = None
        if s in self._chunking:
            self._chunking.remove(s)
        if s.slot is not None:
            self.slots[s.slot] = None
            if not any(g is not None for g in self.slots):
                self._idle_since = time.monotonic()
            # The device carry holds THIS session's last token; the slot's
            # next tenant must be fed its own fresh token.
            self._carry_ok[s.slot] = False
            if self.draft is not None:
                self._spec_carry_ok[s.slot] = False
            s.slot = None
        if isinstance(self.cache, PagedKVCache) and s.cow_src is not None:
            # Parked copy-on-write source ref (normally dropped when
            # _run_prefill enqueues the copy) — leak-proof the teardown.
            self.allocator.free([s.cow_src])
            s.cow_src = None
        if isinstance(self.cache, PagedKVCache) and s.pages:
            if self.ccfg.prefix_caching and not partial:
                # Content-address the pages fully covered by PROMPT tokens so
                # later sessions with the same prefix reuse their KV. Pages
                # touching generated tokens are position-pure too, but their
                # content depends on sampling — only prompt pages are shared.
                ps = self.ccfg.page_size
                if s.prefix_keys is None:
                    s.prefix_keys = PageAllocator.chain_keys(s.prompt, ps)
                for i, key in enumerate(s.prefix_keys):
                    if i < len(s.pages):
                        self.allocator.register(s.pages[i], key)
            self.allocator.free(s.pages)
            s.pages = []
        if s.window_pages:
            self.window_allocator.free(list(s.window_pages.values()))
            s.window_pages = {}
