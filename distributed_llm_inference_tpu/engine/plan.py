"""AttentionPlan: one owner for dispatch shapes, phases, and kernel choice.

Before this module the shape policy lived in four places that had to agree
by convention: ``engine.py:_bucket_for`` picked prompt buckets, the admission
paths padded to them, ``_install_bucket``/``_flush_installs`` kept their own
pad set for page-table scatters, and ``__init__`` resolved which attention
kernel each cache kind got. Every consumer compiled its own executable per
shape, so mixed-length traffic paid one recompile per (bucket, row-count)
pair (the "bucket tax").

The plan centralizes that policy:

* **Row classification & shapes.** A prompt is a PREFILL row (fits one
  dispatch), a CHUNKED-PREFILL row (walks the prompt ``chunk_tokens`` at a
  time), or a DECODE row. In ragged mode every prefill-family dispatch pads
  to ONE token width (``chunk_tokens``, default the largest bucket), so the
  warm executable set is finite and mixed lengths stop recompiling.
* **Partition preservation.** Ragged mode deliberately keeps the LEGACY
  admission partition — group membership via :meth:`bucket_for` and the
  legacy chunk cap — and changes only the padded dispatch widths. The
  engine draws one PRNG key per admission group/single in admission order;
  keeping the partition keeps the key sequence, which is what makes ragged
  on/off byte-exact for sampled decoding, not just greedy (the sampling
  noise depends on the key and row count, never on pad width).
* **Kernel selection.** Resolves ``use_pallas_attention`` (cache-owned
  decode kernels) and the ragged paged kernel (``ops/ragged_attention.py``)
  from one place; the paged cache reads the decision via its
  ``use_kernel``/``use_ragged`` fields.
* **Chunk/decode co-scheduling budget.** A fractional credit accumulator
  (``chunk_decode_share``) rations how many decode ticks also carry a
  chunked-prefill dispatch, so admission of a long prompt stretches over
  ticks instead of stalling the decode batch behind one monolithic prefill.
* **Dispatch telemetry.** Every dispatch funnels through
  :meth:`note_dispatch`, which maintains the seen-shape set behind the
  ``attn_recompiles`` counter (a first-seen (kind, shape) is exactly one
  fresh XLA executable), counts ``attn_ragged_dispatches`` /
  ``attn_chunked_rows``, and keeps the census of what the shapes cost:
  cumulative ``prefill_valid_tokens`` / ``prefill_padded_tokens`` over
  every prefill-family dispatch and ``decode_live_positions`` /
  ``decode_grid_positions`` over every decode dispatch, for a routed
  model ``moe_expert_rows_needed`` / ``moe_expert_rows_computed`` and the
  dispatches by the experts' compute path (``moe_dispatch_grouped`` /
  ``moe_dispatch_live`` / ``moe_dispatch_dense``) with, of a decode
  dispatch, the held experts a step and those it is expected to read
  (``moe_decode_experts_held`` / ``moe_decode_experts_live``), and
  under the ragged plan ``ragged_attn_tiles_live`` /
  ``ragged_attn_tiles_grid``: the tiles of the ragged kernel's grid that
  hold a live (query, key) pair, which it computes, over all it steps
  through; and over a paged cache ``decode_pages_live`` /
  ``decode_pages_joint``: the pages a decode dispatch's rows hold inside
  their windows, and those of them that fill whole tiles of the in-place
  sweep (a block of pages is one tile, a row's last one padded), beside them
  ``decode_scale_rows_by_page`` / ``decode_scale_rows_gathered``: the scale
  rows that sweep's kernel copied itself, a live page's of each stored
  plane, and those its wrapper gathered in XLA instead, every table slot's
  of every row (the fall-back by the pool's shape); where the
  sweep walks a list of its pool's live blocks (the latent pool's),
  ``decode_sweep_steps_walked`` / ``decode_sweep_steps_grid``: the grid
  steps the sweep walks (a row's blocks that hold a live page) over rows x
  the table's blocks.

This is also the fusion point ROADMAP item 4 (batched spec verification)
needs: a verify row is just one more ``num_new == k`` row class.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np

from ..ops.paged_attention import (
    _live_pages, _pages_per_block, _row_steps, _scale_rows_by_page,
)
from ..ops.ragged_attention import _tile_live

__all__ = ["AttentionPlan", "KernelSelection", "PREFILL", "CHUNKED", "DECODE"]

# Row phases (data, not shape: the ragged kernel serves all three in one
# grid call — see ops/ragged_attention.py).
PREFILL = "prefill"
CHUNKED = "chunked_prefill"
DECODE = "decode"


@dataclasses.dataclass(frozen=True)
class KernelSelection:
    """Resolved kernel routing for one engine instance.

    ``use_pallas``: cache-owned Pallas decode kernels (``use_kernel=`` on
    the cache; also gates the flash prefill swap in ``__init__``).
    ``use_ragged``: paged caches serve multi-token rows through the ragged
    mixed-phase kernel instead of the contiguous ``update_and_gather`` copy.
    """

    use_pallas: bool
    use_ragged: bool


class AttentionPlan:
    """Owns dispatch-shape policy, phase classification, and kernel choice.

    ``enabled`` resolves ``EngineConfig.ragged_attention``: ``None`` means
    auto — ON for paged caches on a real TPU backend (where the ragged
    kernel replaces the gather copy), OFF elsewhere so CPU defaults keep
    the legacy bucketed path (tests opt in explicitly; the plan's shaping
    and co-scheduling are backend-agnostic and byte-exact either way).
    """

    def __init__(self, engine_cfg, cache_cfg, metrics=None, backend=None):
        self.ecfg = engine_cfg
        self.ccfg = cache_cfg
        self.metrics = metrics
        self.backend = backend or jax.default_backend()
        self.buckets: Tuple[int, ...] = tuple(engine_cfg.prefill_buckets)
        if engine_cfg.ragged_attention is not None:
            self.enabled = bool(engine_cfg.ragged_attention)
        else:
            self.enabled = (
                self.backend == "tpu" and cache_cfg.kind == "paged"
            )
        self.chunk_tokens = (
            engine_cfg.prefill_chunk_tokens
            if engine_cfg.prefill_chunk_tokens is not None
            else self.buckets[-1]
        )
        if self.chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1, got {self.chunk_tokens}"
            )
        self.share = float(engine_cfg.chunk_decode_share)
        if not 0.0 <= self.share <= 1.0:
            raise ValueError(
                f"chunk_decode_share must be in [0, 1], got {self.share}"
            )
        self._credit = 0.0
        self._shapes = set()
        # Last dispatch seen by note_dispatch, as (kind, shape, valid) —
        # read by the engine's flight recorder so each tick record carries
        # the dispatch shape without a second telemetry funnel.
        self.last_dispatch: Optional[Tuple] = None
        # Every dispatch since the engine last took the list (one tick's
        # worth). None unless the engine's flight recorder is on: nobody
        # would empty it.
        self.dispatches: Optional[list] = None
        # Set by the engine when the cache stores the latent (MLA) fused
        # form: every dispatch then reads latents and decompresses in
        # place via the page walk, which note_dispatch surfaces as the
        # ``latent_decompress_dispatches`` counter.
        self.latent = False
        # Set by the engine for a model with routed experts: ``(rows,
        # seq_len, valid_share) -> (needed, computed, path)``, the expert
        # MLP rows a token over all its expert layers and the experts'
        # compute path in a dispatch of that shape (``ops/moe.py``);
        # note_dispatch keeps their census.
        self.expert_rows = None
        # Set by the engine for a model with a widened residual stream
        # (``ModelConfig.hyper``): the hyper-connection mixes a token
        # crosses, two a layer; note_dispatch keeps their census.
        self.mhc_mixes_per_token: Optional[int] = None
        # A stack of retention layers: the bytes of a row's state and summed
        # keys over every layer, which each decode step of a live row reads
        # (the engine sets it; the fold lies at the page boundaries).
        self.retention_state_bytes: Optional[int] = None
        # A looped stack (``ModelConfig.loop``): ``(laps, layers, the
        # layers' stored bytes)``; note_dispatch counts what the laps cost
        # (:meth:`_count_loop`).
        self.loop: Optional[Tuple[int, int, int]] = None
        # Set by the engine over a paged cache: ``pad width -> block_q``,
        # the q block the ragged kernel picks for this model at that width
        # (``ops/ragged_attention.py:_prep``); note_dispatch keeps the
        # census of the kernel's live tiles.
        self.ragged_block_q = None
        # Set by the engine: the stack's attention kinds as ``(window,
        # layers)`` pairs (``window`` None = every key). The censuses of
        # live tiles and of decode positions walk them: a window layer
        # skips what lies before its window, a full layer nothing. A stack
        # of one kind is one pair of one layer, as the census always
        # counted; a stack of window and full layers weighs each kind by
        # its layers.
        self.attention_layers: Tuple[Tuple[Optional[int], int], ...] = (
            (None, 1),
        )
        # Set by the engine for a model that selects its keys
        # (``ModelConfig.sparse``): note_dispatch keeps the census of the
        # selected and the live keys.
        self.sparse_topk: Optional[int] = None
        # Beside it: ``(scoring, attending)`` layers of such a stack, where
        # some layers attend to the selection of a scoring layer before
        # them (``ModelConfig.index_layers``; equal where every layer
        # scores). note_dispatch counts them a step.
        self.index_layers: Optional[Tuple[int, int]] = None
        # Set by the engine where the fused decode scan runs the in-place
        # sweep by copies (``ops/paged_attention.py``: an int8 paged pool
        # with the kernel): ``(kv heads, stored width, least table
        # capacity)``, the capacity in positions at which a table takes
        # that kernel. note_dispatch keeps the census of the pages it
        # attends a block as one tile.
        self.sweep_pool: Optional[Tuple[int, int, int]] = None
        # Set by the engine where that scan's sweep walks a list of its
        # pool's live blocks instead (pipelined blocks of a stored row
        # Mosaic cannot copy, the int8 latent pool's one plane, without a
        # selection: the cache's ``tail_walk``): ``(kv heads, stored
        # width)``. note_dispatch keeps the census of the grid steps walked.
        self.walked_pool: Optional[Tuple[int, int]] = None

    @property
    def windowed(self) -> bool:
        """Window and full layers in one stack: the census by kind is
        kept (``window_keys_*``)."""
        return len(self.attention_layers) > 1

    # ------------------------------------------------------------------
    # Row classification / shape policy
    # ------------------------------------------------------------------
    def classify(self, new_tokens: int, total_prompt: int) -> str:
        """Phase of a dispatch serving ``new_tokens`` query rows of a
        ``total_prompt``-token prompt (1 query = decode)."""
        if new_tokens <= 1 and total_prompt > 1:
            return DECODE
        if new_tokens < total_prompt:
            return CHUNKED
        return PREFILL

    def bucket_for(self, n: int) -> int:
        """LEGACY prompt bucket — still the admission-partition key in
        ragged mode (see module docstring: partition == PRNG key order)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def prefill_stride(self, legacy_cap: int) -> int:
        """Tokens consumed per chunk when a prompt walks in pieces. Capped
        at the legacy chunk cap (sink caches bound it by the window) so the
        default config's chunk boundaries — hence interior/final dispatch
        sequence — match the legacy path exactly."""
        if not self.enabled:
            return legacy_cap
        return min(self.chunk_tokens, legacy_cap)

    def final_shape(self, rest: int, legacy_cap: int) -> int:
        """Pad width for the final (sampled) chunk of a single-row prefill.
        Ragged mode pads every final to the stride — ONE warm shape per row
        count — instead of the rest's bucket. Cheap in attention since the
        ragged kernel computes only its live tiles: a short prompt's pad
        costs it a skipped grid step a tile, not a tile's matmuls."""
        if not self.enabled:
            return self.bucket_for(rest)
        return self.prefill_stride(legacy_cap)

    def group_shape(self, bucket: int, legacy_cap: int) -> int:
        """Pad width for a batched admission group whose members share
        ``bucket``. Ragged mode pads every group to the largest width so
        all buckets share one executable per row count."""
        if not self.enabled:
            return bucket
        return max(self.prefill_stride(legacy_cap), bucket)

    def install_pads(self, batch: int, max_pages: int) -> Tuple[int, int]:
        """Page-table install scatter pads (small burst, big burst) —
        folded in from ``_flush_installs``/``_install_bucket`` so the warm
        executable set for table writes is owned next to the dispatch
        shapes it serves."""
        big = 1
        while big < max(batch, max_pages):
            big *= 2
        return (4, big)

    # ------------------------------------------------------------------
    # Kernel selection
    # ------------------------------------------------------------------
    def select(self) -> KernelSelection:
        cc = self.ccfg
        tpu = self.backend == "tpu"
        # The ragged kernel is TPU-only in production: interpret mode is
        # orders of magnitude slower than XLA on CPU, so off-TPU the plan
        # keeps the gather path (ragged SHAPES still apply — parity is pad-
        # width-invariant) and the kernel is exercised by ops-level tests.
        use_ragged = self.enabled and tpu and cc.kind == "paged"
        if self.ecfg.use_pallas_attention is not None:
            use_pallas = self.ecfg.use_pallas_attention
        else:
            use_pallas = tpu and (
                (cc.kind in ("dense", "sink") and cc.kv_quant == "int8")
                or use_ragged
            )
        return KernelSelection(use_pallas=use_pallas, use_ragged=use_ragged)

    # ------------------------------------------------------------------
    # Chunk/decode co-scheduling
    # ------------------------------------------------------------------
    def co_schedule_ok(self, prompt_rest: int, temperature: float,
                       legacy_cap: int) -> bool:
        """Config-side eligibility for riding a prompt's prefill on the
        decode cadence: ragged mode on, a non-zero tick share, a prompt
        long enough to need chunking, and greedy decoding (a sampled
        session must keep the legacy key-draw position — chunk ticks would
        move its key relative to admission order)."""
        return (
            self.enabled
            and self.share > 0.0
            and temperature == 0.0
            and prompt_rest > self.prefill_stride(legacy_cap)
        )

    def take_chunk_credit(self, decode_active: bool) -> bool:
        """True when this tick may carry a chunk dispatch. With no decode
        rows to protect the chunk streams at full speed; otherwise credits
        accrue at ``chunk_decode_share`` per tick."""
        if not decode_active:
            return True
        self._credit += self.share
        if self._credit >= 1.0:
            self._credit -= 1.0
            return True
        return False

    # ------------------------------------------------------------------
    # Dispatch telemetry
    # ------------------------------------------------------------------
    def note_dispatch(self, kind: str, shape: Tuple[int, ...],
                      valid_tokens: Optional[int] = None,
                      active_rows: Optional[int] = None,
                      row_spans=None,
                      table_width: Optional[int] = None,
                      query_spans=None) -> None:
        """Record one attention dispatch: first-seen (kind, shape) is one
        fresh executable (``attn_recompiles``); prefill-family dispatches
        under ragged mode count ``attn_ragged_dispatches``.

        The census. A prefill-family ``shape`` is (rows, pad width) and
        ``valid_tokens`` its prompt tokens: they add to
        ``prefill_valid_tokens`` / ``prefill_padded_tokens``. A decode
        ``shape`` is (rows, steps, table width in pages — or ``max_len``
        for a dense cache, with ``page_size`` 1 then) and ``valid_tokens``
        the host-known context lengths of the active rows, summed: they add
        to ``decode_live_positions`` / ``decode_grid_positions`` (rows x
        table width x page size: what one step of the dispatch walks).

        A routed model (``expert_rows``) also counts the expert MLP rows
        its tokens need (valid tokens x the experts a token's result takes)
        and those the program runs (padded tokens x the experts it computes
        a token): ``moe_expert_rows_needed`` / ``moe_expert_rows_computed``.
        A decode dispatch's tokens are its ``active_rows`` (of ``shape[0]``
        rows) times its steps, each step a dispatch of ``shape[0]`` x 1
        tokens to the experts. The path ``ops/moe.py:dispatch_path`` takes
        at the dispatch's shape counts it under ``moe_dispatch_grouped``,
        ``moe_dispatch_live`` or ``moe_dispatch_dense``. A decode dispatch
        also adds, for ONE routed layer, the experts held times its steps to
        ``moe_decode_experts_held`` and those a step reads to
        ``moe_decode_experts_live``: every held one under dense-combine,
        under the live path the ones its ``active_rows`` are EXPECTED to
        pick (uniform routing; which they pick the host does not see).

        A prefill-family dispatch under the ragged plan over a paged cache
        also gives ``row_spans``, a ``(q_start, num_new)`` pair a real row
        (a pad row has no query and so no live tile), and the page table's
        width: of the ragged kernel's (rows, q-blocks, table width) grid,
        ``ragged_attn_tiles_grid``, the tiles :func:`_tile_live` keeps add
        to ``ragged_attn_tiles_live`` — the kernel's own predicate over
        its own ``block_q``, so the count is what it computes, chip or
        not.

        ``query_spans`` is a ``(first position, queries)`` pair a real row
        (a decode row's queries are the dispatch's steps). A model that
        selects its keys (``sparse_topk``): a query at position ``t`` has
        ``t + 1`` live keys and attends to ``min(topk, t + 1)`` of them, in
        every layer that attends alike, whether it scored the selection or
        reuses one. Their sums (ONE layer's) add to ``sparse_keys_live`` /
        ``sparse_keys_selected`` (``_total`` on ``/metrics``) and ride the
        dispatch's record as a fourth entry ``(selected, live)``; the
        layers that score and those that attend (``index_layers``) add, a
        step of the dispatch, to ``index_layers_scored`` /
        ``index_layers_attended``. A stack
        of window and full layers (``windowed``): a window layer's query
        sees ``min(window, t + 1)`` of the ``t + 1`` keys in its context;
        the sums, of ONE window layer, add to ``window_keys_seen`` /
        ``window_keys_in_context`` and ride the record as a fifth entry
        ``(seen, in context)`` behind a fourth that is None.

        A stack of retention layers (``retention_state_bytes``;
        :meth:`_count_retention`) counts what its fixed-size state costs and
        how much of its work is still pair by pair.

        A model with a widened residual stream (``mhc_mixes_per_token``:
        two mixes a layer) counts the hyper-connection mixes its valid
        tokens need and those its padded tokens run, ``mhc_mixes_needed`` /
        ``mhc_mixes_run``, a decode dispatch's tokens as for the experts'
        census.

        A looped stack (``loop``; :meth:`_count_loop`) counts the layer
        applications, the cached positions over every cache layer and the
        weight bytes its laps cost: ``loop_layer_passes``,
        ``loop_kv_positions_read``, ``loop_weight_bytes_read``."""
        shape = tuple(int(x) for x in shape)
        self.last_dispatch = (kind, shape, valid_tokens)
        sparse_keys = window_keys = None
        if self.sparse_topk is not None and query_spans is not None:
            sparse_keys = self._sparse_keys(query_spans)
            self.last_dispatch += (sparse_keys,)
        if self.windowed and query_spans is not None:
            window = next(w for w, _ in self.attention_layers if w)
            window_keys = self._keys_under(window, query_spans)
            self.last_dispatch += (None, window_keys)
        folds = None
        if self.retention_state_bytes is not None and query_spans is not None:
            # a dispatch that folds says so in its record: a sixth entry
            # (rows that fold in it, positions folded)
            folds = self._folds(query_spans)
            if folds[0]:
                self.last_dispatch += (None, None, folds)
        if self.dispatches is not None:
            self.dispatches.append(self.last_dispatch)
        key = (kind,) + shape
        if key not in self._shapes:
            self._shapes.add(key)
            if self.metrics is not None:
                self.metrics.counter("attn_recompiles")
        if self.metrics is None:
            return
        if self.latent:
            self.metrics.counter("latent_decompress_dispatches")
        if sparse_keys is not None:
            self.metrics.counter("sparse_keys_selected", sparse_keys[0])
            self.metrics.counter("sparse_keys_live", sparse_keys[1])
            if self.index_layers is not None:
                self._count_index_layers(shape[1] if kind == DECODE else 1)
        if window_keys is not None:
            self.metrics.counter("window_keys_seen", window_keys[0])
            self.metrics.counter("window_keys_in_context", window_keys[1])
        if self.enabled and kind != DECODE:
            self.metrics.counter("attn_ragged_dispatches")
        if folds is not None:
            self._count_retention(kind, shape, active_rows, query_spans, folds)
        if valid_tokens is None:
            return
        # a dispatch's tokens: a decode dispatch's are its ``active_rows``
        # (of ``shape[0]`` rows) times its steps
        if kind == DECODE:
            valid, padded = (active_rows or 0) * shape[1], shape[0] * shape[1]
        else:
            valid, padded = valid_tokens, shape[0] * shape[1]
        if self.loop is not None:
            self._count_loop(kind, shape, valid, valid_tokens, query_spans)
        if self.mhc_mixes_per_token is not None:
            self.metrics.counter(
                "mhc_mixes_needed", valid * self.mhc_mixes_per_token
            )
            self.metrics.counter(
                "mhc_mixes_run", padded * self.mhc_mixes_per_token
            )
        if self.expert_rows is not None:
            seq_len = 1 if kind == DECODE else shape[1]
            needed, computed, path, (held, read) = self.expert_rows(
                shape[0], seq_len, valid / max(padded, 1)
            )
            self.metrics.counter("moe_expert_rows_needed", valid * needed)
            self.metrics.counter("moe_expert_rows_computed", padded * computed)
            self.metrics.counter(f"moe_dispatch_{path}")
            if kind == DECODE:
                self.metrics.counter("moe_decode_experts_held", held * shape[1])
                self.metrics.counter("moe_decode_experts_live", read * shape[1])
        if kind == DECODE:
            paged = self.ccfg.kind == "paged"
            grid = shape[0] * shape[2] * (self.ccfg.page_size if paged else 1)
            live = valid_tokens
            if window_keys is not None:
                # by kind: a full layer's live positions are the contexts,
                # a window layer's what its window leaves of them (a step's
                # share of the dispatch's census)
                in_window = window_keys[0] // max(shape[1], 1)
                live = sum(
                    n * (valid_tokens if w is None else in_window)
                    for w, n in self.attention_layers
                )
                grid *= sum(n for _, n in self.attention_layers)
            self.metrics.counter("decode_live_positions", live)
            self.metrics.counter("decode_grid_positions", grid)
            if (
                paged and query_spans is not None
                and self.retention_state_bytes is None
            ):
                live, joint, walked, grid = self._swept_pages(
                    shape, query_spans
                )
                self.metrics.counter("decode_pages_live", live)
                self.metrics.counter("decode_pages_joint", joint)
                by_page, gathered = self._scale_rows(shape, live)
                self.metrics.counter("decode_scale_rows_by_page", by_page)
                self.metrics.counter("decode_scale_rows_gathered", gathered)
                if grid:
                    self.metrics.counter("decode_sweep_steps_walked", walked)
                    self.metrics.counter("decode_sweep_steps_grid", grid)
        else:
            self.metrics.counter("prefill_valid_tokens", valid_tokens)
            self.metrics.counter("prefill_padded_tokens", shape[0] * shape[1])
            if self.enabled and table_width is not None:
                live, grid = self._ragged_tiles(shape, row_spans, table_width)
                self.metrics.counter("ragged_attn_tiles_live", live)
                self.metrics.counter("ragged_attn_tiles_grid", grid)

    def _count_loop(self, kind, shape, valid, live, spans) -> None:
        """The census of a looped stack, every dispatch. ``loop_layer_passes``:
        laps x layers x the dispatch's valid tokens (a decode dispatch's are
        its active rows x steps): the layer applications its tokens cross.
        ``loop_kv_positions_read``: the cached positions its queries attend,
        over every CACHE layer (laps x layers): a decode step's are its rows'
        contexts as the host knows them (``live``) plus the steps before it
        in the dispatch, a prefill row's query at position ``t`` attends ``t
        + 1``. ``loop_weight_bytes_read``: laps x the layers' stored bytes,
        a decode step or a prefill dispatch each."""
        laps, layers, layer_bytes = self.loop
        count = self.metrics.counter
        steps = shape[1] if kind == DECODE else 1
        count("loop_layer_passes", laps * layers * valid)
        if kind == DECODE:
            rows = valid // max(steps, 1)
            positions = live * steps + rows * steps * (steps - 1) // 2
        else:
            positions = sum(
                int(n) * int(p) + int(n) * (int(n) + 1) // 2
                for p, n in spans or ()
            )
        count("loop_kv_positions_read", laps * layers * positions)
        count("loop_weight_bytes_read", laps * layer_bytes * steps)

    def _folds(self, spans) -> Tuple[int, int]:
        """(rows that fold, positions folded) of a dispatch of a stack of
        retention layers over ``spans``: a row starts at position ``p`` with
        ``p // PS * PS`` positions folded and ends with every full page
        folded (``cache/retention.py``)."""
        ps, rows, tokens = self.ccfg.page_size, 0, 0
        for start, n in spans:
            crossed = (int(start) + int(n)) // ps - int(start) // ps
            rows += crossed > 0
            tokens += crossed * ps
        return rows, tokens

    def _count_retention(self, kind, shape, active_rows, spans, folds) -> None:
        """The census of a stack of retention layers, ONE layer's positions
        and every layer's bytes. Every dispatch: ``retention_folds`` (rows
        that fold in it) and ``retention_tokens_folded`` (:meth:`_folds`). A
        decode dispatch of ``steps``: ``retention_state_rows_live`` /
        ``_held`` (active rows over the pool's rows, x steps),
        ``retention_decode_row_steps`` and ``retention_tail_positions`` (the
        unfolded positions a step's query attends pair by pair, itself among
        them, summed over rows and steps), ``retention_state_bytes_read``
        (live rows x steps x the state's bytes)."""
        ps, count = self.ccfg.page_size, self.metrics.counter
        count("retention_folds", folds[0])
        count("retention_tokens_folded", folds[1])
        if kind != DECODE:
            return
        rows, steps = active_rows or 0, shape[1]
        count("retention_state_rows_live", rows * steps)
        count("retention_state_rows_held", shape[0] * steps)
        count("retention_decode_row_steps", sum(int(n) for _, n in spans))
        count("retention_tail_positions", sum(
            int(n) * (int(p) % ps + 1) + int(n) * (int(n) - 1) // 2
            for p, n in spans
        ))
        count(
            "retention_state_bytes_read",
            rows * steps * self.retention_state_bytes,
        )

    def _count_index_layers(self, steps: int) -> None:
        """A dispatch's ``steps`` to ``index_layers_scored`` /
        ``index_layers_attended``."""
        scored, attended = self.index_layers
        self.metrics.counter("index_layers_scored", steps * scored)
        self.metrics.counter("index_layers_attended", steps * attended)

    def _sparse_keys(self, spans) -> Tuple[int, int]:
        """(selected, live) keys of a selection's queries over ``spans``."""
        return self._keys_under(self.sparse_topk, spans)

    @staticmethod
    def _keys_under(k: int, spans) -> Tuple[int, int]:
        """(kept, live) keys of queries at positions ``start .. start + n -
        1``, summed over ``spans``, where a query keeps at most ``k`` keys
        (a selection's ``topk``, a layer's window): closed sums of ``min(k,
        t + 1)`` and ``t + 1``."""
        selected = live = 0
        for start, n in spans:
            start, n = int(start), int(n)
            if n <= 0:
                continue
            lo, hi = start + 1, start + n          # live keys: lo .. hi
            live += (lo + hi) * n // 2
            under = max(0, min(hi, k) - lo + 1)    # queries with <= k keys
            selected += (lo + lo + under - 1) * under // 2 + (n - under) * k
        return selected, live

    def _swept_in_place(self, steps, width) -> bool:
        """Whether a decode dispatch of ``steps`` over a table ``width``
        slots wide runs the in-place sweep by copies (``sweep_pool``)."""
        return (
            self.sweep_pool is not None and steps > 1
            and width * self.ccfg.page_size >= self.sweep_pool[2]
        )

    def _scale_rows(self, shape, live) -> Tuple[int, int]:
        """(by page, gathered) scale rows of a decode dispatch of ``shape``
        (rows, steps, table width) whose rows hold ``live`` pages
        (:meth:`_swept_pages`), where the in-place sweep by copies decodes
        (an int8 pool's K and V: two stored planes): the rows its kernel
        copied beside a live page's K and V, that page's of both planes; or,
        where the pool's page size leaves the kernel no copy it may make
        (``ops/paged_attention.py:_scale_rows_by_page``), those the wrapper
        gathered for it: every table slot's of every row, both planes, a
        layer a step, in ``decode_pages_live``'s layers (one where every
        layer is alike). Both 0 where another path decodes."""
        rows, steps, width = shape
        if not self._swept_in_place(steps, width):
            return 0, 0
        planes = 2
        if _scale_rows_by_page(planes * self.ccfg.page_size):
            return planes * live, 0
        layers = sum(n for _, n in self.attention_layers)
        return 0, rows * width * planes * layers * steps

    def _swept_pages(self, shape, spans) -> Tuple[int, int, int, int]:
        """(live, joint) pages and (walked, grid) steps of a decode dispatch
        of ``shape`` (rows, steps, table width) whose active rows' queries
        span ``spans``: a page, a grid step, a layer a step. Live is what a
        row's pool holds inside the layer's window (the kernel's own
        :func:`_live_pages`: the pool's length is the first query's position
        all through the dispatch, the window moves with the query); joint,
        those of them that lie in full blocks of :func:`_pages_per_block`
        pages, the tiles of the in-place sweep (``sweep_pool``) that hold no
        padding: none where a block is one page (the tile it always was) or
        another path decodes. Over a pool whose sweep walks a list
        (``walked_pool``) walked is what the list names
        (``ops/paged_attention.py:_sweep_walk``, by its own
        :func:`_row_steps`): an active row's blocks that hold a live page,
        one step for every other row; grid is rows x the table's blocks,
        what a grid over the table's width steps through. Both 0 where
        another path decodes."""
        rows, steps, width = shape
        page_size = self.ccfg.page_size
        block = walk_block = 0
        if self._swept_in_place(steps, width):
            heads, stored, _ = self.sweep_pool
            block = _pages_per_block(width, heads, page_size, stored, steps)
        if self.walked_pool is not None and steps > 1:
            heads, stored = self.walked_pool
            walk_block = _pages_per_block(
                width, heads, page_size, stored, steps, planes=1
            )
        spans = np.asarray(spans, np.int64).reshape(-1, 2)
        start = spans[:, 0, None]
        query = start + np.arange(steps)[None, :]
        live = joint = walked = grid = 0
        for window, layers in self.attention_layers:
            lo, hi = _live_pages(start, query, page_size, width, window, np)
            pages = np.broadcast_to(hi - lo, query.shape)
            live += layers * int(pages.sum())
            if block > 1:
                joint += layers * int((pages // block * block).sum())
            if walk_block:
                took = np.broadcast_to(
                    _row_steps(lo, hi, walk_block, np), query.shape
                )
                idle = (rows - len(spans)) * steps
                walked += layers * (int(took.sum()) + idle)
                grid += layers * rows * steps * -(-width // walk_block)
        return live, joint, walked, grid

    def _ragged_tiles(self, shape, row_spans, table_width) -> Tuple[int, int]:
        """(live, all) tiles of the ragged kernel's grid for a dispatch of
        ``shape`` (rows, pad width) whose real rows span ``row_spans``: one
        layer's grid where the layers are all alike, each attention kind's
        by its layers where they are not (``attention_layers``); a row's
        live kv is ``q_start + num_new``, as the cache passes it."""
        block_q = self.ragged_block_q(shape[1])
        q_blocks = -(-shape[1] // block_q)
        spans = np.asarray(row_spans, np.int64).reshape(-1, 2)
        start, new = spans[:, 0, None, None], spans[:, 1, None, None]
        live = sum(
            layers * int(np.count_nonzero(_tile_live(
                np.arange(q_blocks)[None, :, None],
                np.arange(table_width)[None, None, :],
                start, new, start + new, block_q=block_q,
                page_size=self.ccfg.page_size, sliding_window=window,
            )))
            for window, layers in self.attention_layers
        )
        return live, shape[0] * q_blocks * table_width * sum(
            layers for _, layers in self.attention_layers
        )

    def note_chunk_rows(self, n: int = 1) -> None:
        if self.metrics is not None:
            self.metrics.counter("attn_chunked_rows", n)
