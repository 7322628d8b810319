"""Structured metrics and timing.

The reference's only observability is two ``print`` statements in its weight
loader (``/root/reference/distributed_llm_inference/utils/model.py:61,82``;
SURVEY §5.5). Here: counters + latency histograms good enough to derive
tokens/sec/chip, p50 TTFT and batch occupancy, plus structured logging hooks.
"""

from __future__ import annotations

import collections
import json
import logging
import re
import statistics
import threading
from typing import Dict, List, Optional

_PROM_NAME = re.compile(r"[^a-zA-Z0-9_:]")

logger = logging.getLogger("distributed_llm_inference_tpu")

# Central metric registry: every name emitted anywhere in the package,
# declared once — name -> (kind, help). ``tools/distcheck`` (DC400/DC401)
# enforces that emitters and this table never drift: an undeclared emit or
# a dead declaration fails tier-1. ``*`` entries match dynamically
# suffixed families (f-string names). Kinds: ``counter`` (monotonic,
# ``_total`` on /metrics), ``gauge`` (last-write-wins), ``summary``
# (observe() histories; ``_seconds`` on /metrics unless the name
# carries its own unit suffix). Names here are pre-exposition — the
# prometheus() renderer appends the suffixes, so declarations must not.
METRICS = {
    # engine: admission + sessions
    "sessions_submitted": ("counter", "Sessions accepted by submit()"),
    "sessions_finished": ("counter", "Sessions retired (any reason)"),
    "sessions_rejected": ("counter", "Sessions refused at admission"),
    "sessions_deadline_expired": ("counter", "Sessions reaped past deadline"),
    "admission_order_errors": ("counter", "Admission-order hook raised; tick fell back to FIFO"),
    "admit_sync_sessions": ("counter", "Sessions admitted synchronously"),
    "admit_overlap_sessions": ("counter", "Sessions admitted via overlap"),
    "loop_exit_lap_sum": (
        "counter", "Exit laps of a looped stack's delivered decode tokens, summed"
    ),
    "loop_exit_positions": (
        "counter", "Delivered decode tokens whose exit lap the device reported"
    ),
    "admit_overlap_spill": ("counter", "Overlap admissions spilled to sync"),
    "admit_overlap_inflight": ("gauge", "Prefills in flight behind decode"),
    "admit_to_merge": ("summary", "Overlap admission to KV-merge latency"),
    # engine: prefill / decode hot path
    "prefill_tokens": ("counter", "Prompt tokens prefilled"),
    "batched_prefills": ("counter", "Prefills served by batched dispatch"),
    "ring_prefills": ("counter", "Prefills served by the ring pipeline"),
    "prefill_fresh_rows": ("counter", "Single-row final prefills over the dispatch's own K/V, installed as whole pages"),
    "prefill_table_rows": ("counter", "Single-row final prefills through the row's page table or dense row"),
    "prefill_pool_inplace_rows": ("counter", "Rows of prefill-family dispatches whose cache writes whole pages and reads at (layer, page) of the carried pool stacks"),
    "prefill_pool_scatter_rows": ("counter", "Rows of prefill-family dispatches whose cache is handed a layer's planes (a position scatter, a dense row, a fresh install)"),
    "prefix_cached_tokens": ("counter", "Prompt tokens served from prefix cache"),
    # prefixstore: CoW sharing / host-DRAM spill tier / prefix routing
    "prefix_hit_rate": ("gauge", "Cumulative fraction of prompt tokens reused"),
    "prefix_pages_shared": ("counter", "Shared prefix-page attachments"),
    "prefix_cow_copies": ("counter", "Copy-on-write splits of shared pages"),
    "prefix_spill_bytes": ("gauge", "Host spill arena bytes resident"),
    "prefix_spilled_pages": ("counter", "Prefix pages spilled to host DRAM"),
    "prefix_spill_reloads": ("counter", "Prefix pages reloaded from the arena"),
    "prefix_reload_ms": ("summary", "Host->device prefix page reload time"),
    "prefix_reload_errors": ("counter", "Arena entries rejected at reload"),
    "routed_by_prefix": ("counter", "Requests routed to a prefix-holding node"),
    # engine: attention plan (ragged mixed-phase dispatch — engine/plan.py)
    "attn_recompiles": ("counter", "First-seen attention dispatch shapes"),
    "attn_ragged_dispatches": ("counter", "Prefill-family ragged dispatches"),
    "attn_chunked_rows": ("counter", "Chunk rows co-scheduled with decode"),
    # census of what the dispatches walk, cumulative (valid / padded and
    # live / grid give the occupancy of any interval between two scrapes)
    "prefill_valid_tokens": ("counter", "Prompt tokens in prefill-family dispatches"),
    "prefill_padded_tokens": ("counter", "Rows x pad width of the same dispatches"),
    "decode_live_positions": ("counter", "Context positions of active decode rows"),
    "decode_grid_positions": ("counter", "Rows x table width x page size walked"),
    "decode_pages_live": (
        "counter", "Pages decode rows hold inside their windows, a layer a step"),
    "decode_pages_joint": (
        "counter", "Of those, pages in full blocks of the in-place sweep (a tile, unpadded)"),
    # the in-place sweep's scale rows: by_page / (by_page + gathered) is the
    # share the kernel copied itself, a live page's, over what its wrapper
    # gathered of every table slot
    "decode_scale_rows_by_page": (
        "counter", "Scale rows the decode sweep copied by the live page, a stored plane each"),
    "decode_scale_rows_gathered": (
        "counter", "Scale rows its wrapper gathered instead: rows x table slots x planes, a layer a step"),
    # a pool whose decode sweep walks a list of its live blocks (the latent
    # pool's): walked / grid is the share of rows x table blocks it keeps
    "decode_sweep_steps_walked": (
        "counter", "Grid steps the latent decode sweep walks, a layer a step"),
    "decode_sweep_steps_grid": (
        "counter", "Rows x table blocks of the same dispatches"),
    # routed experts (ops/moe.py:expert_rows_per_token): needed / computed
    # over an interval is pad waste times the compute strategy's waste
    "moe_expert_rows_needed": (
        "counter", "Expert MLP rows the dispatches' valid tokens need"
    ),
    "moe_expert_rows_computed": (
        "counter", "Expert MLP rows the dispatches' padded tokens run"
    ),
    # dispatches of a routed model by the path its experts took
    # (ops/moe.py:dispatch_path, from the dispatch's shape): grouped,
    # live or dense
    "moe_dispatch_*": (
        "counter", "Dispatches by the experts' compute path at their shape"
    ),
    # a decode dispatch's steps in one routed layer: live / held is the
    # share of the held experts' weights a step reads
    "moe_decode_experts_held": (
        "counter", "Experts a routed layer holds, a decode step"
    ),
    "moe_decode_experts_live": (
        "counter",
        "Of those, the experts a step's active rows are expected to pick "
        "(an expectation under uniform routing; all held where dense)",
    ),
    # a widened residual stream (ops/hyper_connections.py): a token crosses
    # two mixes a layer; needed / run over an interval is the pad waste
    "mhc_mixes_needed": (
        "counter", "Hyper-connection mixes the dispatches' valid tokens need"
    ),
    "mhc_mixes_run": (
        "counter", "Hyper-connection mixes the dispatches' padded tokens run"
    ),
    # the ragged prefill kernel's grid, a layer's a dispatch: live / grid
    # is the share of its steps that compute (ops/ragged_attention.py)
    "ragged_attn_tiles_live": (
        "counter", "Ragged-kernel tiles holding a live (query, key) pair"
    ),
    "ragged_attn_tiles_grid": (
        "counter", "Rows x q-blocks x table width of the same dispatches"
    ),
    # a learned key selection (ops/sparse_attention.py): selected / live
    # over an interval says whether the contexts passed topk
    "sparse_keys_selected": (
        "counter", "Keys the dispatches' queries attend to under a selection"
    ),
    "sparse_keys_live": (
        "counter", "Keys the same queries could see (their positions + 1)"
    ),
    # where layers share a selection (ModelConfig.index_layers): scored /
    # attended over an interval is the share of index scoring left
    "index_layers_scored": (
        "counter", "Layers that scored a selection of their own, a step"
    ),
    "index_layers_attended": (
        "counter", "Layers that attended under a selection, a step"
    ),
    # a stack of window and full layers (cache/paged.py, the two-pool
    # classes): what a window layer's queries see of their contexts, and
    # the window pool's pages as they leave rows and as they stand
    "window_keys_seen": (
        "counter", "Keys the dispatches' queries see in one window layer"
    ),
    "window_keys_in_context": (
        "counter", "Keys the same queries have in context (positions + 1)"
    ),
    "window_pages_released": (
        "counter", "Window-pool pages released as rows' windows passed them"
    ),
    "window_pool_free_pages": ("gauge", "Free pages of the window pool"),
    "kv_pool_free_pages": (
        "gauge", "Free pages of the full layers' pool beside a window pool"
    ),
    "decode_tokens": ("counter", "Tokens emitted by decode"),
    "cache_growths": ("counter", "KV cache reallocations"),
    # latent (MLA) KV compression (cache/latent.py)
    "kv_bytes_per_token": ("gauge", "Stored KV bytes per token, all layers"),
    "latent_decompress_dispatches": (
        "counter", "Attention dispatches reading the latent stored form"
    ),
    # engine: speculative decoding
    "spec_adapt_window_resets": ("counter", "Adaptive-k A/B window resets"),
    "spec_adapt_probes": ("counter", "Adaptive-k probe windows started"),
    "spec_adapt_suspensions": ("counter", "Speculation suspensions (low accept)"),
    # disaggregated prefill/decode
    "disagg_prefills": ("counter", "Remote prefills exported"),
    "disagg_admitted": ("counter", "Sessions admitted from shipped KV"),
    "disagg_fallback_local": ("counter", "Disagg failures served locally"),
    "disagg_kv_frames_sent": ("counter", "KV frames shipped to decode pool"),
    "disagg_prefill_errors": ("counter", "Prefill-pool requests that errored"),
    "kv_transfer_bytes": ("summary", "Shipped KV payload size per session"),
    "kv_transfer_ms": ("summary", "KV ship+decode wall time per session"),
    # distributed client / worker / relay plane
    "connections_opened": ("counter", "Relay connections dialed"),
    "failovers": ("counter", "Mid-generation worker re-routes"),
    "stale_replies_discarded": ("counter", "Replies from abandoned attempts"),
    "row_errors": ("counter", "Per-row errors inside batched replies"),
    "client_batch_group": ("summary", "generate_many co-batch group size"),
    "client_generate_errors": ("counter", "Client-side generate failures"),
    "malformed_frames": ("counter", "Frames dropped by schema checks"),
    "unknown_ops_dropped": ("counter", "Frames dropped for an unknown op"),
    "duplicate_hops_skipped": ("counter", "At-most-once hop dedup skips"),
    "worker_restarts": ("counter", "Consume-thread watchdog restarts"),
    "pool_batch_occupancy": ("summary", "Items per task-pool device call"),
    "pool_batches_size_*": ("counter", "Task-pool batches by exact size"),
    # serving gateway
    "http_requests": ("counter", "Completion requests received"),
    "http_429": ("counter", "Requests shed at capacity"),
    "http_503_breaker": ("counter", "Requests failed fast by the breaker"),
    "ttft": ("summary", "Gateway time to first token"),
    "gateway_tokens": ("counter", "Tokens delivered to HTTP clients"),
    "queue_depth": ("gauge", "Backend queue depth at scrape"),
    "active_sessions": ("gauge", "Live backend sessions at scrape"),
    "http_inflight": ("gauge", "Gateway in-flight completions"),
    "engine_ttft": ("summary", "Engine-side TTFT (sync admission)"),
    "engine_ttft_decode": ("summary", "Engine-side TTFT (overlap admission)"),
    "engine_ttft_prefill": ("summary", "Engine-side TTFT (disagg prefill)"),
    # observed at the event, so _sum/_count give a mean over any interval
    "engine_queue_wait": ("summary", "submit() to the admission dispatch"),
    "engine_first_token_wait": ("summary", "Admission dispatch to first token on the host"),
    # engine tick host clock (utils/tracing.py FlightRecorder; TraceConfig on)
    "engine_ticks": ("counter", "step() calls the flight recorder timed"),
    "engine_tick_seconds": ("counter", "Wall seconds of those ticks, outside included"),
    "engine_tick_*_seconds": ("counter", "The same by host phase (tracing.PHASES)"),
    # the dispatch clock (utils/tracing.py DispatchClock; TraceConfig on and
    # somebody reading the ticks: the clock's lease): counted as a noted
    # dispatch's result is ready on the device, from its enqueue, return and
    # ready stamps
    "engine_clocked_ticks": (
        "counter", "Ticks that ended with the dispatch clock armed"
    ),
    "engine_device_seconds_*": (
        "counter", "Device seconds by kind of dispatch (prefill, chunk, decode)"
    ),
    "engine_dispatches_*": ("counter", "Noted dispatches by kind, as they became ready"),
    "engine_decode_steps": ("counter", "Decode steps of those decode dispatches"),
    "engine_device_idle_seconds": (
        "counter", "Seconds the device had nothing of the engine's to run"
    ),
    "engine_device_idle_*_seconds": (
        "counter", "The same by the host phase that held the gap"
    ),
    "engine_enqueue_seconds": (
        "counter", "Drive-thread seconds inside noted dispatches' compiled calls"
    ),
    # a first token's wait, cut by the dispatches that carried its prompt
    "engine_first_token_prefill_wait": (
        "summary", "First-token wait while the device ran other work or none"
    ),
    "engine_first_token_prefill_own": (
        "summary", "Device seconds of the request's own prompt dispatches"
    ),
    "engine_first_token_deliver": (
        "summary", "Prompt's last dispatch ready to first token on the host"
    ),
    # programs the process loaded (utils/tracing.py ProgramLoads: JAX's own
    # monitoring events), published at the end of each timed tick
    "engine_program_loads": ("counter", "Programs compiled or read from the cache"),
    "engine_program_load_seconds": (
        "counter", "Seconds tracing, lowering and compiling or reading them"
    ),
    "engine_program_load_*_seconds": (
        "counter", "The same by stage (tracing.LOAD_STAGES); the four sum to it"
    ),
    "engine_compile_cache_hits": ("counter", "Of those, persistent-cache reads"),
    # the process's boot (utils/tracing.py BootMarks; TraceConfig on), set as
    # each mark is passed
    "process_start_time_seconds": ("gauge", "Epoch seconds at which the OS started the process"),
    "boot_*_seconds": (
        "gauge", "Seconds from that start to a boot mark (tracing.BOOT_MARKS)"
    ),
    # multi-tenant admission scheduler (sched/)
    "sched_admitted": ("counter", "Tickets admitted by the scheduler"),
    "sched_tenant_admit_*": ("counter", "Admitted tickets by tenant"),
    "sched_reject_rate_limit": ("counter", "429s from a tenant token bucket"),
    "sched_reject_queue_full": ("counter", "429s from lane/gateway depth caps"),
    "sched_shed_early": ("counter", "Requests shed pre-prefill by deadline"),
    "sched_lane_depth_*": ("gauge", "Pending tickets per admission lane"),
    "sched_queue_wait": ("summary", "Ticket admission to first token"),
    # distributed request tracing (utils/tracing.py + serving gateway)
    "traces_sampled": ("counter", "Requests minted a TraceContext"),
    "trace_spans_dropped": ("counter", "Spans evicted by recorder capacity"),
    "trace_pull_failures": ("counter", "trace.pull node collections failed"),
    # circuit breaker
    "breaker_state": ("gauge", "0 closed / 1 open / 2 half-open"),
    "breaker_*_transitions": ("counter", "Breaker transitions into a state"),
    "breaker_failures_recorded": ("counter", "Failure signals seen"),
    # session migration / crash recovery (migrate.* frame plane)
    "sessions_exported": ("counter", "Mid-decode sessions snapshotted"),
    "sessions_resumed": ("counter", "Sessions re-admitted from a snapshot"),
    "checkpoints_shipped": ("counter", "Session checkpoints sent to gateway"),
    "checkpoint_frames_sent": ("counter", "Checkpoint KV frames shipped"),
    "node_deaths_detected": ("counter", "Decode nodes declared dead mid-stream"),
    "resume_attempts": ("counter", "Stream migrations started after a death"),
    "resume_failures": ("counter", "Streams failed after resume budget spent"),
    "resume_shed": ("counter", "Resumes shed by deadline headroom"),
    "tokens_deduped": ("counter", "Replayed tokens suppressed by seq dedup"),
    "stale_frames_fenced": ("counter", "Frames dropped from fenced attempts"),
    "mttr_ms": ("summary", "Death detection to first post-resume token"),
    # elastic fleet controller (fleet/): drain / rebalance / autoscale
    "fleet_drains": ("counter", "Drain operations issued to decode nodes"),
    "fleet_drained_sessions": ("counter", "Streams re-homed by a drain handoff"),
    "fleet_handoffs_sent": ("counter", "Session handoffs shipped by nodes"),
    "fleet_rebalance_migrations": ("counter", "Sessions asked off hot nodes"),
    "fleet_scale_out": ("counter", "Autoscaler pool-grow decisions"),
    "fleet_scale_in": ("counter", "Autoscaler drain-then-fence decisions"),
    "fleet_pool_size": ("gauge", "Live (non-draining) decode nodes at scrape"),
    # bytes-vs-latency placement decisions (fleet/costmodel.py)
    "fleet_query_moved": ("counter", "Placements routed to the prefix holder"),
    "fleet_pages_fetched": ("counter", "Placements that shipped prefix pages"),
    "fleet_migrated": ("counter", "Placements that recompute elsewhere"),
    "fleet_pages_served": ("counter", "Prefix pages exported for a page-ship"),
    "fleet_pages_imported": ("counter", "Shipped prefix pages installed"),
    "fleet_page_ship_failed": ("counter", "Page-ships abandoned (cold fallback)"),
    "fleet_page_ship_ms": ("summary", "Page-ship round trip wall time"),
}


class Metrics:
    """Thread-safe counters, gauges and summaries (the serving loop runs
    host threads around the jitted steps — SURVEY §5.2's concurrency
    caution)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = collections.defaultdict(float)
        self._timings: Dict[str, List[float]] = collections.defaultdict(list)
        self._gauges: Dict[str, float] = {}

    def counter(self, name: str, inc: float = 1.0) -> None:
        with self._lock:
            self._counters[name] += inc

    def gauge(self, name: str, value: float) -> None:
        """Set a persistent gauge (last-write-wins) — for state that an
        owner updates on transition (circuit-breaker state, pool size)
        rather than the caller sampling it at scrape time."""
        with self._lock:
            self._gauges[name] = float(value)

    def get_gauge(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def get_counter(self, name: str) -> float:
        """One counter's current value (snapshot() is unsuitable for
        per-tick reads — it sorts every timing list)."""
        with self._lock:
            return self._counters.get(name, 0.0)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._timings[name].append(value)

    def percentile(self, name: str, q: float) -> float:
        with self._lock:
            vals = sorted(self._timings.get(name, []))
        if not vals:
            return float("nan")
        idx = min(len(vals) - 1, int(q / 100.0 * len(vals)))
        return vals[idx]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._counters)
            out.update(self._gauges)
            for name, vals in self._timings.items():
                if not vals:
                    continue
                out[f"{name}_count"] = len(vals)
                out[f"{name}_mean_s"] = statistics.fmean(vals)
                srt = sorted(vals)
                out[f"{name}_p50_s"] = srt[len(srt) // 2]
                out[f"{name}_p99_s"] = srt[min(len(srt) - 1, int(0.99 * len(srt)))]
        return out

    def log_snapshot(self) -> None:
        logger.info("metrics %s", json.dumps(self.snapshot(), sort_keys=True))

    def prometheus(
        self,
        prefix: str = "dli",
        extra_gauges: Optional[Dict[str, float]] = None,
    ) -> str:
        """Prometheus text exposition (the ``/metrics`` endpoint body).

        Counters become ``<prefix>_<name>_total`` counters; timings become
        ``<prefix>_<name>_seconds`` summaries (p50/p99 quantiles + _sum +
        _count); ``extra_gauges`` are point-in-time gauges (queue depth,
        active sessions) sampled by the caller and merged over the
        persistent ``gauge()`` values."""

        def clean(name: str) -> str:
            return _PROM_NAME.sub("_", f"{prefix}_{name}")

        with self._lock:
            counters = dict(self._counters)
            timings = {k: list(v) for k, v in self._timings.items()}
            gauges = dict(self._gauges)
        gauges.update(extra_gauges or {})
        lines: List[str] = []
        for name in sorted(counters):
            metric = clean(name) + "_total"
            lines.append(f"# TYPE {metric} counter")
            lines.append(f"{metric} {counters[name]:.10g}")
        for name in sorted(timings):
            vals = sorted(timings[name])
            if not vals:
                continue
            # Summaries default to seconds; names that already carry their
            # unit (kv_transfer_bytes, kv_transfer_ms) keep it as-is.
            suffix = "" if name.endswith(("_bytes", "_ms")) else "_seconds"
            metric = clean(name) + suffix
            lines.append(f"# TYPE {metric} summary")
            p50 = vals[len(vals) // 2]
            p99 = vals[min(len(vals) - 1, int(0.99 * len(vals)))]
            lines.append(f'{metric}{{quantile="0.5"}} {p50:.10g}')
            lines.append(f'{metric}{{quantile="0.99"}} {p99:.10g}')
            lines.append(f"{metric}_sum {sum(vals):.10g}")
            lines.append(f"{metric}_count {len(vals)}")
        for name in sorted(gauges):
            metric = clean(name)
            lines.append(f"# TYPE {metric} gauge")
            # thirteen digits: an epoch stamp to the millisecond
            lines.append(f"{metric} {gauges[name]:.13g}")
        return "\n".join(lines) + "\n"
