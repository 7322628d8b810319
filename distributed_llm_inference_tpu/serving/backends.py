"""Generation backends behind the HTTP gateway.

One protocol, two implementations:

* :class:`EngineBackend` — a local :class:`InferenceEngine`. A single
  driver thread owns ``engine.step()`` (the engine's contract: submit and
  cancel are thread-safe, ``step`` must stay single-caller) and fans
  per-token events out to per-request asyncio queues via
  ``loop.call_soon_threadsafe``.
* :class:`ClientBackend` — the relay-tier :class:`DistributedClient`.
  Each request runs ``client.generate`` on its own thread (the client is
  thread-safe per-call) with the streaming/cancel hooks.
* :class:`FleetBackend` — the crash-recoverable decode fleet: requests
  stream from a :class:`~..disagg.decode_node.DecodeNode` as
  sequence-stamped ``migrate.tok`` frames; on node death mid-stream the
  gateway fences the node's directory lease and resumes the session on a
  healthy node from its last shipped checkpoint, deduplicating replayed
  tokens by sequence index so the client sees each token exactly once.

Both expose the same surface the server consumes: ``start(loop)``,
``submit(prompt, options, deadline, ticket=None, trace=None) -> Handle``,
``cancel(handle)``, ``active_sessions()``, ``queue_depth()``,
``stop()``, ``.metrics``, ``attach_scheduler(sched)``,
``attach_tracer(recorder, cfg)``, ``collect_trace(trace_id)``,
``flight_snapshot()``.

Admission policy lives OUTSIDE the backends, in :mod:`..sched`: the
gateway's :class:`~..sched.Scheduler` decides rate limits, lanes and
shedding, stamps each accepted request with a :class:`~..sched.Ticket`,
and backends just carry it — EngineBackend/DisaggBackend forward the
ticket's sort key into the engine's admission-order hook; the routing
backends share the scheduler's placement rule
(:mod:`..sched.placement`) for the prefix-locality-vs-load choice.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import queue
import threading
import time
import uuid
from typing import Dict, List, Optional, Sequence

from ..config import DisaggConfig, FleetConfig, PrefixConfig, SchedConfig
from ..engine.sampling import SamplingOptions
from ..fleet.costmodel import CostModel
from ..fleet.policy import least_loaded, live_decode_rows
from ..sched.placement import choose_decode_node, prefix_worth_detour
from ..utils.metrics import Metrics
from ..utils.tracing import Span, trace_span

logger = logging.getLogger("distributed_llm_inference_tpu")


@dataclasses.dataclass
class TokenEvent:
    """One item on a request's stream queue. ``token == -1`` with
    ``finished`` means the stream ended without a new token (cancel,
    deadline, capacity)."""

    token: int
    finished: bool
    finish_reason: Optional[str] = None
    # Exactly-once bookkeeping (FleetBackend): the token's index in the
    # generated sequence, and how many times the stream was re-homed onto
    # another node. Backends without recovery leave the defaults; the SSE
    # layer then stamps ``seq`` itself from a local counter.
    seq: Optional[int] = None
    resumed: int = 0


@dataclasses.dataclass(eq=False)  # identity-hashed: handles live in sets
class Handle:
    gen_id: str
    queue: "asyncio.Queue[TokenEvent]"
    # ClientBackend's cancel signal (EngineBackend cancels via the engine).
    stop: Optional[threading.Event] = None
    # The admission scheduler's stamp for this request (sched.Ticket);
    # the gateway hands it back to the scheduler at first token / finish
    # for lane-depth and estimator accounting. None = scheduler off.
    ticket: Optional[object] = None
    # Distributed-trace context minted at the gateway
    # (utils.tracing.TraceContext); None = unsampled — every tracing hook
    # along the request path short-circuits on that None.
    trace: Optional[object] = None
    # Epoch time the session entered decode (engine submit / prefilled
    # admit). The fan-out closes the ``gateway.decode_wait`` span from it
    # at the stream's first event, then clears it.
    t_decode0: Optional[float] = None


class Backend:
    """Interface contract (duck-typed; this base just documents it)."""

    metrics: Metrics
    # Distributed-trace recorder + TraceConfig (attach_tracer). Class-level
    # None keeps every per-request tracing hook one attribute test when the
    # gateway runs without tracing.
    tracer = None
    tcfg = None

    def start(self, loop: asyncio.AbstractEventLoop) -> None:
        raise NotImplementedError

    def submit(
        self,
        prompt: Sequence[int],
        options: SamplingOptions,
        deadline: Optional[float],
        ticket=None,
        trace=None,
    ) -> Handle:
        raise NotImplementedError

    def attach_scheduler(self, sched) -> None:
        """Install the gateway's admission scheduler. Backends with a
        local engine wire its admission-order hook; the rest carry
        tickets for accounting only (their admission queue lives
        downstream, already gated by the scheduler at the gateway)."""

    def attach_tracer(self, recorder, cfg) -> None:
        """Install the gateway's span recorder + TraceConfig. Backends
        record their gateway-side child spans into it; remote spans are
        gathered per trace by :meth:`collect_trace`."""
        self.tracer = recorder
        self.tcfg = cfg

    def flight_snapshot(self, last: Optional[int] = None) -> List[dict]:
        """Per-tick engine flight-recorder records for ``/debug/ticks``.
        Backends without a local engine have none."""
        return []

    def _trace_targets(self) -> List[dict]:
        """Directory rows of the remote nodes that may hold spans for this
        gateway's requests — the ``trace.pull`` fan-out set."""
        return []

    def collect_trace(self, trace_id: str) -> Dict[str, List[dict]]:
        """Gather one distributed trace: local (gateway) spans plus a
        ``trace.pull`` round to every remote node this backend routes to.
        Best-effort by design — a node that died or times out just leaves
        its lane out of the stitched trace (``trace_pull_failures``
        counts it); collection must never wedge behind a dead node."""
        out: Dict[str, List[dict]] = {}
        if self.tracer is not None:
            local = self.tracer.spans_for(trace_id)
            if local:
                out["gateway"] = [s.to_dict() for s in local]
        rows = self._trace_targets()
        if rows:
            self._pull_remote_spans(trace_id, rows, out)
        return out

    def _pull_remote_spans(
        self, trace_id: str, rows: List[dict], out: Dict[str, List[dict]]
    ) -> None:
        from ..distributed.messages import pack_frame, unpack_frame
        from ..distributed.relay import RelayClient

        port = getattr(self, "relay_port", None)
        if port is None:
            return
        timeout = (
            self.tcfg.collect_timeout_s if self.tcfg is not None else 2.0
        )
        reply = f"trace.spans.{uuid.uuid4().hex[:12]}"
        client = RelayClient(getattr(self, "relay_host", "127.0.0.1"), port)
        try:
            sent = 0
            for row in rows:
                try:
                    client.put(row["queue"], pack_frame({
                        "op": "trace.pull", "trace": trace_id,
                        "reply": reply,
                    }))
                    sent += 1
                except Exception:  # noqa: BLE001 - node gone: partial trace
                    self.metrics.counter("trace_pull_failures")
            budget = time.monotonic() + timeout
            got = 0
            while got < sent:
                try:
                    frame = client.get(
                        reply, timeout=max(budget - time.monotonic(), 0.001)
                    )
                except Exception:  # noqa: BLE001 - timeout or relay lost
                    # ONE shared budget for the whole round, not per node:
                    # a dead node costs at most collect_timeout_s total.
                    self.metrics.counter("trace_pull_failures", sent - got)
                    break
                try:
                    header, _ = unpack_frame(frame)
                except Exception:  # noqa: BLE001
                    self.metrics.counter("malformed_frames")
                    continue
                if (header.get("op") != "trace.spans"
                        or header.get("trace") != trace_id):
                    self.metrics.counter("unknown_ops_dropped")
                    continue
                got += 1
                node = str(header.get("node") or f"node-{got}")
                out.setdefault(node, []).extend(header.get("spans") or [])
        finally:
            client.close()

    def cancel(self, handle: Handle) -> None:
        raise NotImplementedError

    def active_sessions(self) -> int:
        raise NotImplementedError

    def queue_depth(self) -> int:
        raise NotImplementedError

    def probe(self) -> bool:
        """Cheap health check for the gateway's circuit-breaker probe
        loop (runs on an executor thread — may block briefly)."""
        return True

    def stop(self, timeout: float = 10.0) -> None:
        raise NotImplementedError


class EngineBackend(Backend):
    """Local-engine backend: one driver thread steps the scheduler."""

    def __init__(self, engine, idle_sleep_s: float = 0.002):
        self.engine = engine
        self.metrics = engine.metrics  # one /metrics covers engine + gateway
        self._idle_sleep_s = idle_sleep_s
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._handles: Dict[str, Handle] = {}
        # Held across engine.submit + handle registration (and by the
        # fan-out when resolving handles): the driver may produce this
        # generation's first event the instant the session is visible, and
        # must not find the handle missing.
        self._hlock = threading.Lock()
        self._stop_evt = threading.Event()
        self._unpaused = threading.Event()
        self._unpaused.set()
        self._thread: Optional[threading.Thread] = None

    def start(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop
        self._thread = threading.Thread(
            target=self._drive, name="engine-driver", daemon=True
        )
        self._thread.start()

    # Test/drain hook: a paused driver stops ticking the engine (submitted
    # sessions stay queued), which makes queue-full and deadline scenarios
    # deterministic.
    def pause(self) -> None:
        self._unpaused.clear()

    def resume(self) -> None:
        self._unpaused.set()

    def _drive(self) -> None:
        while not self._stop_evt.is_set():
            if not self._unpaused.is_set() or not self.engine.has_work():
                time.sleep(self._idle_sleep_s)
                continue
            events = self.engine.step()
            if events:
                self._fanout(events)
            self.engine.collect_finished()

    def _fanout(self, events: List) -> None:
        with self._hlock:
            for gid, token, finished in events:
                if finished:
                    h = self._handles.pop(gid, None)
                else:
                    h = self._handles.get(gid)
                if h is None:
                    continue  # caller already gone (disconnect races a tick)
                if h.trace is not None and h.t_decode0 is not None:
                    # First event since the session entered decode: close
                    # the gateway-side decode-wait segment (epoch clock so
                    # it stitches against remote lanes).
                    rec = self.tracer
                    if rec is not None:
                        c = h.trace.child()
                        rec.record(Span(
                            "gateway.decode_wait", h.t_decode0,
                            time.time() - h.t_decode0, {"gen_id": gid},
                            trace_id=c.trace_id, span_id=c.span_id,
                            parent_id=c.parent_id, node="gateway",
                        ))
                    h.t_decode0 = None
                reason = None
                if finished:
                    s = self.engine.sessions.get(gid)
                    reason = s.finish_reason if s is not None else "cancelled"
                    if s is not None and s.ttft is not None:
                        # Engine-side TTFT (submit → first token recorded by
                        # the scheduler): isolates admission stall — the
                        # quantity overlapped admission shrinks — from the
                        # gateway's wall-clock ``ttft`` (which adds HTTP
                        # queueing/fan-out time). Both ride /metrics.
                        # Disaggregated sessions split the measurement: the
                        # decode-side engine only sees admit → first token
                        # (DisaggBackend observes the prefill side as
                        # ``engine_ttft_prefill``), so folding it into
                        # ``engine_ttft`` would skew the colocated summary.
                        name = ("engine_ttft_decode"
                                if getattr(s, "disagg", False)
                                else "engine_ttft")
                        self.metrics.observe(name, s.ttft)
                ev = TokenEvent(token, finished, reason)
                try:
                    self._loop.call_soon_threadsafe(h.queue.put_nowait, ev)
                except RuntimeError:
                    pass  # loop already closed (server exited mid-tick)

    def submit(self, prompt, options, deadline, ticket=None,
               trace=None) -> Handle:
        with self._hlock:
            gid = self.engine.submit(
                prompt, options, deadline=deadline,
                sched_key=ticket.sort_key if ticket is not None else None,
                trace=trace,
            )
            h = Handle(gen_id=gid, queue=asyncio.Queue(), ticket=ticket,
                       trace=trace,
                       t_decode0=time.time() if trace is not None else None)
            self._handles[gid] = h
        return h

    def attach_tracer(self, recorder, cfg) -> None:
        # The engine records a traced session's ``engine.queue`` and
        # ``engine.first_token`` spans into the gateway's own recorder.
        super().attach_tracer(recorder, cfg)
        self.engine.tracer = recorder

    def flight_snapshot(self, last: Optional[int] = None) -> List[dict]:
        fr = getattr(self.engine, "flight", None)
        return fr.snapshot(last) if fr is not None else []

    def attach_scheduler(self, sched) -> None:
        # The engine's admission hook consumes the scheduler's ordering
        # each tick instead of FIFO-popping the waiting queue.
        self.engine.set_admission_order(sched.order_sessions)

    def cancel(self, handle: Handle) -> None:
        # The scheduler reaps at the next tick and emits the terminal
        # event; _fanout pops the handle then.
        self.engine.cancel(handle.gen_id)

    def active_sessions(self) -> int:
        return self.engine.active_sessions()

    def queue_depth(self) -> int:
        return self.engine.queue_depth()

    def probe(self) -> bool:
        # The engine is local: healthy means the driver thread is alive
        # (a dead driver strands every queued session).
        return (
            self._thread is not None
            and self._thread.is_alive()
            and not self._stop_evt.is_set()
        )

    def stop(self, timeout: float = 10.0) -> None:
        self._stop_evt.set()
        self._unpaused.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)


class _TransferAborted(Exception):
    """KV shipment interrupted by cancel/stop — terminal, no fallback."""


class DisaggBackend(EngineBackend):
    """Disaggregated prefill/decode gateway backend.

    The wrapped engine is this gateway's DECODE-pool member; it never runs
    prompt prefill on the happy path. ``submit`` instead ships the prompt
    to a ``role="prefill"`` node discovered through the block directory,
    collects the prefilled KV planes back over the relay as
    :mod:`..disagg.kv_codec` frames, and imports them with
    ``engine.admit_prefilled`` — the session enters decode directly, with
    the first token already sampled on the prefill side.

    Every failure along that path — no prefill node registered, transfer
    timeout, dropped/duplicated/corrupt frames, hash-chain mismatch,
    decode-pool capacity — degrades to plain local prefill
    (``engine.submit``) when :class:`~..config.DisaggConfig` has
    ``fallback_local`` set (counted as ``disagg_fallback_local``), and to
    a terminal error event otherwise. A chaos fault on the KV path must
    slow a request down, never wedge it.
    """

    def __init__(
        self,
        engine,
        relay_port: int,
        relay_host: str = "127.0.0.1",
        disagg_cfg: Optional[DisaggConfig] = None,
        idle_sleep_s: float = 0.002,
        prefix_cfg: Optional[PrefixConfig] = None,
        sched_cfg: Optional[SchedConfig] = None,
    ):
        super().__init__(engine, idle_sleep_s=idle_sleep_s)
        self.relay_host, self.relay_port = relay_host, relay_port
        self.dcfg = disagg_cfg or DisaggConfig()
        self.pcfg = prefix_cfg or PrefixConfig()
        # None = scheduler off: prefix routing keeps its legacy
        # load-blind semantics (a floor-clearing match wins outright).
        self.kcfg = sched_cfg
        self._tlock = threading.Lock()
        self._transfers: Dict[str, threading.Thread] = {}

    def submit(self, prompt, options, deadline, ticket=None,
               trace=None) -> Handle:
        # The engine gen_id doesn't exist until the KV lands; hand the
        # server a provisional handle and rebind it at admission. ``stop``
        # doubles as the cancel signal for the transfer window, when the
        # engine doesn't know the session yet.
        key = f"disagg-{uuid.uuid4().hex[:12]}"
        h = Handle(gen_id=key, queue=asyncio.Queue(), stop=threading.Event(),
                   ticket=ticket, trace=trace)
        t = threading.Thread(
            target=self._run_disagg,
            args=(h, key, list(prompt), options, deadline),
            name=key, daemon=True,
        )
        with self._tlock:
            self._transfers[key] = t
        t.start()
        return h

    def cancel(self, handle: Handle) -> None:
        if handle.stop is not None:
            handle.stop.set()
        # No-op while gen_id is still provisional; the transfer thread
        # re-checks stop after registration, so the cancel can't slip
        # between the two.
        self.engine.cancel(handle.gen_id)

    def queue_depth(self) -> int:
        with self._tlock:
            inflight = len(self._transfers)
        # In-flight KV shipments are queued work the engine can't see yet —
        # admission control must count them or a burst overshoots.
        return self.engine.queue_depth() + inflight

    # -- admission path ----------------------------------------------------

    def _prefer_local(self, prompt) -> bool:
        """Does the local decode engine hold enough cached prefix of
        ``prompt`` that skipping the remote prefill hop wins? Two gates:
        the match must clear the page/`min_shared_tokens` floor, and —
        only when the scheduler is on — the shared placement rule
        (sched/placement.py) must price the reuse above the local
        engine's current contention, so a hot decode engine stops
        pulling prefills onto itself no matter how long the match. With
        the scheduler off the floor alone decides (legacy behavior).
        Probe failures just mean no preference — routing must never add
        a failure mode."""
        if not self.pcfg.route_by_prefix:
            return False
        try:
            got = self.engine.prefix_match_tokens(prompt)
        except Exception:  # noqa: BLE001 - probe only, degrade to no-pref
            return False
        ps = getattr(self.engine.ccfg, "page_size", 1)
        if got < max(self.pcfg.min_shared_tokens, ps):
            return False
        kcfg = getattr(self, "kcfg", None)
        if kcfg is None:
            return True
        local_load = self.engine.active_sessions() + self.engine.queue_depth()
        return prefix_worth_detour(got, local_load, 0.0, kcfg)

    def _pick_prefill_node(self) -> Optional[dict]:
        from ..distributed.directory import DirectoryClient

        with DirectoryClient(self.relay_port, self.relay_host) as d:
            nodes = [
                n for n in d.alive()
                if n.get("role") == "prefill" and not n.get("pending")
            ]
        if not nodes:
            return None
        return min(nodes, key=lambda n: n.get("load", 0))

    def _trace_targets(self) -> List[dict]:
        from ..distributed.directory import DirectoryClient

        try:
            with DirectoryClient(self.relay_port, self.relay_host) as d:
                return [
                    n for n in d.alive() if n.get("role") == "prefill"
                ]
        except Exception:  # noqa: BLE001 - directory blip: partial trace
            return []

    def _fetch_kv(self, node, prompt, options, deadline, stop, trace=None):
        """Ship ``prompt`` to ``node``; return the decoded ``(planes,
        meta)``. Raises on any transport or integrity failure (the caller
        falls back), :class:`_TransferAborted` on cancel/stop."""
        from ..cache.paged import PageAllocator
        from ..disagg.kv_codec import _unpack, decode_kv
        from ..distributed.messages import pack_frame
        from ..distributed.relay import RelayClient

        reply = f"disagg.kv.{uuid.uuid4().hex[:12]}"
        budget = time.monotonic() + self.dcfg.transfer_timeout_s
        if deadline is not None:
            budget = min(budget, deadline)
        frames: List[bytes] = []
        total: Optional[int] = None
        nbytes = 0
        t0 = time.monotonic()
        # A fresh RelayClient per transfer: the client is not thread-safe,
        # and concurrent requests must not serialize on one socket.
        client = RelayClient(self.relay_host, self.relay_port)
        try:
            client.put(node["queue"], pack_frame({
                "op": "prefill", "gen": reply, "reply": reply,
                "prompt": prompt,
                "options": dataclasses.asdict(options),
                "max_frame_bytes": self.dcfg.kv_frame_bytes,
                # Distributed-trace propagation: the worker parents its
                # prefill.export span under this kv_transfer segment.
                "trace": trace.trace_id if trace is not None else None,
                "span": trace.span_id if trace is not None else None,
            }))
            while total is None or len(frames) < total:
                now = time.monotonic()
                if now >= budget:
                    raise TimeoutError(
                        f"kv transfer timed out ({len(frames)} of "
                        f"{total if total is not None else '?'} frames)"
                    )
                if stop.is_set() or self._stop_evt.is_set():
                    raise _TransferAborted()
                try:
                    frame = client.get(reply, timeout=min(0.5, budget - now))
                except TimeoutError:
                    continue
                header, _ = _unpack(frame)
                if "error" in header:
                    raise RuntimeError(
                        f"prefill node error: {header['error']}"
                    )
                total = int(header["n"])
                frames.append(frame)
                nbytes += len(frame)
        finally:
            client.close()
        planes, meta = decode_kv(frames)
        if planes is None:  # pragma: no cover - error frames raise above
            raise RuntimeError(f"prefill node error: {meta.get('error')}")
        if meta["chain"] and meta.get("ps"):
            # The prompt hash chain rides the transfer end-to-end: a
            # mismatch means the planes answer a DIFFERENT prompt (stale
            # reply-queue reuse, worker bug) — reject before import.
            expect = PageAllocator.chain_keys(prompt, meta["ps"])
            if list(meta["chain"]) != list(expect):
                raise ValueError("kv transfer prompt hash-chain mismatch")
        self.metrics.observe("kv_transfer_bytes", float(nbytes))
        self.metrics.observe(
            "kv_transfer_ms", (time.monotonic() - t0) * 1e3
        )
        return planes, meta

    def _run_disagg(self, h, key, prompt, options, deadline) -> None:
        t0 = time.monotonic()
        gid: Optional[str] = None
        fail: Optional[str] = None
        tctx, rec = h.trace, self.tracer
        try:
            try:
                if self._prefer_local(prompt):
                    # Prefix-aware short-circuit: the LOCAL decode engine
                    # already holds a useful cached prefix of this prompt —
                    # shipping the whole prompt to the prefill pool would
                    # recompute (and re-transfer) KV that one admission
                    # tick can reuse in place.
                    self.metrics.counter("routed_by_prefix")
                    with self._hlock:
                        gid = self.engine.submit(
                            prompt, options, deadline=deadline,
                            sched_key=(
                                h.ticket.sort_key
                                if h.ticket is not None else None
                            ),
                            trace=tctx,
                        )
                        h.gen_id = gid
                        if tctx is not None:
                            h.t_decode0 = time.time()
                        self._handles[gid] = h
                    if h.stop.is_set():
                        self.engine.cancel(gid)
                    return
                with trace_span(rec, "gateway.route", tctx, node="gateway"):
                    node = self._pick_prefill_node()
                    # Optional grace for an empty pool (rolling restart of
                    # the prefill tier): poll until a node appears or the
                    # grace lapses, then fall back rather than queue
                    # indefinitely.
                    wait_until = t0 + self.dcfg.prefill_wait_s
                    while (node is None and time.monotonic() < wait_until
                           and not h.stop.is_set()
                           and not self._stop_evt.is_set()):
                        time.sleep(0.1)
                        node = self._pick_prefill_node()
                    if node is None:
                        raise LookupError("no prefill node registered")
                with trace_span(rec, "gateway.kv_transfer", tctx,
                                node="gateway") as kctx:
                    planes, meta = self._fetch_kv(
                        node, prompt, options, deadline, h.stop, trace=kctx
                    )
                with trace_span(rec, "gateway.admit", tctx, node="gateway"):
                    with self._hlock:
                        gid = self.engine.admit_prefilled(
                            prompt, planes, meta["first_token"],
                            options=options, deadline=deadline, trace=tctx,
                        )
                        if gid is not None:
                            h.gen_id = gid
                            if tctx is not None:
                                h.t_decode0 = time.time()
                            self._handles[gid] = h
                if gid is None:
                    raise RuntimeError("decode pool at capacity")
                # Prefill-side TTFT: request arrival → KV imported with the
                # first token in hand (pairs with ``engine_ttft_decode``).
                self.metrics.observe(
                    "engine_ttft_prefill", time.monotonic() - t0
                )
            except _TransferAborted:
                fail = "cancelled"
            except Exception as e:  # noqa: BLE001 - degrade, never wedge
                if not self.dcfg.fallback_local:
                    fail = f"error: {type(e).__name__}"
                else:
                    logger.warning(
                        "disagg admission failed (%r); prefilling locally", e
                    )
                    self.metrics.counter("disagg_fallback_local")
                    try:
                        with self._hlock:
                            gid = self.engine.submit(
                                prompt, options, deadline=deadline,
                                sched_key=(
                                    h.ticket.sort_key
                                    if h.ticket is not None else None
                                ),
                                trace=tctx,
                            )
                            h.gen_id = gid
                            if tctx is not None:
                                h.t_decode0 = time.time()
                            self._handles[gid] = h
                    except Exception as e2:  # noqa: BLE001
                        fail = f"error: {type(e2).__name__}"
            if gid is not None and h.stop.is_set():
                self.engine.cancel(gid)  # cancel raced the registration
        finally:
            with self._tlock:
                self._transfers.pop(key, None)
            if fail is not None and self._loop is not None:
                # The stream never reached the engine: it still owes its
                # consumer a terminal event or the gateway handler hangs.
                try:
                    self._loop.call_soon_threadsafe(
                        h.queue.put_nowait, TokenEvent(-1, True, fail)
                    )
                except RuntimeError:
                    pass  # loop already closed

    def stop(self, timeout: float = 10.0) -> None:
        end = time.monotonic() + timeout
        self._stop_evt.set()  # aborts in-flight transfers at the next poll
        with self._tlock:
            transfers = list(self._transfers.values())
        for t in transfers:
            t.join(timeout=max(0.0, end - time.monotonic()))
        super().stop(timeout=max(0.0, end - time.monotonic()))


class ClientBackend(Backend):
    """Relay-tier backend: one worker thread per in-flight generation
    (the relay hop IS the batching point — workers co-batch sessions on
    their task pools, so per-request client threads don't serialize).

    With ``batch_max > 1`` admitted requests instead feed the client's
    BATCHED decode loop: a collector groups up to ``batch_max`` requests
    within ``batch_window_s`` (greedy drain, single deadline from the first
    request — the TaskPool discipline) and drives each group through one
    ``generate_many`` call, so the group's hidden states travel the chain
    as ONE stacked frame per hop instead of meeting by pool-window luck."""

    def __init__(self, client, request_timeout_s: float = 60.0,
                 batch_max: int = 0, batch_window_s: float = 0.01):
        self.client = client
        # Share the client's Metrics when it has one: its failover /
        # stale-reply counters then ride the gateway's /metrics for free.
        self.metrics = getattr(client, "metrics", None) or Metrics()
        self._request_timeout_s = request_timeout_s
        self._batch_max = int(batch_max)
        self._batch_window_s = batch_window_s
        self._pending: Optional[queue.Queue] = (
            queue.Queue() if self._batch_max > 1 else None
        )
        self._active: set = set()  # gen_ids admitted to the batched loop
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._threads: Dict[str, threading.Thread] = {}
        self._tlock = threading.Lock()
        self._stop_evt = threading.Event()
        self._collector: Optional[threading.Thread] = None
        self._ids = 0

    def start(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop
        if self._pending is not None:
            self._collector = threading.Thread(
                target=self._collect, name="client-batcher", daemon=True
            )
            self._collector.start()

    def submit(self, prompt, options, deadline, ticket=None,
               trace=None) -> Handle:
        if self._stop_evt.is_set():
            # The server drains before backend.stop(), so this only fires
            # on a race — but a request enqueued after stop would never get
            # a terminal event.
            raise RuntimeError("backend is stopping")
        with self._tlock:
            self._ids += 1
            gid = f"req-{self._ids}"
        # Carried for the X-Trace-Id echo only: the relay tier predates the
        # trace header protocol, so no remote spans exist to stitch.
        h = Handle(gen_id=gid, queue=asyncio.Queue(), stop=threading.Event(),
                   ticket=ticket, trace=trace)
        if self._pending is not None:
            # Not added to _active yet: a queued request is counted by
            # queue_depth() alone until the collector claims it (admission
            # control must not double-count it).
            self._pending.put((h, list(prompt), options, deadline))
            return h
        t = threading.Thread(
            target=self._run, args=(h, list(prompt), options, deadline),
            name=f"client-{gid}", daemon=True,
        )
        with self._tlock:
            self._threads[gid] = t
        t.start()
        return h

    def _claim(self, item):
        """Move a popped request from the queued count into the active
        count the moment it leaves ``_pending`` — each request is counted
        by exactly one of ``queue_depth()`` / ``active_sessions()``."""
        with self._tlock:
            self._active.add(item[0].gen_id)
        return item

    def _collect(self) -> None:
        """Group admitted requests for generate_many. Greedy drain + one
        window deadline from the first request; each group runs on its own
        thread so collection never blocks behind a long generation."""
        while not self._stop_evt.is_set():
            try:
                first = self._claim(self._pending.get(timeout=0.1))
            except queue.Empty:
                continue
            group = [first]
            deadline = time.monotonic() + self._batch_window_s
            while len(group) < self._batch_max:
                try:
                    group.append(self._claim(self._pending.get_nowait()))
                except queue.Empty:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    try:
                        group.append(self._claim(
                            self._pending.get(timeout=remaining)
                        ))
                    except queue.Empty:
                        break
            key = f"batch-{group[0][0].gen_id}"
            t = threading.Thread(target=self._run_group, args=(group, key),
                                 name=f"client-{key}", daemon=True)
            with self._tlock:
                self._threads[key] = t
            t.start()

    def _run_group(self, group, key: str) -> None:
        handles = [g[0] for g in group]
        opts = [g[2] for g in group]
        deadlines = [g[3] for g in group]
        n = len(group)
        expired = [False] * n
        reasons: Dict[int, str] = {}

        def emit(h: Handle, ev: TokenEvent) -> None:
            try:
                self._loop.call_soon_threadsafe(h.queue.put_nowait, ev)
            except RuntimeError:
                pass  # loop already closed (server exited mid-generation)

        def stop_check(i: int) -> bool:
            if handles[i].stop.is_set():
                return True
            d = deadlines[i]
            if d is not None and time.monotonic() >= d:
                expired[i] = True
                return True
            return False

        self.metrics.observe("client_batch_group", n)
        try:
            self.client.generate_many(
                [g[1] for g in group],
                max_new_tokens=[o.max_new_tokens for o in opts],
                timeout=self._request_timeout_s,
                options=opts,
                on_token=lambda i, t: emit(handles[i], TokenEvent(t, False)),
                stop_check=stop_check,
                on_finish=lambda i, r: reasons.__setitem__(i, r),
            )
        except Exception as e:  # noqa: BLE001 - every stream must terminate
            self.metrics.counter("client_generate_errors")
            for i in range(n):
                reasons.setdefault(i, f"error: {type(e).__name__}")
        finally:
            for i, h in enumerate(handles):
                reason = reasons.get(i, "length")
                if expired[i]:
                    reason = "deadline"
                    self.metrics.counter("sessions_deadline_expired")
                elif h.stop.is_set():
                    reason = "cancelled"
                elif reason == "stopped":
                    reason = "cancelled"
                self.metrics.counter("sessions_finished")
                emit(h, TokenEvent(-1, True, reason))
            with self._tlock:
                for h in handles:
                    self._active.discard(h.gen_id)
                self._threads.pop(key, None)

    def _run(self, h: Handle, prompt, options, deadline) -> None:
        def emit(ev: TokenEvent) -> None:
            try:
                self._loop.call_soon_threadsafe(h.queue.put_nowait, ev)
            except RuntimeError:
                pass  # loop already closed (server exited mid-generation)

        expired = [False]

        def stop_check() -> bool:
            if h.stop.is_set():
                return True
            if deadline is not None and time.monotonic() >= deadline:
                expired[0] = True
                return True
            return False

        eos = options.eos_token_id if options.eos_token_id >= 0 else None
        out: List[int] = []
        reason = "length"
        try:
            out = self.client.generate(
                prompt,
                max_new_tokens=options.max_new_tokens,
                eos_token_id=eos,
                timeout=self._request_timeout_s,
                options=options,
                on_token=lambda t: emit(TokenEvent(t, False)),
                stop_check=stop_check,
            )
            if expired[0]:
                reason = "deadline"
                self.metrics.counter("sessions_deadline_expired")
            elif h.stop.is_set():
                reason = "cancelled"
            elif eos is not None and out and out[-1] == eos:
                reason = "eos"
        except Exception as e:  # noqa: BLE001 - the stream must terminate
            self.metrics.counter("client_generate_errors")
            reason = f"error: {type(e).__name__}"
        finally:
            self.metrics.counter("sessions_finished")
            emit(TokenEvent(-1, True, reason))
            with self._tlock:
                self._threads.pop(h.gen_id, None)

    def cancel(self, handle: Handle) -> None:
        if handle.stop is not None:
            handle.stop.set()

    def active_sessions(self) -> int:
        with self._tlock:
            if self._pending is not None:
                return len(self._active)
            return len(self._threads)

    def queue_depth(self) -> int:
        if self._pending is not None:
            return self._pending.qsize()  # awaiting group formation
        return 0  # admission happens downstream, on the workers

    def probe(self) -> bool:
        # Healthy means a route covering every layer exists RIGHT NOW —
        # this is what a submitted request would need. Raises → False:
        # relay down, directory down, or a coverage gap all open the
        # breaker; a replacement node registering heals it.
        try:
            self.client.plan_route()
            return True
        except Exception:  # noqa: BLE001 - any failure mode means unhealthy
            return False

    def stop(self, timeout: float = 10.0) -> None:
        self._stop_evt.set()
        deadline = time.monotonic() + timeout
        if self._collector is not None:
            # Join the collector FIRST so the drain below has no concurrent
            # consumer racing it for queued requests.
            self._collector.join(
                timeout=max(0.0, deadline - time.monotonic())
            )
        if self._pending is not None:
            # Requests admitted but never grouped still owe their streams a
            # terminal event — without one the gateway handler blocks for
            # the full request timeout.
            while True:
                try:
                    h = self._pending.get_nowait()[0]
                except queue.Empty:
                    break
                self.metrics.counter("sessions_finished")
                if self._loop is not None:
                    try:
                        self._loop.call_soon_threadsafe(
                            h.queue.put_nowait,
                            TokenEvent(-1, True, "cancelled"),
                        )
                    except RuntimeError:
                        pass  # loop already closed
        with self._tlock:
            threads = list(self._threads.values())
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))


class FleetBackend(Backend):
    """Crash-recoverable decode-fleet backend.

    Each request runs on its own thread: pick the least-loaded live
    ``role="decode"`` node from the block directory, send a
    ``migrate.submit`` op, and forward the node's sequence-stamped
    ``migrate.tok`` frames to the request's stream. The node also ships
    periodic session checkpoints (``migrate.ckpt`` kv_codec frames);
    the gateway keeps the latest COMPLETE one raw — validation is the
    resume target's job.

    Death detection: a silent stream for ``dead_after_s`` (default: the
    lease TTL) combined with the node missing from the directory's
    ``alive()`` view (or re-registered under a different epoch) declares
    the node dead. Recovery then: fence the incarnation in the directory
    (so a zombie can never re-register with its stale epoch), pick a
    healthy node, and either replay the checkpoint (``migrate.resume``
    with the delivered-token cursor — the node re-emits any undelivered
    checkpoint tail and regenerates the rest deterministically) or, with
    no checkpoint yet, resubmit the prompt cold. Every frame carries the
    attempt tag ``att``; frames from a fenced attempt are dropped
    (``stale_frames_fenced``), and replayed tokens whose sequence index
    precedes the delivered cursor are suppressed (``tokens_deduped``) —
    together: exactly-once delivery, zero token loss.

    Bounded: at most ``resume_max_attempts`` re-homes per request, and a
    resume is shed (``resume_shed``) when the request's remaining
    deadline is under ``shed_headroom_s`` x the number of concurrent
    recoveries — a recovery storm must not burn decode on streams that
    cannot finish in time.

    The same machinery serves the elastic fleet (fleet/): a node being
    drained or rebalanced ships a fresh checkpoint followed by a
    ``fleet.handoff`` marker, and the gateway re-homes the stream through
    this recovery path — proactive migration and crash recovery are one
    code path, exactly-once either way. Placement is shared with the
    controller via ``fleet.policy`` (draining nodes take no new work),
    and with ``fleet_cfg`` set the bytes-vs-latency cost model arbitrates
    overloaded-prefix-holder placements between query-move, page-ship,
    and plain migration.
    """

    def __init__(
        self,
        relay_port: int,
        relay_host: str = "127.0.0.1",
        disagg_cfg: Optional[DisaggConfig] = None,
        metrics: Optional[Metrics] = None,
        pool_wait_s: float = 2.0,
        prefix_cfg: Optional[PrefixConfig] = None,
        sched_cfg: Optional[SchedConfig] = None,
        fleet_cfg: Optional[FleetConfig] = None,
    ):
        self.relay_host, self.relay_port = relay_host, relay_port
        self.dcfg = disagg_cfg or DisaggConfig()
        self.pcfg = prefix_cfg or PrefixConfig()
        # None = scheduler off: prefix routing keeps its legacy
        # load-blind semantics (the advertised holder wins outright).
        self.kcfg = sched_cfg
        self.metrics = metrics or Metrics()
        # None = cost-model placement off: prefix routing ignores holder
        # load (or defers to the scheduler rule) exactly as before.
        self.cost = (CostModel(fleet_cfg, self.metrics)
                     if fleet_cfg is not None else None)
        self._dead_after = self.dcfg.dead_after_s or self.dcfg.lease_ttl_s
        self._pool_wait_s = pool_wait_s
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._tlock = threading.Lock()
        self._threads: Dict[str, threading.Thread] = {}
        # Concurrent-recovery census for the shed heuristic: each extra
        # stream mid-recovery inflates the headroom a resume must clear.
        self._rec_lock = threading.Lock()
        self._recovering = 0
        self._stop_evt = threading.Event()

    def start(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop

    def submit(self, prompt, options, deadline, ticket=None,
               trace=None) -> Handle:
        if self._stop_evt.is_set():
            raise RuntimeError("backend is stopping")
        key = f"fleet-{uuid.uuid4().hex[:12]}"
        h = Handle(gen_id=key, queue=asyncio.Queue(), stop=threading.Event(),
                   ticket=ticket, trace=trace)
        t = threading.Thread(
            target=self._run_fleet,
            args=(h, key, list(prompt), options, deadline),
            name=key, daemon=True,
        )
        with self._tlock:
            self._threads[key] = t
        t.start()
        return h

    def cancel(self, handle: Handle) -> None:
        if handle.stop is not None:
            handle.stop.set()

    def active_sessions(self) -> int:
        with self._tlock:
            return len(self._threads)

    def queue_depth(self) -> int:
        return 0  # admission happens downstream, on the decode nodes

    def probe(self) -> bool:
        from ..distributed.directory import DirectoryClient

        try:
            with DirectoryClient(self.relay_port, self.relay_host) as d:
                return any(
                    n.get("role") == "decode" and not n.get("pending")
                    for n in d.alive()
                )
        except Exception:  # noqa: BLE001 - any failure means unhealthy
            return False

    def stop(self, timeout: float = 10.0) -> None:
        self._stop_evt.set()
        end = time.monotonic() + timeout
        with self._tlock:
            threads = list(self._threads.values())
        for t in threads:
            t.join(timeout=max(0.0, end - time.monotonic()))

    def _trace_targets(self) -> List[dict]:
        from ..distributed.directory import DirectoryClient

        try:
            with DirectoryClient(self.relay_port, self.relay_host) as d:
                return [
                    n for n in d.alive() if n.get("role") == "decode"
                ]
        except Exception:  # noqa: BLE001 - directory blip: partial trace
            return []

    # -- per-request stream loop -------------------------------------------

    def _pick_prefix(self, directory, prompt, dead_ids) -> Optional[dict]:
        """The live decode node holding the longest advertised prefix of
        ``prompt``. With the scheduler on, the shared placement rule
        (sched/placement.py) must also price its match above its load
        disadvantage — a loaded holder loses to an idle node once the
        queueing it would add outweighs the prefill the match saves, so
        routing stops contradicting the scheduler it feeds; scheduler
        off keeps the legacy load-blind pick. ``None`` = no useful match
        (the caller falls back to least-loaded). A directory blip or a
        matched-but-gone node also yields ``None``: prefix routing is an
        optimization and must never add a failure mode to placement."""
        if not self.pcfg.route_by_prefix:
            return None
        try:
            nid, tokens = directory.match_prefix(prompt)
            if (nid is None or nid in dead_ids
                    or tokens < max(self.pcfg.min_shared_tokens, 1)):
                return None
            nodes = live_decode_rows(directory.alive(), dead_ids)
            if self.kcfg is None:
                best = next(
                    (n for n in nodes if n.get("node_id") == nid), None)
            else:
                best = choose_decode_node(nodes, nid, tokens, self.kcfg)
                if best is not None and best.get("node_id") != nid:
                    best = None
            if best is not None:
                self.metrics.counter("routed_by_prefix")
                return best
        except Exception:  # noqa: BLE001 - probe only, fall back
            pass
        return None

    def _emit(self, h: Handle, ev: TokenEvent) -> None:
        try:
            self._loop.call_soon_threadsafe(h.queue.put_nowait, ev)
        except RuntimeError:
            pass  # loop already closed (server exited mid-stream)

    def _place_cost(self, directory, client, prompt, dead_ids):
        """Bytes-vs-latency placement (fleet/costmodel.py): when the
        prefix holder is busier than the best alternative, arbitrate per
        event between decoding on the holder anyway (query-move), copying
        the prefix pages to the idle node first (page-ship), and plain
        migration (re-prefill there). ``None`` = no useful prefix match —
        the caller falls back to the legacy picks. Probe-only: any
        failure yields ``None``, never a failed request."""
        if not self.pcfg.route_by_prefix:
            return None
        try:
            nid, tokens = directory.match_prefix(prompt)
            if (nid is None or nid in dead_ids
                    or tokens < max(self.pcfg.min_shared_tokens, 1)):
                return None
            rows = live_decode_rows(directory.alive(), dead_ids)
            holder = next(
                (n for n in rows if n.get("node_id") == nid), None)
            if holder is None:
                return None
            alt = least_loaded(
                [n for n in rows if n.get("node_id") != nid])
            if alt is None or (int(holder.get("load", 0))
                               <= int(alt.get("load", 0))):
                # The holder is also the cheapest seat: plain prefix
                # routing, no decision event to arbitrate.
                self.metrics.counter("routed_by_prefix")
                return holder
            choice = self.cost.decide(
                tokens, holder.get("load", 0), alt.get("load", 0))
            if choice == "query_move":
                self.metrics.counter("routed_by_prefix")
                return holder
            if choice == "page_ship":
                # Success or failure, decode lands on the idle target;
                # a failed ship just means it re-prefills the prefix.
                self._ship_pages(client, holder, alt, prompt)
            return alt
        except Exception:  # noqa: BLE001 - placement probe only
            return None

    def _ship_pages(self, client, holder, target, prompt) -> bool:
        """Copy ``holder``'s cached prefix pages for ``prompt`` to
        ``target`` over the relay (fleet.pages → fleet.pages.put) and
        feed the measured round trip back into the cost model. Returns
        True when the target acked the install."""
        from ..disagg.kv_codec import _unpack
        from ..distributed.messages import pack_frame, unpack_frame

        t0 = time.monotonic()
        budget = t0 + min(self.dcfg.transfer_timeout_s, 10.0)
        pgq = f"fleet.pg.{uuid.uuid4().hex[:12]}"
        try:
            client.put(holder["queue"], pack_frame({
                "op": "fleet.pages", "gen": pgq, "reply": pgq,
                "prompt": prompt,
            }))
            frames: List[bytes] = []
            nbytes = 0
            total: Optional[int] = None
            while total is None or len(frames) < total:
                frame = client.get(
                    pgq, timeout=max(budget - time.monotonic(), 0.001))
                # kv_codec frames carry a multi-plane record payload, not
                # pack_frame's single-array body: header-only parse here.
                header, _ = _unpack(frame)
                if header.get("error"):
                    raise RuntimeError(str(header["error"]))
                total = int(header["n"])
                frames.append(frame)
                nbytes += len(frame)
            # Re-home the frames onto a fresh queue the target pulls from.
            kvq = f"fleet.pg.{uuid.uuid4().hex[:12]}"
            client.put_many((kvq, f) for f in frames)
            ackq = f"fleet.ack.{uuid.uuid4().hex[:12]}"
            client.put(target["queue"], pack_frame({
                "op": "fleet.pages.put", "gen": pgq, "kv": kvq,
                "nf": len(frames), "reply": ackq,
            }))
            while True:
                frame = client.get(
                    ackq, timeout=max(budget - time.monotonic(), 0.001))
                header, _ = unpack_frame(frame)
                if header.get("op") != "fleet.ack":
                    self.metrics.counter("unknown_ops_dropped")
                    continue
                if not header.get("ok"):
                    raise RuntimeError(str(header.get("error")))
                break
            dt = time.monotonic() - t0
            self.metrics.observe("fleet_page_ship_ms", dt * 1e3)
            self.cost.observe_ship(nbytes, dt)
            return True
        except Exception:  # noqa: BLE001 - ship is best-effort
            self.metrics.counter("fleet_page_ship_failed")
            return False

    def _run_fleet(self, h, key, prompt, options, deadline) -> None:
        from ..distributed.directory import DirectoryClient
        from ..distributed.messages import pack_frame, unpack_frame
        from ..distributed.relay import RelayClient

        reply = f"fleet.tok.{uuid.uuid4().hex[:12]}"
        delivered = 0  # exactly-once cursor: next sequence index to accept
        resumed = 0
        attempt = 0
        att = f"{key}#0"  # fences frames from superseded attempts
        ckpt: Optional[List[bytes]] = None  # latest complete checkpoint
        partial: List[bytes] = []
        dead_ids: set = set()
        node: Optional[dict] = None
        t_detect: Optional[float] = None  # death detection time (MTTR)
        in_recovery = False
        fail: Optional[str] = None
        finished = False
        cancel_sent: Optional[float] = None
        tctx, rec = h.trace, self.tracer
        # Fresh relay/directory clients per request: neither is
        # thread-safe, and request threads must not serialize on a socket.
        client = RelayClient(self.relay_host, self.relay_port)
        try:
            directory = DirectoryClient(self.relay_port, self.relay_host)
        except BaseException:
            client.close()
            raise

        def enter_recovery() -> None:
            nonlocal in_recovery
            if not in_recovery:
                in_recovery = True
                with self._rec_lock:
                    self._recovering += 1

        def exit_recovery() -> None:
            nonlocal in_recovery
            if in_recovery:
                in_recovery = False
                with self._rec_lock:
                    self._recovering -= 1

        def remaining_s() -> Optional[float]:
            if deadline is None:
                return None
            return max(deadline - time.monotonic(), 0.0)

        def dispatch(n: dict) -> None:
            """Send this attempt to node ``n``: checkpoint replay when we
            have one, cold prompt resubmission otherwise. Either frame
            carries the trace ids so the node's decode spans parent under
            this request's trace (None keys when unsampled)."""
            child = tctx.child() if tctx is not None else None
            tid = child.trace_id if child is not None else None
            sid = child.span_id if child is not None else None
            if ckpt:
                kvq = f"fleet.kv.{uuid.uuid4().hex[:12]}"
                client.put_many((kvq, f) for f in ckpt)
                client.put(n["queue"], pack_frame({
                    "op": "migrate.resume", "gen": key, "reply": reply,
                    "att": att, "kv": kvq, "nf": len(ckpt),
                    "from": delivered, "deadline_s": remaining_s(),
                    "trace": tid, "span": sid,
                }))
            else:
                client.put(n["queue"], pack_frame({
                    "op": "migrate.submit", "gen": key, "reply": reply,
                    "att": att, "prompt": prompt,
                    "options": dataclasses.asdict(options),
                    "deadline_s": remaining_s(),
                    "trace": tid, "span": sid,
                }))

        def pick(wait_s: float) -> Optional[dict]:
            end = time.monotonic() + wait_s
            while True:
                try:
                    # Shared placement rule (fleet/policy.py): routable =
                    # decode role, registered, not draining, not locally
                    # fenced — the same filter the fleet controller uses.
                    nodes = live_decode_rows(directory.alive(), dead_ids)
                except Exception:  # noqa: BLE001 - directory blip
                    nodes = []
                if nodes:
                    return least_loaded(nodes)
                if (time.monotonic() >= end or self._stop_evt.is_set()
                        or h.stop.is_set()):
                    return None
                time.sleep(0.05)

        def node_alive() -> bool:
            if node is None:
                return False
            try:
                rows = directory.alive()
            except Exception:  # noqa: BLE001
                # Directory unreachable says nothing about the node:
                # don't trigger a (possibly destructive) fence on a
                # control-plane blip.
                return True
            for r in rows:
                if r.get("node_id") == node.get("node_id"):
                    # Same name, different epoch = a NEW incarnation;
                    # the one serving this stream is gone.
                    return r.get("epoch") == node.get("epoch")
            return False

        def recover(fence: bool) -> bool:
            """Re-home the stream. Returns False with ``fail`` set when
            the request is out of road (budget, deadline, empty pool)."""
            nonlocal node, att, attempt, t_detect, partial, fail
            r0 = time.time()
            enter_recovery()
            if t_detect is None:
                t_detect = time.monotonic()
            if fence:
                self.metrics.counter("node_deaths_detected")
                if node is not None:
                    dead_ids.add(node.get("node_id"))
                    try:
                        directory.fence(
                            node.get("node_id"), node.get("epoch")
                        )
                    except Exception:  # noqa: BLE001
                        pass  # lease expiry fences the zombie for us
            attempt += 1
            if attempt > self.dcfg.resume_max_attempts:
                self.metrics.counter("resume_failures")
                fail = "error: resume attempts exhausted"
                return False
            rem = remaining_s()
            if rem is not None:
                with self._rec_lock:
                    storm = self._recovering
                if rem < self.dcfg.shed_headroom_s * max(1, storm):
                    self.metrics.counter("resume_shed")
                    fail = "shed"
                    return False
            self.metrics.counter("resume_attempts")
            partial = []  # a half-shipped checkpoint dies with its node
            wait = self._dead_after
            if rem is not None:
                wait = min(wait, rem)
            nxt = pick(wait)
            if nxt is None:
                self.metrics.counter("resume_failures")
                fail = "error: no decode node available"
                return False
            node = nxt
            att = f"{key}#{attempt}"
            try:
                dispatch(node)
            except (ConnectionError, OSError):
                self.metrics.counter("resume_failures")
                fail = "error: relay lost"
                return False
            if rec is not None and tctx is not None:
                # The re-home segment: death/handoff detection through the
                # replacement dispatch, on the gateway's trace lane.
                c = tctx.child()
                rec.record(Span(
                    "gateway.rehome", r0, time.time() - r0,
                    {"attempt": attempt, "fenced": fence,
                     "node": node.get("node_id")},
                    trace_id=c.trace_id, span_id=c.span_id,
                    parent_id=c.parent_id, node="gateway",
                ))
            return True

        try:
            # Prefix-aware routing: ask the directory which decode node
            # already holds the longest cached prefix of this prompt and
            # prefer it over plain least-loaded — the hit skips that much
            # prefill. Initial placement only: recovery placement (pick())
            # stays availability-first, and the dead node's advertisement
            # died with its lease anyway.
            node = None
            if self.cost is not None:
                node = self._place_cost(directory, client, prompt, dead_ids)
            if node is None:
                node = self._pick_prefix(directory, prompt, dead_ids)
            if node is None:
                node = pick(self._pool_wait_s)
            if node is None:
                fail = "error: no decode node registered"
                return
            try:
                dispatch(node)
            except (ConnectionError, OSError):
                fail = "error: relay lost"
                return
            last_frame = time.monotonic()
            while True:
                if self._stop_evt.is_set():
                    fail = "cancelled"
                    return
                now = time.monotonic()
                if h.stop.is_set():
                    if cancel_sent is None:
                        cancel_sent = now
                        try:
                            client.put(node["queue"], pack_frame(
                                {"op": "migrate.cancel", "gen": key}
                            ))
                        except (ConnectionError, OSError):
                            fail = "cancelled"
                            return
                    elif now - cancel_sent > 2.0:
                        fail = "cancelled"  # node never acked — give up
                        return
                if deadline is not None and now >= deadline:
                    try:
                        client.put(node["queue"], pack_frame(
                            {"op": "migrate.cancel", "gen": key}
                        ))
                    except (ConnectionError, OSError):
                        pass
                    fail = "deadline"
                    return
                try:
                    frame = client.get(reply, timeout=0.2)
                except TimeoutError:
                    if (time.monotonic() - last_frame >= self._dead_after
                            and not node_alive()):
                        if not recover(True):
                            return
                        last_frame = time.monotonic()
                    continue
                except (ConnectionError, OSError):
                    fail = "error: relay lost"
                    return
                last_frame = time.monotonic()
                try:
                    header, _ = unpack_frame(frame)
                except Exception:  # noqa: BLE001
                    self.metrics.counter("malformed_frames")
                    continue
                if header.get("att") != att:
                    self.metrics.counter("stale_frames_fenced")
                    continue
                op = header.get("op")
                if op == "migrate.ckpt":
                    # Single sender per attempt -> frames arrive in order;
                    # keep only a COMPLETE set (a torn one can't resume).
                    i, n = header.get("i"), header.get("n")
                    partial = [frame] if i == 0 else partial + [frame]
                    if isinstance(n, int) and i == n - 1 \
                            and len(partial) == n:
                        ckpt, partial = partial, []
                    continue
                if op == "migrate.err":
                    # The node declined (pool pressure, bad transfer) but
                    # is healthy: retry elsewhere without fencing it.
                    if not recover(False):
                        return
                    last_frame = time.monotonic()
                    continue
                if op == "fleet.handoff":
                    # The node released this stream (fleet drain or
                    # rebalance): the fresh checkpoint that preceded this
                    # marker on the same queue re-homes it, seq dedup
                    # keeps delivery exactly-once. Exclude the node
                    # locally (no fence — it is healthy) so the re-pick
                    # cannot bounce the stream straight back before the
                    # draining heartbeat lands in the directory.
                    self.metrics.counter("fleet_drained_sessions")
                    if rec is not None and tctx is not None:
                        # The marker carries the node-side handoff span ids:
                        # record the link so a stitched trace joins this
                        # re-home to the node's drain.handoff span even if
                        # a later trace.pull races the node's shutdown.
                        c = tctx.child()
                        rec.record(Span(
                            "gateway.handoff_marker", time.time(), 0.0,
                            {"node_trace": header.get("trace"),
                             "node_span": header.get("span")},
                            trace_id=c.trace_id, span_id=c.span_id,
                            parent_id=c.parent_id, node="gateway",
                        ))
                    if node is not None:
                        dead_ids.add(node.get("node_id"))
                    if not recover(False):
                        return
                    last_frame = time.monotonic()
                    continue
                if op != "migrate.tok":
                    self.metrics.counter("unknown_ops_dropped")
                    continue
                seq, tok = header.get("seq"), header.get("tok")
                fin = bool(header.get("fin"))
                reason = header.get("reason")
                if tok is not None and int(tok) >= 0 and seq is not None:
                    seq = int(seq)
                    if seq == delivered:
                        delivered += 1
                        if t_detect is not None:
                            self.metrics.observe(
                                "mttr_ms",
                                (time.monotonic() - t_detect) * 1e3,
                            )
                            t_detect = None
                            resumed += 1
                            exit_recovery()
                        self._emit(h, TokenEvent(
                            int(tok), fin, reason if fin else None,
                            seq=seq, resumed=resumed,
                        ))
                        if fin:
                            finished = True
                            return
                    elif seq < delivered:
                        # Replayed prefix of a resumed stream: suppress —
                        # the client already has this token.
                        self.metrics.counter("tokens_deduped")
                        if fin:
                            self._emit(h, TokenEvent(
                                -1, True, reason, resumed=resumed
                            ))
                            finished = True
                            return
                    else:
                        # Sequence gap: the node lost state it already
                        # streamed — its engine diverged. Re-home.
                        if not recover(True):
                            return
                        last_frame = time.monotonic()
                elif fin:  # finish without a token (cancel, deadline)
                    self._emit(h, TokenEvent(
                        -1, True, reason, resumed=resumed
                    ))
                    finished = True
                    return
        finally:
            exit_recovery()
            with self._tlock:
                self._threads.pop(key, None)
            try:
                client.close()
            except Exception:  # noqa: BLE001
                pass
            try:
                directory.close()
            except Exception:  # noqa: BLE001
                pass
            if not finished and self._loop is not None:
                # The stream still owes its consumer a terminal event.
                self._emit(h, TokenEvent(
                    -1, True, fail or "error: stream aborted",
                    resumed=resumed,
                ))
