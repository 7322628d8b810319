"""Tracing / profiling (SURVEY §5.1 — absent in the reference).

The reference has no profiler hooks at all (its only observability is two
``print`` statements, ``/root/reference/distributed_llm_inference/utils/
model.py:61,82``). This module supplies the two tiers the TPU rebuild needs:

* **Device profiling** — :func:`profile_trace` / :func:`start_profile` wrap
  ``jax.profiler`` so a serving window dumps an XLA trace (TensorBoard /
  Perfetto-viewable). The engine's ticks and their host phases are on that
  trace's host plane, on the profiler's own clock: one
  ``StepTraceAnnotation("engine_tick", step_num=<tick id>)`` a ``step()``
  and one ``TraceAnnotation("engine.<phase>")`` a region (see
  :class:`FlightRecorder`).
* **Distributed request tracing** — :class:`TraceContext` carries a
  (trace_id, span_id, parent) triple from the gateway across relay frame
  headers (the flat ``"trace"``/``"span"`` keys, so the distcheck DC500/
  DC501 closed world sees them); every node records child spans with
  **epoch** (``time.time``) timestamps into its own recorder, and
  :func:`stitch_chrome_trace` merges the per-node span sets the
  ``trace.pull`` collector gathers into ONE Chrome trace-event document —
  one ``pid`` lane per node, all on the shared epoch clock.
* **Flight recorder** — :class:`FlightRecorder` keeps a bounded ring of
  per-engine-tick records (tick kind, occupancy, admitted/chunked/parked
  rows, every dispatch's shape, free pages, and the tick's wall time split
  into the host phases ``admit`` / ``dispatch`` / ``blocked`` / ``deliver``
  / ``outside``) for the ``/debug/ticks`` endpoint, and adds the same split
  to the ``engine_tick_*`` counters on ``/metrics``. It is ``None`` on
  engines without a :class:`~..config.TraceConfig`, so the decode tick pays
  exactly one attribute load + branch when disabled.
* **Dispatch clock** — :class:`DispatchClock`, the recorder's: every
  dispatch the engine notes gets an enqueue, a return and a device-ready
  stamp (``dispatch_clock`` in the tick record), and from them the engine
  itself counts what the device ran by kind, when it had nothing to run and
  under which host phase, how long the drive thread was held inside the
  compiled calls, and the pieces of a first token's wait. It is armed by
  demand: a read of the ticks (:meth:`FlightRecorder.snapshot`) takes a
  lease of :data:`CLOCK_LEASE_S` seconds, and an engine nobody watches
  calls its programs bare, with no stamp, no thread and no counter.
  :class:`ProgramLoads` counts the programs the process traces, lowers and
  compiles or reads from the persistent cache, from JAX's own monitoring,
  always: a set-up is counted from its first trace, by stage and by program.
  :class:`BootMarks` dates the process's start and, from it, the engine's
  construction and its first request: the rest of a set-up.

Clocks: every stamp that leaves the process (``Span.start_s``, a tick's
``t`` and ``t0_ns``, a dispatch's ``enq_ns`` / ``ret_ns`` / ``ready_ns``) is
epoch time, and the tick's and the dispatch clock's durations are
differences of such stamps (``time.time_ns``), so one reading serves both.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import queue
import random
import threading
import time
import uuid
import weakref
from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple,
)

import jax

logger = logging.getLogger("distributed_llm_inference_tpu")

__all__ = [
    "Span",
    "SpanRecorder",
    "TraceContext",
    "FlightRecorder",
    "DispatchClock",
    "CLOCK_LEASE_S",
    "ProgramLoads",
    "PROGRAM_LOADS",
    "LOAD_STAGES",
    "BootMarks",
    "BOOT",
    "BOOT_MARKS",
    "PHASES",
    "trace_span",
    "stitch_chrome_trace",
    "profile_trace",
    "start_profile",
    "stop_profile",
]


@dataclass
class Span:
    name: str
    start_s: float  # epoch (time.time()): spans of every process share it
    duration_s: float
    args: Optional[Dict[str, Any]] = None
    # Distributed-trace attribution (None for a span outside any trace).
    trace_id: Optional[str] = None
    span_id: Optional[str] = None
    parent_id: Optional[str] = None
    node: str = ""

    def to_dict(self) -> Dict[str, Any]:
        """JSON-safe form for the ``trace.spans`` wire reply."""
        d: Dict[str, Any] = {
            "name": self.name,
            "start_s": self.start_s,
            "duration_s": self.duration_s,
        }
        if self.args:
            d["args"] = self.args
        if self.trace_id is not None:
            d["trace_id"] = self.trace_id
            d["span_id"] = self.span_id
            d["parent_id"] = self.parent_id
        if self.node:
            d["node"] = self.node
        return d


@dataclass(frozen=True)
class TraceContext:
    """One request's position in a distributed trace: which trace it
    belongs to, the current span, and that span's parent. Immutable —
    :meth:`child` derives the context a sub-operation records under, and
    :meth:`to_header` / :meth:`from_header` move it across relay frame
    headers as the flat ``"trace"`` / ``"span"`` keys."""

    trace_id: str
    span_id: str = field(default="")
    parent_id: Optional[str] = None

    @staticmethod
    def mint(sample_rate: float = 1.0) -> Optional["TraceContext"]:
        """Gateway entry point: a fresh root context, or ``None`` when the
        request is not sampled (the whole tracing path then short-circuits
        on ``is None`` checks — sampling is the zero-cost switch)."""
        if sample_rate <= 0.0 or random.random() >= sample_rate:
            return None
        return TraceContext(
            trace_id=uuid.uuid4().hex[:16], span_id=uuid.uuid4().hex[:8]
        )

    def child(self) -> "TraceContext":
        return TraceContext(
            trace_id=self.trace_id,
            span_id=uuid.uuid4().hex[:8],
            parent_id=self.span_id,
        )

    def to_header(self) -> Dict[str, str]:
        """Flat frame-header keys (merge into an outgoing frame dict)."""
        return {"trace": self.trace_id, "span": self.span_id}

    @staticmethod
    def from_header(header: Dict[str, Any]) -> Optional["TraceContext"]:
        tid = header.get("trace")
        if not tid:
            return None
        return TraceContext(
            trace_id=str(tid), span_id=str(header.get("span") or "")
        )


class SpanRecorder:
    """Bounded, thread-safe span log (per-trace export:
    :func:`stitch_chrome_trace`).

    The engine's host threads (SURVEY §5.2's concurrency caution) may record
    concurrently; the newest ``capacity`` spans are kept. Eviction is NOT
    silent (the repo's "no silent caps" rule): :attr:`dropped` counts
    evicted spans and, when a ``metrics`` sink is attached, every eviction
    bumps the ``trace_spans_dropped`` counter.
    """

    def __init__(self, capacity: int = 100_000, metrics=None):
        self.capacity = capacity
        self.metrics = metrics
        self.dropped = 0
        self._lock = threading.Lock()
        # deque(maxlen): O(1) append-with-evict — record() sits on the
        # per-decode-step hot path.
        self._spans: collections.deque[Span] = collections.deque(maxlen=capacity)

    def record(self, s: Span) -> None:
        with self._lock:
            evicting = len(self._spans) >= self.capacity
            self._spans.append(s)
            if evicting:
                self.dropped += 1
        if evicting and self.metrics is not None:
            self.metrics.counter("trace_spans_dropped")

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def depth(self) -> int:
        with self._lock:
            return len(self._spans)

    def spans_for(self, trace_id: str) -> List[Span]:
        """Spans attributed to one distributed trace (collector op)."""
        with self._lock:
            return [s for s in self._spans if s.trace_id == trace_id]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


@contextlib.contextmanager
def trace_span(
    recorder: Optional[SpanRecorder],
    name: str,
    ctx: Optional[TraceContext],
    node: str = "",
    **args: Any,
) -> Iterator[Optional[TraceContext]]:
    """Record one distributed-trace child span (epoch clock).

    Yields the child :class:`TraceContext` the region runs under — put it
    on outgoing frame headers so remote spans parent correctly. A ``None``
    recorder or context makes the whole region a no-op yielding ``None``
    (the unsampled fast path)."""
    if recorder is None or ctx is None:
        yield None
        return
    child = ctx.child()
    t0 = time.time()
    try:
        yield child
    finally:
        # Record even when the region raises — a failed KV transfer or
        # admission is exactly the segment worth seeing on the timeline.
        recorder.record(Span(
            name, t0, time.time() - t0, args or None,
            trace_id=child.trace_id, span_id=child.span_id,
            parent_id=child.parent_id, node=node,
        ))


def stitch_chrome_trace(
    trace_id: str, node_spans: Dict[str, List[Dict[str, Any]]]
) -> Dict:
    """Assemble per-node span dicts (``Span.to_dict`` form, as gathered by
    the ``trace.pull`` collector) into ONE Chrome trace-event document:
    one ``pid`` lane per node, events on the shared epoch clock, sorted by
    start time. Nodes that failed to answer the pull are simply absent —
    a partial trace renders fine, it just has fewer lanes."""
    events = []
    for node, spans in sorted(node_spans.items()):
        for s in spans:
            if s.get("trace_id") not in (None, trace_id):
                continue
            ev = {
                "name": s.get("name", "?"),
                "ph": "X",
                "ts": float(s.get("start_s", 0.0)) * 1e6,
                "dur": float(s.get("duration_s", 0.0)) * 1e6,
                "pid": node,
                "tid": 0,
            }
            args = dict(s.get("args") or {})
            for k in ("span_id", "parent_id"):
                if s.get(k):
                    args[k] = s[k]
            if args:
                ev["args"] = args
            events.append(ev)
    events.sort(key=lambda e: e["ts"])
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"trace_id": trace_id, "nodes": sorted(node_spans)},
    }


#: Where the drive thread is, for every instant of a tick's wall time (the
#: engine's region helper names them; ``engine/engine.py``): ``admit``
#: (planning, page allocation, prefill-family dispatches, and what of
#: ``step()`` lies outside every other region), ``dispatch`` (building the
#: decode inputs and calling the decode program), ``blocked`` (inside
#: ``jax.device_get`` on the tick path), ``deliver`` (what follows a fetch)
#: and ``outside`` (from the end of the previous ``step()`` to the start of
#: this one: fan-out, ``collect_finished``, idle sleep).
PHASES = ("admit", "dispatch", "blocked", "deliver", "outside")
_ADMIT, _OUTSIDE = PHASES.index("admit"), PHASES.index("outside")
_ANNOTATION = tuple(f"engine.{p}" for p in PHASES)

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: The stages of a program's load, as :class:`ProgramLoads` keeps them: a
#: ``backend_compile_duration`` is ``cache_read`` where its thread reported a
#: retrieval from the persistent cache inside it, ``compile`` where not.
LOAD_STAGES = ("trace", "lower", "compile", "cache_read")
_TRACE, _LOWER, _COMPILE, _CACHE_READ = range(len(LOAD_STAGES))
_STAGE_OF = {_TRACE_EVENT: _TRACE, _LOWER_EVENT: _LOWER, _COMPILE_EVENT: _COMPILE}
_STAGE_KEYS = tuple(f"{stage}_s" for stage in LOAD_STAGES)
#: The reports a thread keeps until a later one holds them. An unrolled
#: stack's trace has a few reports a layer DIRECTLY inside it, and what has
#: been dropped by then is counted twice: at 64 a probe that took 11.8 s read
#: 16.9 s of tracing and lowering (PERF.md section 6, PR 60).
_OPEN_REPORTS = 4096


class _ThreadLoads(threading.local):
    """A thread's own of :class:`ProgramLoads`: the reported intervals not
    yet inside a later one (``stack``: start, seconds), the seconds since
    its last load by stage (``pending``), whether the persistent cache
    answered the compile in progress (``cache_read``), who watches
    (``sink``)."""

    def __init__(self):
        self.stack: List[Tuple[float, float]] = []
        self.pending = [0.0] * len(LOAD_STAGES)
        self.cache_read = False
        self.sink = None


class ProgramLoads:
    """The programs this process loads, counted from JAX's own monitoring:
    ``loads``, one a ``backend_compile_duration`` event (a compile or a read
    of the persistent cache: the step that waits for it stands still either
    way); ``seconds``, of tracing, lowering and that compile or read, as JAX
    reports them; ``cache_hits``, the reads. The reports nest (a jitted
    function traced inside another's trace reports inside it), so the
    seconds are those of the union of a thread's reported intervals, each
    second under the stage of the innermost report that holds it
    (``stages``, by :data:`LOAD_STAGES`: the four sum to ``seconds``).
    ``programs`` keeps the same by program: what a thread gathered since its
    last load goes to the ``fun_name`` its compile event names.

    One instance a process, :data:`PROGRAM_LOADS`; :meth:`install` registers
    its listener once (``enable_compile_cache`` calls it, so an entry point
    counts from before its first trace, and so does the first
    :class:`FlightRecorder`). A flight recorder hears of the loads of its own
    tick: :meth:`watch` names, for the calling thread, who is told
    ``(fun_name, seconds)`` as each program's compile ends."""

    def __init__(self):
        self.loads = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.stages = [0.0] * len(LOAD_STAGES)
        # fun_name -> {loads, cache_hits, <stage>_s...}
        self.programs: Dict[str, Dict[str, float]] = {}
        self._lock = threading.Lock()
        self._installed = False
        self._local = _ThreadLoads()

    def install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def watch(self, sink) -> None:
        self._local.sink = sink

    def unwatch(self) -> None:
        self._local.sink = None

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """A copy of ``programs``, as ``/debug/ticks`` carries it: ``fun_name
        -> {loads, cache_hits, trace_s, lower_s, compile_s, cache_read_s}``."""
        with self._lock:
            return {name: dict(row) for name, row in self.programs.items()}

    def _duration(self, event: str, seconds: float, **kw: Any) -> None:
        local = self._local
        if event == _CACHE_READ_EVENT:
            # reported inside the compile event it answers, on its thread
            local.cache_read = True
            with self._lock:
                self.cache_hits += 1
            return
        stage = _STAGE_OF.get(event)
        if stage is None:
            return
        stack = local.stack
        # the report comes as its interval ends: what started inside it was
        # reported before it, and is counted already
        start = time.time() - seconds
        inside = 0.0
        while stack and stack[-1][0] >= start:
            inside += stack.pop()[1]
        stack.append((start, seconds))
        del stack[:-_OPEN_REPORTS]
        own = max(0.0, seconds - inside)
        loaded = stage == _COMPILE
        if loaded and local.cache_read:
            stage, local.cache_read = _CACHE_READ, False
        pending = local.pending
        pending[stage] += own
        with self._lock:
            self.seconds += own
            self.stages[stage] += own
            if loaded:
                # the compile event names the program: what the thread
                # gathered since its last load is this program's
                name = kw.get("fun_name", "?")
                self.loads += 1
                row = self.programs.get(name)
                if row is None:
                    row = self.programs[name] = {
                        "loads": 0, "cache_hits": 0,
                        **{key: 0.0 for key in _STAGE_KEYS},
                    }
                row["loads"] += 1
                row["cache_hits"] += int(stage == _CACHE_READ)
                for key, s in zip(_STAGE_KEYS, pending):
                    row[key] += s
        if not loaded:
            return
        whole = sum(pending)
        pending[:] = [0.0] * len(LOAD_STAGES)
        if local.sink is not None:
            local.sink(name, whole)


PROGRAM_LOADS = ProgramLoads()

#: What :class:`BootMarks` dates, in the order a process passes them.
BOOT_MARKS = ("engine_build", "engine_built", "first_request")


def _process_start() -> float:
    """Epoch seconds at which the OS started this process: its start in
    clock ticks since the machine's boot (``/proc/self/stat``, field 22)
    against the boot clock's reading now. Where the OS does not say, now:
    this module's import."""
    now = time.time()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rpartition(")")[2].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - (
            ticks / os.sysconf("SC_CLK_TCK")
        )
    except (OSError, ValueError, IndexError, AttributeError):
        return now
    return now - age if age >= 0.0 else now


class BootMarks:
    """The process's life up to its first request, on the clock every other
    stamp that leaves the process uses: ``start``, epoch seconds at which the
    OS started it (not the first line Python ran), and as seconds since it
    ``engine_build`` (the first :class:`FlightRecorder` made, which an
    engine's constructor does as it starts: interpreter, imports, backend
    and weights lie before it), ``engine_built`` (the constructor left) and
    ``first_request`` (the first ``submit``: a probe and a server's bind lie
    before it). Each mark is written once, by the first to come by, and
    only for a recorder: an engine without one marks nothing, as it records
    nothing. :meth:`mark` also sets the recorder's gauges
    (``process_start_time_seconds``, ``boot_<mark>_seconds``) of what is
    written so far, so every traced engine's ``/metrics`` tells the
    process's boot.

    One instance a process, :data:`BOOT`."""

    def __init__(self):
        self.start = _process_start()
        self.engine_build: Optional[float] = None
        self.engine_built: Optional[float] = None
        self.first_request: Optional[float] = None
        self._lock = threading.Lock()

    def mark(self, name: str, recorder: Optional["FlightRecorder"]) -> None:
        if recorder is None:
            return
        with self._lock:
            if getattr(self, name) is None:
                setattr(self, name, time.time() - self.start)
        m = recorder.metrics
        if m is not None:
            m.gauge("process_start_time_seconds", self.start)
            for passed in BOOT_MARKS:
                seconds = getattr(self, passed)
                if seconds is not None:
                    m.gauge(f"boot_{passed}_seconds", seconds)

    def snapshot(self) -> Dict[str, Optional[float]]:
        """``{"start": epoch seconds, <mark>: seconds since it or None}``,
        as ``/debug/ticks`` carries it."""
        return {"start": self.start,
                **{name: getattr(self, name) for name in BOOT_MARKS}}


BOOT = BootMarks()

#: Seconds the dispatch clock stays armed after a read of the ticks
#: (``FlightRecorder.snapshot``): longer than any poll of ``/debug/ticks``
#: (the benchmark's is 1.0 s, 0.25 s under a one-token engine) and a few
#: ticks of the slowest engine, short enough that a glance costs seconds.
CLOCK_LEASE_S = 5.0


class DispatchClock:
    """Three stamps on every dispatch the engine notes, and what follows
    from them.

    The drive thread calls :meth:`enter` as it enters a noted dispatch's
    compiled call (``enq_ns``) and :meth:`leave` as the call returns
    (``ret_ns``), handing over the dispatch's small output (the emitted
    tokens, a prefill's token: never the donated cache). A watcher thread
    waits for each output in turn and stamps ``ready_ns``
    (:meth:`settle`), so the drive thread gains no sync; where the drive
    thread itself fetches an output it settles the dispatch too, and the
    earlier of the two stands. The device runs the dispatches in the order
    they were enqueued, so with ``start_i = max(ready_(i-1), enq_i)``:

    * ``engine_device_seconds_<kind>`` gets ``ready_i - start_i`` (the small
      programs between two noted dispatches fall to the one that follows),
      ``engine_dispatches_<kind>`` one, ``engine_decode_steps`` a decode
      dispatch's steps;
    * ``engine_device_idle_seconds`` gets ``max(0, enq_i - ready_(i-1))``,
      the time the device had nothing of the engine's to run, and
      ``engine_device_idle_<phase>_seconds`` the same cut by the phase the
      drive thread was in (the recorder's marks), which sum to it;
    * ``engine_enqueue_seconds`` gets ``ret_i - enq_i`` less the seconds of
      a program load inside the call: how long the compiled calls held the
      drive thread.

    The stamps ride the tick record as ``dispatch_clock``, index for index
    with ``dispatches``: ``enq_ns``, ``ret_ns``, ``ready_ns`` (None until
    the result is ready: a tick later, as a rule), ``device_ms``,
    ``idle_ms`` with ``idle_phase_ms`` where the device waited, and
    ``compile_ms`` where the call loaded a program.

    **Armed by demand.** All of the above happens while a lease is out and
    not otherwise. Any thread takes or renews one (:meth:`lease`: it writes
    the lease's end and nothing else); the drive thread, which alone touches
    the clock, compares it at a tick's start, arms or disarms there
    (:meth:`turn`) and tells the engine (:attr:`on_turn`), which puts its
    ``_clocked`` wrappers around its step programs while the clock is armed
    and takes them off again. Unarmed the engine calls its programs as if
    there were no clock: no wrapper, no stamp, no mark kept, no hand-off, no
    watcher thread (it ends with the lease, once it has stamped what was in
    flight, and the next lease starts another), no ``dispatch_clock`` in a
    record, no counter touched. Arming forgets the last ready stamp, so no
    idle gap spans an unarmed stretch."""

    def __init__(self, metrics, marks):
        self.metrics = metrics
        self._marks = marks             # the recorder's (t_ns, phase)
        self.entries: List[dict] = []   # the tick in progress
        self.load_s = 0.0               # program loads inside the open call
        self.lease_ns = 0.0             # epoch ns the lease runs to
        self.armed = False              # the drive thread's, as is the next
        self.armed_at = 0.0             # ``time.monotonic()`` of the arming
        # who hears of a turn, as ``on_turn()(armed)``: a weak reference to
        # the engine's method (the recorder's finalizer holds the clock)
        self.on_turn: Optional[Callable[[], Optional[Callable]]] = None
        # ``_pending`` (enqueue order) and ``_last_ready_ns`` belong to
        # whoever holds the lock: the drive thread or the watcher
        self._lock = threading.Lock()
        self._pending: collections.deque = collections.deque()
        self._last_ready_ns: Optional[int] = None
        # the watcher and its queue: a lease's own, so a watcher that a
        # join gave up on ends on its own sentinel and takes nothing of the
        # next one's
        self._queue: Optional[queue.SimpleQueue] = None
        self._thread: Optional[threading.Thread] = None

    def lease(self, seconds: float) -> None:
        """Somebody watches: the clock is armed from the next tick on until
        ``seconds`` from now, or as far as an earlier lease runs."""
        self.lease_ns = max(self.lease_ns, time.time_ns() + seconds * 1e9)

    def turn(self) -> None:
        """The drive thread, at the start of a tick that finds the lease
        and :attr:`armed` apart: arm, or disarm."""
        if self.armed:
            self._disarm()
            return
        # the last lease's watcher is long gone, as a rule; what it could not
        # stamp is dropped (``settle`` knows an entry by its place in
        # ``_pending``)
        self._join()
        with self._lock:
            self._pending.clear()
            self._last_ready_ns = None
        self._marks.clear()
        self.armed_at = time.monotonic()
        self.armed = True
        self._tell()

    def _disarm(self) -> None:
        """The watcher stamps what is in flight, then ends."""
        self.armed = False
        self._tell()
        if self._thread is not None:
            self._queue.put(None)

    def _tell(self) -> None:
        heard = self.on_turn() if self.on_turn is not None else None
        if heard is not None:
            heard(self.armed)

    def _join(self) -> None:
        t, self._thread = self._thread, None
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)

    def enter(self) -> dict:
        """The drive thread enters a noted dispatch's compiled call. With
        nothing of the engine's in flight the device has waited since the
        last result was ready."""
        self.load_s = 0.0
        now = time.time_ns()
        with self._lock:
            since = None if self._pending else self._last_ready_ns
        idle = 0 if since is None else max(0, now - since)
        entry = {
            "enq_ns": now, "ret_ns": None, "ready_ns": None,
            "device_ms": None, "idle_ms": idle / 1e6,
        }
        if idle:
            by_phase = self._by_phase(since, now)
            entry["idle_phase_ms"] = {
                PHASES[i]: ns / 1e6 for i, ns in enumerate(by_phase) if ns
            }
            m = self.metrics
            if m is not None:
                m.counter("engine_device_idle_seconds", idle / 1e9)
                for name, ns in zip(PHASES, by_phase):
                    if ns:
                        m.counter(
                            f"engine_device_idle_{name}_seconds", ns / 1e9
                        )
        self.entries.append(entry)
        return entry

    def _by_phase(self, lo: int, hi: int) -> List[int]:
        """Nanoseconds of ``[lo, hi)`` by the phase the drive thread was in;
        what is older than the marks kept is ``outside``."""
        out = [0] * len(PHASES)
        for t, phase in reversed(self._marks):
            if t >= hi:
                continue
            out[phase] += hi - max(t, lo)
            hi = max(t, lo)
            if t <= lo:
                break
        out[_OUTSIDE] += hi - lo
        return out

    def leave(
        self, entry: dict, out, kind: str, steps: int = 1,
        watched: bool = True,
    ) -> None:
        """The call returned: ``out`` is what becomes ready when the
        dispatch has run, for the watcher to wait on, unless the drive
        thread fetches it at once (``watched`` false, or no ``out``) and
        settles the dispatch itself (:meth:`fetched`, :meth:`settle`)."""
        now = entry["ret_ns"] = time.time_ns()
        held = (now - entry["enq_ns"]) / 1e9
        if self.load_s:
            entry["compile_ms"] = self.load_s * 1e3
            held = max(0.0, held - self.load_s)
        m = self.metrics
        if m is not None:
            m.counter("engine_enqueue_seconds", held)
        with self._lock:
            self._pending.append((entry, kind, steps, out))
        if out is None or not watched:
            return
        if self._thread is None:
            self._queue = queue.SimpleQueue()
            self._thread = threading.Thread(
                target=self._watch, args=(self._queue,),
                name="dispatch-clock", daemon=True,
            )
            self._thread.start()
        self._queue.put((entry, out))

    def settle(self, entry: dict) -> None:
        """``entry``'s result is ready on the device now, and so is every
        dispatch enqueued before it. Whoever knows first, the watcher or
        the drive thread at a fetch, stamps; the other finds it done."""
        if entry["ready_ns"] is not None or entry["ret_ns"] is None:
            return
        m = self.metrics
        with self._lock:
            # settled meanwhile, or enqueued under a lease before this one
            if not any(p[0] is entry for p in self._pending):
                return
            now = time.time_ns()
            while self._pending:
                e, kind, steps, _ = self._pending.popleft()
                start = max(self._last_ready_ns or 0, e["enq_ns"])
                e["device_ms"] = (now - start) / 1e6
                e["ready_ns"] = self._last_ready_ns = now
                if m is not None:
                    m.counter(
                        f"engine_device_seconds_{kind}", (now - start) / 1e9
                    )
                    m.counter(f"engine_dispatches_{kind}")
                    if kind == "decode":
                        m.counter("engine_decode_steps", steps)
                if e is entry:
                    break

    def fetched(self, x) -> None:
        """The drive thread holds ``x`` on the host (a result, or a list of
        them): the newest dispatch in flight whose ``out`` is among them has
        run, and so has every dispatch before it."""
        outs = x if isinstance(x, (list, tuple)) else (x,)
        with self._lock:
            entry = next(
                (p[0] for p in reversed(self._pending)
                 if any(p[3] is o for o in outs)), None,
            )
        if entry is not None:
            self.settle(entry)

    def first_token(
        self, entries: Sequence[dict], wait_s: float
    ) -> Tuple[float, float, float]:
        """The pieces of a first token's wait of ``wait_s`` seconds, the
        host holding the token now, for a request whose prompt ``entries``
        carried: ``(prefill_wait, prefill_own, deliver)`` seconds. Own is
        the device's time on the request's own dispatches, deliver the time
        its token lay ready, and the wait is the rest: the device ran other
        work, or nothing. They sum to ``wait_s``."""
        self.settle(entries[-1])
        own = sum(e["device_ms"] or 0.0 for e in entries) / 1e3
        ready = entries[-1]["ready_ns"]     # None: its call raised
        deliver = 0.0 if ready is None else max(0, time.time_ns() - ready) / 1e9
        return wait_s - own - deliver, own, deliver

    def _watch(self, handed: queue.SimpleQueue) -> None:
        while True:
            item = handed.get()
            if item is None:
                return
            entry, out = item
            try:
                jax.block_until_ready(out)
            except Exception as e:  # the drive thread meets it at its fetch
                logger.warning("dispatch clock: %r waiting for a result", e)
            self.settle(entry)
            del item, entry, out

    def stop(self) -> None:
        """End the watcher and wait for it (the recorder's finalizer calls
        this, when the recorder goes and at interpreter exit): a daemon
        thread still inside ``block_until_ready`` when the interpreter
        finalizes aborts the process."""
        if self.armed:
            self._disarm()
        self._join()


class _Region:
    """One host phase of the tick in progress: a ``TraceAnnotation`` on the
    profiler's host plane and exclusive host seconds on the tick record (a
    region entered inside another suspends the outer one, so the phases of
    a tick sum to its wall time)."""

    __slots__ = ("_fr", "_phase", "_ann")

    def __init__(self, fr: "FlightRecorder", phase: int):
        self._fr, self._phase = fr, phase

    def __enter__(self) -> None:
        fr = self._fr
        now = fr._charge()
        fr._stack.append(self._phase)
        if fr.clock.armed:
            fr._marks.append((now, self._phase))
        self._ann = jax.profiler.TraceAnnotation(_ANNOTATION[self._phase])
        self._ann.__enter__()

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        fr = self._fr
        now = fr._charge()
        fr._stack.pop()
        if fr.clock.armed:
            fr._marks.append((now, fr._stack[-1]))


class FlightRecorder:
    """Bounded ring of per-engine-tick records — the "what was the engine
    doing at 14:32:07" tool — and the tick's host clock.

    The engine brackets every ``step()`` with :meth:`begin` / :meth:`end`
    and its host phases with :meth:`region`; ``end`` appends one dict (tick
    kind, batch occupancy, admitted/chunked/parked rows, every dispatch of
    the tick and its clock, free pages, the five :data:`PHASES` in ms, the
    programs a load of which stalled it) and adds the same seconds to the
    ``engine_tick_*`` counters of ``metrics``, so two ``/metrics`` scrapes
    give the split of any interval. ``/debug/ticks`` snapshots the ring. The
    ring is thread-safe (``step()`` appends from the drive thread while HTTP
    handlers read); the tick clock is not, and the engine touches it under
    its scheduler lock alone. ``clock`` is the :class:`DispatchClock`, which
    reads the tick clock's marks from the drive thread only; a
    :meth:`snapshot` arms it for :data:`CLOCK_LEASE_S` seconds, so the
    records carry ``dispatch_clock`` while somebody reads them.

    ``tick`` is the id of the tick in progress: the ``step_num`` of its
    ``engine_tick`` step in a profiler trace and the ``tick`` of its
    record."""

    def __init__(self, capacity: int = 512, metrics=None):
        self.capacity = capacity
        self.metrics = metrics
        self.tick = 0
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        # the tick clock: epoch nanoseconds, one reading a phase change
        self._acc = [0] * len(PHASES)
        self._stack = [_ADMIT]      # the phase that what is in no region has
        self._mark = self._t0 = time.time_ns()
        self._end: Optional[int] = None
        # every phase change of an armed clock as (t_ns, the phase from then
        # on): what cuts an idle gap of the device by phase
        self._marks: collections.deque = collections.deque(maxlen=1024)
        self.clock = DispatchClock(metrics, self._marks)
        weakref.finalize(self, self.clock.stop)
        # programs the tick in progress loaded, and the process's totals as
        # the counters last had them
        self._compiled: List[Tuple[str, float]] = []
        self._loads_seen = (0, 0.0, 0)
        self._stages_seen = [0.0] * len(LOAD_STAGES)
        PROGRAM_LOADS.install()
        BOOT.mark("engine_build", self)

    def record(self, **fields: Any) -> None:
        with self._lock:
            fields["tick"] = self.tick
            fields["t"] = time.time()
            self.tick += 1
            self._ring.append(fields)

    def snapshot(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        """The ring's records, oldest first. Reading them is watching: the
        dispatch clock is armed for :data:`CLOCK_LEASE_S` seconds from now
        (a handler's thread may call this: it writes the lease's end and the
        drive thread does the rest at its next tick)."""
        self.clock.lease(CLOCK_LEASE_S)
        with self._lock:
            items = list(self._ring)
        if last is not None and last > 0:
            items = items[-last:]
        return items

    def _charge(self) -> int:
        """The time since the last charge goes to the phase on top."""
        now = time.time_ns()
        self._acc[self._stack[-1]] += max(0, now - self._mark)
        self._mark = now
        return now

    def region(self, phase: str) -> _Region:
        return _Region(self, PHASES.index(phase))

    def _loaded(self, fun_name: str, seconds: float) -> None:
        """A program's load ended inside the tick in progress."""
        self._compiled.append((fun_name, round(seconds * 1e3, 3)))
        self.clock.load_s += seconds

    def begin(self) -> int:
        """A ``step()`` starts: what passed since the last one ended is this
        tick's ``outside``. Returns the tick's id."""
        now = time.time_ns()
        self._acc = [0] * len(PHASES)
        if self._end is not None:
            self._acc[_OUTSIDE] = max(0, now - self._end)
        self._t0 = self._mark = now
        del self._stack[1:]
        clock = self.clock
        if (now < clock.lease_ns) != clock.armed:
            clock.turn()    # a lease was taken, or the last one ran out
        if clock.armed:
            self._marks.append((now, _ADMIT))
        PROGRAM_LOADS.watch(self._loaded)
        return self.tick

    def end(self, **fields: Any) -> None:
        """The ``step()`` ends: close the clock, add the tick to the
        counters and append its record."""
        self._end = self._charge()
        clock = self.clock
        if clock.armed:
            self._marks.append((self._end, _OUTSIDE))
        loads = PROGRAM_LOADS
        loads.unwatch()
        acc = [ns / 1e9 for ns in self._acc]
        m = self.metrics
        if m is not None:
            m.counter("engine_ticks")
            m.counter("engine_tick_seconds", sum(acc))
            for name, seconds in zip(PHASES, acc):
                m.counter(f"engine_tick_{name}_seconds", seconds)
            seen = (loads.loads, loads.seconds, loads.cache_hits)
            if seen != self._loads_seen:
                was, self._loads_seen = self._loads_seen, seen
                m.counter("engine_program_loads", seen[0] - was[0])
                m.counter("engine_program_load_seconds", seen[1] - was[1])
                m.counter("engine_compile_cache_hits", seen[2] - was[2])
                stages = list(loads.stages)
                for name, now, then in zip(LOAD_STAGES, stages, self._stages_seen):
                    m.counter(f"engine_program_load_{name}_seconds", now - then)
                self._stages_seen = stages
        fields["t0_ns"] = self._t0
        fields["host_ms"] = (self._end - self._t0) / 1e6
        for name, seconds in zip(PHASES, acc):
            fields[f"{name}_ms"] = seconds * 1e3
        if clock.armed:
            if m is not None:
                m.counter("engine_clocked_ticks")
            fields["dispatch_clock"], clock.entries = clock.entries, []
        if self._compiled:
            fields["compiled"], self._compiled = self._compiled, []
        self.record(**fields)


_profile_lock = threading.Lock()
_profile_dir: Optional[str] = None


def start_profile(log_dir: str) -> bool:
    """Begin a ``jax.profiler`` device trace into ``log_dir``. Returns True
    when this call started the trace; False when one was already running (the
    running trace is left untouched)."""
    global _profile_dir
    with _profile_lock:
        if _profile_dir is not None:
            return False
        jax.profiler.start_trace(log_dir)
        _profile_dir = log_dir
        return True


def stop_profile() -> Optional[str]:
    """Stop the running device trace; returns its log dir (None if idle)."""
    global _profile_dir
    with _profile_lock:
        if _profile_dir is None:
            return None
        out, _profile_dir = _profile_dir, None
        jax.profiler.stop_trace()
        return out


@contextlib.contextmanager
def profile_trace(log_dir: Optional[str]) -> Iterator[None]:
    """Profile the enclosed region into ``log_dir`` (no-op when None).

    Only stops a trace this context actually started — nesting inside an
    externally started ``start_profile`` window leaves that trace running.
    """
    if log_dir is None:
        yield
        return
    started = start_profile(log_dir)
    try:
        yield
    finally:
        if started:
            stop_profile()
