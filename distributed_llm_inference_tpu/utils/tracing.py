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

Clocks: every stamp that leaves the process (``Span.start_s``, a tick's
``t`` and ``t0_ns``) is epoch time; ``perf_counter`` measures durations only
and is never stored.
"""

from __future__ import annotations

import collections
import contextlib
import random
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import jax

__all__ = [
    "Span",
    "SpanRecorder",
    "TraceContext",
    "FlightRecorder",
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
        fr._charge()
        fr._stack.append(self._phase)
        self._ann = jax.profiler.TraceAnnotation(_ANNOTATION[self._phase])
        self._ann.__enter__()

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        fr = self._fr
        fr._charge()
        fr._stack.pop()


class FlightRecorder:
    """Bounded ring of per-engine-tick records — the "what was the engine
    doing at 14:32:07" tool — and the tick's host clock.

    The engine brackets every ``step()`` with :meth:`begin` / :meth:`end`
    and its host phases with :meth:`region`; ``end`` appends one dict (tick
    kind, batch occupancy, admitted/chunked/parked rows, every dispatch of
    the tick, free pages, the five :data:`PHASES` in ms) and adds the same
    seconds to the ``engine_tick_*`` counters of ``metrics``, so two
    ``/metrics`` scrapes give the split of any interval. ``/debug/ticks``
    snapshots the ring. The ring is thread-safe (``step()`` appends from
    the drive thread while HTTP handlers read); the clock is not, and the
    engine touches it under its scheduler lock alone.

    ``tick`` is the id of the tick in progress: the ``step_num`` of its
    ``engine_tick`` step in a profiler trace and the ``tick`` of its
    record."""

    def __init__(self, capacity: int = 512, metrics=None):
        self.capacity = capacity
        self.metrics = metrics
        self.tick = 0
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        # the tick clock: perf_counter, durations only
        self._acc = [0.0] * len(PHASES)
        self._stack = [_ADMIT]      # the phase that what is in no region has
        self._mark = self._t0 = time.perf_counter()
        self._t0_ns = 0
        self._end: Optional[float] = None

    def record(self, **fields: Any) -> None:
        with self._lock:
            fields["tick"] = self.tick
            fields["t"] = time.time()
            self.tick += 1
            self._ring.append(fields)

    def snapshot(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        with self._lock:
            items = list(self._ring)
        if last is not None and last > 0:
            items = items[-last:]
        return items

    def _charge(self) -> float:
        """The time since the last charge goes to the phase on top."""
        now = time.perf_counter()
        self._acc[self._stack[-1]] += now - self._mark
        self._mark = now
        return now

    def region(self, phase: str) -> _Region:
        return _Region(self, PHASES.index(phase))

    def begin(self) -> int:
        """A ``step()`` starts: what passed since the last one ended is this
        tick's ``outside``. Returns the tick's id."""
        now = time.perf_counter()
        self._acc = [0.0] * len(PHASES)
        if self._end is not None:
            self._acc[_OUTSIDE] = now - self._end
        self._t0 = self._mark = now
        self._t0_ns = time.time_ns()
        del self._stack[1:]
        return self.tick

    def end(self, **fields: Any) -> None:
        """The ``step()`` ends: close the clock, add the tick to the
        counters and append its record."""
        self._end = self._charge()
        acc = self._acc
        m = self.metrics
        if m is not None:
            m.counter("engine_ticks")
            m.counter("engine_tick_seconds", sum(acc))
            for name, seconds in zip(PHASES, acc):
                m.counter(f"engine_tick_{name}_seconds", seconds)
        fields["t0_ns"] = self._t0_ns
        fields["host_ms"] = (self._end - self._t0) * 1e3
        for name, seconds in zip(PHASES, acc):
            fields[f"{name}_ms"] = seconds * 1e3
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
