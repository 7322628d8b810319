"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to where the device's
time went and what the host was doing while the device waited.

The reference has no profiling story at all (SURVEY §5.1 — its only
observability is two ``print`` calls in the weight loader,
``/root/reference/distributed_llm_inference/utils/model.py:61,82``); here the
profiler is a first-class tool: ``tools/xplane_profile.py`` prints
:func:`aggregate`'s result.

The trace is read with ``jax.profiler.ProfileData`` into plain data, so the
arithmetic below is checked on made-up planes as well as on a trace. What it
knows of a trace:

* a chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` has one event
  for every operation the chip ran (named by its HLO text, kept here as
  ``<opcode>:<result name>``), ``XLA Modules`` one for every execution of a
  compiled program. Busy time is the UNION of the operations' intervals,
  never the sum of their durations;
* the host is the plane ``/host:CPU``, one line a thread. The engine's drive
  thread carries one ``engine_tick`` event a ``step()`` (stat ``step_num`` =
  the flight recorder's tick id) and, nested in it, the ``engine.<phase>``
  regions (``utils/tracing.py``). Both planes are on the profiler's clock,
  so an idle gap of device 0 belongs to the phase the drive thread was in:
  the innermost region that holds the instant, ``admit`` inside a tick but
  outside every region (as the tick's own clock has it), ``outside`` between
  two ticks.

The engine's dispatch clock (``utils/tracing.py``: ``dispatch_clock`` in a
tick's record, epoch nanoseconds) and the trace are joined by
:func:`join_dispatches`: a tick's ``t0_ns`` and its ``engine_tick`` event
are one instant on the two clocks, and device 0 runs the noted dispatches in
the order the engine enqueued them.

Times are nanoseconds.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .tracing import PHASES

DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TICK, REGION = "engine_tick", "engine."
#: operations that only hold others (their events enclose their bodies')
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"([a-z][a-z\-]*)\(")
#: the programs of the dispatches the engine notes, as a module event's name
#: holds them (``jit__decode_scan(<id>)``), with the dispatch's kind; the
#: first that is found in a name decides
NOTED_PROGRAMS = (
    ("_prefill_row_nosample", "chunk"), ("_prefill_row", "prefill"),
    ("_decode_scan", "decode"), ("_decode_step", "decode"),
)

Event = Tuple[str, int, int, dict]  # name, start_ns, duration_ns, stats
Interval = Tuple[int, int]


def short_op_name(text: str) -> str:
    """``%x.1 = f32[2]{0} custom-call(...)`` → ``custom-call:x.1``; a name
    that is not HLO text is kept."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text
    found = _OPCODE.search(rhs)
    return f"{found.group(1) if found else '?'}:{lhs.lstrip('%')}"


def read_planes(path: str) -> List[dict]:
    """Device planes (their ``XLA Ops`` / ``XLA Modules`` lines) and the host
    plane's lines that carry the engine's annotations, as plain data."""
    import jax

    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                (e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats))
                for e in line.events
                if device or e.name == TICK or e.name.startswith(REGION)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def merged(intervals: Iterable[Interval]) -> List[Interval]:
    """``(start, end)`` intervals, sorted, overlaps joined."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def host_segments(planes: Sequence[dict]) -> List[Tuple[int, int, str]]:
    """The drive thread's time as ``(start, end, phase)``, no two
    overlapping: the innermost ``engine.<phase>`` region at every instant of
    an ``engine_tick``, ``admit`` where a tick is in no region. What lies
    between two ticks is in no segment (it is ``outside``)."""
    events = [
        e for p in planes if p["name"] == HOST_PLANE
        for ln in p["lines"] for e in ln["events"]
    ]
    out: List[Tuple[int, int, str]] = []
    # properly nested on one thread: a sweep with a stack of open events
    stack: List[Tuple[int, str]] = []     # (end, phase)
    cursor = 0

    def close_until(t: int) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, phase = stack.pop()
            if end > cursor:
                out.append((cursor, end, phase))
                cursor = end

    for name, start, dur, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = max(cursor, start)
        phase = "admit" if name == TICK else name[len(REGION):]
        stack.append((start + dur, phase))
    close_until(max((s + d for _, s, d, _ in events), default=0))
    return out


def split_by_phase(
    gaps: Sequence[Interval], segments: Sequence[Tuple[int, int, str]]
) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` in each host phase; what no segment covers is
    ``outside``."""
    out = {p: 0 for p in PHASES}
    i = 0
    for lo, hi in gaps:
        covered = 0
        while i < len(segments) and segments[i][1] <= lo:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < hi:
            a, b, phase = segments[j]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                out[phase] = out.get(phase, 0) + part
                covered += part
            j += 1
        out["outside"] += (hi - lo) - covered
    return out


def _line(plane: dict, name: str) -> Sequence[Event]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return ()


def _device_planes(planes: Sequence[dict]) -> List[dict]:
    """The device planes, by device number (not by file order)."""
    return sorted(
        (p for p in planes if DEVICE_PLANE.match(p["name"])),
        key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)),
    )


def _tick_starts(planes: Sequence[dict]) -> Dict[int, int]:
    """``step_num`` (the tick's id) -> start of its ``engine_tick`` event."""
    return {
        e[3].get("step_num"): e[1] for p in planes if p["name"] == HOST_PLANE
        for ln in p["lines"] for e in ln["events"] if e[0] == TICK
    }


def reduce_planes(planes: Sequence[dict]) -> dict:
    """Per device plane: the traced span, busy time as a union, idle time.
    Of device 0: every operation's summed duration and count (containers
    left out: their bodies' operations are listed), the programs run, and
    its idle time by the host phase that holds it. Of the host: the ticks
    seen and the drive thread's time by phase."""
    devices = []
    ops: collections.Counter = collections.Counter()
    counts: collections.Counter = collections.Counter()
    modules: collections.Counter = collections.Counter()
    gaps0: List[Interval] = []
    for plane in _device_planes(planes):
        events = _line(plane, OPS_LINE)
        if not events:
            continue
        busy = merged((s, s + d) for _, s, d, _ in events)
        first, last = busy[0][0], busy[-1][1]
        busy_ns = sum(b - a for a, b in busy)
        if not devices:
            for name, _, d, _ in events:
                name = short_op_name(name)
                if name.partition(":")[0] in CONTAINERS:
                    continue
                ops[name] += d
                counts[name] += 1
            for name, _, d, _ in _line(plane, MODULES_LINE):
                modules[re.sub(r"\(\d+\)$", "", name)] += d
            gaps0 = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
        devices.append({
            "plane": plane["name"], "first_ns": first, "last_ns": last,
            "busy_ns": busy_ns, "idle_ns": (last - first) - busy_ns,
        })
    segments = host_segments(planes)
    host = {p: 0 for p in PHASES}
    for a, b, phase in segments:
        host[phase] = host.get(phase, 0) + (b - a)
    if segments:  # between the first tick's start and the last one's end
        host["outside"] = (
            segments[-1][1] - segments[0][0] - sum(host.values())
        )
    ticks = sorted(_tick_starts(planes))
    return {
        "devices": devices, "ops_ns": ops, "op_counts": counts,
        "modules_ns": modules,
        "idle_by_phase_ns": split_by_phase(gaps0, segments) if devices else {},
        "host_by_phase_ns": host, "ticks": ticks,
    }


def module_kind(name: str) -> Optional[str]:
    """The kind of noted dispatch a module event of that name ran, if any."""
    for part, kind in NOTED_PROGRAMS:
        if part in name:
            return kind
    return None


def _idle_inside(busy: Sequence[Interval], starts: Sequence[int],
                 lo: int, hi: int) -> List[Interval]:
    """What of ``[lo, hi)`` the merged intervals ``busy`` (``starts``: their
    starts) leave uncovered."""
    out = []
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(busy) and busy[i][0] < hi and lo < hi:
        if busy[i][0] > lo:
            out.append((lo, busy[i][0]))
        lo = max(lo, busy[i][1])
        i += 1
    if lo < hi:
        out.append((lo, hi))
    return out


def join_dispatches(planes: Sequence[dict], ticks: Sequence[dict]) -> dict:
    """The dispatch clock beside the device trace, dispatch by dispatch.

    ``ticks`` are tick records (``/debug/ticks``) with ``dispatches`` and
    ``dispatch_clock``. Device 0's module events of the noted programs, in
    the order they ran, are matched to the ticks' noted dispatches, in the
    order they were enqueued: the first event to the dispatch of its kind
    whose ``ready_ns`` lies nearest the event's end (the clocks' offset is
    the median of ``t0_ns`` minus the ``engine_tick`` event's start over the
    ticks both hold), the rest in turn, anchored anew where the kinds part.
    The first and the last such event are left out: the trace's edges cut
    them (a 215 ms dispatch read 62 and 42 ms there: my chip run, PR 40).
    Dispatches not yet ready when the ticks were polled are left out too.

    Returns ``pairs`` (a dict a matched dispatch: ``tick``, ``index``,
    ``kind``, ``trace_ms`` the module event's duration, ``clock_ms`` the
    clock's ``device_ms``, and from the second pair on ``trace_idle_ms``, the
    time since the previous matched event's end in which no operation ran,
    beside ``clock_idle_ms``); ``kinds`` (a kind's ``n``, ``trace_ms``,
    ``clock_ms`` summed and ``worst``, the pair where the two part most);
    ``idle`` (``trace_ms`` / ``clock_ms`` over the matched span, and
    ``by_phase``: a phase's ``[trace, clock]`` ms, the trace's by
    :func:`split_by_phase`); ``unmatched`` module events and
    ``offset_ns``."""
    found = _device_planes(planes)
    out = {"pairs": [], "kinds": {}, "unmatched": 0, "offset_ns": None,
           "idle": {"trace_ms": 0.0, "clock_ms": 0.0, "by_phase": {}}}
    if not found:
        return out
    ran = sorted(
        (s, s + d, module_kind(name))
        for name, s, d, _ in _line(found[0], MODULES_LINE)
        if module_kind(name)
    )[1:-1]
    started = _tick_starts(planes)
    both = [t for t in ticks if t["tick"] in started and "t0_ns" in t]
    if not ran or not both:
        return out
    offset = out["offset_ns"] = int(statistics.median(
        t["t0_ns"] - started[t["tick"]] for t in both
    ))
    noted = [
        (t["tick"], i, d[0], c)
        for t in sorted(ticks, key=lambda t: t["tick"])
        for i, (d, c) in enumerate(
            zip(t.get("dispatches", ()), t.get("dispatch_clock", ()))
        )
        if c.get("ready_ns") is not None
    ]
    busy = merged((s, s + d) for _, s, d, _ in _line(found[0], OPS_LINE))
    busy_starts = [a for a, _ in busy]
    by_phase = out["idle"]["by_phase"] = {p: [0.0, 0.0] for p in PHASES}
    idle_gaps: List[Interval] = []
    k = None            # the dispatch the next event should be
    previous = None     # the previous event's end, if it matched in turn
    for start, end, kind in ran:
        if k is None or k >= len(noted) or noted[k][2] != kind:
            near = [
                (abs(c["ready_ns"] - (end + offset)), j)
                for j, (_, _, kd, c) in enumerate(noted)
                if kd == kind and (k is None or j >= k)
            ]
            if not near:
                out["unmatched"] += 1
                previous = None
                continue
            k, previous = min(near)[1], None
        tick, index, _, c = noted[k]
        pair = {
            "tick": tick, "index": index, "kind": kind,
            "trace_ms": (end - start) / 1e6, "clock_ms": c["device_ms"],
        }
        if previous is not None:
            gaps = _idle_inside(busy, busy_starts, previous, start)
            idle_gaps.extend(gaps)
            pair["trace_idle_ms"] = sum(b - a for a, b in gaps) / 1e6
            pair["clock_idle_ms"] = c["idle_ms"]
            out["idle"]["trace_ms"] += pair["trace_idle_ms"]
            out["idle"]["clock_ms"] += c["idle_ms"]
            for phase, ms in c.get("idle_phase_ms", {}).items():
                by_phase[phase][1] += ms
        out["pairs"].append(pair)
        previous, k = end, k + 1
    for pair in out["pairs"]:
        kind = out["kinds"].setdefault(pair["kind"], {
            "n": 0, "trace_ms": 0.0, "clock_ms": 0.0, "worst": pair,
        })
        kind["n"] += 1
        kind["trace_ms"] += pair["trace_ms"]
        kind["clock_ms"] += pair["clock_ms"]
        if abs(pair["clock_ms"] - pair["trace_ms"]) > abs(
            kind["worst"]["clock_ms"] - kind["worst"]["trace_ms"]
        ):
            kind["worst"] = pair
    # the trace's side: the same gaps, by the phase that holds each instant
    for phase, ns in split_by_phase(idle_gaps, host_segments(planes)).items():
        by_phase[phase][0] = ns / 1e6
    return out


def aggregate(path: str) -> dict:
    """:func:`reduce_planes` of one ``*.xplane.pb``."""
    return reduce_planes(read_planes(path))


def find_xplane(trace_dir: str) -> str:
    """Locate the ``*.xplane.pb`` under a ``jax.profiler.trace`` output dir."""
    hits = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not hits:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


def describe(path: str, per_line: int = 3, like: Sequence[str] = ()) -> List[str]:
    """What a trace calls things: every plane and line with its event count
    and a few events (name, duration, stats), and every distinct event whose
    name or stats hold one of the words in ``like`` — for finding where a
    kernel's ``name=``, a ``jax.named_scope`` or an annotation landed."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            seen = set()
            for e in events:
                key = e.name.partition("(")[0][:60]
                if key in seen:
                    continue
                stats = {k: str(v)[:200] for k, v in dict(e.stats).items()}
                text = e.name + " ".join(stats.values())
                if len(seen) >= per_line and not any(w in text for w in like):
                    continue
                seen.add(key)
                out.append(
                    f"    {e.name[:200]!r} {int(e.duration_ns)} ns {stats}"
                )
    return out
