"""From a ``jax.profiler`` trace to the numbers the benchmark reports.

The trace is read with ``jax.profiler.ProfileData`` into a plain structure
(``planes`` → ``lines`` → ``(name, start_ns, duration_ns)`` events), so the
reduction below runs the same on a recorded sample (``sample_trace.json``,
checked by the tests) as on a fresh trace. What it knows of a TPU trace: a
chip is a plane named ``/device:TPU:<n>``; its line ``XLA Ops`` has one event
for every operation the chip ran, ``XLA Modules`` one for every execution of
a compiled program, named ``<jit name>(<fingerprint>)``. An operation's event
is named by its whole HLO text (``%fusion.3 = bf16[...] fusion(...)``); it is
kept here as ``<opcode>:<result name>`` (``fusion:fusion.3``,
``custom-call:closed_call.17``), which is all the reduction reads.

* busy time is the UNION of the operations' intervals, never the sum of
  their durations: nested and overlapping events would count twice;
* every device plane is reduced by itself and the busy time averaged;
* a share (custom calls, all-reduces) is that subset's union over busy.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
Event = Tuple[str, int, int]
#: operations that only hold others (their events enclose their bodies')
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"([a-z][a-z\-]*)\(")


def short_op_name(text: str) -> str:
    """``%x.1 = f32[2]{0} custom-call(...)`` → ``custom-call:x.1``; a name
    that is not HLO text is kept."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text
    found = _OPCODE.search(rhs)
    return f"{found.group(1) if found else '?'}:{lhs.lstrip('%')}"


def read_xplane(path: str) -> List[dict]:
    """Device planes of an ``.xplane.pb`` file as plain data."""
    import jax

    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = [{
            "name": line.name,
            "events": [
                (short_op_name(e.name) if line.name == OPS_LINE else e.name,
                 int(e.start_ns), int(e.duration_ns))
                for e in line.events
            ],
        } for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def trace_files(trace_dir: str) -> List[str]:
    """The ``.xplane.pb`` files the profiler wrote under ``trace_dir``."""
    return glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    ))


def trace_missing(trace_dir: str, open_profile=None) -> Optional[str]:
    """Which part of a trace just stopped is missing, or ``None`` where the
    file is there and a device plane has at least one operation: what
    ``reduce_trace`` needs to return anything. Cheap, because it runs inside
    the window: the file is opened, no line is walked past its first event.
    ``open_profile`` stands in for ``ProfileData.from_file`` in the tests."""
    files = trace_files(trace_dir)
    if not files:
        return "no *.xplane.pb file was written"
    if open_profile is None:
        import jax

        open_profile = jax.profiler.ProfileData.from_file
    devices = [
        p for p in open_profile(files[0]).planes if DEVICE_PLANE.match(p.name)
    ]
    if not devices:
        return "the file holds no device plane"
    for plane in devices:
        for line in plane.lines:
            if line.name == OPS_LINE and next(iter(line.events), None) is not None:
                return None
    return "no device plane has an operation on its XLA Ops line"


def read_sample(path: str) -> List[dict]:
    with open(path) as f:
        planes = json.load(f)["planes"]
    for plane in planes:
        for line in plane["lines"]:
            line["events"] = [tuple(e) for e in line["events"]]
    return planes


def write_sample(planes: List[dict], path: str, first_ns: int, span_ns: int) -> None:
    """A cut of ``planes`` (events that start inside the span) small enough
    to keep beside the reduction as its test's input."""
    cut = [{
        "name": p["name"],
        "lines": [{
            "name": ln["name"],
            "events": [
                e for e in ln["events"]
                if first_ns <= e[1] < first_ns + span_ns
            ],
        } for ln in p["lines"]],
    } for p in planes]
    with open(path, "w") as f:
        json.dump({"planes": cut}, f, separators=(",", ":"))


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Total length covered by ``(start, end)`` intervals, overlaps once."""
    total, cur_lo, cur_hi = 0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def merged(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def _line(plane: dict, name: str) -> Sequence[Event]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return ()


def module_name(event_name: str) -> str:
    """``jit__decode_scan(123456)`` → ``jit__decode_scan``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def opcode(name: str) -> str:
    return name.partition(":")[0]


def is_custom_call(name: str) -> bool:
    return opcode(name) == "custom-call"


def is_all_reduce(name: str) -> bool:
    return opcode(name).startswith("all-reduce")


def kernel_name(name: str) -> str:
    """``custom-call:quantized_paged_fused_attention.5`` → the kernel's own
    name, ``quantized_paged_fused_attention``: a ``pallas_call``'s ``name=``
    is its operation's result name, and the suffix is XLA's instance
    number, which moves with every recompile. XLA's own custom calls
    (``custom-call.14``, nanoseconds each) fold into ``custom-call``."""
    return re.sub(r"\.\d+$", "", name.partition(":")[2].lstrip("%"))


def reduce_trace(planes: List[dict]) -> Optional[dict]:
    """Everything the per-layer metrics read from a trace, or ``None`` where
    no operation ran on any device plane."""
    per_device = []
    for plane in planes:
        ops = _line(plane, OPS_LINE)
        if not ops:
            continue
        spans = [(s, s + d) for _, s, d in ops]
        busy = union_ns(spans)
        by_op: Dict[str, int] = {}
        for name, _, d in ops:
            if opcode(name) in CONTAINERS:
                continue            # its body's operations are listed
            by_op[name] = by_op.get(name, 0) + d
        kernels: Dict[str, List[int]] = {}
        for name, _, d in ops:
            if is_custom_call(name):
                seen = kernels.setdefault(kernel_name(name), [0, 0])
                seen[0] += 1
                seen[1] += d
        modules: Dict[str, List[int]] = {}
        for name, _, d in _line(plane, MODULES_LINE):
            modules.setdefault(module_name(name), []).append(d)
        per_device.append({
            "plane": plane["name"],
            "first_ns": min(s for s, _ in spans),
            "last_ns": max(e for _, e in spans),
            "busy_ns": busy,
            "custom_call_ns": union_ns(
                (s, s + d) for n, s, d in ops if is_custom_call(n)
            ),
            "all_reduce_ns": union_ns(
                (s, s + d) for n, s, d in ops if is_all_reduce(n)
            ),
            "ops_by_time": sorted(by_op.items(), key=lambda kv: -kv[1]),
            "kernels": kernels,
            "modules": modules,
            "busy_intervals": merged(spans),
            "module_events": sorted(
                (s, s + d, module_name(n))
                for n, s, d in _line(plane, MODULES_LINE)
            ),
        })
    if not per_device:
        return None
    # One traced span for every chip: a chip that started late was idle.
    first = min(d["first_ns"] for d in per_device)
    last = max(d["last_ns"] for d in per_device)
    n = len(per_device)
    dev0 = per_device[0]
    # A gap is labelled by the program that runs next on the chip: until
    # host spans share the profiler's clock that is all the trace knows of
    # what the host was doing meanwhile.
    starts = [lo for lo, _, _ in dev0["module_events"]]
    gaps = []
    for (_, a_hi), (b_lo, _) in zip(
        dev0["busy_intervals"], dev0["busy_intervals"][1:]
    ):
        i = bisect.bisect_left(starts, a_hi)
        inside = i > 0 and dev0["module_events"][i - 1][1] >= b_lo
        if inside:
            label = "inside " + dev0["module_events"][i - 1][2]
        elif i < len(starts):
            label = "before " + dev0["module_events"][i][2]
        else:
            label = "before the end of the trace"
        gaps.append((label, b_lo - a_hi))
    by_gap: Dict[str, int] = {}
    for label, ns in gaps:
        by_gap[label] = by_gap.get(label, 0) + ns
    return {
        "devices": n,
        "window_s": (last - first) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_device) / n / 1e9,
        "custom_call_s": sum(d["custom_call_ns"] for d in per_device) / n / 1e9,
        "all_reduce_device0_s": dev0["all_reduce_ns"] / 1e9,
        "busy_device0_s": dev0["busy_ns"] / 1e9,
        "modules_device0_s": {
            k: [x / 1e9 for x in v] for k, v in dev0["modules"].items()
        },
        "device_ops": [
            [k, v / 1e9] for k, v in dev0["ops_by_time"][:10]
        ],
        # every custom call by its kernel's name, however small: what a
        # ``<kernel>_roofline`` reader divides its bytes and operations by
        "kernels_device0": {
            k: {"count": n, "sum_s": ns / 1e9}
            for k, (n, ns) in sorted(dev0["kernels"].items())
        },
        "idle_gaps": [
            [k, v / 1e9]
            for k, v in sorted(by_gap.items(), key=lambda kv: -kv[1])[:10]
        ],
        "longest_gap_s": max((ns for _, ns in gaps), default=0) / 1e9,
    }
