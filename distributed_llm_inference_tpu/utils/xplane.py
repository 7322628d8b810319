"""Minimal xplane.pb parser: aggregate TPU device-op durations from a
``jax.profiler`` trace.

The reference has no profiling story at all (SURVEY §5.1 — its only
observability is two ``print`` calls in the weight loader,
``/root/reference/distributed_llm_inference/utils/model.py:61,82``); here the
profiler is a first-class tool: ``tools/xplane_profile.py`` drives this module
interactively, and ``bench.py`` uses :func:`device_time_ps` to report the
device-only component of TTFT (a synchronous wall-clock measurement also
counts the host's dispatch and fetch; how much that is on a directly
attached chip has not been measured).

Durations in the xplane protobuf are picoseconds.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Counter, Tuple


def read_varint(buf: bytes, i: int):
    r = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7


def fields(buf: bytes):
    """Iterate (field_number, value) over a serialized protobuf message."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = read_varint(buf, i)
        fnum, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = read_varint(buf, i)
            yield fnum, v
        elif wt == 2:
            ln, i = read_varint(buf, i)
            yield fnum, buf[i : i + ln]
            i += ln
        elif wt == 5:
            yield fnum, buf[i : i + 4]
            i += 4
        elif wt == 1:
            yield fnum, buf[i : i + 8]
            i += 8
        else:
            raise ValueError(f"wire type {wt}")


def aggregate(path: str, device: str = "/device:TPU:0") -> Tuple[
    int, Counter, Counter
]:
    """Parse one ``*.xplane.pb`` and sum per-op durations on ``device``.

    Returns ``(total_ps, dur_ps_by_op, count_by_op)``. Umbrella lines
    ("Steps", "XLA Modules") are excluded so the total counts each op once.
    """
    space = open(path, "rb").read()
    for fnum, plane_buf in fields(space):
        if fnum != 1:
            continue
        name = None
        meta = {}
        lines = []
        for pf, pv in fields(plane_buf):
            if pf == 2 and isinstance(pv, bytes):
                name = pv.decode(errors="replace")
            elif pf == 4:  # event_metadata map entry
                mid, mname = None, ""
                for mf, mv in fields(pv):
                    if mf == 1:
                        mid = mv
                    elif mf == 2:
                        for ef, ev in fields(mv):
                            if ef == 2 and isinstance(ev, bytes):
                                mname = ev.decode(errors="replace")
                meta[mid] = mname
            elif pf == 3:
                lines.append(pv)
        if name != device:
            continue
        agg: Counter = collections.Counter()
        cnt: Counter = collections.Counter()
        for line_buf in lines:
            lname = ""
            evs = []
            for lf, lv in fields(line_buf):
                if lf == 2 and isinstance(lv, bytes):
                    try:
                        lname = lv.decode()
                    except Exception:
                        lname = repr(lv)
                elif lf == 4:
                    evs.append(lv)
            if "Step" in lname or "Modules" in lname:
                continue  # whole-program umbrella lines
            for ev in evs:
                mid, dur = None, 0
                for ef, v in fields(ev):
                    if ef == 1:
                        mid = v
                    elif ef == 3:
                        dur = v
                agg[meta.get(mid, f"id{mid}")] += dur
                cnt[meta.get(mid, f"id{mid}")] += 1
        return sum(agg.values()), agg, cnt
    return 0, collections.Counter(), collections.Counter()


def find_xplane(trace_dir: str) -> str:
    """Locate the ``*.xplane.pb`` under a ``jax.profiler.trace`` output dir."""
    hits = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not hits:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


def device_time_ps(trace_dir: str, device: str = "/device:TPU:0") -> int:
    """Total device-op time (picoseconds) recorded in a trace directory."""
    total, _, _ = aggregate(find_xplane(trace_dir), device)
    return total
