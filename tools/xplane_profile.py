"""CLI over ``distributed_llm_inference_tpu.utils.xplane``: what a
``jax.profiler`` trace's ``*.xplane.pb`` says of the device's time, and of the
host's while the device waited.

    python tools/xplane_profile.py <file.xplane.pb | trace dir> [--describe [word ...]]
    python tools/xplane_profile.py <file.xplane.pb | trace dir> --ticks <ticks.json> [--all]
    python tools/xplane_profile.py <file.xplane.pb | trace dir> --inside <word> [--top N]

``--describe`` lists every plane and line with a few events and their stats
instead, and every distinct event that holds one of the words: where a
kernel's ``name=``, a ``jax.named_scope`` or the engine's ``engine_tick`` /
``engine.<phase>`` annotations landed.

``--ticks`` joins the engine's dispatch clock to the trace
(``xplane.join_dispatches``): ``ticks.json`` is a ``/debug/ticks`` body (or
its list of records) polled while the trace ran. It prints, a kind of
dispatch, the device seconds the clock counted beside the trace's module
events for the same dispatches and the dispatch where the two part most, and
the device's idle time inside the gaps, in all and by host phase, the clock's
beside the trace's; ``--all`` adds a line a dispatch. Where the two part, the
program's numbers (``engine_device_seconds_*``, ``engine_device_idle_*``) are
not the device's: this is the tool that says so.

``--inside`` lists device 0's operations inside the executions of every
program whose name holds the word (``_prefill_row``), a compiled shape at a
time (``ops_inside``): ms a run, events a run and the operation's HLO
text, which carries the shapes that say what it is.
"""
import bisect
import collections
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_llm_inference_tpu.utils import xplane  # noqa: E402


def report(agg, top=40):
    """The lines ``main`` prints for one :func:`xplane.aggregate` result."""
    out = []
    for d in agg["devices"]:
        span = d["last_ns"] - d["first_ns"]
        out.append(
            f"{d['plane']}: busy {d['busy_ns'] / 1e6:.2f} ms of "
            f"{span / 1e6:.2f} ms ({100.0 * d['idle_ns'] / span:.1f}% idle)"
        )
    if agg["ticks"]:
        out.append(
            f"host: {len(agg['ticks'])} engine ticks "
            f"(step_num {agg['ticks'][0]}..{agg['ticks'][-1]}); "
            "phase: drive thread ms / idle ms of device 0 inside it"
        )
        for phase, ns in agg["host_by_phase_ns"].items():
            idle = agg["idle_by_phase_ns"].get(phase, 0)
            out.append(f"  {phase:<9}{ns / 1e6:10.2f} {idle / 1e6:10.2f}")
    for name, ns in agg["modules_ns"].most_common(top):
        out.append(f"{ns / 1e6:9.3f} ms  module {name}")
    for name, ns in agg["ops_ns"].most_common(top):
        out.append(f"{ns / 1e6:9.3f} ms  x{agg['op_counts'][name]:<5} {name[:120]}")
    return out


def clock_report(joined, every=False):
    """The lines ``--ticks`` prints for one :func:`xplane.join_dispatches`
    result."""
    pairs = joined["pairs"]
    if not pairs:
        return ["no noted dispatch of these ticks ran in this trace"]
    out = [
        f"{len(pairs)} dispatches matched, {joined['unmatched']} module "
        f"events unmatched; ticks {pairs[0]['tick']}..{pairs[-1]['tick']}; "
        f"clock - trace {joined['offset_ns']} ns",
        "kind        n   clock ms   trace ms  clock/trace   worst: "
        "tick.index clock | trace ms",
    ]
    for kind, k in sorted(joined["kinds"].items()):
        w = k["worst"]
        out.append(
            f"{kind:<8}{k['n']:>5}{k['clock_ms']:>11.3f}{k['trace_ms']:>11.3f}"
            f"{k['clock_ms'] / k['trace_ms']:>13.4f}   "
            f"{w['tick']}.{w['index']} {w['clock_ms']:.3f} | {w['trace_ms']:.3f}"
        )
    idle = joined["idle"]
    ticks = max(1, pairs[-1]["tick"] - pairs[0]["tick"])
    out.append(
        f"idle in the gaps: clock {idle['clock_ms']:.3f} ms, trace "
        f"{idle['trace_ms']:.3f} ms; by phase, ms (a tick): clock | trace"
    )
    for phase, (trace_ms, clock_ms) in idle["by_phase"].items():
        out.append(
            f"  {phase:<9}{clock_ms:>10.3f} ({clock_ms / ticks:.3f}) |"
            f"{trace_ms:>10.3f} ({trace_ms / ticks:.3f})"
        )
    if every:
        out.append("tick.index kind: device clock | trace ms; idle before it clock | trace ms")
        for p in pairs:
            line = (f"{p['tick']}.{p['index']} {p['kind']}: "
                    f"{p['clock_ms']:.3f} | {p['trace_ms']:.3f}")
            if "trace_idle_ms" in p:
                line += f"; {p['clock_idle_ms']:.3f} | {p['trace_idle_ms']:.3f}"
            out.append(line)
    return out


def ops_inside(planes, word):
    """Device 0's operations INSIDE the executions of every program whose
    module event's name holds ``word``, a program at a time (a module event
    is named ``<jit name>(<fingerprint>)``: one a compiled shape, so a
    prefill's pad widths come apart). Of each: ``runs``, ``ns`` (the module
    events' durations summed), and an operation's summed duration
    (``ops_ns``), count (``op_counts``) and whole HLO text as the trace names
    it (``text``: the result's shape, the opcode, the operands), containers
    left out as in ``xplane.reduce_planes``."""
    found = xplane._device_planes(planes)
    if not found:
        return {}
    ops = sorted(xplane._line(found[0], xplane.OPS_LINE), key=lambda e: e[1])
    starts = [e[1] for e in ops]
    out = {}
    for name, start, dur, _ in xplane._line(found[0], xplane.MODULES_LINE):
        if word not in name:
            continue
        prog = out.setdefault(name, {
            "runs": 0, "ns": 0, "ops_ns": collections.Counter(),
            "op_counts": collections.Counter(), "text": {},
        })
        prog["runs"] += 1
        prog["ns"] += dur
        lo = bisect.bisect_left(starts, start)
        for text, _, d, _ in ops[lo:bisect.bisect_left(starts, start + dur)]:
            op = xplane.short_op_name(text)
            if op.partition(":")[0] in xplane.CONTAINERS:
                continue
            prog["ops_ns"][op] += d
            prog["op_counts"][op] += 1
            prog["text"].setdefault(op, text)
    return out


def inside_report(programs, top=60, width=260):
    """The lines ``--inside`` prints for one :func:`ops_inside` result."""
    out = []
    for name, p in sorted(programs.items(), key=lambda kv: -kv[1]["ns"]):
        runs = p["runs"]
        listed = sum(p["ops_ns"].values())
        out.append(
            f"program {name}: {runs} runs, {p['ns'] / runs / 1e6:.3f} ms a "
            f"run, its operations {listed / runs / 1e6:.3f} ms a run"
        )
        for op, ns in p["ops_ns"].most_common(top):
            out.append(
                f"{ns / runs / 1e6:9.4f} ms  x{p['op_counts'][op] / runs:<6g} "
                f"{p['text'][op][:width]}"
            )
    return out


def main(argv):
    path = argv[0]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    if "--ticks" in argv[1:]:
        with open(argv[argv.index("--ticks") + 1]) as f:
            ticks = json.load(f)
        if isinstance(ticks, dict):
            ticks = ticks["ticks"]
        joined = xplane.join_dispatches(xplane.read_planes(path), ticks)
        print("\n".join(clock_report(joined, every="--all" in argv[1:])))
    elif "--inside" in argv[1:]:
        top = int(argv[argv.index("--top") + 1]) if "--top" in argv[1:] else 60
        programs = ops_inside(
            xplane.read_planes(path), argv[argv.index("--inside") + 1]
        )
        print("\n".join(inside_report(programs, top)))
    elif "--describe" in argv[1:]:
        words = [a for a in argv[1:] if a != "--describe"]
        print("\n".join(xplane.describe(path, like=words)))
    else:
        print("\n".join(report(xplane.aggregate(path))))


if __name__ == "__main__":
    main(sys.argv[1:])
