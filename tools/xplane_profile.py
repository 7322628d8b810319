"""CLI over ``distributed_llm_inference_tpu.utils.xplane``: what a
``jax.profiler`` trace's ``*.xplane.pb`` says of the device's time, and of the
host's while the device waited.

    python tools/xplane_profile.py <file.xplane.pb | trace dir> [--describe [word ...]]

``--describe`` lists every plane and line with a few events and their stats
instead, and every distinct event that holds one of the words: where a
kernel's ``name=``, a ``jax.named_scope`` or the engine's ``engine_tick`` /
``engine.<phase>`` annotations landed.
"""
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


def main(argv):
    path = argv[0]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    if "--describe" in argv[1:]:
        words = [a for a in argv[1:] if a != "--describe"]
        print("\n".join(xplane.describe(path, like=words)))
    else:
        print("\n".join(report(xplane.aggregate(path))))


if __name__ == "__main__":
    main(sys.argv[1:])
