"""The window layers' decode kernel's share of its roofline: the least time
the chip could take for the calls of ``window_paged_fused_attention`` in the
trace (the larger of their bytes over the HBM peak and their operations over
the bf16 peak) over the time the trace shows for them. The pattern of
``paged_decode_attn_roofline_pct``: time and count from ``kernels_device0``
(one event is one WINDOW layer of one decode step), what a call needs from
the decode dispatches of the tick records of the same span, whose fifth
entry is the window's census ``(seen, in context)`` keys over the dispatch's
rows and steps (``plan.note_dispatch``: a query at position ``t`` sees
``min(window, t + 1)``), and the two counts checked against each other.
Bytes (``benchmark/kernels/window_paged_fused_attention.py``): K, V and
scale rows of the in-window positions; the kernel fetches whole pages, so
the share reads low by up to a third, honestly. A program without the
kernel's name or the census (the parent of PR 35) gives nothing.
"""

from benchmark import peaks
from benchmark.kernels import window_paged_fused_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "window_paged_fused_attention"
LAYER_TYPE = "sliding_attention"


def census(d):
    """A decode dispatch's positions a step for this kernel's layers."""
    return d[4][0] / d[1][1] if len(d) > 4 and d[4] else None


def read(run, kernel_name=KERNEL, layer_type=LAYER_TYPE, census=census):
    trace = run.closed.get("trace")
    span = run.closed.get("trace_epoch_s")
    seen = (trace or {}).get("kernels_device0", {}).get(kernel_name)
    if not seen or not seen["sum_s"] or not span or len(span) != 2:
        return None
    decodes = [
        d
        for t in run.ticks.values()
        if span[0] <= t["t0_ns"] / 1e9 < span[1]
        for d in t.get("dispatches", ())
        if d[0] == "decode" and d[2] is not None and census(d) is not None
    ]
    steps = sum(d[1][1] for d in decodes)
    layers = list(run.conf.get("layer_types", ())).count(layer_type)
    if not steps or not layers:
        return None
    edges = 2 * layers * max(d[1][1] for d in decodes)
    if abs(steps * layers - seen["count"]) > edges + 0.1 * seen["count"]:
        return None
    # a dispatch's census is one step's: weigh each dispatch by its steps
    positions = sum(d[1][1] * census(d) for d in decodes) / steps
    peak = peaks.peaks_for(run.device["kind"])
    least_s = seen["count"] * max(
        kernel.bytes_read(run.conf, positions) / peak["hbm_bytes_per_s"],
        kernel.operations(run.conf, positions) / peak["bf16_flops"],
    )
    return 100.0 * least_s / seen["sum_s"]
