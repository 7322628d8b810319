"""The window layers' prefill kernel's share of its roofline: the least time
the chip could take for the calls of ``window_ragged_paged_attention`` in the
trace over the time the trace shows for them. Time and count from
``kernels_device0`` (one event is one WINDOW layer of one prefill-family
dispatch: a prompt, a group, a chunk); what a call needs from those
dispatches in the tick records of the same span: ``(kind, (rows, pad
width), valid tokens, None, (seen, in context))``, the last the window's
census over the dispatch's valid queries (``plan.note_dispatch``: a query at
position ``t`` keeps ``min(window, t + 1)`` of ``t + 1`` pairs), counted by
``benchmark/kernels/window_ragged_paged_attention.py``. Nothing is returned
where the records' calls (dispatches x window layers) and the trace's events
differ by more than a dispatch at either end and a tenth. A program without
the kernel's name or the census (the parent of PR 35) gives nothing.
"""

from benchmark import peaks
from benchmark.kernels import window_ragged_paged_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "window_ragged_paged_attention"


def read(run):
    trace = run.closed.get("trace")
    span = run.closed.get("trace_epoch_s")
    seen = (trace or {}).get("kernels_device0", {}).get(KERNEL)
    if not seen or not seen["sum_s"] or not span or len(span) != 2:
        return None
    found = [
        d
        for t in run.ticks.values()
        if span[0] <= t["t0_ns"] / 1e9 < span[1]
        for d in t.get("dispatches", ())
        if d[0] != "decode" and d[2] and len(d) > 4 and d[4]
    ]
    layers = list(run.conf.get("layer_types", ())).count("sliding_attention")
    if not found or not layers:
        return None
    if abs(len(found) * layers - seen["count"]) > 2 * layers + 0.1 * seen["count"]:
        return None
    peak = peaks.peaks_for(run.device["kind"])
    query_bytes = 4.0 if run.conf["serve"]["dtype"] == "float32" else 2.0
    window = run.conf["sliding_window"]
    least_s = sum(
        max(
            kernel.bytes_read(
                run.conf, d[1][0], d[2], d[1][0] * (window - 1), query_bytes
            ) / peak["hbm_bytes_per_s"],
            kernel.operations(run.conf, d[4][0]) / peak["bf16_flops"],
        )
        for d in found
    ) * seen["count"] / len(found)
    return 100.0 * least_s / seen["sum_s"]
