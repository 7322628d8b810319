"""The sparse decode kernel's share of its roofline: the least time the chip
could take for the calls of ``sparse_paged_fused_attention`` in the trace
(the larger of their bytes over the HBM peak and their operations over the
bf16 peak) over the time the trace shows for them. The pattern of
``paged_decode_attn_roofline_pct``: time and count from ``kernels_device0``
(one event is one layer of one decode step), what a call needs from the
decode dispatches of the tick records of the same span, whose fourth entry
is the selection's census ``(selected, live)`` keys over the dispatch's rows
and steps (``plan.note_dispatch``), and the two counts checked against each
other. Bytes (``benchmark/kernels/sparse_paged_fused_attention.py``): K, V
and scale rows of the SELECTED positions and the index keys of the live
ones; the kernel as built reads every live page and the index keys are read
by the scoring before it, so the share reads low. A program without the
kernel's name or the census (the parent of PR 32) gives nothing.
"""

from benchmark import peaks
from benchmark.kernels import sparse_paged_fused_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "sparse_paged_fused_attention"


def read(run):
    trace = run.closed.get("trace")
    span = run.closed.get("trace_epoch_s")
    seen = (trace or {}).get("kernels_device0", {}).get(KERNEL)
    if not seen or not seen["sum_s"] or not span or len(span) != 2:
        return None
    decodes = [
        d
        for t in run.ticks.values()
        if span[0] <= t["t0_ns"] / 1e9 < span[1]
        for d in t.get("dispatches", ())
        if d[0] == "decode" and len(d) > 3 and d[3]
    ]
    steps = sum(d[1][1] for d in decodes)
    if not steps:
        return None
    layers = run.conf["num_hidden_layers"]
    edges = 2 * layers * max(d[1][1] for d in decodes)
    if abs(steps * layers - seen["count"]) > edges + 0.1 * seen["count"]:
        return None
    # a dispatch's census is over its steps: a call is one step's share
    selected = sum(d[3][0] for d in decodes) / steps
    live = sum(d[3][1] for d in decodes) / steps
    peak = peaks.peaks_for(run.device["kind"])
    least_s = seen["count"] * max(
        kernel.bytes_read(run.conf, selected, live) / peak["hbm_bytes_per_s"],
        kernel.operations(run.conf, selected, live) / peak["bf16_flops"],
    )
    return 100.0 * least_s / seen["sum_s"]
