"""The paged decode kernel's share of its roofline: the least time the chip
could take for the calls of ``quantized_paged_fused_attention`` in the trace
(the larger of their bytes over the HBM peak and their operations over the
bf16 peak, the type its matmuls run in: the bytes, by a factor of 30 at
Mistral's widths) over the time the trace shows for them.

Both over the SAME span, the profiler trace's. Time: ``kernels_device0``,
the summed device durations of the kernel's events, and their count: one
event is one layer of one decode step. Bytes and operations of one call:
``benchmark/kernels/quantized_paged_fused_attention.py`` for the live
positions it sweeps, taken from the flight recorder's tick records whose
tick started inside the trace (``trace_epoch_s``, the same clock): a decode
dispatch's census (``dispatches``: kind, (rows, steps, table width), live
positions) is the lengths of its rows at the dispatch, and the page pool is
read-only for all of a dispatch's steps (the write-behind tail holds the new
tokens), so every step of it sweeps exactly that. A dispatch of K steps is
K calls a layer, so the mean a call weighs each dispatch by its steps.

The two sides are checked against each other: the tick records' calls
(steps x layers, summed) and the trace's events of the kernel. The device
runs a dispatch about one tick after the host issued it, so a dispatch at
either end of the span may be in one count and not the other; beyond that
and a tenth of the events, the decode dispatches of the span did not all run
this kernel (another table width, another cache), the mean is of other
calls, and the reader returns nothing rather than a share of the wrong
bytes. Within it the events are the count and the records give the mean;
rows grow by 2% a tick in ``mistral-7b.reason``, which is the error of this
number. The census counts a row's whole length: in a cell whose contexts
pass the sliding window it would count positions the kernel skips
(``mistral-7b.reason`` stays under 2304 of 4096).
"""

from benchmark import peaks
from benchmark.kernels import quantized_paged_fused_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "quantized_paged_fused_attention"


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
        if d[0] == "decode" and d[2] is not None
    ]
    steps = sum(d[1][1] for d in decodes)
    if not steps:
        return None
    layers = run.conf["num_hidden_layers"]
    edges = 2 * layers * max(d[1][1] for d in decodes)
    if abs(steps * layers - seen["count"]) > edges + 0.1 * seen["count"]:
        return None
    positions = sum(d[1][1] * d[2] for d in decodes) / steps
    peak = peaks.peaks_for(run.device["kind"])
    least_s = seen["count"] * max(
        kernel.bytes_read(run.conf, positions) / peak["hbm_bytes_per_s"],
        kernel.operations(run.conf, positions) / peak["bf16_flops"],
    )
    return 100.0 * least_s / seen["sum_s"]
