"""The paged decode kernel's share of its roofline in a looped stack:
``paged_decode_attn_roofline_pct``'s reading of
``quantized_paged_fused_attention`` (the same kernel and the same count of
one call's bytes and operations, imported:
``benchmark/kernels/quantized_paged_fused_attention.py``) with the events
checked against ``total_ut_steps x num_hidden_layers`` calls a decode step,
one a CACHE layer, which that reader's ``num_hidden_layers`` cannot count:
192 calls a step at a group of ONE query a key-value head (16 key-value
heads, twice Mistral's bytes a position a layer). A dispatch's census is its
rows' contexts at the dispatch, the pool being read-only through its steps;
the reader returns nothing where the records' calls and the trace's events
disagree by more than a dispatch at either end and a tenth, as that one
does. A configuration without ``total_ut_steps`` gives nothing."""

from benchmark import peaks
from benchmark.kernels import quantized_paged_fused_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "quantized_paged_fused_attention"


def read(run):
    laps = run.conf.get("total_ut_steps")
    trace = run.closed.get("trace")
    span = run.closed.get("trace_epoch_s")
    seen = (trace or {}).get("kernels_device0", {}).get(KERNEL)
    if not laps or not seen or not seen["sum_s"] or not span or len(span) != 2:
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
    layers = int(laps) * run.conf["num_hidden_layers"]
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
