"""The latent decode kernel's share of its roofline: the least time the chip
could take for the calls of ``quantized_latent_paged_attention`` in the
trace (the larger of their bytes over the HBM peak and their operations
over the bf16 peak) over the time the trace shows for them. The pattern of
``paged_decode_attn_roofline_pct``: time and count from ``kernels_device0``
(one event is one layer of one decode step), the live positions of a call
from the decode dispatches of the tick records of the same span (K steps a
dispatch are K calls a layer: since PR 31 an int8 latent engine runs the
fused 16-step scan, K = 16, as every one-chip cell does), and the two counts
checked against each other: a dispatch at either end of the span and a tenth
of the events may differ, beyond that nothing is returned. The bytes are the
live positions' latents ONCE (``benchmark/kernels/
quantized_latent_paged_attention.py``), which is how the fused one-plane
form now reads them: a row's live pages, fetched once as pipelined blocks of
8 pages (the name is the fused form's since PR 31; the ``(slots, table
width)`` grid form that took the pool as K and again as V is traced as
``quantized_latent_paged_grid_attention`` and no cell runs it). A program
without this kernel's name (the parent of PR 26) gives nothing.
"""

from benchmark import peaks
from benchmark.kernels import quantized_latent_paged_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "quantized_latent_paged_attention"


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
