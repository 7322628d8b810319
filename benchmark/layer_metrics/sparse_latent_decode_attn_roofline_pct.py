"""The sparse latent decode kernel's share of its roofline: the least time
the chip could take for the calls of ``sparse_latent_paged_fused_attention``
in the trace (the larger of their bytes over the HBM peak and their
operations over the bf16 peak, a call) over the time the trace shows for
them. The pattern of ``sparse_decode_attn_roofline_pct``: time and count
from ``kernels_device0`` (one event is one layer of one decode step), what a
call needs from the decode dispatches the device ended inside the same span
(``benchmark/clocked.py``: by the dispatch clock's ready stamp, since this
cell's device runs seconds behind its host),
whose fourth entry is the selection's census ``(selected, live)`` keys over
the dispatch's rows and steps (``plan.note_dispatch``), and the two counts
checked against each other. Of the events, the share ``indexer_types`` marks
``full`` are scoring layers' calls, which also need the live context's index
keys scored; the others reuse (``benchmark/kernels/
sparse_latent_paged_fused_attention.py``). The kernel as built reads every
live page, and the index keys are read by the scoring before it, so the
share reads low. A program without the kernel's name or the census (the
parent of PR 44) gives nothing.
"""

from benchmark import clocked, peaks
from benchmark.kernels import sparse_latent_paged_fused_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "sparse_latent_paged_fused_attention"


def read(run):
    trace = run.closed.get("trace")
    span = run.closed.get("trace_epoch_s")
    seen = (trace or {}).get("kernels_device0", {}).get(KERNEL)
    kinds = run.conf.get("indexer_types")
    if not seen or not seen["sum_s"] or not span or len(span) != 2 or not kinds:
        return None
    decodes = [
        d for d in clocked.dispatches_in_span(run, span)
        if d[0] == "decode" and len(d) > 3 and d[3]
    ]
    steps = sum(d[1][1] for d in decodes)
    if not steps:
        return None
    layers = len(kinds)
    edges = 2 * layers * max(d[1][1] for d in decodes)
    if abs(steps * layers - seen["count"]) > edges + 0.1 * seen["count"]:
        return None
    # a dispatch's census is over its steps: a call is one step's share
    selected = sum(d[3][0] for d in decodes) / steps
    live = sum(d[3][1] for d in decodes) / steps
    peak = peaks.peaks_for(run.device["kind"])

    def least(scoring):
        return max(
            kernel.bytes_read(run.conf, selected, live, scoring)
            / peak["hbm_bytes_per_s"],
            kernel.operations(run.conf, selected, live, scoring)
            / peak["bf16_flops"],
        )

    full = kinds.count("full") / layers
    least_s = seen["count"] * (full * least(True) + (1 - full) * least(False))
    return 100.0 * least_s / seen["sum_s"]
