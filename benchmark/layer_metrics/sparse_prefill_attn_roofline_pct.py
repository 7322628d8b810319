"""The sparse prefill kernel's share of its roofline: the least time the
chip could take for the calls of ``sparse_ragged_paged_attention`` in the
trace over the time the trace shows for them. Time and count from
``kernels_device0`` (one event is one layer of one prefill-family dispatch,
a whole prompt or a chunk); what a call needs from those dispatches in the
tick records of the same span: ``(kind, (rows, pad width), valid tokens,
(selected, live))``, the last the selection's census over the dispatch's
valid queries (``plan.note_dispatch``: a query at position ``t`` keeps
``min(topk, t + 1)`` of ``t + 1`` pairs), counted by
``benchmark/kernels/sparse_ragged_paged_attention.py``. A chunk's context is
taken from its pairs: ``live / valid`` is its queries' mean context, and its
last query's is about half a chunk more. Nothing is returned where the
records' calls (dispatches x layers) and the trace's events differ by more
than a dispatch at either end and a tenth. The kernel as built is the ragged
kernel under a mask: it computes every live tile, selected or not, so the
share reads low by about context / topk.
"""

from benchmark import peaks
from benchmark.kernels import sparse_ragged_paged_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "sparse_ragged_paged_attention"


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
        if d[0] != "decode" and d[2] and len(d) > 3 and d[3]
    ]
    if not found:
        return None
    layers = run.conf["num_hidden_layers"]
    if abs(len(found) * layers - seen["count"]) > 2 * layers + 0.1 * seen["count"]:
        return None
    peak = peaks.peaks_for(run.device["kind"])
    query_bytes = 4.0 if run.conf["serve"]["dtype"] == "float32" else 2.0
    least_s = sum(
        max(
            kernel.bytes_read(
                run.conf, d[2], d[3][1] / d[2] + d[2] / 2, query_bytes
            ) / peak["hbm_bytes_per_s"],
            kernel.operations(run.conf, d[3][0], d[3][1]) / peak["bf16_flops"],
        )
        for d in found
    ) * seen["count"] / len(found)
    return 100.0 * least_s / seen["sum_s"]
