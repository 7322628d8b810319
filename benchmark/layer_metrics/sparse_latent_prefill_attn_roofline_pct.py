"""The sparse latent prefill kernel's share of its roofline: the least time
the chip could take for the calls of ``sparse_latent_ragged_paged_attention``
in the trace over the time the trace shows for them. The pattern of
``sparse_prefill_attn_roofline_pct``: time and count from
``kernels_device0`` (one event is one layer of one prefill-family dispatch,
a whole prompt or a chunk); what a call needs from the dispatches the device
ended inside the same span (``benchmark/clocked.py``: by the dispatch clock's
ready stamp, since this cell's device runs seconds behind its host), whose
records are ``(kind, (rows, pad width), valid tokens,
(selected, live))``, the last the selection's census over the dispatch's
valid queries (``plan.note_dispatch``), counted by ``benchmark/kernels/
sparse_latent_ragged_paged_attention.py``, a scoring layer's call (the share
of the layers that ``indexer_types`` marks ``full``) with the indexer's
work and a reusing layer's without. A chunk's context is taken from its
pairs: ``live / valid`` is its queries' mean context, and its last query's
is about half a chunk more. Nothing is returned where the records' calls
(dispatches x layers) and the trace's events differ by more than a dispatch
at either end and a tenth. The kernel as built is the ragged kernel under a
mask: it computes every live tile, selected or not, so the share reads low
by about context / topk.
"""

from benchmark import clocked, peaks
from benchmark.kernels import sparse_latent_ragged_paged_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "sparse_latent_ragged_paged_attention"


def read(run):
    trace = run.closed.get("trace")
    span = run.closed.get("trace_epoch_s")
    seen = (trace or {}).get("kernels_device0", {}).get(KERNEL)
    kinds = run.conf.get("indexer_types")
    if not seen or not seen["sum_s"] or not span or len(span) != 2 or not kinds:
        return None
    found = [
        d for d in clocked.dispatches_in_span(run, span)
        if d[0] != "decode" and d[2] and len(d) > 3 and d[3]
    ]
    if not found:
        return None
    layers = len(kinds)
    if abs(len(found) * layers - seen["count"]) > 2 * layers + 0.1 * seen["count"]:
        return None
    peak = peaks.peaks_for(run.device["kind"])
    query_bytes = 4.0 if run.conf["serve"]["dtype"] == "float32" else 2.0

    def least(d, scoring):
        return max(
            kernel.bytes_read(
                run.conf, d[2], d[3][1] / d[2] + d[2] / 2, scoring, query_bytes
            ) / peak["hbm_bytes_per_s"],
            kernel.operations(run.conf, d[3][0], d[3][1], scoring)
            / peak["bf16_flops"],
        )

    full = kinds.count("full") / layers
    least_s = sum(
        full * least(d, True) + (1 - full) * least(d, False) for d in found
    ) * seen["count"] / len(found)
    return 100.0 * least_s / seen["sum_s"]
