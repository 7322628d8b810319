"""The int8 prefill kernel's share of its roofline: the least time the chip
could take for the calls of ``quantized_ragged_paged_attention`` in the trace
over the time the trace shows for them. Time and count from
``kernels_device0`` (one event is one layer of one prefill dispatch); what a
call needs from the prefill dispatches of the tick records of the same span
(``dispatches``: kind, (rows, pad width), valid tokens), each counted by
``benchmark/kernels/quantized_ragged_paged_attention.py``: causal operations
over the VALID positions (the pad is the waste this share shows) over the
bf16 peak, or its bytes over the HBM peak, whichever is larger.

Nothing is returned where the span holds a chunked dispatch (its queries
start past 0 and the record does not say where: ``mixtral-8x7b-8l.rag`` is
not listed), or where the records' calls (dispatches x layers) and the
trace's events differ by more than a dispatch at either end and a tenth.
``latent_prefill_attn_roofline_pct.py`` is the same reading of the latent
sibling: a later ``benchmark`` PR may fold the two.
"""

from benchmark import peaks
from benchmark.kernels import quantized_ragged_paged_attention as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "quantized_ragged_paged_attention"


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
        if d[0] != "decode" and d[2] is not None
    ]
    if not found or any(d[0] != "prefill" for d in found):
        return None
    layers = run.conf["num_hidden_layers"]
    if abs(len(found) * layers - seen["count"]) > 2 * layers + 0.1 * seen["count"]:
        return None
    peak = peaks.peaks_for(run.device["kind"])
    query_bytes = 4.0 if run.conf["serve"]["dtype"] == "float32" else 2.0
    least_s = sum(
        max(
            kernel.bytes_read(run.conf, d[1][0], d[2], query_bytes)
            / peak["hbm_bytes_per_s"],
            kernel.operations(run.conf, d[1][0], d[2]) / peak["bf16_flops"],
        )
        for d in found
    ) * seen["count"] / len(found)
    return 100.0 * least_s / seen["sum_s"]
