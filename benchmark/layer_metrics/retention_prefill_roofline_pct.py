"""The retention prefill kernel's share of its roofline: the least time the
chip could take for the calls of ``power_retention_prefill`` in the trace
(the larger of their operations over the bf16 peak and their bytes over the
HBM peak: the operations, by a factor of 6 at a full chunk) over the time the
trace shows for them.

Time: ``kernels_device0``, the summed device durations of the kernel's
events, and their count: one event is one layer of one prefill dispatch.
Operations and bytes of one call: ``benchmark/kernels/
power_retention_prefill.py`` for a dispatch's rows and VALID tokens, the
mean over the prefill-family dispatches of the WINDOW's tick records (the
flight recorder's census, ``dispatches``: kind, (rows, pad width), valid
tokens), as the decode kernel's reader takes the window's means. Not the
records of the trace's own seconds: the host runs ahead of a device that is
never idle here, so a tick's stamp does not say when its dispatch ran (read
that way, one traced run in six of PR 52's found the two sides apart and
gave nothing), and a trace of six seconds holds about eight dispatches of 2048 to 4096
valid tokens, whose mix the window's sixty estimate to a tenth: that is this
number's error. The count is the mathematics' least (a chunk of 4096 is
cheapest pair by pair throughout, 0.56 of what the program's tiling, a state
query a token and pairs inside 256, computes) and the kernel's matmuls take
the state and the features in two bfloat16 halves each (three passes): a
share of a fifth is this form's ceiling. A program without the kernel (the
parent of PR 52) gives nothing."""

from benchmark import peaks, samples
from benchmark.kernels import power_retention_prefill as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "power_retention_prefill"


def read(run):
    trace = run.closed.get("trace")
    seen = (trace or {}).get("kernels_device0", {}).get(KERNEL)
    found = [
        d
        for t in samples.ticks_in_window(run)
        for d in t.get("dispatches", ())
        if d[0] != "decode" and d[2] is not None
    ]
    if not seen or not seen["sum_s"] or not found:
        return None
    peak = peaks.peaks_for(run.device["kind"])
    least_s = sum(
        max(
            kernel.operations(run.conf, d[1][0], d[2]) / peak["bf16_flops"],
            kernel.bytes_read(run.conf, d[1][0], d[2])
            / peak["hbm_bytes_per_s"],
        )
        for d in found
    ) / len(found) * seen["count"]
    return 100.0 * least_s / seen["sum_s"]
