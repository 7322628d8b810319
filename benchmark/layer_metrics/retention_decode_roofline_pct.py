"""The retention decode kernel's share of its roofline: the least time the
chip could take for the calls of ``power_retention_decode`` in the trace (the
larger of their bytes over the HBM peak and their operations over the bf16
peak: the bytes, by a factor of 60 at this model's widths) over the time the
trace shows for them.

Time: ``kernels_device0``, the summed device durations of the kernel's
events, and their count: one event is one layer of one decode step. Bytes
and operations of one call: ``benchmark/kernels/power_retention_decode.py``
for the rows a call walks and the pairs a row attends, both the WINDOW's
means from the program's census (``plan.note_dispatch``):
``retention_state_rows_live`` over ``retention_state_rows_held`` times the
pool's rows, and ``retention_tail_positions`` over
``retention_decode_row_steps``. A closed loop of as many clients as rows
keeps the rows within one of full through the window, which is this number's
error (the trace is six seconds of it); a row's state is the same bytes at
every context. A program without the kernel or the census (the parent of
PR 52) gives nothing."""

from benchmark import counters, peaks
from benchmark.kernels import power_retention_decode as kernel

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "power_retention_decode"


def read(run):
    trace = run.closed.get("trace")
    seen = (trace or {}).get("kernels_device0", {}).get(KERNEL)
    live = counters.ratio(
        run, ["retention_state_rows_live"], "retention_state_rows_held"
    )
    pairs = counters.ratio(
        run, ["retention_tail_positions"], "retention_decode_row_steps"
    )
    if not seen or not seen["sum_s"] or live is None or pairs is None:
        return None
    rows = live * run.conf["serve"]["engine"]["max_batch_size"]
    peak = peaks.peaks_for(run.device["kind"])
    least_s = seen["count"] * max(
        kernel.bytes_read(run.conf, rows, pairs) / peak["hbm_bytes_per_s"],
        kernel.operations(run.conf, rows, pairs) / peak["bf16_flops"],
    )
    return 100.0 * least_s / seen["sum_s"]
