"""Median device time of one execution of a prefill program (the jitted
``_prefill_row``, ``_prefill_row_nosample``, ``_prefill_rows*``), from the
trace's module events on device 0."""

from benchmark import stats

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    trace = run.closed.get("trace")
    if not trace:
        return None
    durations = [
        d for name, ds in trace["modules_device0_s"].items()
        if "prefill" in name for d in ds
    ]
    value = stats.median(durations)
    return None if value is None else value * 1e3
