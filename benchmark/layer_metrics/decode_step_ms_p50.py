"""Median device time of one decode step: the duration of one execution of
a decode program (``_decode_scan``, ``_decode_step``) over the tokens per row
it decodes, from the trace's module events on device 0."""

from benchmark import stats

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    trace = run.closed.get("trace")
    if not trace:
        return None
    durations = [
        d for name, ds in trace["modules_device0_s"].items()
        if "decode" in name for d in ds
    ]
    value = stats.median(durations)
    if value is None:
        return None
    return value * 1e3 / run.shapes["decode_steps"]
