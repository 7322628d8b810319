"""Device milliseconds a prefill-family dispatch, by the engine's own
dispatch clock: the device seconds of the window's ``prefill`` and ``chunk``
dispatches over their count. ``prefill_step_ms_p50`` reads the median module
event of the trace; this is a mean over the whole window, a grouped
admission and a lone chunk alike."""

from benchmark import clock_counters as clock

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return clock.per(
        clock.device_seconds(run, clock.PREFILL_KINDS),
        clock.dispatches(run, clock.PREFILL_KINDS), 1e3,
    )
