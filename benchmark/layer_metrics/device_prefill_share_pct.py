"""Share of the device seconds the dispatch clock counted in the window that
went to prefill-family dispatches (``prefill`` and ``chunk``), the rest being
decode: what a gain in either is worth in the cell."""

from benchmark import clock_counters as clock

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return clock.per(
        clock.device_seconds(run, clock.PREFILL_KINDS),
        clock.device_seconds(run), 100.0,
    )
