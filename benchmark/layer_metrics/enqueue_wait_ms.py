"""Milliseconds a tick the drive thread spent INSIDE the compiled calls of
noted dispatches (``engine_enqueue_seconds``: return stamp minus enqueue
stamp, less a program load inside the call), over the window's clocked ticks
(``engine_clocked_ticks``: those that ended with the clock armed). Behind
a busy device the call holds the thread until the device takes the program;
the tick clock books that under ``dispatch`` or ``admit``, so
``tick_host_dispatch_ms`` + ``tick_host_admit_ms`` less this is the most the
host itself worked in those phases."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(run, ["engine_enqueue_seconds"], "engine_clocked_ticks", 1e3)
