"""Share of the device's time in which it had nothing of the engine's to run,
by the engine's own dispatch clock: the seconds between a noted dispatch's
ready stamp and the next one's enqueue (``engine_device_idle_seconds``) over
those plus the device seconds of the three kinds of dispatch, in the window.
``device_idle_pct`` reads the same from the trace's operations, over the
traced span."""

from benchmark import clock_counters as clock

LAYER = "device"
DEVICE_METRIC = True


def read(run):
    idle, busy = clock.idle_seconds(run), clock.device_seconds(run)
    if idle is None or busy is None:
        return None
    return clock.per(idle, idle + busy, 100.0)
