"""Milliseconds a tick in which the device had nothing of the engine's to run
while the drive thread was in ``blocked``, ``deliver`` or ``outside`` (a
fetch's own latency, what follows a fetch, and the time between two
``step()`` calls, grouped as ``tick_host_deliver_ms`` groups them): the
dispatch clock's idle gaps under those phases over the window's clocked
ticks (``engine_clocked_ticks``)."""

from benchmark import clock_counters as clock
from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return clock.per(
        clock.idle_seconds(run, ["blocked", "deliver", "outside"]),
        counters.delta(run, "engine_clocked_ticks"), 1e3,
    )
