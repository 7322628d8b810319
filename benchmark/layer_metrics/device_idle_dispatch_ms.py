"""Milliseconds a tick in which the device had nothing of the engine's to run
while the drive thread was in ``dispatch``: the dispatch clock's idle gaps cut
by the tick clock's phases (``engine_device_idle_dispatch_seconds``) over the
window's clocked ticks (``engine_clocked_ticks``: those that ended with the
clock armed). ``tick_host_dispatch_ms`` is how long the thread was in the
phase; this is how much of it the device waited for."""

from benchmark import clock_counters as clock
from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return clock.per(
        clock.idle_seconds(run, ["dispatch"]),
        counters.delta(run, "engine_clocked_ticks"), 1e3,
    )
