"""Share of the engine's wall time in which its drive thread was NOT waiting
in ``jax.device_get``: 100 x (tick seconds - blocked seconds) / tick seconds
over the window, the time between two ``step()`` calls counted in both. Host
time the device does not hide is at most this; where the device is never idle
the host's work runs under the device's and this only says how busy the
thread is."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    blocked = counters.ratio(
        run, ["engine_tick_blocked_seconds"], "engine_tick_seconds", 100.0
    )
    return None if blocked is None else 100.0 - blocked
