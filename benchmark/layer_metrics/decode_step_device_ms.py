"""Device milliseconds a decode step, by the engine's own dispatch clock: the
device seconds of the window's decode dispatches (ready stamp minus the later
of the previous ready stamp and the enqueue; the small programs before a
dispatch fall to it) over their steps (``engine_decode_steps``: a fused
dispatch's K, 1 on the one-token path). ``decode_step_ms_p50`` reads the
median module event of the trace; this is a mean over the whole window."""

from benchmark import clock_counters as clock
from benchmark import counters

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return clock.per(
        clock.device_seconds(run, ["decode"]),
        counters.delta(run, "engine_decode_steps"), 1e3,
    )
