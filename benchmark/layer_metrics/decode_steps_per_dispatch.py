"""Decode steps a decode dispatch, by the engine's own dispatch clock: the
window's ``engine_decode_steps`` (a fused dispatch's K, 1 on the one-token
path) over its decode dispatches (``engine_dispatches_decode``), the pair
``decode_step_device_ms`` reads. 16 where the engine decodes through the
fused write-behind scan, 1 where every token is a dispatch, a fetch and a
delivery of its own (the tp=4 mesh engine before PR 49). A program without a
dispatch clock, or a window without a decode dispatch, gives nothing."""

from benchmark import clock_counters as clock
from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return clock.per(
        counters.delta(run, "engine_decode_steps"),
        clock.dispatches(run, ["decode"]), 1,
    )
