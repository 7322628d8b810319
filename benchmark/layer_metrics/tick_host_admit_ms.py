"""Host milliseconds a tick in the ``admit`` phase: planning, page
allocation and the prefill-family dispatches (``_admit``,
``_chunk_dispatch``), the time blocked in a synchronous admission's fetch
taken out. Window's seconds over window's ticks."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(
        run, ["engine_tick_admit_seconds"], "engine_ticks", 1e3
    )
