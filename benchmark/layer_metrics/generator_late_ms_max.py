"""The latest send of the window: the largest send time minus due time over
the timed requests. ``generator_late_ms_p90`` is blind to a stall that holds
two or three sends of a hundred: in one run of twenty-four (my chip runs, PR 37)
the generator's loop stood still for 0.64 s, two requests went out 644 and
185 ms late with ``generator_late_ms_p90`` at 3 ms as in every run, and
``ttft_ms_p90`` read 1352 where 1320 is usual. A run whose reading here is
over some tens of milliseconds measured a host that stood still (the
machine's cores are shared), not the server."""

from benchmark import samples

LAYER = "benchmark client"
DEVICE_METRIC = True


def read(run):
    late = samples.lateness_s(run)
    return max(late) * 1e3 if late else None
