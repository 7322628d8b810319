"""Seconds from the engine's first ``submit`` to the window's open: the
warm-up of the cell's shapes and the traffic's lead-in. The window's open as
the run knows it (``run.t0`` on the monotonic clock, ``run.epoch_offset`` to
epoch seconds) less the first request's instant as the program dates it
(``process_start_time_seconds`` plus ``boot_first_request_seconds``, as READ
when the window opens): two clocks of one host."""

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    got = run.metrics_open or {}
    start = got.get("process_start_time_seconds")
    first = got.get("boot_first_request_seconds")
    if start is None or first is None or run.t0 is None:
        return None
    return run.t0 + run.epoch_offset - (start + first)
