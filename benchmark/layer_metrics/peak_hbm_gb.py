"""``memory_stats()["peak_bytes_in_use"]`` after the window, the largest
over the devices."""

LAYER = "device"
DEVICE_METRIC = True


def read(run):
    return run.closed["memory"]["peak_bytes"] / 1e9
