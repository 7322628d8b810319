"""Mean occupied slots over ``max_batch_size``, over the flight recorder's
ticks inside the window."""

from benchmark import samples

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    ticks = samples.ticks_in_window(run)
    if not ticks:
        return None
    mean = sum(t["occupancy"] for t in ticks) / len(ticks)
    return 100.0 * mean / run.shapes["max_batch_size"]
