"""1 - the union of the intervals in which an operation ran on the device
over the traced span, averaged over the chips."""

LAYER = "device"
DEVICE_METRIC = True


def read(run):
    trace = run.closed.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
