"""Seconds from the engine's constructor to its first ``submit``: the probe
against the plain reference, the gateway's bind and the parent's first
warm-up request on its way (``boot_first_request_seconds`` less
``boot_engine_built_seconds``, as READ when the window opens)."""

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    got = run.metrics_open or {}
    built = got.get("boot_engine_built_seconds")
    first = got.get("boot_first_request_seconds")
    if built is None or first is None:
        return None
    return first - built
