"""Share of the device's busy time inside custom-call operations (the Pallas
kernels are the program's only custom calls), from the trace. Not a roofline
share: that needs a name on every ``pallas_call`` (PERF.md, tracing to-do)."""

LAYER = "kernels"
DEVICE_METRIC = True


def read(run):
    trace = run.closed.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["custom_call_s"] / trace["busy_s"]
