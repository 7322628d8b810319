"""Share of device 0's busy time inside all-reduce operations, from the
trace."""

LAYER = "collectives"
DEVICE_METRIC = True


def read(run):
    trace = run.closed.get("trace")
    if not trace or not trace["busy_device0_s"]:
        return None
    return 100.0 * trace["all_reduce_device0_s"] / trace["busy_device0_s"]
