"""1 - the least ``free_count()`` of the page allocator over ``num_pages``,
sampled every 20 ms by the process that holds the engine."""

LAYER = "cache"
DEVICE_METRIC = False


def read(run):
    least = run.closed.get("pool_free_least")
    if least is None:
        return None
    return 100.0 * (1.0 - least / run.closed["num_pages"])
