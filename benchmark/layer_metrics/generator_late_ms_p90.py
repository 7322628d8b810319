"""How late the generator sent: send time minus due time. A starved
generator is not a fast server."""

from benchmark import samples, stats

LAYER = "benchmark client"
DEVICE_METRIC = True


def read(run):
    value = stats.percentile(samples.lateness_s(run), 90.0)
    return None if value is None else value * 1e3
