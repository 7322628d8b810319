"""90th percentile (nearest rank) of the time to the first token; a failed
or refused request counts as slower than any."""

from benchmark import samples

DEVICE_METRIC = True


def read(run):
    return samples.ttft_percentile_ms(run, 90.0)
