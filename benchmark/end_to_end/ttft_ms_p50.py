"""Median (nearest rank) time to the first streamed token, from the due time
(open loop) or the send (closed loop)."""

from benchmark import samples

DEVICE_METRIC = True
#: as in ``ttft_ms_p90``: what the tier-1 test counts the rank from
PERCENTILE = 50.0


def read(run):
    return samples.ttft_percentile_ms(run, PERCENTILE)
