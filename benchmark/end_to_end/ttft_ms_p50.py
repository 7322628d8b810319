"""Median time to the first streamed token, from the due time (open loop)
or the send (closed loop)."""

from benchmark import samples

DEVICE_METRIC = True


def read(run):
    return samples.ttft_percentile_ms(run, 50.0)
