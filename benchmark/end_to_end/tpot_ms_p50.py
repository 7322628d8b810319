"""Median, over requests of at least 32 output tokens that ended inside the
window, of (last token time - first token time) / (tokens - 1)."""

from benchmark import samples, stats

DEVICE_METRIC = True


def read(run):
    value = stats.median(samples.tpot_s(run))
    return None if value is None else value * 1e3
