"""The gateway's ``/metrics`` text as plain numbers."""

from __future__ import annotations


def parse(text: str, prefix: str = "dli_") -> dict:
    """``{name: value}``: counters without ``_total``, gauges as they are,
    summaries as ``<name>_seconds_sum`` / ``_count`` and
    ``<name>_seconds{quantile="0.5"}``."""
    out = {}
    for line in text.splitlines():
        if not line.startswith(prefix):
            continue
        name, _, value = line.rpartition(" ")
        name = name[len(prefix):]
        if name.endswith("_total"):
            name = name[: -len("_total")]
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out
