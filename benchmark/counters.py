"""The window's share of a cumulative ``/metrics`` number: its reading when
the window closed minus its reading when it opened, in the names
``benchmark/prom.py`` gives (a counter without ``_total``; a summary's
``<name>_seconds_sum`` / ``<name>_seconds_count``). A program that has no
such counter gives ``None``, and so does every ratio built on it: the line
then leaves the metric out."""

from __future__ import annotations


def delta(run, name: str):
    close = run.metrics_close or {}
    if name not in close:
        return None
    return close[name] - (run.metrics_open or {}).get(name, 0.0)


def ratio(run, numerators, denominator: str, scale: float = 1.0):
    """``scale`` x the sum of the numerators' deltas over the denominator's;
    ``None`` where one is missing or the denominator did not move."""
    below = delta(run, denominator)
    above = [delta(run, n) for n in numerators]
    if below is None or below <= 0 or any(a is None for a in above):
        return None
    return scale * sum(above) / below
