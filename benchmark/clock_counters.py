"""The window's share of the engine's dispatch-clock counters (``/metrics``
deltas through ``benchmark/counters.py``): device seconds and dispatches by
kind of noted dispatch (``engine_device_seconds_<kind>``,
``engine_dispatches_<kind>``), and the seconds the device had nothing of the
engine's to run, in all and by the host phase that held the gap
(``engine_device_idle_seconds``, ``engine_device_idle_<phase>_seconds``).

The clock is armed by a read of ``/debug/ticks`` and counts while somebody
reads them: a traced run's first poll arms it right after the window's open
reading, so the counters start at zero there and cover the window less its
first poll, and a per-tick reading divides by ``engine_clocked_ticks``, the
ticks that ended with the clock armed, not by ``engine_ticks``.

A kind the window never dispatched, or a phase no gap fell under, has no
counter yet and counts 0. A program without a dispatch clock (a tree before
it, an engine without a ``TraceConfig``, or a run that never read the ticks)
has no ``engine_enqueue_seconds``: every sum here is then ``None``, and so is
every metric built on one."""

from __future__ import annotations

from benchmark import counters

KINDS = ("prefill", "chunk", "decode")
PREFILL_KINDS = ("prefill", "chunk")


def _sum(run, names):
    if counters.delta(run, "engine_enqueue_seconds") is None:
        return None
    return sum(counters.delta(run, name) or 0.0 for name in names)


def device_seconds(run, kinds=KINDS):
    return _sum(run, [f"engine_device_seconds_{k}" for k in kinds])


def dispatches(run, kinds=KINDS):
    return _sum(run, [f"engine_dispatches_{k}" for k in kinds])


def idle_seconds(run, phases=None):
    """All of it, or what fell under ``phases``."""
    if phases is None:
        return _sum(run, ["engine_device_idle_seconds"])
    return _sum(run, [f"engine_device_idle_{p}_seconds" for p in phases])


def per(above, below, scale: float = 1.0):
    """``scale * above / below``; ``None`` where one is missing or ``below``
    did not move."""
    if above is None or not below:
        return None
    return scale * above / below
