"""Of the sessions the engine admitted in the window, the share whose first
token's fetch was deferred behind a tick in flight (overlapped admission):
100 x ``admit_overlap_sessions`` / (``admit_overlap_sessions`` +
``admit_sync_sessions``), each the window's share of the counter. A
synchronous admission is a host round trip with the device waiting on it (the
prefill's first token fetched, delivered, and only then the next program's
inputs built); an overlapped one rides the next tick's fetch. A counter the
program never moved is not in ``/metrics`` and counts as 0 where the other
moved (a mesh engine that admits every session synchronously reads 0, not
nothing); a window with no admission, or a program with neither counter,
gives nothing."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    overlapped = counters.delta(run, "admit_overlap_sessions") or 0.0
    admitted = overlapped + (counters.delta(run, "admit_sync_sessions") or 0.0)
    return 100.0 * overlapped / admitted if admitted > 0 else None
