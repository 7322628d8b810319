"""Output tokens delivered inside the window, per second. The window's
edges are tapered (``stats.tapered_tokens``) over a ninth of its length each,
5 s of 45: tokens arrive in bursts of a fused decode tick, and with hard
edges the reading stepped by a burst (2.6% in a cell of four rows) with the
phase of the ticks against the window (my chip runs, PR 22)."""

from benchmark import samples, stats

DEVICE_METRIC = True
EDGE_SHARE = 1.0 / 9.0


def read(run):
    lo, hi = samples.bounds(run)
    edge = run.seconds * EDGE_SHARE
    total = sum(
        stats.tapered_tokens(r.arrivals, lo, hi, edge) for r in run.records
    )
    return total / (run.seconds - edge)
