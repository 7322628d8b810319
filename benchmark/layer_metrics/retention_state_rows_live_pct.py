"""The state pool's rows that a decode step reads, over the rows it holds:
``retention_state_rows_live_total`` over ``retention_state_rows_held_total``
(a decode dispatch's active rows over the pool's rows, x its steps;
``plan.note_dispatch``). A row's state is the same bytes whether its context
is a hundred positions or ten thousand, so this is the share of the pool a
step pays for, and 100 minus it what the decode kernel's walk of live rows
spares. A program without the counters (the parent of PR 52) gives nothing."""

from benchmark import counters

LAYER = "cache"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["retention_state_rows_live"], "retention_state_rows_held", 100.0
    )
