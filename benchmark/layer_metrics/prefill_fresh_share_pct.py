"""Of the single-row final prefill dispatches of the window, the share that
took the fresh-row program (``engine/engine.py:_prefill_row_fresh``): 100 x
``prefill_fresh_rows`` / (``prefill_fresh_rows`` + ``prefill_table_rows``),
each the window's share of the counter. A fresh row's one-piece prompt, where
no kernel reads the pages in place and the cache can install a contiguous
K/V (the value-dtype paged pool: a mesh engine), prefills against the
dispatch's own K/V and installs whole pages; every other row (a prefix hit,
the tail of a chunked prompt, an engine whose kernel reads the pages) writes
position by position through its page table and attends its table span. A
counter the program never moved counts as 0 where the other moved; a window
without such a dispatch, or a program with neither counter (the parent of
PR 55), gives nothing."""

from benchmark import counters

LAYER = "cache"
DEVICE_METRIC = False


def read(run):
    fresh = counters.delta(run, "prefill_fresh_rows") or 0.0
    rows = fresh + (counters.delta(run, "prefill_table_rows") or 0.0)
    return 100.0 * fresh / rows if rows > 0 else None
