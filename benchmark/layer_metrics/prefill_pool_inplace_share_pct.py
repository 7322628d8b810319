"""Of the rows of the window's prefill-family dispatches (a prompt's one
piece, a chunk, a group of admitted rows), the share whose cache wrote the
piece by whole pages and read it at ``(layer, page)`` of the carried pool
stacks (``cache/paged.py:QuantizedPagedKVCache.ragged_reads_whole_stacks``):
100 x ``prefill_pool_inplace_rows`` / (``prefill_pool_inplace_rows`` +
``prefill_pool_scatter_rows``), each the window's share of the counter
(``engine/engine.py:_note_prefill``). Every other cache is handed a layer's
planes, sliced out of the carry and written back a layer (the int8 pool
under a learned selection, the latent pools, the value-dtype pool). A
counter the program never moved counts as 0 where the other moved; a window
without such a dispatch, or a program with neither counter (the parent of
PR 58), gives nothing."""

from benchmark import counters

LAYER = "cache"
DEVICE_METRIC = False


def read(run):
    inplace = counters.delta(run, "prefill_pool_inplace_rows") or 0.0
    rows = inplace + (counters.delta(run, "prefill_pool_scatter_rows") or 0.0)
    return 100.0 * inplace / rows if rows > 0 else None
