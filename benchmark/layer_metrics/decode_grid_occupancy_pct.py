"""Live positions over the positions the decode dispatches walk: the
host-known context lengths of the active rows, summed, over rows x table
width x page size (``decode_live_positions`` / ``decode_grid_positions``),
every decode dispatch of the window. The layer metric of "decode costs what
the table's width and the slot count say, not what is live"."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["decode_live_positions"], "decode_grid_positions", 100.0
    )
