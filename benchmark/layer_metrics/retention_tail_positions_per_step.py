"""How much of a decode step is still quadratic: the unfolded positions a
row's query attends pair by pair (its open page's, its write-behind tail's,
itself), a row and step: ``retention_tail_positions_total`` over
``retention_decode_row_steps_total``, every decode dispatch of the window
(``plan.note_dispatch``). Everything before them is read through the state.
With pages of 64 folded as they fill it lies between 1 and 79; a fold that
came later would raise it, and the pages a row holds with it. A program
without the counters (the parent of PR 52) gives nothing."""

from benchmark import counters

LAYER = "cache"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["retention_tail_positions"], "retention_decode_row_steps"
    )
