"""Expert MLP rows the window's tokens needed over those the program ran:
``moe_expert_rows_needed`` (valid tokens x the experts a token's result
takes, its picks and the shared ones, x expert layers) over
``moe_expert_rows_computed`` (padded tokens x the experts the program runs a
token, all of them under dense-combine, x expert layers), every dispatch of
the window (``plan.note_dispatch``). Pad waste times the compute strategy's
waste: what sorted dispatch and a ragged pad would move. A program without
the counters (the parent of PR 26) gives nothing."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["moe_expert_rows_needed"], "moe_expert_rows_computed", 100.0
    )
