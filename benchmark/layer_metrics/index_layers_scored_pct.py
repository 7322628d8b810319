"""Layers that scored a selection of their own over the layers that attended
under one: ``index_layers_scored_total`` over ``index_layers_attended_total``,
every step of every dispatch of the window (``plan.note_dispatch``). A stack
whose layers share selections (``indexer_types``: 3 ``full`` of 9 here)
reads their ratio, 33.3; one that scores in every layer reads 100. Lower is
less index scoring for the same attention. A program without the counters
(the parent of PR 44) gives nothing."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["index_layers_scored"], "index_layers_attended", 100.0
    )
