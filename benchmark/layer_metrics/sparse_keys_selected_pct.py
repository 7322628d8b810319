"""Keys the window's queries attended to over the keys they could see:
``sparse_keys_selected_total`` over ``sparse_keys_live_total``, every
dispatch of the window (``plan.note_dispatch``: a query at position ``t``
sees ``t + 1`` keys and a learned selection keeps ``min(topk, t + 1)`` of
them; a decode dispatch's queries are its rows x steps, a prefill chunk's
its valid tokens). Whether the traffic made the mechanism work: it reads 100
where no context passes ``topk``. A program without the counters (the parent
of PR 32) gives nothing."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["sparse_keys_selected"], "sparse_keys_live", 100.0
    )
