"""Keys the window layers' queries saw over the keys in their contexts:
``window_keys_seen_total`` over ``window_keys_in_context_total``, every
dispatch of the window (``plan.note_dispatch``: a query at position ``t``
has ``t + 1`` keys in context and a window layer sees ``min(window, t + 1)``
of them; a decode dispatch's queries are its rows x steps, a prefill
chunk's its valid tokens). Whether the traffic made the mechanism work: it
reads 100 where no context passes the window, and the share of a full
layer's reads that a window layer is spared is 100 minus it. A program
without the counters (the parent of PR 35) gives nothing."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["window_keys_seen"], "window_keys_in_context", 100.0
    )
