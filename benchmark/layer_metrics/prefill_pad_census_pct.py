"""Valid over padded tokens of EVERY prefill-family dispatch of the window
(``prefill_valid_tokens`` / ``prefill_padded_tokens``, counted where the
shapes are chosen): a census, where ``prefill_pad_occupancy_pct`` samples the
last dispatch of each tick and finds nothing under a mesh."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["prefill_valid_tokens"], "prefill_padded_tokens", 100.0
    )
