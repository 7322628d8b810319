"""Valid tokens over padded tokens, summed over the prefill-family
dispatches the flight recorder shows inside the window. A tick record
carries its LAST dispatch only: a tick that admits several single rows shows
one of them (the sum is a sample, not a census), and under a mesh the
tick's decode dispatch hides its prefill, so the reader finds nothing."""

from benchmark import samples

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    valid = padded = 0
    for t in samples.ticks_in_window(run):
        d = t.get("dispatch")
        if not d or d[0] == "decode" or d[2] is None:
            continue
        rows_by_width = 1
        for x in d[1]:
            rows_by_width *= x
        valid += d[2]
        padded += rows_by_width
    return 100.0 * valid / padded if padded else None
