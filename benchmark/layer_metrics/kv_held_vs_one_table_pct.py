"""Bytes the two pools of a stack of window and full layers hold for live
rows, over what ONE page table over all the layers would hold for the same
rows: the mean over the window's ticks of ``(full layers x full-pool pages
+ window layers x window-pool pages) / (all layers x full-pool pages)``. A
page is the same bytes in either pool; the full pool holds what one table
would (every position of every live row), so the denominator is its pages
in every layer. From the flight recorder's tick records (``kv_pages_held``:
pages held in the full layers' pool and in the window pool, the null pages
left out) and the configuration's ``layer_types``. A program whose ticks
lack the field (one pool; the parent of PR 35) gives nothing."""

from benchmark import samples

LAYER = "cache"
DEVICE_METRIC = False


def read(run):
    kinds = list(run.conf.get("layer_types", ()))
    full, window = kinds.count("full_attention"), kinds.count("sliding_attention")
    shares = [
        (full * t["kv_pages_held"][0] + window * t["kv_pages_held"][1])
        / ((full + window) * t["kv_pages_held"][0])
        for t in samples.ticks_in_window(run)
        if t.get("kv_pages_held") and t["kv_pages_held"][0]
    ]
    if not shares or not full or not window:
        return None
    return 100.0 * sum(shares) / len(shares)
