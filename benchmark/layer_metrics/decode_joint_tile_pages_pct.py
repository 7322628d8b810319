"""Of the pages the decode dispatches' rows hold inside their windows (a page
a layer a step), the share that lies in full blocks of the in-place sweep
(``decode_pages_joint`` / ``decode_pages_live``), every decode dispatch of
the window: how much of what ``ops/paged_attention.py``'s copies' sweep
attends fills its tiles (a block of pages is ONE tile, a row's last one
padded). 0 where another path decodes (a mesh, a latent pool) or a block is
one page, and near 0 for a window layer's few pages; a program without the
counters gives nothing."""

from benchmark import counters

LAYER = "kernels"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["decode_pages_joint"], "decode_pages_live", 100.0
    )
