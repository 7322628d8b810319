"""Of the grid steps a sweep over the table's width would take (rows x the
table's blocks, a layer a step of every decode dispatch of the window), the
share the latent pool's decode sweep walks: ``decode_sweep_steps_walked`` /
``decode_sweep_steps_grid`` (``engine/plan.py``, by the kernel's own list:
``ops/paged_attention.py:_sweep_walk``). A walked step is a block of a
row's table that holds a live page, or the one step of a row that holds
none; what is not walked is not executed at all. Counted only where the
fused decode scan's sweep walks such a list (the int8 latent pool's
576-wide row without a selection: the two reason1k cells; under a selection,
glm, the sweep still steps through rows x table blocks and counts nothing);
a program without the counters (the parent of PR 48) gives nothing."""

from benchmark import counters

LAYER = "kernels"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["decode_sweep_steps_walked"], "decode_sweep_steps_grid", 100.0
    )
