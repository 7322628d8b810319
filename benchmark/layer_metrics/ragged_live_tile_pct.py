"""Tiles of the ragged prefill kernel's grid that hold a live (query, key)
pair over all the tiles it steps through, every prefill-family dispatch of
the window: ``ragged_attn_tiles_live`` / ``ragged_attn_tiles_grid``
(``plan.note_dispatch``, by the kernel's own predicate and q block, a layer's
grid a dispatch). The kernel computes the live ones; what 100 minus this
leaves is steps it skips, and the room a narrower pad or packed prompts
would take back. A program without the counters (the parent of PR 27, or an
engine off the ragged plan) gives nothing."""

from benchmark import counters

LAYER = "kernels"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["ragged_attn_tiles_live"], "ragged_attn_tiles_grid", 100.0
    )
