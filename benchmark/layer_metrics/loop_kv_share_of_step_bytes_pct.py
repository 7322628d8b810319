"""Of all the bytes the window's dispatches read, the share that is cached K
and V: ``loop_kv_positions_read`` at the pool's bytes a position a layer
(4224 B a position over ``2 x 16 x 132`` in the int8 pool with its float32
scales) over weights, K/V and head together
(``looped_gqa_hbm_util_pct.bytes_read``). Lower is better: what is not K/V is
the four reads of the layers' weights, which a longer context does not move.
A program without the counters (the parent of PR 56) gives nothing."""

from benchmark.layer_metrics import looped_gqa_hbm_util_pct as model

LAYER = "cache"
DEVICE_METRIC = False


def read(run):
    total, kv = model.bytes_read(run), model.kv_bytes(run)
    if not total or kv is None:
        return None
    return 100.0 * kv / total
