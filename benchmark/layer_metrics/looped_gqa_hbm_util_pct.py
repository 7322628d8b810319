"""Bytes the window's dispatches read, as the program's own counters say,
for a looped stack: ``loop_weight_bytes_read`` (the layers' stored bytes a
lap, every decode step and every prefill dispatch) and
``loop_kv_positions_read`` (the cached positions the queries attend, over
every cache layer) at the pool's bytes a position a layer
(``benchmark/flops_looped_gqa.py:kv_bytes_per_position`` over its cache
layers), and the head once a decode step (``engine_decode_steps`` is the
dispatch clock's; without it the steps are ``decode_tokens`` over the mean
occupied rows), over window x chips x the HBM peak. A program without the
counters (the parent of PR 56) gives nothing."""

from benchmark import counters, peaks, samples
from benchmark import flops_looped_gqa as flops

LAYER = "model"
DEVICE_METRIC = True


def stored(run):
    """``(bytes a stored weight, bytes a stored K or V value)``."""
    serve = run.conf["serve"]
    d = run.conf.get("head_dim") or (
        run.conf["hidden_size"] // run.conf["num_attention_heads"]
    )
    return (
        1.0 if serve["weights"] == "int8" else 2.0,
        1.0 + 4.0 / d if serve["cache"].get("kv_quant") == "int8" else 2.0,
    )


def kv_bytes(run):
    """The window's ``loop_kv_positions_read`` in bytes, or None."""
    positions = counters.delta(run, "loop_kv_positions_read")
    if positions is None:
        return None
    per_layer = flops.kv_bytes_per_position(
        run.conf, stored(run)[1]
    ) / flops.cache_layers(run.conf)
    return positions * per_layer


def decode_steps(run):
    steps = counters.delta(run, "engine_decode_steps")
    if steps:
        return steps
    ticks = samples.ticks_in_window(run)
    rows = sum(t["occupancy"] for t in ticks) / len(ticks) if ticks else 0
    decoded = counters.delta(run, "decode_tokens")
    return decoded / rows if rows and decoded else None


def bytes_read(run):
    """Every byte the counters say the window's dispatches read, or None."""
    weights = counters.delta(run, "loop_weight_bytes_read")
    kv, steps = kv_bytes(run), decode_steps(run)
    if not weights or kv is None or steps is None:
        return None
    head = run.conf["hidden_size"] * run.conf["vocab_size"] * stored(run)[0]
    return weights + kv + head * steps


def read(run):
    total = bytes_read(run)
    if total is None:
        return None
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
