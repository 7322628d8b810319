"""Bytes the decode steps of the window must read, for the ``brumby``
block: the stored weight bytes of the layers run here and of the head once a
step, and each decoded token's row's state and summed keys in every layer
(``benchmark/flops_gqa_retention.py``; the same at every context, which is
the model's promise), over window x chips x the HBM peak.
``mla_moe_hbm_util_pct``'s arithmetic with this family's counts: steps are
the gateway's ``decode_tokens`` over the window over the mean occupied rows
the flight recorder shows. A program without the retention census (the
parent of PR 52) gives nothing."""

from benchmark import counters, peaks, samples
from benchmark import flops_gqa_retention as flops

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    ticks = samples.ticks_in_window(run)
    rows = sum(t["occupancy"] for t in ticks) / len(ticks) if ticks else 0
    decoded = counters.delta(run, "decode_tokens")
    if (
        not rows or not decoded
        or counters.delta(run, "retention_state_bytes_read") is None
    ):
        return None
    weight_bytes = 1.0 if run.conf["serve"]["weights"] == "int8" else 2.0
    total = (
        flops.stored_weight_bytes(run.conf, weight_bytes) * decoded / rows
        + flops.state_bytes_per_token(run.conf) * decoded
    )
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
