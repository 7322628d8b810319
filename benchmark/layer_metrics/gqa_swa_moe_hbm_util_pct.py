"""Bytes the decode steps of the window must read, for the ``exaone_moe``
block: every stored weight byte held here once a step
(``benchmark/flops_gqa_swa_moe.py``: the attention projections of every
layer, the dense layer's MLP, the HELD experts and the shared expert of each
expert layer, the routers, the head's slice), and each decoded token's KV by
layer kind (a full layer its whole context, a window layer ``min(window,
context)``), over window x chips x the HBM peak. ``hbm_util_pct``'s
arithmetic with this family's counts: steps are the gateway's
``decode_tokens`` over the window over the mean occupied rows the flight
recorder shows. A configuration without ``layer_types`` gives nothing."""

from benchmark import flops_gqa_swa_moe, peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    ticks = samples.ticks_in_window(run)
    rows = sum(t["occupancy"] for t in ticks) / len(ticks) if ticks else 0
    if not rows or "layer_types" not in run.conf:
        return None
    decoded = (
        run.metrics_close.get("decode_tokens", 0.0)
        - run.metrics_open.get("decode_tokens", 0.0)
    )
    serve = run.conf["serve"]
    weight_bytes = 1.0 if serve["weights"] == "int8" else 2.0
    int8_pool = serve["cache"].get("kv_quant") == "int8"
    lo, hi = samples.bounds(run)
    total = (
        flops_gqa_swa_moe.stored_weight_bytes(run.conf, weight_bytes)
        * decoded / rows
        + sum(
            flops_gqa_swa_moe.kv_bytes_read(run.conf, r.prompt_len + i, int8_pool)
            for r in run.records
            for i, t in enumerate(r.arrivals[1:], start=1) if lo <= t < hi
        )
    )
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
