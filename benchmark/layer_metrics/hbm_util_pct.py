"""Bytes the decode steps of the window must read — the stored weights once
a step, and each decoded token's live context of K and V, from shapes
(``benchmark/flops.py``) — over window x chips x the HBM peak. Steps are the
gateway's ``decode_tokens`` counted over the window, over the mean occupied
rows the flight recorder shows."""

from benchmark import flops, peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    ticks = samples.ticks_in_window(run)
    rows = sum(t["occupancy"] for t in ticks) / len(ticks) if ticks else 0
    if not rows:
        return None
    decoded = (
        run.metrics_close.get("decode_tokens", 0.0)
        - run.metrics_open.get("decode_tokens", 0.0)
    )
    serve = run.conf["serve"]
    weight_bytes = 1.0 if serve["weights"] == "int8" else 2.0
    d = run.conf.get("head_dim") or (
        run.conf["hidden_size"] // run.conf["num_attention_heads"]
    )
    kv_bytes = 1.0 + 4.0 / d if serve["cache"].get("kv_quant") == "int8" else 2.0
    lo, hi = samples.bounds(run)
    context = sum(
        r.prompt_len + i
        for r in run.records
        for i, t in enumerate(r.arrivals[1:], start=1) if lo <= t < hi
    )
    # contexts stay under the sliding window (max_seq_len 4096 <= 4096)
    total = (
        flops.stored_weight_bytes(run.conf, weight_bytes) * decoded / rows
        + flops.kv_bytes_per_token(run.conf, kv_bytes) * context
    )
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
