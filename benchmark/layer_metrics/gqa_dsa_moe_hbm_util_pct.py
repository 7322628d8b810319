"""Bytes the decode steps of the window must read, for the ``KeyeVL2``
block: every stored weight byte of the layers run here and of the head once
a step (``benchmark/flops_gqa_dsa_moe.py``: all 128 experts, the attention
and indexer projections, the router), each decoded token's live context of
index keys and its SELECTED positions' K and V (``min(topk, context)``),
over window x chips x the HBM peak. ``hbm_util_pct``'s arithmetic with this
family's counts: steps are the gateway's ``decode_tokens`` over the window
over the mean occupied rows the flight recorder shows. A configuration
without ``sa_config`` gives nothing."""

from benchmark import flops_gqa_dsa_moe, peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    ticks = samples.ticks_in_window(run)
    rows = sum(t["occupancy"] for t in ticks) / len(ticks) if ticks else 0
    if not rows or "sa_config" not in run.conf:
        return None
    decoded = (
        run.metrics_close.get("decode_tokens", 0.0)
        - run.metrics_open.get("decode_tokens", 0.0)
    )
    serve = run.conf["serve"]
    weight_bytes = 1.0 if serve["weights"] == "int8" else 2.0
    int8_pool = serve["cache"].get("kv_quant") == "int8"
    topk = run.conf["sa_config"]["topk"]
    lo, hi = samples.bounds(run)
    contexts = [
        r.prompt_len + i
        for r in run.records
        for i, t in enumerate(r.arrivals[1:], start=1) if lo <= t < hi
    ]
    total = (
        flops_gqa_dsa_moe.stored_weight_bytes(run.conf, weight_bytes)
        * decoded / rows
        + flops_gqa_dsa_moe.index_bytes_per_token(
            run.conf, 4.0 if serve["dtype"] == "float32" else 2.0
        ) * sum(contexts)
        + flops_gqa_dsa_moe.kv_bytes_per_token(run.conf, int8_pool)
        * sum(min(topk, c) for c in contexts)
    )
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
