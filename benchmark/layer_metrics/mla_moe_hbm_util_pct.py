"""Bytes the decode steps of the window must read, for the DeepSeek-V2/V3
block: every stored weight byte of the layers run here and of the head once
a step (``benchmark/flops_mla_moe.py``: all routed and shared experts, the
dense layers' MLP, the latent projections), and each decoded token's live
context of stored latents, over window x chips x the HBM peak.
``hbm_util_pct``'s arithmetic with this family's counts: steps are the
gateway's ``decode_tokens`` over the window over the mean occupied rows the
flight recorder shows."""

from benchmark import flops_mla_moe, peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    ticks = samples.ticks_in_window(run)
    rows = sum(t["occupancy"] for t in ticks) / len(ticks) if ticks else 0
    if not rows or "kv_lora_rank" not in run.conf:
        return None
    decoded = (
        run.metrics_close.get("decode_tokens", 0.0)
        - run.metrics_open.get("decode_tokens", 0.0)
    )
    serve = run.conf["serve"]
    weight_bytes = 1.0 if serve["weights"] == "int8" else 2.0
    lo, hi = samples.bounds(run)
    context = sum(
        r.prompt_len + i
        for r in run.records
        for i, t in enumerate(r.arrivals[1:], start=1) if lo <= t < hi
    )
    total = (
        flops_mla_moe.stored_weight_bytes(run.conf, weight_bytes) * decoded / rows
        + flops_mla_moe.latent_bytes_per_token(
            run.conf, serve["cache"].get("kv_quant") == "int8"
        ) * context
    )
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
