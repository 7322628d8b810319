"""Bytes the decode steps of the window must read, for the ``xing4_0``
block: the stored weight bytes of the layers run here and of the head once a
step (``benchmark/flops_mla_mhc_moe.py``: the routed experts weighed by the
LIVE share the window's decode dispatches counted, ``moe_decode_experts_live``
over ``moe_decode_experts_held``), and each decoded token's live context of
stored latents, over window x chips x the HBM peak. ``mla_moe_hbm_util_pct``'s
arithmetic with this family's counts: steps are the gateway's
``decode_tokens`` over the window over the mean occupied rows the flight
recorder shows. A program without the experts' census gives nothing."""

from benchmark import counters, peaks, samples
from benchmark import flops_mla_mhc_moe as flops

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    ticks = samples.ticks_in_window(run)
    rows = sum(t["occupancy"] for t in ticks) / len(ticks) if ticks else 0
    live = counters.ratio(
        run, ["moe_decode_experts_live"], "moe_decode_experts_held"
    )
    decoded = counters.delta(run, "decode_tokens")
    if not rows or live is None or not decoded or "hc_mult" not in run.conf:
        return None
    serve = run.conf["serve"]
    weight_bytes = 1.0 if serve["weights"] == "int8" else 2.0
    lo, hi = samples.bounds(run)
    context = sum(
        r.prompt_len + i
        for r in run.records
        for i, t in enumerate(r.arrivals[1:], start=1) if lo <= t < hi
    )
    total = (
        flops.stored_weight_bytes(run.conf, weight_bytes, live) * decoded / rows
        + flops.latent_bytes_per_token(
            run.conf, serve["cache"].get("kv_quant") == "int8"
        ) * context
    )
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
