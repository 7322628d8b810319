"""Bytes the decode steps of the window must read, for the ``glm_moe_dsa``
block: the stored weight bytes held here that a step touches
(``benchmark/flops_mla_dsa_moe.py``: every layer's attention projections, an
indexer in the scoring layers, the dense layer's MLP, the held experts the
step's picks fall on and the shared expert, the routers, the head's slice),
each decoded token's live context of index keys in the scoring layers and
its SELECTED positions' stored latents (``min(index_topk, context)``) in
every layer, over window x chips x the HBM peak. ``hbm_util_pct``'s
arithmetic with this family's counts: steps are the gateway's
``decode_tokens`` over the window over the mean occupied rows the flight
recorder shows. A configuration without ``indexer_types`` gives nothing."""

from benchmark import flops_mla_dsa_moe, peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    ticks = samples.ticks_in_window(run)
    rows = sum(t["occupancy"] for t in ticks) / len(ticks) if ticks else 0
    if not rows or "indexer_types" not in run.conf:
        return None
    decoded = (
        run.metrics_close.get("decode_tokens", 0.0)
        - run.metrics_open.get("decode_tokens", 0.0)
    )
    serve = run.conf["serve"]
    weight_bytes = 1.0 if serve["weights"] == "int8" else 2.0
    value_bytes = 4.0 if serve["dtype"] == "float32" else 2.0
    int8_pool = serve["cache"].get("kv_quant") == "int8"
    lo, hi = samples.bounds(run)
    total = (
        flops_mla_dsa_moe.stored_weight_bytes(
            run.conf, weight_bytes, rows, value_bytes
        ) * decoded / rows
        + sum(
            flops_mla_dsa_moe.cache_bytes_read(
                run.conf, r.prompt_len + i, int8_pool, value_bytes
            )
            for r in run.records
            for i, t in enumerate(r.arrivals[1:], start=1) if lo <= t < hi
        )
    )
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
