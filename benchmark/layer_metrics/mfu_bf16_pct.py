"""End-to-end utilisation, named as such and not a roofline share: the
FLOPs the architecture needs for the VALID prompt tokens whose first token
arrived inside the window and for the output tokens that arrived inside it
(top-k experts only, no padding; ``benchmark/flops.py``), over window x
chips x the bf16 peak."""

from benchmark import flops, peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    lo, hi = samples.bounds(run)
    cfg = run.conf
    total = 0.0
    for r in run.records:
        if r.first_t is not None and lo <= r.first_t < hi:
            total += flops.prompt_flops(cfg, r.prompt_len)
        for i, t in enumerate(r.arrivals[1:], start=1):
            if lo <= t < hi:
                total += flops.decode_token_flops(cfg, r.prompt_len + i)
    peak = peaks.peaks_for(run.device["kind"])["bf16_flops"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
