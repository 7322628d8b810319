"""End-to-end utilisation of the whole step, named as such and not a
roofline share: the FLOPs the ``xing4_0`` block needs for the VALID prompt
tokens whose first token arrived inside the window and for the output tokens
that arrived inside it (``benchmark/flops_mla_mhc_moe.py``: latent attention
with compressed queries at the context's length, 4 + 1 experts, the dense
layer, the head, and the hyper-connection mixes), over window x chips x the
bf16 peak. ``mfu_bf16_pct``'s arithmetic over this family's count."""

from benchmark import flops_mla_mhc_moe as flops
from benchmark import peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    cfg = run.conf
    if "hc_mult" not in cfg:
        return None
    lo, hi = samples.bounds(run)
    total = 0.0
    for r in run.records:
        if r.first_t is not None and lo <= r.first_t < hi:
            total += flops.prompt_flops(cfg, r.prompt_len)
        for i, t in enumerate(r.arrivals[1:], start=1):
            if lo <= t < hi:
                total += flops.decode_token_flops(cfg, r.prompt_len + i)
    peak = peaks.peaks_for(run.device["kind"])["bf16_flops"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
