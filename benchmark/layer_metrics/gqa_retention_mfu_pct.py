"""End-to-end utilisation of the whole step, named as such and not a
roofline share: the FLOPs the ``brumby`` block needs for the VALID prompt
tokens whose first token arrived inside the window and for the output tokens
that arrived inside it (``benchmark/flops_gqa_retention.py``: projections,
gate, MLP, head, and retention by its recurrent form, the same at every
context), over window x chips x the bf16 peak. ``mla_mhc_moe_mfu_pct``'s
arithmetic over this family's count."""

from benchmark import flops_gqa_retention as flops
from benchmark import peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    cfg = run.conf
    if cfg.get("model_type") != "brumby":
        return None
    lo, hi = samples.bounds(run)
    total = 0.0
    for r in run.records:
        if r.first_t is not None and lo <= r.first_t < hi:
            total += flops.prompt_flops(cfg, r.prompt_len)
        total += flops.decode_token_flops(cfg) * sum(
            1 for t in r.arrivals[1:] if lo <= t < hi
        )
    peak = peaks.peaks_for(run.device["kind"])["bf16_flops"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
