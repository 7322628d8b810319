"""End-to-end utilisation of the whole step, named as such and not a
roofline share: the FLOPs the ``ouro`` block needs for the VALID prompt
tokens whose first token arrived inside the window and for the output tokens
that arrived inside it, each at its own context (``benchmark/
flops_looped_gqa.py``: the projections and the MLP a lap, attention over the
context in every cache layer, the gate, the head once), over window x chips
x the bf16 peak. ``gqa_retention_mfu_pct``'s arithmetic over this family's
count."""

from benchmark import flops_looped_gqa as flops
from benchmark import peaks, samples

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    cfg = run.conf
    if cfg.get("model_type") != "ouro":
        return None
    lo, hi = samples.bounds(run)
    total = 0.0
    for r in run.records:
        if r.first_t is not None and lo <= r.first_t < hi:
            total += flops.prompt_flops(cfg, r.prompt_len)
        total += sum(
            flops.decode_token_flops(cfg, r.prompt_len + i + 1)
            for i, t in enumerate(r.arrivals[1:], start=1) if lo <= t < hi
        )
    peak = peaks.peaks_for(run.device["kind"])["bf16_flops"]
    return 100.0 * total / (run.seconds * run.cell["chips"] * peak)
