"""The least share of the window the hyper-connection mixes need at the HBM
peak: the mixes the window's dispatches ran over their PADDED tokens
(``mhc_mixes_run``, ``plan.note_dispatch``: tokens x 2 x layers) times the
bytes of the stream one mix must move (``flops_mla_mhc_moe.mhc_bytes_per_mix``:
``(3n + 1) C`` values at the activations' width), over window x chips x the
HBM peak. A floor, not a time: beside the device trace's reading of the
``mhc_pre`` / ``mhc_post`` scopes it says how far from the bandwidth bound
the mixes run. A program without the counter gives nothing."""

from benchmark import counters, peaks
from benchmark import flops_mla_mhc_moe as flops

LAYER = "model"
DEVICE_METRIC = True


def read(run):
    mixes = counters.delta(run, "mhc_mixes_run")
    if mixes is None or "hc_mult" not in run.conf:
        return None
    value_bytes = 4.0 if run.conf["serve"]["dtype"] == "float32" else 2.0
    moved = mixes * flops.mhc_bytes_per_mix(run.conf, value_bytes)
    peak = peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / (run.seconds * run.cell["chips"] * peak)
