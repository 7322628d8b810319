"""The FULL layers' decode kernel's share of its roofline, in a stack of
window and full layers: ``paged_decode_attn_roofline_pct``'s reading of
``quantized_paged_fused_attention`` (the same kernel, bytes and operations:
``benchmark/kernels/quantized_paged_fused_attention.py``; a dispatch's
census is the rows' whole contexts, which a full layer sweeps), with the
events checked against the FULL layers of ``layer_types`` a step, which that
reader's ``num_hidden_layers`` cannot count: the window layers' calls of the
same body run under a name of their own
(``window_decode_attn_roofline_pct``). A configuration without
``layer_types`` gives nothing.
"""

from benchmark.layer_metrics import window_decode_attn_roofline_pct as pattern

LAYER = "kernels"
DEVICE_METRIC = True
KERNEL = "quantized_paged_fused_attention"


def read(run):
    return pattern.read(
        run, KERNEL, "full_attention", lambda d: d[2],
    )
