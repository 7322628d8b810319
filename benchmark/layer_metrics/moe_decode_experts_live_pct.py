"""Of the experts a routed layer holds, the share a decode step is EXPECTED
to read: ``moe_decode_experts_live`` over ``moe_decode_experts_held``, every
decode dispatch of the window (``plan.note_dispatch``; one routed layer, a
step). Under the live path of ``ops/moe.py`` a step reads the weights of the
experts its live rows picked and of no others: the count is the expectation
under uniform routing, ``held x (1 - (1 - k / E)^active rows)``, which
experts they pick being data the host does not see. 100 where decode runs
dense-combine (a mesh); a program without the counters (the parent of PR
46) gives nothing."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = False


def read(run):
    return counters.ratio(
        run, ["moe_decode_experts_live"], "moe_decode_experts_held", 100.0
    )
