"""Host milliseconds a tick in the ``dispatch`` phase: building the decode
inputs (the ``jnp.asarray`` conversions a trace shows as
``jit_convert_element_type``) and calling the decode program, up to the
fetch. Window's seconds over window's ticks."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(
        run, ["engine_tick_dispatch_seconds"], "engine_ticks", 1e3
    )
