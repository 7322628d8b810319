"""Host milliseconds a tick after its fetch: ``deliver`` (``_deliver``,
``_finish_prefill``, finish bookkeeping) plus ``outside`` (between two
``step()`` calls: the fan-out to the streams, ``collect_finished``, idle
sleep). Window's seconds over window's ticks."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(
        run,
        ["engine_tick_deliver_seconds", "engine_tick_outside_seconds"],
        "engine_ticks", 1e3,
    )
