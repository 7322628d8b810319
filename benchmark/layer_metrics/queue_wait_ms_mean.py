"""Mean time a request waits inside the engine before the admission dispatch
that takes it: ``engine.submit`` to the tick that dispatches its prefill (the
engine's ``engine_queue_wait`` summary, observed at that event; the
``engine.queue`` span of a traced request is the same interval). Sum over
count between the window's two ``/metrics`` readings, so the mean is over
exactly the admissions of the window."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(
        run, ["engine_queue_wait_seconds_sum"],
        "engine_queue_wait_seconds_count", 1e3,
    )
