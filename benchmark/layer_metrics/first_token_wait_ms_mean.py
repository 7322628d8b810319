"""Mean time from a request's admission dispatch to the moment the host
holds its first token (the engine's ``engine_first_token_wait`` summary,
observed at that event; the ``engine.first_token`` span of a traced request):
the prefill's run on the device and the wait for the fetch its token rides.
Sum over count between the window's two ``/metrics`` readings."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(
        run, ["engine_first_token_wait_seconds_sum"],
        "engine_first_token_wait_seconds_count", 1e3,
    )
